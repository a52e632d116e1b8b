"""Milliseconds a step in the slowest task of the traced stretch: the
intervals behind ``loop.step_interval_ms`` averaged over one task's worth
at a time, and the largest of those means.  A single interval says
little in the per-step loop, which runs a task's steps ahead and waits
in the last one's fence (three of ~5 ms, then one of a whole task's
device time); a task's worth always holds one fence pass, so a stall
shows as a task above the mean and the normal burst does not.  Nothing
under one whole task; with one, it is the mean itself."""

from benchmark.lib import spans


def read(run):
    tasks = spans.task_step_ms(run)
    return max(tasks) if tasks else None
