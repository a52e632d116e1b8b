"""Milliseconds a step in ``edl.progress_rpc``: the ``report_batch_done``
call to the master (and, at a task's last batch, the task's result), over
the steps of the traced stretch."""

from benchmark.lib import spans


def read(run):
    s = spans.with_steps(run)
    if s is None:
        return None
    return s.total_ns("edl.progress_rpc") / 1e6 / s.steps
