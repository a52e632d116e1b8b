"""Throughput of a managed job, from the times its tasks completed.

The window opens at a task completion and closes at the first task
completion at or after ``seconds`` later, so it holds whole tasks only
and every second of the ``seconds`` asked for.  ``records_per_s`` is all
the records of those tasks over all the time from the opening to the
closing completion: a stall anywhere in the window moves it.

Beside it stands a steadier reading, for ``loop.stall_share``: the window
cut into consecutive slices, each closed at a task completion and at
least ``MIN_SLICE_S`` long; a slice's reading is its records over its
duration, and the median of the readings is what the job does between
stalls.  One rule for every cell: no per-cell knob.
"""

import statistics

MIN_SLICE_S = 3.0   # a slice is closed at the first completion at or past this
MIN_SLICES = 3      # fewer whole slices than this give no median


class NoWholeTask(ValueError):
    pass


def window_completions(completions, t_open, seconds):
    """Completion times of the window's tasks, relative to ``t_open``: up
    to and including the first at or after ``seconds``.  ``completions``
    are absolute times, ascending, one per completed task; the completion
    that opened the window is ``t_open`` itself, the window's start and
    not one of its tasks."""
    rel = []
    for t in completions:
        if t > t_open:
            rel.append(t - t_open)
            if rel[-1] >= seconds:
                break
    return rel


def cut(rel_completions, min_slice_s=MIN_SLICE_S):
    """[(duration_s, n_tasks)] of consecutive whole-task slices."""
    out, start, n = [], 0.0, 0
    for t in rel_completions:
        n += 1
        if t - start >= min_slice_s:
            out.append((t - start, n))
            start, n = t, 0
    return out


def throughput(completions, t_open, seconds, records_per_task):
    """The window's numbers as a dict:

    ``records_per_s``  the window's tasks' records over its length;
    ``window_s``  its length, opening to closing completion;
    ``tasks``  whole tasks in it;
    ``slices``  each slice's reading; ``median_slice_records_per_s`` and
    ``stall_share`` (1 - rate / median, in percent) where there are
    ``MIN_SLICES`` slices, else None.
    """
    rel = window_completions(completions, t_open, seconds)
    if not rel or rel[-1] < seconds:
        raise NoWholeTask(
            "no task completed at or after %.1f s of the window (%d "
            "completion(s) in it)" % (seconds, len(rel)))
    rate = len(rel) * records_per_task / rel[-1]
    readings = [n * records_per_task / d for d, n in cut(rel)]
    median = statistics.median(readings) if len(
        readings) >= MIN_SLICES else None
    return {
        "records_per_s": rate,
        "window_s": rel[-1],
        "tasks": len(rel),
        "slices": readings,
        "median_slice_records_per_s": median,
        "stall_share": None if median is None
        else 100.0 * (1.0 - rate / median),
    }
