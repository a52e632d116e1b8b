"""Milliseconds from one ``edl.step``'s start to the next one's, per
optimizer step: the mean over the longest run of traced steps that holds
whole tasks (benchmark/lib/spans.py, ``step_intervals_ms``).  The step as
the program itself marks it, on the profiler's clock: it must agree with
batch / ``records_per_s``."""

from benchmark.lib import spans


def read(run):
    intervals = spans.step_intervals_ms(run)
    if not intervals:
        return None
    return sum(intervals) / len(intervals)
