"""XLA programs the workers built (or loaded from the persistent cache)
inside the measured window: the worker's ``xla compile:`` log lines, one
per fresh jit, stamped between the window's opening and its closing.
``cache.compiles_in_window`` counts new files in the cache directory and
so misses every compile shorter than the persistent cache's minimum
compile time.  Nothing where the program logs no such line at all."""

from benchmark.lib import job

MARK = "xla compile:"


def read(run):
    stamps = [job.stamp_seconds(line)
              for line in run.job.text.splitlines() if MARK in line]
    if not stamps:
        return None
    return float(sum(1 for t in stamps if t is not None and
                     run.times["open"] <= t <= run.times["close"]))
