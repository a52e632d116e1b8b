"""Programs the first worker compiled and wrote to the persistent cache
before its set-up was over (``worker setup:`` ``cache_misses``): 0 in a warm
run, by the program's own word."""

from benchmark.lib import setup_line


def read(run):
    return setup_line.worker_sum(run, "cache_misses")
