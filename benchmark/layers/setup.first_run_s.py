"""Seconds from the first step's dispatch to the first task's fence
(``worker setup:`` ``first_run_s``): the program's load onto the device, the
first task's steps, the fetch of its last loss."""

from benchmark.lib import setup_line


def read(run):
    return setup_line.worker_sum(run, "first_run_s")
