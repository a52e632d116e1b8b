"""Seconds from ``Worker.run()`` to the first batch prepared (``worker
setup:`` ``first_task_fetch_s`` + ``first_batch_s``): the first ``get_task``,
the reader's first batch, ``prepare_batch``."""

from benchmark.lib import setup_line


def read(run):
    return setup_line.worker_sum(run, "first_task_fetch_s", "first_batch_s")
