"""Seconds from the ``worker device:`` line to the call of the model's
``init_fn`` (``worker setup:`` ``build_s``): the zoo module's import and its
ModelSpec, the master client and its registration, the reader."""

from benchmark.lib import setup_line


def read(run):
    return setup_line.worker_sum(run, "build_s")
