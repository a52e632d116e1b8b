"""Seconds of the trainer's construction (``worker setup:``
``param_init_s``): ``init_fn``'s jits compiled or loaded and run,
``optimizer.init``, the state's placement on the mesh."""

from benchmark.lib import setup_line


def read(run):
    return setup_line.worker_sum(run, "param_init_s")
