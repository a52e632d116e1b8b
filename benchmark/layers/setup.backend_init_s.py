"""Seconds from the first worker's ``main()`` to its ``worker device:``
line (``worker setup:`` ``backend_init_s``): arguments, the compile cache's
place, ``jax.devices()`` (TPU init), the memory statistics."""

from benchmark.lib import setup_line


def read(run):
    return setup_line.worker_sum(run, "backend_init_s")
