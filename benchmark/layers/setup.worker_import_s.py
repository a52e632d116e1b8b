"""Seconds from the OS's start of the first worker's process to its
``main()`` (``worker setup:`` ``import_s``): the interpreter and the import
chain of ``worker/main.py`` (JAX, the ops package, gRPC)."""

from benchmark.lib import setup_line


def read(run):
    return setup_line.worker_sum(run, "import_s")
