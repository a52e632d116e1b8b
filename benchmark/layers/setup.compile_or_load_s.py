"""Seconds the backend spent on the first step's program (``worker setup:``
``compile_or_load_s`` of ``first_dispatch``): its compile, or its load from
the persistent cache."""

from benchmark.lib import setup_line


def read(run):
    return setup_line.worker_sum(run, "compile_or_load_s")
