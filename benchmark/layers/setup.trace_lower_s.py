"""Seconds of Python the first step's program cost before the compiler saw
it (``worker setup:`` ``trace_s`` + ``lower_s`` of ``first_dispatch``): the
model's trace to a jaxpr and its lowering to MLIR, as JAX reports them."""

from benchmark.lib import setup_line


def read(run):
    return setup_line.worker_sum(run, "trace_s", "lower_s")
