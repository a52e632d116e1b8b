"""Seconds of the master's set-up by its own account (``master setup:``
``total_s``): from the OS's start of its process through its imports,
arguments, shards and servers to its first ``launched worker``."""

from benchmark.lib import setup_line


def read(run):
    fields = setup_line.master(run)
    return None if fields is None else fields.get("total_s")
