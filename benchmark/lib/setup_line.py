"""The program's own account of its set-up: the ``master setup:`` line of
the master and the ``worker setup:`` line of the first worker, each logged
once when that process's set-up timeline closes (elasticdl_tpu/utils/
timing.py, docs/observability.md "Set-up timeline"), as numbers by field.
``None`` where the program prints no such line, as every commit before
PR 35."""

import re

from benchmark.lib import job

_MASTER = re.compile(r"master setup: (.*)$", re.M)
_WORKER = re.compile(r"worker setup: (.*)$", re.M)


def _first(pattern, run):
    m = pattern.search(run.job.text)
    if m is None:
        return None
    return {key: float(value) for key, value in job.fields(
        m.group(1)).items()}


def master(run):
    return _first(_MASTER, run)


def worker(run):
    return _first(_WORKER, run)


def worker_sum(run, *names):
    """The sum of the worker line's fields ``names``; None without the
    line or without one of them."""
    fields = worker(run)
    if fields is None or any(name not in fields for name in names):
        return None
    return sum(fields[name] for name in names)
