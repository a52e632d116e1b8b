"""The largest ``|row or column sum - 1|`` of any Sinkhorn map
(``H_res``) of a step, from the ``hc_err=`` field of the worker's loss
lines stamped inside the measured window (``step N loss L (version V)
mtp= hc_err=``: it leaves the step with the loss): how far 20 rounds
leave the stream's mixing from doubly stochastic.  The largest over
the window's lines.  Nothing where the program logs no such field (a
parent; a model whose stream is one wide)."""

import re

from benchmark.lib import job

_LOSS = re.compile(r"step \d+ loss (\S+)")


def lines(run):
    """[(loss, {field: value})] of the window's loss lines that carry
    fields behind the loss."""
    out = []
    for line in run.job.text.splitlines():
        m = _LOSS.search(line)
        if not m or "=" not in line[m.end():]:
            continue
        at = job.stamp_seconds(line)
        if at is not None and run.times["open"] <= at <= run.times["close"]:
            out.append((float(m.group(1)), {
                k: float(v) for k, v in job.fields(line[m.end():]).items()}))
    return out


def read(run):
    seen = [f["hc_err"] for _, f in lines(run) if "hc_err" in f]
    return max(seen) if seen else None
