"""The mean entropy, in nats a token, of a looped stack's exit
distribution, from the `` exit_entropy=`` field of the worker's loss
lines stamped inside the measured window (``step N loss L (version V)
ut_loss= exit= exit_entropy=``: it leaves the step with the loss): log R
at most (1.386 at four turns), ~1.04 at a gate drawn at zero (1/2, 1/4,
1/4).  The gate's engagement counter: absent or 0 means the gate or the
entropy term fell out of the step, or the distribution collapsed onto
one turn.  The mean over the window's lines.  Nothing where the program
logs no such field (a parent; a model that runs its stack once)."""

import re

from benchmark.lib import job

_FIELD = re.compile(r"step \d+ loss \S+.* exit_entropy=(\S+)")


def window_values(run, pattern):
    """The first group of ``pattern`` in each of the job's lines stamped
    inside the measured window."""
    out = []
    for line in run.job.text.splitlines():
        m = pattern.search(line)
        at = job.stamp_seconds(line) if m else None
        if at is not None and run.times["open"] <= at <= run.times["close"]:
            out.append(m.group(1))
    return out


def read(run):
    seen = [float(value) for value in window_values(run, _FIELD)]
    return sum(seen) / len(seen) if seen else None
