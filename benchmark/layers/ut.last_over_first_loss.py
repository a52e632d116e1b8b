"""The last turn's mean cross entropy over the first turn's, ``l_R /
l_1`` of the `` ut_loss=<l1>/<l2>/../<lR>`` field of the worker's loss
lines stamped inside the measured window (a looped stack's heads, one a
turn on one set of weights): near 1 at random weights, under 1 once the
later turns have learned to refine the earlier ones'.  The mean over
the window's lines.  Nothing where the program logs no such field or
fewer than two turns' losses (a parent; a model that runs its stack
once: fewer than two heads ran)."""

import re

from benchmark.lib import manifest

_FIELD = re.compile(r"step \d+ loss \S+.* ut_loss=(\S+)")
window_values = manifest.load_named("layers", "ut.exit_entropy").window_values


def read(run):
    turns = [[float(x) for x in value.split("/")]
             for value in window_values(run, _FIELD)]
    seen = [t[-1] / t[0] for t in turns if len(t) >= 2 and t[0] > 0]
    return sum(seen) / len(seen) if seen else None
