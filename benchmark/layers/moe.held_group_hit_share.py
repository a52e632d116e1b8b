"""Tokens among whose chosen routing groups is the one the held experts
lie in, as a share of the tokens, from the `` group_hit=`` field of the
worker's ``moe load:`` lines inside the measured window (a router
limited to groups, ``n_group`` / ``topk_group``: the mean over the
step's expert layers, out of the step program itself): it sets the rows
this chip can be sent at all, ``topk_group / n_group`` at balance (50%
for 4 of 8).  The mean over the window's lines.  Nothing where the
program logs no such field (a parent; a router without groups)."""

from benchmark.lib import manifest

load = manifest.load_named("layers", "moe.load_max_over_mean")


def read(run):
    seen = [f["group_hit"] for f in load.lines(run) if "group_hit" in f]
    return 100.0 * sum(seen) / len(seen) if seen else None
