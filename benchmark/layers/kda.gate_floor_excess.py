"""How far under its floor (``kda_lower_bound``) the lowest log decay of
any KDA layer of a step lies, ``max(0, floor - min g)``, from the
`` g_excess=`` field of the worker's loss lines stamped inside the
measured window (it leaves the step with the loss): 0 by construction
of the bounded gate, and the promise a kernel that factors a sub-block's
decays would rest on (a floor of -5 a token bounds a 16-token
sub-block's exponent by 80 < 88).  The largest over the window's lines.
Nothing where the program logs no such field (a parent; a model whose
decay gate has no floor)."""

from benchmark.lib import manifest

fields = manifest.load_named("layers", "hyper.sinkhorn_err")


def read(run):
    seen = [f["g_excess"] for _, f in fields.lines(run) if "g_excess" in f]
    return max(seen) if seen else None
