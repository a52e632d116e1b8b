"""The share of a Mamba-2 layer's state that outlives a chunk:
``exp(sum of a chunk's log decays)``, the mean over the step's Mamba-2
layers, heads and chunks of 128 tokens (the PUBLISHED ``chunk_size``,
whatever chunk the kernel walks), from the `` chunk_keep=`` field of the
worker's loss lines stamped inside the measured window (it leaves the
step with the loss): whether the state the scan carries across chunk
boundaries does any work at these weights (0: every head forgets inside
a chunk and the carried state is idle; 1: nothing decays).  The mean
over the window's lines.  Nothing where the program logs no such field
(a parent; a model without a state-space layer)."""

from benchmark.lib import manifest

fields = manifest.load_named("layers", "hyper.sinkhorn_err")


def read(run):
    seen = [f["chunk_keep"] for _, f in fields.lines(run)
            if "chunk_keep" in f]
    return sum(seen) / len(seen) if seen else None
