"""The multi-token-prediction module's loss over the model's own, from
the ``mtp=`` field of the worker's loss lines stamped inside the
measured window (the module's mean loss before its weight; the line's
loss is ``main + mtp_loss_factor x mtp``, the configuration's factor):
near 1 at random weights, where both are near ln V.  The mean over the
window's lines.  Absent or 0 means the module fell out of the step.
Nothing where the program logs no such field (a parent; a model with no
module)."""

from benchmark.lib import manifest

fields = manifest.load_named("layers", "hyper.sinkhorn_err")


def read(run):
    weight = run.config.get("mtp_loss_factor", 0.0)
    seen = [f["mtp"] / (loss - weight * f["mtp"])
            for loss, f in fields.lines(run)
            if "mtp" in f and loss > weight * f["mtp"]]
    return sum(seen) / len(seen) if seen else None
