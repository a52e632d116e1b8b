"""The fullest held expert's rows over the mean held expert's, from the
worker's ``moe load: ... max= mean= ... moved=`` lines inside the
measured window, where ``max`` and ``mean`` are over the experts this
chip holds alone (a model with a share says ``moved=``): the imbalance
the grouped matmul's groups see.  The mean over the window's lines.
Nothing where the program logs no ``moved=``."""

from benchmark.lib import manifest

dead = manifest.load_named("layers", "moe.dead_row_share")


def read(run):
    seen = [f["max"] / f["mean"] for f in dead.lines(run) if f.get("mean")]
    return sum(seen) / len(seen) if seen else None
