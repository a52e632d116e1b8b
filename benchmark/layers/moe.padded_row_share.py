"""Rows the grouped matmul computed beyond the real ones (row tiles that
straddle two experts are computed once for each), as a share of the real
rows, over the ``moe load:`` lines inside the measured window.  Nothing
where the program logs no such line."""

from benchmark.lib import manifest

load = manifest.load_named("layers", "moe.load_max_over_mean")


def read(run):
    seen = load.lines(run)
    rows = sum(f["rows"] for f in seen)
    if not rows:
        return None
    return 100.0 * sum(f["padded_rows"] for f in seen) / rows
