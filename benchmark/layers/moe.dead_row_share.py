"""Rows the dispatch sorted and gathered for no expert held here, as a
share of the rows it moved: ``1 - rows / moved`` over the worker's ``moe
load: ... rows= ... moved=`` lines inside the measured window (``rows``:
assignments to held experts; ``moved``: every (token, choice) row, all
layers).  0 where every expert is held; 1 - held / experts at a balanced
router while the sorted buffer stays tokens x K rows.  Nothing where
the program logs no ``moved=`` (a parent; a dense model)."""

from benchmark.lib import manifest

load = manifest.load_named("layers", "moe.load_max_over_mean")


def lines(run):
    """The fields of the window's ``moe load:`` lines that say ``moved``."""
    return [f for f in load.lines(run) if f.get("moved")]


def read(run):
    seen = lines(run)
    moved = sum(f["moved"] for f in seen)
    if not moved:
        return None
    return 100.0 * (1.0 - sum(f["rows"] for f in seen) / moved)
