"""Slots of the row kernel's buffer the vector phase of its sums
(``rows_sum``, ``ops/row_moves.py``) walked, as a share of the tokens x
K a call it would walk with every choice in a slot of its own:
``100 * sum_terms / sum_slots`` over the worker's ``moe load: ...
sum_terms= sum_slots=`` lines inside the measured window (both from the
step program itself: sixteen result rows walk as many terms as the
fullest of them has).  100 where every token has all its K rows here;
13 where a chip holds 8 of 320 experts (2.5% of the slots hold a row).
Nothing where the program logs no ``sum_slots=`` (a parent; the jnp row
moves; a model with no share)."""

from benchmark.lib import manifest

load = manifest.load_named("layers", "moe.load_max_over_mean")


def read(run):
    seen = [f for f in load.lines(run) if f.get("sum_slots")]
    slots = sum(f["sum_slots"] for f in seen)
    if not slots:
        return None
    return 100.0 * sum(f["sum_terms"] for f in seen) / slots
