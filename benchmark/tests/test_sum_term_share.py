"""What PR 69 brought: ``moe.sum_term_share`` reads the ``sum_terms=``
and ``sum_slots=`` fields of the worker's ``moe load:`` lines, and
nothing from a run whose lines lack them.  The fixtures are the ``moe
load:``, ``moe dispatch:`` and loss lines of two real job logs, one
seed (3690000100) of ``solar-open2-250b.seq16384`` traced on both trees in the
builder's chip call ``B`` of PR 69: ``moe_load_job_log.txt`` the
change's, ``moe_load_job_log_parent.txt`` the parent's (commit
47262f6), whose lines end at ``spilled=``."""

import os
import types

import pytest

from benchmark.lib import manifest

ROOT = os.path.dirname(manifest.BENCH_DIR)
BOOK = manifest.Manifest(ROOT)
FIXTURES = os.path.join(manifest.BENCH_DIR, "fixtures")

# each run's window as its detail.json had it (seconds of the epoch)
WINDOWS = {"moe_load_job_log.txt": (1791201608.8117816, 1791201630.19669),
           "moe_load_job_log_parent.txt": (1791201248.0962307,
                                           1791201268.6802046)}
# what the change's traced run printed for the metric
PRINTED = 13.1982421875


def _run(name, window=None):
    with open(os.path.join(FIXTURES, name)) as fh:
        text = fh.read()
    open_, close = window or WINDOWS[name]
    return types.SimpleNamespace(
        job=types.SimpleNamespace(text=text),
        times={"open": open_, "close": close})


def test_the_share_is_read_where_the_lines_have_the_fields():
    run = _run("moe_load_job_log.txt")
    read = BOOK.reader("moe.sum_term_share")
    load = manifest.load_named("layers", "moe.load_max_over_mean")
    seen = load.lines(run)
    assert seen and all(f["sum_slots"] > 0 for f in seen)
    want = 100.0 * sum(f["sum_terms"] for f in seen) / sum(
        f["sum_slots"] for f in seen)
    assert read(run) == pytest.approx(want)
    assert read(run) == pytest.approx(PRINTED)
    assert 0 < read(run) < 30           # a thin share: 8 of 320 experts
    # no line inside the window: nothing
    assert read(_run("moe_load_job_log.txt", (0.0, 1.0))) is None


def test_a_parents_lines_give_nothing_and_raise_nothing():
    run = _run("moe_load_job_log_parent.txt")
    load = manifest.load_named("layers", "moe.load_max_over_mean")
    seen = load.lines(run)
    assert seen and not any("sum_slots" in f for f in seen)
    assert BOOK.reader("moe.sum_term_share")(run) is None
    # the readers the lines had still read them
    assert 0 < BOOK.reader("moe.dead_row_share")(run) < 100


def test_the_metric_is_the_eight_share_cells():
    entry, = [m for m in BOOK.doc["per_layer"]
              if m["name"] == "moe.sum_term_share"]
    dead, = [m for m in BOOK.doc["per_layer"]
             if m["name"] == "moe.dead_row_share"]
    assert entry == {
        "name": "moe.sum_term_share", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "kernels",
        "moves": "records_per_s", "workloads": dead["workloads"]}
    assert len(entry["workloads"]) == 8
    assert BOOK.doc["per_layer"][-1] == entry       # appended, last
    for cell in BOOK.doc["workloads"]:
        names = {m["name"] for m in BOOK.cell(cell["name"])["per_layer"]}
        assert ("moe.sum_term_share" in names) == (
            cell["name"] in entry["workloads"]), cell["name"]
