"""What PR 34 brought: ``kernel.row_move_share`` reads the row kernel's
named calls alone, in the two cells with a held share of the experts."""

import os
import types

import pytest

from benchmark.lib import manifest

ROOT = os.path.dirname(manifest.BENCH_DIR)
BOOK = manifest.Manifest(ROOT)
CELLS = ["lfm2-24b-a2b.seq8192", "smallthinker-21b-a3b.seq16384"]

TAIL = 'custom-call(%a, %b, %c), custom_call_target="tpu_custom_call"'
ROWS = {
    "%rows_pack.7 = u32[32768,1,1024]{2,1,0:T(1,128)} " + TAIL: [0.10, 40.0],
    "%rows_gather.3 = bf16[32768,2048]{1,0:T(8,128)(2,1)} " + TAIL:
        [0.20, 20.0],
    "%checkpoint_rows_gather__.1 = bf16[32768,2048]{1,0} " + TAIL:
        [0.05, 5.0],
    "%transpose_jvp_rows_sum__.2 = f32[32768,2048]{1,0:T(8,128)} " + TAIL:
        [0.25, 10.0],
}
OTHERS = {
    "%gmm_nn.33 = bf16[32768,1536]{1,0} " + TAIL: [0.5, 10.0],
    # a fusion that reads a row call's result is not a row call
    "%fusion.9 = bf16[32768,2048]{1,0} fusion(%rows_gather.3, "
    "%custom-call.4), kind=kLoop": [0.7, 10.0],
}


def _run(custom_calls=None):
    trace = None if custom_calls is None else {
        "custom_calls": custom_calls, "busy_s": 6.0}
    return types.SimpleNamespace(trace=trace)


def test_the_reader_takes_the_row_kernels_named_calls_alone():
    share = BOOK.reader("kernel.row_move_share")
    assert share(_run(dict(ROWS, **OTHERS))) == pytest.approx(
        100 * 0.60 / 6.0)
    assert share(_run(OTHERS)) is None        # a parent: no such call
    assert share(_run()) is None              # an untraced run


def test_the_metric_is_the_two_share_cells():
    entry = [m for m in BOOK.doc["per_layer"]
             if m["name"] == "kernel.row_move_share"][0]
    assert entry == {
        "name": "kernel.row_move_share", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "records_per_s", "workloads": entry["workloads"]}
    # the two share cells of PR 34 first; later share cells joined
    assert entry["workloads"][:2] == CELLS
    for cell in BOOK.doc["workloads"]:
        names = {m["name"] for m in BOOK.cell(cell["name"])["per_layer"]}
        assert ("kernel.row_move_share" in names) == (
            cell["name"] in entry["workloads"]), cell["name"]
