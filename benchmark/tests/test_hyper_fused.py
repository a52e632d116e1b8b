"""``kernel.hyper_fused_share``: the fused pair's two call names in a
fixture trace, apart from the four that ``kernel.hyper_mix_share``
reads; nothing on a parent's trace."""

import json
import os
import types

import pytest

from benchmark.lib import manifest

ROOT = os.path.dirname(manifest.BENCH_DIR)
BOOK = manifest.Manifest(ROOT)
NAME = "xing4.0-29b-a4b.seq4096"
CELL = BOOK.cell(NAME)

TAIL = 'custom-call(%a, %b, %c), custom_call_target="tpu_custom_call"'
WIDE, ONE, MAPS = ("bf16[8192,14336]{1,0}", "bf16[8192,3584]{1,0}",
                   "f32[8192,128]{1,0}")
# The calls as the compiled step names them (operands cut): the second
# forward's under remat, the backward's as the transposed jit leaves it.
FUSED_FWD = ("%checkpoint_hc_post_pre_fwd__.2 = (" + ", ".join(
    [WIDE, ONE, MAPS]) + ") " + TAIL)
FUSED_FWD_FIRST = FUSED_FWD.replace("checkpoint_hc_post_pre_fwd__.2",
                                    "hc_post_pre_fwd.5")
FUSED_BWD = ("%hc_pre_post_bwd.9 = (" + ", ".join(
    [WIDE, ONE, MAPS, MAPS, MAPS]) + ") " + TAIL)
PRE_FWD = "%hc_pre_fwd.3 = (" + ONE + ", " + MAPS + ") " + TAIL
POST_FWD = "%hc_post_fwd.7 = " + WIDE + " " + TAIL
GMM = "%gmm_nn.33 = bf16[24576,1024]{1,0} " + TAIL


def _run(custom_calls, trace_dir=None):
    trace = None if custom_calls is None else {
        "custom_calls": custom_calls, "busy_s": 4.0, "steps": 3.0}
    return types.SimpleNamespace(
        trace=trace, trace_dir=trace_dir, config=CELL["config"],
        traffic=CELL["traffic"], cell={"chips": 1},
        device={"kind": "TPU v5 lite"})


def _events(tmp_path):
    """The raw events of three executions of the step program, 1,000 ns
    each, the first cut by the trace's edge (its first half missing): a
    whole one makes two forward calls each of a first and a second
    forward and one backward call a layer, of two layers."""
    one = [(FUSED_FWD_FIRST, 100), (FUSED_FWD_FIRST, 200), (PRE_FWD, 250),
           (FUSED_FWD, 500), (FUSED_FWD, 600), (FUSED_BWD, 700),
           (FUSED_BWD, 800)]
    ops = [[name, 1000 * k + at, 50] for k in range(3) for name, at in one
           if k or at >= 500]
    raw = {"devices": {"/device:TPU:0": ops},
           "modules": {"/device:TPU:0": [
               ["jit_train_step", 1000 * k, 990] for k in range(3)] + [
               ["jit_small", 2995, 3]]}}
    with open(tmp_path / "reduced.json", "w") as fh:
        json.dump(raw, fh)
    return str(tmp_path)


def test_the_fused_calls_are_read_by_name_and_the_four_are_not(capsys,
                                                               tmp_path):
    fused = BOOK.reader("kernel.hyper_fused_share")
    share = BOOK.reader("kernel.hyper_mix_share")
    separate = {PRE_FWD: [0.04, 140.0], POST_FWD: [0.05, 60.0]}
    pair = {FUSED_FWD: [0.003, 6.0], FUSED_FWD_FIRST: [0.002, 4.0],
            FUSED_BWD: [0.155, 6.0]}
    run = _run(dict(separate, **pair, **{GMM: [0.5, 10.0]}),
               _events(tmp_path))
    assert fused(run) == pytest.approx(100 * 0.16 / 4.0)
    lines = [l for l in capsys.readouterr().err.splitlines()
             if "hyper_fused" in l]
    assert [l.split()[2] for l in lines] == ["post_pre_fwd:", "pre_post_bwd:"]
    # a whole execution's four forward and two backward calls, though
    # ``steps`` (3) counts the cut one whole
    assert "10.0 calls, 4 a step program" in lines[0]
    assert "6.0 calls, 2 a step program" in lines[1]
    assert "0.5000 ms a call" in lines[0]
    # without the raw events beside the trace: the calls and their time
    assert fused(_run(pair)) == pytest.approx(100 * 0.16 / 4.0)
    assert "10.0 calls, - a step program" in capsys.readouterr().err
    # the accepted share reads the separately named calls alone
    assert share(run) == pytest.approx(100 * 0.09 / 4.0)
    assert share(_run(pair)) is None
    # a parent (no fused call) and an untraced run report nothing
    assert fused(_run(dict(separate, **{GMM: [0.5, 10.0]}))) is None
    assert fused(_run(None)) is None


def test_the_metric_is_the_cells_alone():
    entry, = [m for m in BOOK.doc["per_layer"]
              if m["name"] == "kernel.hyper_fused_share"]
    assert entry == {
        "name": "kernel.hyper_fused_share", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "records_per_s", "workloads": [NAME]}
    assert BOOK.doc["per_layer"][-1] == entry
    assert "kernel.hyper_fused_share" in {
        m["name"] for m in CELL["per_layer"]}
