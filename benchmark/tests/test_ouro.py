"""What the ``ouro-2.6b`` configuration brought: its two readers on
worker log lines with and without the looped stack's fields (a parent's
loss line has none), its entries in ``BENCHMARK.json``, the
configuration's file against the catalog's row, and its plain reference
against the product at the rehearsal's size, its operation count
against hand counts and ``lm_dense.py``, and the accepted
``remat.estimate_over_gb`` reader on a ``remat keep:`` line that ends in
``turns=`` (both here and not in ``test_opcounts.py`` /
``test_remat_metric.py``: a PR that adds a cell edits no file the
benchmark has)."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.lib import manifest

ROOT = os.path.dirname(manifest.BENCH_DIR)
BOOK = manifest.Manifest(ROOT)
NAME = "ouro-2.6b.seq8192"
CELL = BOOK.cell(NAME)
CONFIG = CELL["config"]
ROW = "/opt/skills/guides/model-configs/architectures.jsonl"

STAMP = "[2026-10-04 21:36:%02d,420] [INFO] [worker-0] [elasticdl_tpu." \
        "worker.worker:420:_process_minibatch] "
# as the parent logs a step, and as a looped stack's worker does
PLAIN = "step %d loss 10.5 (version %d)"
LOOPED = PLAIN + (" ut_loss=%s exit=0.499029/0.250533/0.125219/0.125219 "
                  "exit_entropy=%s")


def _run(lines, window=(0.0, 1e12)):
    return types.SimpleNamespace(
        config=CONFIG, traffic=CELL["traffic"], cell={"chips": 1},
        device={"kind": "TPU v5 lite"}, trace=None, window=None,
        job=types.SimpleNamespace(text="\n".join(lines)),
        times={"open": window[0], "close": window[1]})


def _looped(second, step, turns, entropy):
    return STAMP % second + LOOPED % (step, step, turns, entropy)


def test_the_two_readers_take_the_windows_lines():
    entropy = BOOK.reader("ut.exit_entropy")
    ratio = BOOK.reader("ut.last_over_first_loss")
    lines = [_looped(1, 8, "10.8/10.7/10.6/10.5", "1.20"),
             _looped(2, 16, "10.0/10.0/10.0/9.0", "1.10"),
             "remat keep: names=- turns=4 exit_entropy=9.9"]
    run = _run(lines)
    assert entropy(run) == pytest.approx(1.15)
    assert ratio(run) == pytest.approx((10.5 / 10.8 + 0.9) / 2)
    # the window: the first line lies before it
    from benchmark.lib import job
    opened = job.stamp_seconds(lines[1]) - 0.5
    late = _run(lines, (opened, opened + 10))
    assert entropy(late) == pytest.approx(1.10)
    assert ratio(late) == pytest.approx(0.9)
    # one turn's loss alone is no ratio
    alone = _run([_looped(1, 8, "10.8", "0.0")])
    assert ratio(alone) is None and entropy(alone) == 0.0


@pytest.mark.parametrize("lines", [
    [STAMP % 1 + PLAIN % (8, 8), STAMP % 2 + PLAIN % (16, 16)],
    [STAMP % 1 + PLAIN % (8, 8) + " mtp=10.1 hc_err=1.2e-06"],
    []], ids=["parent", "another-models-fields", "no-loss-line"])
def test_a_loss_line_without_the_fields_gives_nothing(lines):
    """A parent (or a model that runs its stack once) logs no ``ut_loss=``
    and no ``exit_entropy=``: both readers return None and raise
    nothing, and the result's line leaves the metrics out."""
    run = _run(lines)
    assert BOOK.reader("ut.exit_entropy")(run) is None
    assert BOOK.reader("ut.last_over_first_loss")(run) is None


def test_the_manifest_holds_the_cell_and_its_two_metrics():
    doc = BOOK.doc
    assert doc["workloads"][-1] == {
        "name": NAME, "config": "ouro-2.6b", "traffic": "tokens-b1-task4",
        "chips": 1, "why": doc["workloads"][-1]["why"]}
    assert len(doc["workloads"][-1]["why"]) <= 200
    by_name = {m["name"]: m for m in doc["per_layer"]}
    assert by_name["ut.exit_entropy"] == {
        "name": "ut.exit_entropy", "unit": "nats", "better": "higher",
        "source": "program_counter", "layer": "model",
        "moves": "records_per_s", "workloads": [NAME]}
    assert by_name["ut.last_over_first_loss"] == {
        "name": "ut.last_over_first_loss", "unit": "ratio",
        "better": "lower", "source": "program_counter", "layer": "model",
        "moves": "records_per_s", "workloads": [NAME]}
    assert [m["name"] for m in doc["per_layer"][-2:]] == [
        "ut.exit_entropy", "ut.last_over_first_loss"]
    for joined in ("kernel.flash_attention_roofline",
                   "remat.estimate_over_gb"):
        assert by_name[joined]["workloads"][-1] == NAME
    names = {m["name"] for m in CELL["per_layer"]}
    assert {"ut.exit_entropy", "ut.last_over_first_loss", "trainer.mfu",
            "trainer.peak_hbm_gb", "remat.estimate_over_gb",
            "kernel.flash_attention_roofline"} <= names
    assert {m["name"] for m in CELL["end_to_end"]} == {
        "records_per_s", "setup_s"}
    assert CONFIG["opcounts"] == "lm_looped_dense"
    assert CONFIG["kernels"] == ["flash_attention"]
    assert CELL["traffic"]["flags"]["batch_size"] == 1
    assert CELL["traffic"]["flags"]["num_minibatches_per_task"] == 4


def test_the_configuration_keeps_every_key_of_the_catalogs_row_but_depth():
    with open(ROW) as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == "Ouro-2.6B")
    entry = next(c for c in BOOK.doc["configs"] if c["name"] == "ouro-2.6b")
    assert entry["source"] == row["source_url"]
    assert CONFIG["source"].startswith(row["source_url"])
    assert entry["reduced"] == CONFIG["reduced"] == ["num_hidden_layers"]
    depth = CONFIG["num_hidden_layers"]
    assert 4 <= depth < 48 and CONFIG["published"] == {
        "num_hidden_layers": 48}
    for key, value in row["config"].items():
        assert CONFIG[key] == (depth if key == "num_hidden_layers"
                               else value), key
    params = CONFIG["cli"]["model_params"]
    assert params == {
        "dim": CONFIG["hidden_size"], "num_heads": 16, "num_layers": depth,
        "vocab_size": 49152, "seq_len": 8192, "ffn_dim": 5632,
        "rope_theta": 1000000, "post_norms": True, "tied_embeddings": False,
        "ut_steps": CONFIG["total_ut_steps"], "ut_entropy_weight": 0.1,
        "remat": True}
    assert CONFIG["hidden_size"] // params["num_heads"] == CONFIG["head_dim"]
    # 509,661,185 at six layers, a layer's 51,388,416 more or fewer
    assert ("%d layers = %s parameters" % (depth, format(
        509661185 + (depth - 6) * 51388416, ","))) in CONFIG["reduced_why"]
    for reading in ("block_norms", "carry", "exit_gate"):
        assert "the other reading" in CONFIG["assumed"][reading], reading
    for reading in ("ut_entropy_weight", "attention_bias"):
        assert "no key" in CONFIG["assumed"][reading], reading
    assert "pipeline stages" in CONFIG["deployment"]
    rehearsal = CONFIG["rehearsal"]
    assert (rehearsal["total_ut_steps"], rehearsal["num_hidden_layers"],
            rehearsal["cli"]["model_params"]["ut_steps"]) == (3, 2, 3)


def test_the_reference_agrees_with_the_product_at_the_rehearsals_size():
    done = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH_DIR, "lib",
                                      "compare.py"),
         "--config-file", CELL["config_file"], "--seed", "3000000019",
         "--rehearse"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu",
                           PYTHONPATH=ROOT),
        capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.splitlines()[-1])
    assert result["ok"] and result["rel_diff"] < 1e-5
    said, = [json.loads(l) for l in done.stderr.splitlines()
             if l.startswith("{")]
    assert len(said["turn_state_errors"]) == len(said["exit_errors"]) == 3
    assert max(said["turn_state_errors"]) < 1e-4 < said["ceiling"]
    assert max(said["exit_errors"]) < 1e-5 < said["exit_ceiling"]
    assert "loop stack: turns=3 layers=2 rows=64 carry=normed gate=linear " \
        "heads=3 turns_as=scan logits=" in done.stderr
    assert "head loss: tokens=64 vocab=256 logits=float32 bytes=65536 " \
        "calls=3" in done.stderr


def test_a_looped_stack_counts_its_turns_and_one_turn_is_lm_dense():
    looped = manifest.load_named("opcounts", "lm_looped_dense")
    lm_dense = manifest.load_named("opcounts", "lm_dense")
    L, E, F, V, T, R = CONFIG["num_hidden_layers"], 2048, 5632, 49152, 8192, 4
    macs = looped.forward_macs(CONFIG)
    per_layer = 4 * E * E + 3 * E * F
    assert per_layer == 51_380_224      # the issue's 51.38 M, norms apart
    assert macs == {"matmuls": R * L * T * per_layer,
                    "attention": R * L * T * T * E,
                    "head": R * T * E * V, "gate": (R - 1) * T * E}
    assert looped.train_flops(CONFIG) == 6 * sum(macs.values())
    # the head a fifth of the forward where the whole model's is 3%
    at_six = looped.forward_macs(dict(CONFIG, num_hidden_layers=6))
    assert at_six["head"] / sum(at_six.values()) == pytest.approx(
        0.2, abs=0.01)
    whole = looped.forward_macs(dict(CONFIG, num_hidden_layers=48))
    assert whole["head"] / sum(whole.values()) == pytest.approx(0.03,
                                                               abs=0.005)
    # 6 x parameters x tokens counts the stack and the head once
    assert looped.train_flops(dict(CONFIG, num_hidden_layers=6)) / (
        6 * 509_661_185 * T) > 3.9
    once = dict(CONFIG, total_ut_steps=1)
    assert looped.train_flops(once) == lm_dense.train_flops(once)


def test_the_remat_reader_takes_a_line_that_ends_in_its_turns():
    """``remat keep: .. fallback=0 turns=4``: what a looped stack says
    behind the fields every stack says leaves ``predicted_peak=`` where
    the accepted reader finds it."""
    read = BOOK.reader("remat.estimate_over_gb")
    with open(os.path.join(manifest.BENCH_DIR, "fixtures",
                           "fence_job_log.txt")) as fh:
        text = fh.read()
    said = next(l for l in text.splitlines() if "remat keep: " in l)
    looped = said.replace("fallback=0", "fallback=0 turns=4")
    assert looped != said and looped.endswith(" turns=4")
    run = lambda text: types.SimpleNamespace(
        job=types.SimpleNamespace(text=text),
        memory_peak_bytes=lambda: 15_190_061_568)
    assert read(run(text.replace(said, looped))) == read(run(text))
    assert read(run(text)) == pytest.approx(
        (15807565064 - 15_190_061_568) / 1e9)
