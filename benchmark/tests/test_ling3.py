"""What the ``ling-3.0-flash`` configuration brought: its two readers
and the eight accepted ones it joined on fixture runs, its operation
count against hand counts, the configuration's file against the
catalog's row, and its plain reference against the product at the
rehearsal's size."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.lib import kernels, manifest, peaks

ROOT = os.path.dirname(manifest.BENCH_DIR)
BOOK = manifest.Manifest(ROOT)
NAME = "ling-3.0-flash.seq16384"
CELL = BOOK.cell(NAME)
CONFIG = CELL["config"]
ROW = "/opt/skills/guides/model-configs/architectures.jsonl"

TAIL = 'custom-call(%a, %b, %c), custom_call_target="tpu_custom_call"'
KDA_FWD = ("%checkpoint_kda_fwd__.2 = (bf16[8,16384,128]{2,1,0}, "
           "f32[8,256,128,128]{3,2,1,0}, bf16[8,128,64,128]{3,2,1,0}) "
           + TAIL)
LATENT = ("%flash_fwd_qk192_v128.4 = (bf16[8,16384,128]{2,1,0}, "
          "f32[8,1,16384]{2,1,0}, f32[8,1,16384]{2,1,0}) " + TAIL)
GMM = "%gmm_nn.33 = bf16[6656,768]{1,0} " + TAIL


def _run(custom_calls=None, config=None, text="", window=(0.0, 1e12)):
    trace = None if custom_calls is None else {
        "custom_calls": custom_calls, "busy_s": 6.0}
    return types.SimpleNamespace(
        trace=trace, config=config or CONFIG, traffic=CELL["traffic"],
        cell={"chips": 1}, device={"kind": "TPU v5 lite"},
        job=types.SimpleNamespace(text=text),
        times={"open": window[0], "close": window[1]}, window=None)


def test_the_cell_reports_what_the_issue_lists():
    names = {m["name"] for m in CELL["per_layer"]}
    assert {"kernel.kda_roofline", "kernel.kda_share",
            "kernel.latent_attention_roofline",
            "kernel.latent_attention_share", "moe.dead_row_share",
            "moe.held_load_max_over_mean", "kernel.row_move_share",
            "mtp.loss_over_main", "moe.held_group_hit_share",
            "kda.gate_floor_excess", "trainer.mfu",
            "trainer.peak_hbm_gb"} <= names
    assert {m["name"] for m in CELL["end_to_end"]} == {
        "records_per_s", "setup_s"}
    assert CELL["traffic"]["flags"]["batch_size"] == 1
    assert CONFIG["kernels"] == ["kda", "latent_attention", "grouped_matmul"]
    for name in ("moe.held_group_hit_share", "kda.gate_floor_excess"):
        entry = next(m for m in BOOK.doc["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [NAME] and entry["layer"] == "model"


def test_the_trace_readers_classify_both_mixers_calls_in_this_cell():
    """The accepted readers take this cell's scans (8 held heads x
    16,384 x 128 | 128) and its latent calls (8 heads, one RoPE key of
    64) at the least work of ``solar-open2-250b``'s and
    ``kanana-2-30b-a3b``'s shapes, by the configuration's published key
    names, and neither takes the other's or the grouped matmul's."""
    kda = manifest.load_named("kernels", "kda")
    latent = manifest.load_named("kernels", "latent_attention")
    want_kda = kda.call(1, 8, 16384, 128, 128, "fwd")
    want_latent = latent.call(1, 8, 16384, 192, 128, "fwd", 64)
    roofline = BOOK.reader("kernel.kda_roofline")
    calls = {KDA_FWD: [0.1, 24.0], LATENT: [0.2, 8.0], GMM: [0.3, 40.0]}
    layer = lambda name: manifest.load_named("layers", name)
    found = layer("kernel.kda_roofline").calls(_run(custom_calls=calls))
    assert found == [("fwd", want_kda, 0.1, 24.0)]
    found = layer("kernel.latent_attention_roofline").calls(
        _run(custom_calls=calls))
    # what ``classify`` said, the seconds, the calls (lib/kernels.calls)
    assert found == [(("fwd", want_latent), 0.2, 8.0)]
    least = peaks.roofline_seconds(*want_kda, "TPU v5 lite")[0]
    assert roofline(_run(custom_calls=calls)) == pytest.approx(
        100 * 24 * least / 0.1)
    assert BOOK.reader("kernel.kda_share")(
        _run(custom_calls=calls)) == pytest.approx(100 * 0.1 / 6.0)
    assert BOOK.reader("kernel.latent_attention_share")(
        _run(custom_calls=calls)) == pytest.approx(100 * 0.2 / 6.0)
    assert kernels.parse_call(KDA_FWD) is not None


STAMP = "[2026-10-02 10:59:%02d,545] [INFO] [worker-0] [w:1:f] "


def test_the_two_new_fields_are_read_inside_the_window():
    from benchmark.lib import job

    hit = BOOK.reader("moe.held_group_hit_share")
    excess = BOOK.reader("kda.gate_floor_excess")
    loss = STAMP + "step %d loss %s (version %d) mtp=%s g_excess=%s"
    load = (STAMP + "moe load: step=%d layers=7 rows=14000 max=600 "
            "mean=250.0 padded_rows=100 moved=46592 spilled=0 group_hit=%s")
    text = "\n".join([
        loss % (10, 8, "9.9", 8, "9.0", "1.0e-01"),     # before the window
        load % (10, 8, "0.9000"),
        loss % (20, 16, "11.0", 16, "10.0", "0.000e+00"),
        load % (20, 16, "0.5100"),
        loss % (30, 24, "11.0", 24, "10.0", "2.500e-03"),
        load % (30, 24, "0.4900"),
        loss % (40, 32, "9.9", 32, "9.0", "1.0e-01"),    # after it
        load % (40, 32, "0.1000"),
    ])
    at = lambda second: job.stamp_seconds(STAMP % second)
    run = _run(text=text, window=(at(15), at(35)))
    assert hit(run) == pytest.approx(50.0)
    assert excess(run) == pytest.approx(2.5e-3)
    ratio = BOOK.reader("mtp.loss_over_main")(run)
    assert ratio == pytest.approx(10.0 / (11.0 - 0.1 * 10.0))
    assert BOOK.reader("moe.dead_row_share")(run) == pytest.approx(
        100 * (1 - 14000 / 46592))
    assert BOOK.reader("moe.held_load_max_over_mean")(run) == pytest.approx(
        600 / 250)
    # a parent's lines carry neither field: nothing, and no raise
    plain = text.replace(" g_excess=", " x=").replace(" group_hit=", " y=")
    run = _run(text=plain, window=(at(15), at(35)))
    assert hit(run) is None and excess(run) is None


def test_the_operation_count_is_the_hand_count():
    module = manifest.load_named("opcounts", CONFIG["opcounts"])
    parts = module.per_token(CONFIG)
    E, H, d = 2560, 8, 128
    assert parts["kda_projections"] == 6 * (6 * E * H * d + E * H)
    assert parts["kda_scan"] == 6 * 3 * H * d * d
    assert parts["latent_projections"] == 2 * (
        E * H * 192 + E * 576 + 512 * H * 256 + E * H + H * 128 * E)
    assert parts["dense"] == 3 * E * 6144
    assert parts["router"] == 7 * E * 512
    assert parts["shared"] == 7 * 3 * E * 768
    assert parts["experts"] == pytest.approx(7 * 0.125 * 3 * E * 768)
    assert parts["head"] == 2 * E * 19648
    assert parts["mtp_projection"] == 2 * E * E
    pairs = 16384 * 16385 // 2
    assert module.scores_per_sequence(CONFIG) == 2 * H * pairs * 320
    per_token = sum(parts.values()) + 2 * H * pairs * 320 / 16384
    assert per_token == pytest.approx(373.5e6, rel=1e-3)
    assert module.train_flops(CONFIG) == pytest.approx(
        6 * 16384 * per_token)
    # the mfu reader on a window of 1.8 records a second
    run = _run()
    run.window = {"records_per_s": 1.8}
    assert BOOK.reader("trainer.mfu")(run) == pytest.approx(
        100 * module.train_flops(CONFIG) * 1.8 / 197e12, rel=1e-3)


def test_the_configuration_keeps_every_width_of_the_catalogs_row():
    with open(ROW) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "Ling-3.0-flash")
    entry = next(c for c in BOOK.doc["configs"]
                 if c["name"] == "ling-3.0-flash")
    assert entry["source"] == row["source_url"]
    assert CONFIG["source"].startswith(row["source_url"])
    assert "catalog row Ling-3.0-flash" in CONFIG["source"]
    changed = {"num_hidden_layers": 7, "first_k_dense_replace": 1,
               "num_attention_heads": 8, "num_key_value_heads": 8,
               "num_experts": 8, "vocab_size": 19648,
               "mtp_loss_scaling_factor": 0.1}
    assert sorted(entry["reduced"]) == sorted(changed)
    assert sorted(CONFIG["reduced"]) == sorted(changed)
    for key, value in row["config"].items():
        assert CONFIG[key] == changed.get(key, value), key
        assert CONFIG["published"].get(key, value) == value, key
    assert set(CONFIG["published"]) == set(changed)
    # no width among the changed keys
    assert not [key for key in changed if key.endswith(("_dim", "_rank"))
                or ("size" in key and key != "vocab_size")]
    assert CONFIG["layers_kept"] == [0, 36, 37, 38, 39, 40, 41]
    params = CONFIG["cli"]["model_params"]
    kept = lambda name: ",".join(str(CONFIG[name][i])
                                 for i in CONFIG["layers_kept"])
    assert params["ffn_limits"] == kept("expert_swiglu_limit_list")
    assert params["shared_limits"] == kept("share_expert_swiglu_limit_list")
    assert (params["moe_groups"], params["moe_top_groups"]) == (
        CONFIG["n_group"], CONFIG["topk_group"])
    assert params["delta_gate_floor"] == CONFIG["kda_lower_bound"]
    assert params["mtp_weight"] == CONFIG["mtp_loss_factor"] == CONFIG[
        "mtp_loss_scaling_factor"]
    assert list(params)[0] == "moe_groups"    # a parent's first TypeError
    for reading in ("kda_safe_gate", "kda_output", "attention_gate",
                    "latent_attention", "swiglu_limit", "layer_kinds"):
        assert "other reading" in CONFIG["assumed"][reading] or (
            "not taken" in CONFIG["assumed"][reading]) or (
                "not this row's" in CONFIG["assumed"][reading]), reading
    for word in ("4 chips", "64 chips", "by 8", "pipeline stages"):
        assert word in CONFIG["deployment"], word


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
    said = [json.loads(l) for l in done.stderr.splitlines()
            if l.startswith("{")]
    assert said[0]["routing_same_input"] == 1.0
    assert max(said[1]["layers_same_input"].values()) < 1e-4
    assert said[2]["mtp_loss"] > 0
    assert "decay=channel rank=0 gate=floor-5" in done.stderr
