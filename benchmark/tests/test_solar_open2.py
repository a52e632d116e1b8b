"""What the ``solar-open2-250b`` configuration brought: the vector-decay
delta rule's readers on its calls (8 heads x 16,384 x 128 | 128), the
work counted by the recurrence and not by a chunk or a sub-block, its
operation count against hand counts, the configuration's file against
the catalog's row, and its plain reference against the product at tiny
sizes."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.lib import kernels, manifest, peaks

ROOT = os.path.dirname(manifest.BENCH_DIR)
BOOK = manifest.Manifest(ROOT)
CONFIG = "solar-open2-250b"
NAME = CONFIG + ".seq16384"
CELL = BOOK.cell(NAME)
ROW = "/opt/skills/guides/model-configs/architectures.jsonl"

TAIL = 'custom-call(%a, %b, %c, %d), custom_call_target="tpu_custom_call"'
PLANE = "bf16[8,16384,128]{2,1,0}"
STATES = "f32[8,256,128,128]{3,2,1,0}"
INVERSES = "bf16[8,128,64,128]{3,2,1,0}"
# The calls of one step as the compiled program names them (operands cut).
CALLS = {
    "fwd": "%kda_fwd.35 = (" + ", ".join([PLANE, STATES, INVERSES]) + ") "
           + TAIL,
    "fwd_again": "%checkpoint_kda_fwd__.15 = (" + ", ".join(
        [PLANE, STATES, INVERSES]) + ") " + TAIL,
    "bwd": "%kda_bwd.6 = (" + ", ".join(
        [PLANE, PLANE, PLANE, "f32[8,16384,128]{2,1,0}",
         "f32[8,256,1,64]{3,2,1,0}"]) + ") " + TAIL,
}
SCALAR = ("%gdn_fwd.12 = (bf16[15,16384,192]{2,1,0}, "
          "f32[15,256,96,192]{3,2,1,0}) " + TAIL)
TOKENS = 8 * 16384
NEW = ("kernel.kda_roofline", "kernel.kda_share")
SHARE_LISTS = ("moe.dead_row_share", "moe.held_load_max_over_mean",
               "kernel.row_move_share")


def test_the_work_is_the_recurrences_whatever_the_chunk_or_sub_block():
    module = manifest.load_named("kernels", "kda")
    flops, nbytes = module.call(1, 8, 16384, 128, 128, "fwd")
    assert flops == 2 * 3 * TOKENS * 128 * 128 == 12_884_901_888
    # q, k, v, o in bfloat16; the decay a float32 vector, beta a scalar
    assert nbytes == TOKENS * (4 * 128 * 2 + 4 * (128 + 1)) == 201_850_880
    least, bound = peaks.roofline_seconds(flops, nbytes, "TPU v5 lite")
    assert bound == "memory" and least * 1e3 == pytest.approx(0.2465,
                                                              rel=1e-3)
    flops, nbytes = module.call(1, 8, 16384, 128, 128, "bwd")
    assert flops == 2 * 9 * TOKENS * 128 * 128
    assert nbytes == TOKENS * (8 * 128 * 2 + 8 * (128 + 1))
    for kind, hlo in CALLS.items():
        got = module.classify(*kernels.parse_call(hlo), hlo=hlo)
        assert got == (kind[:3], module.call(1, 8, 16384, 128, 128,
                                             kind[:3])), kind
    # another chunk writes other states: the same work
    other = CALLS["fwd"].replace("f32[8,256,128,128]", "f32[8,128,128,128]")
    assert module.classify(*kernels.parse_call(other), hlo=other) == (
        "fwd", module.call(1, 8, 16384, 128, 128, "fwd"))
    # the scalar decay's calls are not this kernel's, nor these its
    assert module.classify(*kernels.parse_call(SCALAR), hlo=SCALAR) is None
    scalar = manifest.load_named("kernels", "gated_delta")
    for hlo in CALLS.values():
        assert scalar.classify(*kernels.parse_call(hlo), hlo=hlo) is None


def _run(custom_calls=None, config=None):
    trace = None if custom_calls is None else {
        "custom_calls": custom_calls, "busy_s": 5.0}
    return types.SimpleNamespace(
        trace=trace, config=CELL["config"] if config is None else config,
        device={"kind": "TPU v5 lite"})


def test_the_two_readers_read_the_cells_calls_and_nothing_of_a_parents():
    roofline, share = (BOOK.reader(name) for name in NEW)
    # 15 steps: 3 layers x (forward, forward again, backward)
    calls = {CALLS["fwd"]: (45 * 3.8e-3, 45.0),
             CALLS["fwd_again"]: (45 * 3.8e-3, 45.0),
             CALLS["bwd"]: (45 * 4.2e-3, 45.0), SCALAR: (1.0, 45.0)}
    run = _run(calls)
    module = manifest.load_named("kernels", "kda")
    least = sum(peaks.roofline_seconds(
        *module.call(1, 8, 16384, 128, 128, kind), "TPU v5 lite")[0]
        for kind in ("fwd", "fwd", "bwd"))
    assert roofline(run) == pytest.approx(
        100 * least / (3.8e-3 + 3.8e-3 + 4.2e-3))
    assert 0 < roofline(run) < 100
    assert share(run) == pytest.approx(100 * 45 * 11.8e-3 / 5.0)
    # a parent's program makes no such call; an untraced run has no trace
    assert roofline(_run({SCALAR: (1.0, 45.0)})) is None
    assert share(_run({SCALAR: (1.0, 45.0)})) is None
    assert roofline(_run()) is None
    other = dict(CELL["config"], kernels=["gated_delta"])
    assert roofline(_run(calls, other)) is None
    # and the scalar decay's readers find nothing in this cell's calls
    assert BOOK.reader("kernel.gated_delta_roofline")(_run(
        {k: v for k, v in calls.items() if k != SCALAR},
        dict(CELL["config"], kernels=["gated_delta"]))) is None


def test_lm_hybrid_kda_moe_counts_the_operations_of_a_record():
    counts = manifest.load_named("opcounts", "lm_hybrid_kda_moe")
    config = CELL["config"]
    per = counts.per_token(config)
    E = 4096
    assert per["kda_projections"] == 3 * (
        3 * E * 1024 + 1024 * E + 2 * (E * 128 + 128 * 1024) + E * 8)
    assert per["kda_scan"] == 3 * 3 * 8 * 128 * 128
    assert per["attention_projections"] == 3 * E * 1024 + 2 * E * 128
    assert per["router"] == 4 * E * 320
    assert per["shared"] == 4 * 3 * E * 1280
    assert per["experts"] == 4 * (8 * 8 / 320) * 3 * E * 1280
    assert per["head"] == E * 24576
    T = 16384
    scores = (T * (T + 1) // 2) * 8 * 2 * 128
    assert counts.scores_per_sequence(config) == scores
    total = sum(per.values()) + scores / T
    assert total == pytest.approx(267.4e6, rel=1e-3)
    assert per["head"] / total == pytest.approx(0.38, abs=0.01)
    assert per["kda_scan"] / total < 0.005
    assert counts.train_flops(config) == pytest.approx(26.28e12, rel=1e-3)


def test_the_cells_metrics_and_the_lists_it_joined():
    mine = {m["name"] for m in CELL["per_layer"]}
    assert mine >= set(NEW) | set(SHARE_LISTS) | {
        "trainer.mfu", "trainer.peak_hbm_gb", "kernel.mosaic_share"}
    assert not {name for name in mine if name.startswith(
        ("kernel.gated_delta", "kernel.banded"))}
    # the full layer's ``flash_fwd`` / ``flash_bwd``, by name (PR 65)
    assert "kernel.flash_attention_roofline" in mine
    for entry in BOOK.doc["per_layer"]:
        if entry["name"] in NEW:      # ``ling-3.0-flash`` joined in PR 56
            assert entry["workloads"][0] == NAME
            assert (entry["layer"], entry["moves"], entry["source"],
                    entry["unit"]) == ("kernels", "records_per_s",
                                       "device_trace", "%")
        if entry["name"] in SHARE_LISTS:
            # the fifth of each list; later cells stand behind it
            assert entry["workloads"][4] == NAME
            assert len(entry["workloads"]) >= 5
    assert CELL["config"]["kernels"] == ["kda", "flash_attention",
                                         "grouped_matmul"]
    assert CELL["chips"] == 1
    flags = CELL["traffic"]["flags"]
    assert (flags["batch_size"], flags["num_minibatches_per_task"],
            flags["num_workers"], flags["log_loss_steps"]) == (1, 4, 1, 8)
    workload = [w for w in BOOK.doc["workloads"] if w["name"] == NAME][0]
    assert workload["traffic"] == "tokens-b1-task4"
    assert len(workload["why"]) <= 200 and "1/40" in workload["why"]


def test_the_configuration_keeps_every_width_of_the_catalogs_row():
    """The catalog row's ``config``, key by key: only the keys of
    ``reduced`` differ, each with its published value beside it; no
    width among them; the model_params run those sizes."""
    if not os.path.isfile(ROW):
        pytest.skip("no catalog on this machine")
    with open(ROW) as fh:
        row = [json.loads(l) for l in fh if '"Solar-Open2-250B"' in l][0]
    config = CELL["config"]
    entry = [c for c in BOOK.doc["configs"] if c["name"] == CONFIG][0]
    assert entry["source"] == row["source_url"]
    assert entry["file"] == "benchmark/configs/solar-open2-250b.json"
    assert entry["reduced"] == config["reduced"]
    reduced = set(config["reduced"])
    assert reduced == {
        "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
        "linear_attn_config", "linear_attn_config.num_heads",
        "n_routed_experts", "vocab_size"}
    assert not [k for k in reduced if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    for key, value in row["config"].items():
        if key not in reduced:
            assert config[key] == value, key
    linear = dict(row["config"]["linear_attn_config"], num_heads=8)
    assert config["linear_attn_config"] == linear       # head_dim kept
    published = config["published"]
    assert [published[k] for k in (
        "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
        "n_routed_experts", "vocab_size")] == [48, 64, 8, 320, 196608]
    assert published["linear_attn_config"] == {"num_heads": 64}
    assert [config[k] for k in (
        "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
        "n_routed_experts", "vocab_size")] == [4, 8, 1, 8, 196608 // 8]
    p = config["cli"]["model_params"]
    assert p["layer_pattern"] == "".join(
        "a" if i in config["gqa_layers"] else "d"
        for i in config["layers_kept"]) == "addd"
    assert (p["dim"], p["num_heads"], p["num_kv_heads"], p["head_dim"],
            p["delta_key_dim"], p["delta_value_dim"], p["delta_rank"],
            p["conv_kernel"], p["ffn_dim"], p["norm_eps"]) == (
                4096, 8, 1, 128, 128, 128, 128, 4, 1280, 1e-05)
    assert (p["moe_experts"], p["moe_top_k"], p["moe_experts_held"],
            p["moe_share_index"], p["moe_shared_experts"], p["moe_router"],
            p["moe_norm_topk"], p["moe_route_scale"]) == (
                320, 8, 8, 0, 1, "sigmoid_bias", True, 1.0)
    assert (p["rope_kinds"], p["attn_gate"], p["delta_kind"],
            p["delta_neg_eigval"], p["tied_embeddings"], p["remat"],
            p["head_shares"], p["warmup_steps"]) == (
                "w", True, "kda", True, False, True, 8, 2000)
    assert p["num_heads"] * p["head_shares"] == 64
    assert p["seq_len"] == config["seq_len"] == 16384
    assert "840,875,672" in config["reduced_why"]
    assert "WITHOUT either exchange" in config["deployment"]
    for key in ("router_scoring", "norm_placement", "qk_norm", "gqa_gate",
                "kda_layer", "decay_draw", "optimizer", "compute_dtype"):
        assert key in config["assumed"], key
    assert "softmax over the 320" in config["assumed"]["router_scoring"]
    assert "as recalled" in config["assumed"]["kda_layer"]


def test_the_reference_imports_nothing_of_the_scans_op():
    path = os.path.join(manifest.BENCH_DIR, "reference", CONFIG + ".py")
    with open(path) as fh:
        text = fh.read()
    imports = [l for l in text.splitlines()
               if l.lstrip().startswith(("import ", "from "))]
    assert not [l for l in imports if "gated_delta" in l or "ops" in l]
    assert "jax.lax.scan(token" in text


def test_product_loss_and_layers_agree_with_the_reference_at_tiny_size():
    done = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH_DIR, "lib",
                                      "compare.py"),
         "--config-file", CELL["config_file"], "--seed", "3000000017",
         "--rehearse"],
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT),
        capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert done.returncode == 0, done.stderr[-2000:]
    row = json.loads(done.stdout.strip().splitlines()[-1])
    assert row["ok"] and row["rel_diff"] <= row["tolerance"], row
    layers = json.loads([l for l in done.stderr.splitlines()
                         if l.startswith('{"layers')][-1])
    assert set(layers["layers_same_input"]) == {
        "kda", "attention", "shared_expert", "routed_experts"}
    assert max(layers["layers_same_input"].values()) <= layers["ceiling"]
    routing = json.loads([l for l in done.stderr.splitlines()
                          if l.startswith('{"routing')][-1])
    assert routing["routing_same_input"] >= routing["floor"]
    assert "heads_held=2/16" in done.stderr
    assert "decay=channel rank=16" in done.stderr


def test_the_rehearsal_runs_the_cell_end_to_end_on_the_cpu():
    done = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH_DIR, "run.py"),
         "--workload", NAME, "--seed", "3000000019", "--seconds", "4",
         "--trace", "0", "--rehearse"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600, cwd=ROOT)
    assert done.returncode == 3, done.stderr[-2000:]
    row = json.loads(done.stdout.strip().splitlines()[-1])
    assert row["correct"] and row["failed"] == 0 and row["attempted"] > 0
    assert row["rehearsal"] == ["records_per_s", "setup_s"]
