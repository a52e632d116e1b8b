"""What the ``xing4.0-29b-a4b`` configuration brought: the named
hyper-connection calls, their least bytes against hand counts, its four
readers on fixture runs, its operation count, the configuration's file
against the catalog's numbers, and its plain reference against the
product at tiny sizes."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.lib import kernels, manifest, peaks

ROOT = os.path.dirname(manifest.BENCH_DIR)
BOOK = manifest.Manifest(ROOT)
NAME = "xing4.0-29b-a4b.seq4096"
CELL = BOOK.cell(NAME)

TAIL = 'custom-call(%a, %b, %c), custom_call_target="tpu_custom_call"'
WIDE, ONE, MAPS = ("bf16[8192,14336]{1,0}", "bf16[8192,3584]{1,0}",
                   "f32[8192,128]{1,0}")
# The calls of one step as the compiled program names them (operands cut).
CALLS = {
    "pre_fwd": "%checkpoint_hc_pre_fwd__.2 = (" + ONE + ", " + MAPS + ") "
               + TAIL,
    "post_fwd": "%hc_post_fwd.7 = " + WIDE + " " + TAIL,
    "post_bwd": "%transpose_jvp_hc_post_fwd__.3 = (" + ", ".join(
        [WIDE, ONE, MAPS]) + ") " + TAIL,
    "pre_bwd": "%hc_pre_bwd.11 = (" + ", ".join([WIDE, MAPS, MAPS]) + ") "
               + TAIL,
}
CALLS["post_bwd"] = CALLS["post_bwd"].replace(
    "transpose_jvp_hc_post_fwd__", "hc_post_bwd")
GMM = "%gmm_nn.33 = bf16[24576,1024]{1,0} " + TAIL
LATENT = ("%flash_fwd_qk192_v128.4 = (bf16[16,4096,128]{2,1,0}, "
          "f32[16,1,4096]{2,1,0}, f32[16,1,4096]{2,1,0}) " + TAIL)
ROWS, C, N = 8192, 3584, 4


def test_the_named_calls_are_told_and_counted_at_their_least_bytes():
    module = manifest.load_named("kernels", "hyper_mix")
    logits = 24 * 4
    want = {
        "pre_fwd": (2 * ROWS * N * C * 24, ROWS * ((N + 1) * C * 2 + logits)),
        "post_fwd": (0, ROWS * ((2 * N + 1) * C * 2 + logits)),
        "post_bwd": (0, ROWS * ((3 * N + 2) * C * 2 + 2 * logits)),
        "pre_bwd": (2 * ROWS * N * C * 24,
                    ROWS * ((3 * N + 1) * C * 2 + 2 * logits)),
    }
    for kind, hlo in CALLS.items():
        assert module.classify(*kernels.parse_call(hlo), hlo=hlo,
                               streams=N) == (kind, want[kind]), kind
        # every call is bound by memory on a v5e
        assert peaks.roofline_seconds(*want[kind], "TPU v5 lite")[1] == (
            "memory")
        # without the configuration's stream count: not counted
        assert module.classify(*kernels.parse_call(hlo), hlo=hlo) is None
    for hlo in (GMM, LATENT):
        assert module.classify(*kernels.parse_call(hlo), hlo=hlo,
                               streams=N) is None
    # a sublayer's forward: three passes of the stream and a bit
    forward = want["pre_fwd"][1] + want["post_fwd"][1]
    assert forward / (ROWS * N * C * 2) == pytest.approx(3.5, abs=0.02)
    assert forward == pytest.approx(0.82e9, rel=0.02)


def _run(custom_calls=None, config=None, text="", window=(0.0, 1e12)):
    trace = None if custom_calls is None else {
        "custom_calls": custom_calls, "busy_s": 6.0}
    return types.SimpleNamespace(
        trace=trace, config=config or CELL["config"],
        traffic=CELL["traffic"], cell={"chips": 1},
        device={"kind": "TPU v5 lite"}, job=types.SimpleNamespace(text=text),
        times={"open": window[0], "close": window[1]})


def test_the_trace_readers_take_the_named_calls_alone(capsys):
    module = manifest.load_named("kernels", "hyper_mix")
    roofline = BOOK.reader("kernel.hyper_mix_roofline")
    share = BOOK.reader("kernel.hyper_mix_share")
    least = {kind: peaks.roofline_seconds(
        *module.classify(*kernels.parse_call(hlo), hlo=hlo, streams=N)[1],
        "TPU v5 lite")[0] for kind, hlo in CALLS.items()}
    # 15 steps of 12 sublayers: the forward twice a step under remat,
    # every call at half its roofline
    counts = {"pre_fwd": 360.0, "post_fwd": 360.0, "post_bwd": 180.0,
              "pre_bwd": 180.0}
    calls = {CALLS[k]: [2 * counts[k] * least[k], counts[k]] for k in CALLS}
    others = {GMM: [0.5, 10.0], LATENT: [0.5, 10.0]}
    run = _run(custom_calls=dict(calls, **others))
    taken = sum(2 * counts[k] * least[k] for k in CALLS)
    assert roofline(run) == pytest.approx(50.0)
    assert share(run) == pytest.approx(100 * taken / 6.0)
    lines = [l for l in capsys.readouterr().err.splitlines()
             if "hyper_mix" in l]
    assert [l.split()[2] for l in lines] == ["post_bwd:", "post_fwd:",
                                             "pre_bwd:", "pre_fwd:"]
    assert all("memory-bound" in l and "(50.00%)" in l for l in lines)
    # a parent (no named call), an untraced run, another configuration
    for reader in (roofline, share):
        assert reader(_run(custom_calls=others)) is None
        assert reader(_run()) is None
        other = BOOK.cell("kanana-2-30b-a3b.seq16384")["config"]
        assert reader(_run(custom_calls=calls, config=other)) is None


STAMP = "[2026-10-02 10:59:%02d,545] [INFO] [worker-0] [w:1:f] "


def test_the_loss_lines_fields_are_read_inside_the_window():
    from benchmark.lib import job

    err = BOOK.reader("hyper.sinkhorn_err")
    ratio = BOOK.reader("mtp.loss_over_main")
    line = STAMP + "step %d loss %s (version %d) mtp=%s hc_err=%s"
    text = "\n".join([
        line % (10, 8, "9.9", 8, "9.0", "1.0e-01"),      # before the window
        line % (20, 16, "6.6", 16, "6.0", "4.0e-05"),
        line % (30, 24, "5.5", 24, "5.0", "6.0e-05"),
        STAMP % 31 + "moe load: step=24 layers=5 rows=1 max=1 mean=1.0",
        STAMP % 32 + "step 32 loss 5.0 (version 32)",    # a parent's line
    ])
    at = lambda second: job.stamp_seconds(STAMP % second)
    run = _run(text=text, window=(at(15), at(40)))
    assert err(run) == pytest.approx(6.0e-05)
    # main = loss - 0.1 mtp: 6.0 / 6.0 and 5.0 / 5.0
    assert ratio(run) == pytest.approx(1.0)
    parent = _run(text=STAMP % 20 + "step 16 loss 6.6 (version 16)",
                  window=(at(15), at(40)))
    assert err(parent) is None and ratio(parent) is None


def test_the_operation_count_holds_the_module_both_heads_and_the_maps():
    module = manifest.load_named("opcounts", "lm_mhc_mla_moe_mtp")
    config = CELL["config"]
    E, T = 3584, 4096
    attention = (E * 768 + 768 * 8 * 192 + E * 576 + 512 * 8 * 256
                 + 8 * 128 * E)
    parts = module.per_token(config)
    assert parts == {
        "attention": 6 * attention,
        "dense": 3 * E * 9216,
        "router": 5 * E * 64,
        "shared": 5 * 3 * E * 1024,
        "experts": 5 * (4 * 8 / 64) * 3 * E * 1024,
        "head": 2 * E * 16384,
        "mtp_projection": 2 * E * E,
        "mixing": 6 * 2 * 4 * E * 24 + 2 * 4 * E * 4,
    }
    assert attention == pytest.approx(10.72e6, rel=1e-3)
    assert parts["head"] == pytest.approx(117.4e6, rel=1e-3)
    assert parts["dense"] == pytest.approx(99.1e6, rel=1e-3)
    assert parts["attention"] == pytest.approx(64.3e6, rel=1e-3)
    assert parts["shared"] == pytest.approx(55.1e6, rel=1e-3)
    assert parts["experts"] == pytest.approx(27.5e6, rel=1e-3)
    assert parts["mtp_projection"] == pytest.approx(25.7e6, rel=1e-3)
    assert parts["mixing"] == pytest.approx(4.2e6, rel=2e-2)
    scores = module.scores_per_sequence(config)
    assert scores == 6 * 8 * (T * (T + 1) // 2) * 320
    assert scores / T == pytest.approx(31.5e6, rel=2e-3)
    total = sum(parts.values()) + scores / T
    assert total == pytest.approx(425.9e6, rel=2e-3)       # the issue's ~425 M
    assert module.train_flops(config) == 3 * 2 * (
        T * sum(parts.values()) + scores)
    # the module's block, its projection and its pass of the head: over
    # a quarter of the multiply-adds (the issue's ~26% leaves its scores
    # out)
    mtp = (attention + E * 64 + 3 * E * 1024 + 0.5 * 3 * E * 1024
           + E * 16384 + 2 * E * E + 2 * 4 * E * 24 + 4 * E * 4
           + scores / T / 6)
    assert mtp / total == pytest.approx(0.277, abs=0.005)


def test_the_cells_metrics_hold_the_new_ones_and_the_shared_kernels():
    mine = {m["name"] for m in CELL["per_layer"]}
    new = {"kernel.hyper_mix_share", "kernel.hyper_mix_roofline",
           "hyper.sinkhorn_err", "mtp.loss_over_main"}
    assert mine >= new | {
        "kernel.latent_attention_roofline", "kernel.latent_attention_share",
        "moe.dead_row_share", "moe.held_load_max_over_mean",
        "kernel.row_move_share", "trainer.mfu", "trainer.peak_hbm_gb",
        "kernel.mosaic_share"}
    assert {m["name"] for m in CELL["end_to_end"]} >= {"records_per_s",
                                                       "setup_s"}
    # ``mtp.loss_over_main`` is also ``ling-3.0-flash``'s since PR 56
    shared = {"mtp.loss_over_main": [NAME, "ling-3.0-flash.seq16384"]}
    for entry in BOOK.doc["workloads"]:
        if entry["name"] != NAME:
            theirs = {m["name"] for m in BOOK.cell(
                entry["name"])["per_layer"]}
            assert not theirs & (new - set(shared)), entry["name"]
    for metric in BOOK.doc["per_layer"]:
        if metric["name"] in new:
            assert metric["workloads"] == shared.get(metric["name"], [NAME])
            assert metric["moves"] == "records_per_s"
    assert CELL["config"]["kernels"] == ["latent_attention",
                                         "grouped_matmul", "hyper_mix"]
    assert CELL["chips"] == 1
    flags = CELL["traffic"]["flags"]
    assert (flags["batch_size"], flags["num_minibatches_per_task"],
            flags["num_workers"], flags["log_loss_steps"]) == (2, 8, 1, 8)
    assert CELL["traffic"]["generator"] == "tokens_zipf_fixed_ids"
    assert flags["batch_size"] * CELL["config"]["seq_len"] == 8192
    # the latent kernel's reader splits this cell's 16 planes into two
    # sequences of the 8 held heads
    latent = manifest.load_named("kernels", "latent_attention")
    got = latent.classify(*kernels.parse_call(LATENT), hlo=LATENT,
                          heads=CELL["config"]["num_attention_heads"],
                          d_rope=CELL["config"]["qk_rope_head_dim"])
    assert got == ("fwd", latent.call(2, 8, 4096, 192, 128, "fwd", 64))


def test_the_configuration_keeps_every_published_width():
    """The catalog row's numbers, key by key: only the six keys of
    ``reduced`` differ, each with its published value beside it; the
    model_params run those sizes."""
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        rows = [json.loads(line) for line in fh]
    row, = [r for r in rows if r["name"] == "Xing4.0-29B-A4B"]
    catalog = row["config"]
    config = CELL["config"]
    reduced = ["num_hidden_layers", "first_k_dense_replace",
               "num_attention_heads", "num_key_value_heads",
               "n_routed_experts", "vocab_size"]
    assert config["reduced"] == reduced
    entry, = [e for e in BOOK.doc["configs"]
              if e["name"] == "xing4.0-29b-a4b"]
    assert entry["reduced"] == reduced
    assert entry["source"] == row["source_url"]
    assert config["source"].startswith(row["source_url"])
    for key, value in catalog.items():
        if key in reduced:
            assert config["published"][key] == value, key
            assert config[key] != value, key
        else:
            assert config[key] == value, key
    assert [config[key] for key in reduced] == [5, 1, 8, 8, 8, 131072 // 8]
    assert config["layers_kept"] == [0, 2, 3, 4, 5]
    assert config["head_shares"] == 4
    p = config["cli"]["model_params"]
    assert (p["dim"], p["num_heads"], p["head_shares"], p["kv_latent_rank"],
            p["q_latent_rank"], p["qk_nope_dim"], p["qk_rope_dim"],
            p["v_head_dim"], p["dense_ffn_dim"], p["ffn_dim"],
            p["moe_experts"], p["moe_top_k"], p["moe_shared_experts"],
            p["moe_route_scale"], p["rope_theta"], p["norm_eps"]) == (
                3584, 8, 4, 512, 768, 128, 64, 128, 9216, 1024, 64, 4, 1,
                2, 10000, 1e-06)
    scaling = catalog["rope_scaling"]
    assert p["rope_scaling"] == "%d,%d,%d,%d" % (
        scaling["factor"], scaling["original_max_position_embeddings"],
        scaling["beta_fast"], scaling["beta_slow"])
    assert (p["hyper_streams"], p["hyper_sinkhorn_iters"],
            p["mtp_modules"], p["mtp_weight"]) == (
                catalog["hc_mult"], catalog["hc_sinkhorn_iters"],
                catalog["num_nextn_predict_layers"],
                config["mtp_loss_factor"])
    assert (p["moe_router"], p["moe_norm_topk"], p["moe_aux_weight"],
            p["tied_embeddings"], p["embed_scale"], p["remat"],
            p["warmup_steps"], p["dense_layers"], p["scan_periods"]) == (
                "sigmoid_bias", True, 0, False, 1.0, True, 2000, 1, False)
    assert (p["num_layers"], p["dense_layers"], p["num_heads"],
            p["moe_experts_held"], p["vocab_size"]) == (5, 1, 8, 8, 16384)
    assert p["seq_len"] == config["seq_len"] == 4096 == (
        scaling["original_max_position_embeddings"])
    assert "807.4 M" in config["reduced_why"]
    assert "4 chips of one v5e host" in config["deployment"]
    for key in ("hyper_connections", "mtp", "attention", "rope_layout",
                "router", "shared_experts", "balance_loss", "optimizer",
                "remat", "compute_dtype", "seq_len", "embedding", "stack"):
        assert key in config["assumed"], key


def test_the_products_tree_is_the_configurations_parameter_count():
    import jax

    from benchmark.lib.runner import params_string
    from elasticdl_tpu.models.spec import load_model_spec

    cli = CELL["config"]["cli"]
    spec = load_model_spec(cli["model_zoo"],
                           model_params=params_string(cli["model_params"]))
    shapes = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    count = lambda tree: sum(a.size for a in jax.tree_util.tree_leaves(tree))
    assert count(shapes) == 807_416_462
    assert 16 * count(shapes) == pytest.approx(12.92e9, rel=1e-3)
    assert count(shapes["mtp"]) == pytest.approx(136.49e6, rel=1e-4)
    # one period of four layers, no loop over stacked weights
    period = shapes["layers"]["period"]
    assert sorted(period) == ["0", "1", "2", "3"]
    assert period["0"]["ln1"].shape == (1, 3584)
    assert count(period["0"]) == pytest.approx(110.73e6, rel=1e-4)
    assert count(shapes["layers"]["lead"]) == pytest.approx(110.50e6,
                                                            rel=1e-4)


def test_product_loss_routing_and_layers_agree_with_the_reference_tiny():
    done = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH_DIR, "lib",
                                      "compare.py"),
         "--config-file", CELL["config_file"], "--seed", "2147483659",
         "--rehearse"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    row = json.loads(done.stdout.strip().splitlines()[-1])
    assert row["ok"] and row["rel_diff"] <= row["tolerance"], row
    assert row["microbatch"] == 2
    said = lambda mark: json.loads([l for l in done.stderr.splitlines()
                                    if l.startswith(mark)][-1])
    routing = said('{"routing')
    assert routing["routing_same_input"] >= routing["floor"]
    layers = said('{"layers')
    assert set(layers["layers_same_input"]) == {
        "attention", "shared_expert", "routed_experts", "mtp", "mixing",
        "sinkhorn_columns"}
    for part, error in layers["layers_same_input"].items():
        assert error <= layers["ceilings"][part], part
    assert layers["ceilings"]["mixing"] < layers["ceilings"]["mtp"]
    losses = said('{"main_loss')
    assert row["reference_loss"] == pytest.approx(
        losses["main_loss"] + 0.1 * losses["mtp_loss"], rel=1e-6)
    assert ("layer stack: pattern=aaa lead=a period=aa periods=1"
            in done.stderr)
    assert "hyper=4 sinkhorn=20 mtp=1 q_latent=48" in done.stderr
    assert "hyper residual: tokens=128 streams=4 width=128" in done.stderr


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
