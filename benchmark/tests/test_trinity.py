"""What the ``trinity-mini`` configuration brought: the banded readers
on its calls (a window of 2,048, 32 heads), its operation count against
hand counts, the reader of the ``attention block:`` line on a fixture
log, the configuration's file against the catalog's numbers, and its
plain reference against the product at tiny sizes."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.lib import kernels, manifest, peaks

ROOT = os.path.dirname(manifest.BENCH_DIR)
BOOK = manifest.Manifest(ROOT)
NAME = "trinity-mini.seq16384"
CELL = BOOK.cell(NAME)

TAIL = 'custom-call(%a, %b, %c), custom_call_target="tpu_custom_call"'
OUT = "bf16[32,16384,128]{2,1,0}"
STAT = "f32[32,1,16384]{2,1,0}"
# The calls of one step as the compiled program names them (operands cut).
FLASH = {
    ("fwd", 0): "%flash_fwd.8 = (" + ", ".join([OUT, STAT, STAT]) + ") "
                + TAIL,
    ("dq", 0): "%flash_dq.2 = " + OUT + " " + TAIL,
    ("dkv", 0): "%flash_dkv.2 = (" + OUT + ", " + OUT + ") " + TAIL,
    ("fwd", 2048): "%checkpoint_flash_fwd_w2048__.16 = (" + ", ".join(
        [OUT, STAT, STAT]) + ") " + TAIL,
    ("dq", 2048): "%flash_dq_w2048.6 = " + OUT + " " + TAIL,
    ("dkv", 2048): "%transpose_jvp_flash_dkv_w2048__.7 = (" + OUT + ", "
                   + OUT + ") " + TAIL,
}
UNNAMED = "%custom-call.9 = " + OUT + " " + TAIL
GMM = "%gmm_nn.227 = bf16[32768,1024]{1,0} " + TAIL
FULL_PAIRS, BAND_PAIRS = 134_225_920, 31_458_304     # the issue's counts


def test_the_banded_readers_tell_the_calls_of_a_window_of_2048():
    band = manifest.load_named("kernels", "banded_attention")
    assert band.pairs(16384) == FULL_PAIRS
    assert band.pairs(16384, 2048) == BAND_PAIRS == (
        2048 * 2049 // 2 + (16384 - 2048) * 2048)
    for (kind, window), hlo in FLASH.items():
        got = band.classify(*kernels.parse_call(hlo), hlo=hlo)
        assert got[0] == kind and got[2] == window, (kind, window)
        assert got[1] == band.call(32, 16384, 128, kind, window)
    for hlo in (UNNAMED, GMM):
        assert band.classify(*kernels.parse_call(hlo), hlo=hlo) is None
    # a windowed forward: 2 matmuls over the band's pairs, 32 heads
    flops, nbytes = band.call(32, 16384, 128, "fwd", 2048)
    assert flops == 2 * 2 * 32 * BAND_PAIRS * 128
    assert nbytes == 4 * 32 * 16384 * 128 * 2 + 2 * 4 * 32 * 16384
    least, bound = peaks.roofline_seconds(flops, nbytes, "TPU v5 lite")
    assert bound == "compute"
    assert least * 1e3 == pytest.approx(
        2 * 2 * 32 * BAND_PAIRS * 128 / 197e12 * 1e3, rel=1e-3)


def _run(custom_calls=None, config=None, text=""):
    trace = None if custom_calls is None else {
        "custom_calls": custom_calls, "busy_s": 6.0}
    return types.SimpleNamespace(
        trace=trace, config=config or CELL["config"],
        traffic=CELL["traffic"], cell={"chips": 1},
        device={"kind": "TPU v5 lite"},
        job=types.SimpleNamespace(text=text))


def test_the_three_banded_readers_read_the_cells_calls(capsys):
    band = manifest.load_named("kernels", "banded_attention")
    roofline = BOOK.reader("kernel.banded_attention_roofline")
    share = BOOK.reader("kernel.banded_attention_share")
    ratio = BOOK.reader("attn.window_over_full_time")
    least = {key: peaks.roofline_seconds(
        *band.call(32, 16384, 128, *key), "TPU v5 lite")[0] for key in FLASH}
    # 10 steps of four windowed layers and a full one: a full call at
    # half its roofline, a windowed one at a quarter
    calls = {FLASH[key]: [
        10 * (4 if key[1] else 1) * least[key] * (4 if key[1] else 2),
        10.0 * (4 if key[1] else 1)] for key in FLASH}
    calls.update({GMM: [0.5, 10.0], UNNAMED: [0.5, 10.0]})
    run = _run(custom_calls=calls)
    full = sum(v for k, v in least.items() if not k[1])
    banded = sum(v for k, v in least.items() if k[1])
    assert roofline(run) == pytest.approx(
        100 * (full + 4 * banded) / (2 * full + 16 * banded))
    assert share(run) == pytest.approx(
        100 * 10 * (2 * full + 16 * banded) / 6.0)
    # a windowed layer over a full one: twice the pairs' 0.234
    assert ratio(run) == pytest.approx(4 * banded / (2 * full))
    assert ratio(run) == pytest.approx(2 * BAND_PAIRS / FULL_PAIRS, rel=1e-3)
    lines = [l for l in capsys.readouterr().err.splitlines()
             if "banded_attention" in l]
    assert [tuple(l.split()[2:4]) for l in lines] == [
        ("full", "dkv:"), ("full", "dq:"), ("full", "fwd:"),
        ("window=2048", "dkv:"), ("window=2048", "dq:"),
        ("window=2048", "fwd:")]
    assert "(25.0%)" in lines[3] and "40.0 calls" in lines[3]


# The calls of a step since PR 41 fused the backward, as the compiled
# program of this cell names them (my chip run, PR 60, layouts cut): a
# grouped-query call states the query heads beside the K/V heads.
Q, KV, ROWS = "bf16[32,16384,128]", "bf16[4,16384,128]", "f32[32,1,16384]"
FWD_TAIL = ("custom-call(s32[45] %copy-done.1053, s32[45] %copy-done.1049, "
            + Q + " %bitcast.4450, " + KV + " %bitcast.4536, " + KV
            + ' %bitcast.4902), custom_call_target="tpu_custom_call"')
BWD_TAIL = ("custom-call(s32[45] %constant.1482, s32[45] %constant.1483, "
            + KV + " %bitcast.4533, " + KV + " %bitcast.4899, " + Q
            + " %bitcast.4449, " + Q + " %get-tuple-element.7829, " + ROWS
            + " %bitcast.4634, " + ROWS + " %reshape.1092), "
            'custom_call_target="tpu_custom_call"')
FUSED = {
    ("fwd", 0): "%flash_fwd.8 = (" + ", ".join([Q, ROWS, ROWS]) + ") "
                + FWD_TAIL,
    ("bwd", 0): "%flash_bwd.2 = (" + ", ".join([KV, KV, Q]) + ") "
                + BWD_TAIL,
    ("fwd", 2048): "%jvp_flash_fwd_w2048_.3 = (" + ", ".join(
        [Q, ROWS, ROWS]) + ") " + FWD_TAIL,
    ("bwd", 2048): "%flash_bwd_w2048.8 = (" + ", ".join([KV, KV, Q]) + ") "
                   + BWD_TAIL,
}


def test_the_fused_backward_is_told_by_name_and_counted_at_its_heads():
    band = manifest.load_named("kernels", "banded_attention")
    flash = manifest.load_named("kernels", "flash_attention")
    plane = 16384 * 128 * 2
    for (kind, window), hlo in FUSED.items():
        results, operands = kernels.parse_call(hlo)
        assert flash.name_of(hlo) == (kind, window, None)
        assert flash.heads_of(results, hlo, 16384) == (32, 4)
        got = band.classify(results, operands, hlo=hlo)
        assert (got[0], got[2]) == (kind, window)
        assert got[1] == band.call(32, 16384, 128, kind, window, kv_heads=4)
    # three results say nothing: the backward has as many as the forward
    assert len(kernels.parse_call(FUSED[("bwd", 0)])[0]) == len(
        kernels.parse_call(FUSED[("fwd", 0)])[0]) == 3
    for window, pairs in ((0, FULL_PAIRS), (2048, BAND_PAIRS)):
        fwd, fwd_bytes = band.call(32, 16384, 128, "fwd", window, kv_heads=4)
        bwd, bwd_bytes = band.call(32, 16384, 128, "bwd", window, kv_heads=4)
        # S, dP, dV, dQ, dK where the forward has S and PV
        assert bwd == 5 * 2 * 32 * pairs * 128 and 2 * bwd == 5 * fwd
        # q, o (and dO, dq) at 32 heads, K, V (and dk, dv) at 4
        assert fwd_bytes == (2 * 32 + 2 * 4) * plane + 2 * 4 * 32 * 16384
        assert bwd_bytes == (4 * 32 + 4 * 4) * plane + 2 * 4 * 32 * 16384
    # a full backward call: 27.9 ms of the MXU's time on a v5e, under
    # the 30.3 ms it takes (ops/flash_attention.py): no reading over 100%
    least, bound = peaks.roofline_seconds(
        *band.call(32, 16384, 128, "bwd", kv_heads=4), "TPU v5 lite")
    assert bound == "compute" and least * 1e3 == pytest.approx(27.9, rel=2e-3)


def test_the_three_banded_readers_read_a_fused_trace(capsys):
    """The traced window of the ledger's PR 60 line, call by call: the
    share is every ``flash_*`` second over the busy time, and a
    windowed layer's forward and backward over a full layer's."""
    roofline = BOOK.reader("kernel.banded_attention_roofline")
    share = BOOK.reader("kernel.banded_attention_share")
    ratio = BOOK.reader("attn.window_over_full_time")
    at = lambda key, n: FUSED[key].replace(".8 =", ".%d =" % n).replace(
        ".3 =", ".%d =" % n)
    calls = {FUSED[("fwd", 0)]: [0.1992, 14.0],
             FUSED[("bwd", 0)]: [0.4252, 14.0],
             at(("fwd", 2048), 3): [0.0656, 15.0],
             at(("fwd", 2048), 2): [0.0612, 14.0],
             at(("bwd", 2048), 8): [0.1206, 15.0],
             at(("bwd", 2048), 9): [0.1125, 14.0],
             GMM: [0.5, 10.0], UNNAMED: [0.5, 10.0]}
    run = _run(custom_calls=calls)
    flash_s = 0.1992 + 0.4252 + 0.0656 + 0.0612 + 0.1206 + 0.1125
    assert share(run) == pytest.approx(100 * flash_s / 6.0)
    want = ((0.0656 + 0.0612) / 29 + (0.1206 + 0.1125) / 29) / (
        0.1992 / 14 + 0.4252 / 14)
    assert ratio(run) == pytest.approx(want)
    assert 0.2 < ratio(run) < 0.3        # the pairs say 0.234
    assert 60 < roofline(run) < 100
    lines = [l for l in capsys.readouterr().err.splitlines()
             if "banded_attention" in l]
    assert [tuple(l.split()[2:4]) for l in lines] == [
        ("full", "bwd:"), ("full", "fwd:"),
        ("window=2048", "bwd:"), ("window=2048", "fwd:")]
    for line in lines:       # forward and backward apart, none over 100%
        assert float(line.split("(")[1].split("%")[0]) <= 100.0, line
    # one kind of layer lacks a kind the other has: no ratio
    del calls[FUSED[("bwd", 0)]]
    assert ratio(_run(custom_calls=calls)) is None
    assert share(_run(custom_calls=calls)) is not None
    # a path that splits one layer kind's backward alone: no ratio either
    calls[FLASH[("dq", 0)]] = calls[FLASH[("dkv", 0)]] = [0.2, 14.0]
    assert ratio(_run(custom_calls=calls)) is None
    # the split names on both layer kinds are still read
    split = {FLASH[key]: [1.0, 2.0] for key in FLASH}
    assert ratio(_run(custom_calls=split)) == pytest.approx(1.0)
    assert roofline(_run(custom_calls=split)) is not None


LINE = ("[2026-09-29 21:16:51,936] [INFO] [elasticdl_tpu.ops."
        "flash_attention:845:announce_attention] attention block: rows=%d "
        "heads=32 kv_heads=4 head_dim=128 qk_norm=head gate=1 out_norms=1 "
        "embed_multiplier=45.2548 layers=5 kv_repeat_bytes=%d "
        "kv_repeat_again_bytes=%d")


def test_the_new_reader_reads_the_attention_block_line():
    """``attn.kv_repeat_gb_per_step``: the layers times the line's two
    byte counts, of the largest shape the worker compiled (the training
    step's); nothing from a log without the line (a parent)."""
    read = BOOK.reader("attn.kv_repeat_gb_per_step")
    repeat = 4 * 28 * 16384 * 128 * 2
    assert repeat == 469_762_048
    text = "\n".join([
        "[..] worker device: platform=tpu",
        LINE % (16384, repeat, repeat // 2),
        LINE % (64, 4 * 28 * 64 * 128 * 2, 0),      # a smaller shape
        "[..] remat keep: names=flash_out bytes=1"])
    assert read(_run(text=text)) == pytest.approx(
        5 * (repeat + repeat // 2) / 1e9)
    assert read(_run(text=text)) == pytest.approx(3.5232, rel=1e-4)
    assert read(_run(text="[..] worker device: platform=tpu")) is None
    assert read(_run(text="")) is None
    # a line of another form (no byte counts) is nobody's
    assert read(_run(text="x attention block: rows=4 heads=2")) is None
    # without GQA the program says 0 and so does the metric
    assert read(_run(text=LINE % (16384, 0, 0))) == 0.0
    entry = [m for m in BOOK.doc["per_layer"]
             if m["name"] == "attn.kv_repeat_gb_per_step"][0]
    assert entry == {
        "name": "attn.kv_repeat_gb_per_step", "unit": "GB",
        "better": "lower", "source": "program_counter", "layer": "model",
        "moves": "records_per_s", "workloads": [NAME]}


def test_lm_gated_banded_moe_counts_the_active_operations_of_a_record():
    module = manifest.load_named("opcounts", "lm_gated_banded_moe")
    config = CELL["config"]
    E, T = 2048, 16384
    parts = module.per_token(config)
    assert parts == {
        # q, o and the gate at 4,096; k and v at 512
        "attention": 5 * (3 * E * 4096 + 2 * E * 512),
        "dense": 3 * E * 6144,
        "router": 4 * E * 128,
        "shared": 4 * 3 * E * 1024,
        "experts": 4 * (8 * 16 / 128) * 3 * E * 1024,
        "head": E * 25024,
    }
    assert parts["attention"] == pytest.approx(136.3e6, rel=1e-3)
    assert parts["shared"] == parts["experts"] == pytest.approx(
        25.2e6, rel=2e-3)
    assert parts["head"] == pytest.approx(51.25e6, rel=1e-3)
    assert module.layer_pairs(config) == [
        BAND_PAIRS, BAND_PAIRS, FULL_PAIRS, BAND_PAIRS, BAND_PAIRS]
    scores = module.scores_per_sequence(config)
    assert scores == (FULL_PAIRS + 4 * BAND_PAIRS) * 32 * 256
    assert scores / T == pytest.approx(130.0e6, rel=1e-3)    # the issue's
    total = sum(parts.values()) + scores / T
    assert total == pytest.approx(406.7e6, rel=1e-3)
    # the attention block 65% of the forward, its scores 32%
    assert (parts["attention"] + scores / T) / total == pytest.approx(
        0.655, abs=0.005)
    assert scores / T / total == pytest.approx(0.32, abs=0.005)
    assert module.train_flops(config) == 3 * 2 * (
        T * sum(parts.values()) + scores)
    assert module.train_flops(config) == pytest.approx(39.98e12, rel=1e-3)


def test_the_cells_metrics_hold_the_new_one_and_the_six_lists():
    """``>=``, not ``==``: a later PR may list the cell under more
    (PERF.md section 7 (14))."""
    mine = {m["name"] for m in CELL["per_layer"]}
    assert mine >= {
        "attn.kv_repeat_gb_per_step", "kernel.banded_attention_roofline",
        "kernel.banded_attention_share", "attn.window_over_full_time",
        "moe.dead_row_share", "moe.held_load_max_over_mean",
        "kernel.row_move_share", "trainer.mfu", "trainer.peak_hbm_gb",
        "kernel.mosaic_share"}
    assert {m["name"] for m in CELL["end_to_end"]} >= {"records_per_s",
                                                       "setup_s"}
    for entry in BOOK.doc["workloads"]:
        if entry["name"] != NAME:
            theirs = {m["name"] for m in BOOK.cell(
                entry["name"])["per_layer"]}
            assert "attn.kv_repeat_gb_per_step" not in theirs, entry["name"]
    # flash's older reader counts every call as full causal, and the
    # grouped matmul's asks the configuration for another model's keys
    assert not mine & {"kernel.flash_attention_roofline",
                       "kernel.grouped_matmul_roofline",
                       "kernel.grouped_matmul_share"}
    assert set(CELL["config"]["kernels"]) >= {"banded_attention",
                                              "grouped_matmul"}
    assert CELL["chips"] == 1
    flags = CELL["traffic"]["flags"]
    assert (flags["batch_size"], flags["num_minibatches_per_task"],
            flags["num_workers"], flags["log_loss_steps"]) == (1, 4, 1, 8)
    assert CELL["traffic"]["generator"] == "tokens_zipf_fixed_ids"
    assert flags["batch_size"] * CELL["config"]["seq_len"] == 16384
    # the new entries stood last in their lists; later cells follow
    assert [c["name"] for c in BOOK.doc["configs"]].index(
        "trinity-mini") == 5
    assert [w["name"] for w in BOOK.doc["workloads"]].index(NAME) == 6
    assert "attn.kv_repeat_gb_per_step" in [
        m["name"] for m in BOOK.doc["per_layer"]]


def test_the_configuration_keeps_every_published_width():
    """The catalog row's numbers, key by key: only the four keys of
    ``reduced`` differ, each with its published value beside it; the
    model_params run those sizes."""
    catalog = {
        "global_attn_every_n_layers": 4, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "load_balance_coeff": 0.001,
        "max_position_embeddings": 131072, "model_type": "afmoe",
        "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
        "num_attention_heads": 32, "num_dense_layers": 2,
        "num_expert_groups": 1, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 32,
        "num_key_value_heads": 4, "num_limited_groups": 1,
        "num_shared_experts": 1, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
        "route_scale": 2.826, "score_func": "sigmoid",
        "sliding_window": 2048, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_mm": True, "vocab_size": 200192,
        "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 8}
    config = CELL["config"]
    reduced = ["num_hidden_layers", "num_dense_layers", "num_experts",
               "vocab_size"]
    assert config["reduced"] == reduced
    entry = BOOK.doc["configs"][5]
    assert entry["name"] == "trinity-mini" and entry["reduced"] == reduced
    assert entry["source"] == (
        "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json")
    assert "catalog row Trinity-Mini" in entry["why"]
    for key, value in catalog.items():
        if key in reduced:
            assert config["published"][key] == value, key
            assert config[key] != value, key
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["num_dense_layers"],
            config["num_experts"], config["vocab_size"]) == (
                5, 1, 16, 200192 // 8)
    assert config["layers_kept"] == [1, 2, 3, 4, 5]
    p = config["cli"]["model_params"]
    letters = {"sliding_attention": "w", "full_attention": "a"}
    assert p["layer_pattern"] == "".join(
        letters[config["layer_types"][i]] for i in config["layers_kept"])
    assert (p["dim"], p["num_heads"], p["num_kv_heads"], p["head_dim"],
            p["window"], p["dense_ffn_dim"], p["ffn_dim"], p["moe_experts"],
            p["moe_top_k"], p["moe_shared_experts"], p["moe_route_scale"],
            p["rope_theta"], p["norm_eps"]) == (
                2048, 32, 4, 128, 2048, 6144, 1024, 128, 8, 1, 2.826,
                10000, 1e-05)
    assert (p["rope_kinds"], p["qk_norm"], p["attn_gate"], p["post_norms"],
            p["moe_router"], p["moe_norm_topk"], p["moe_aux_weight"],
            p["tied_embeddings"], p["embed_scale"], p["remat"],
            p["warmup_steps"]) == (
                "w", "head", True, True, "sigmoid_bias", True, 0, False,
                0.02, True, 2000)
    assert p["embed_multiplier"] == pytest.approx(2048 ** 0.5, rel=1e-12)
    assert (p["num_layers"], p["dense_layers"], p["moe_experts_held"],
            p["vocab_size"]) == tuple(config[k] for k in reduced)
    assert p["seq_len"] == config["seq_len"] == 16384
    # the arithmetic of the cut: 16 B a parameter
    attention = (3 * 2048 * 4096 + 2 * 2048 * 512      # q, o, gate; k, v
                 + 2 * 128 + 4 * 2048)                 # six norms' scales
    expert_layer = (attention + 2048 * 128 + 128 + 3 * 2048 * 1024
                    + 16 * 3 * 2048 * 1024)
    total = (attention + 3 * 2048 * 6144 + 4 * expert_layer
             + 2 * 25024 * 2048 + 2048)
    assert total == 705_474_304
    assert 16 * total == pytest.approx(11.29e9, rel=1e-3)
    assert "705.4 M" in config["reduced_why"]
    assert "8 chips share each layer" in config["deployment"]
    for key in ("attention_gate", "sandwich_norms", "qk_norm",
                "nope_on_full_layers", "router_epsilon", "mup",
                "initializer_range", "optimizer", "remat", "compute_dtype",
                "seq_len", "expert_bias"):
        assert key in config["assumed"], key
    for key in ("attention_gate", "sandwich_norms", "qk_norm",
                "nope_on_full_layers", "router_epsilon", "mup"):
        assert config["assumed"][key].startswith(
            "afmoe modelling code, recalled; family description agrees")


def test_the_products_tree_is_the_configurations_parameter_count():
    import jax

    from benchmark.lib.runner import params_string
    from elasticdl_tpu.models.spec import load_model_spec

    cli = CELL["config"]["cli"]
    spec = load_model_spec(cli["model_zoo"],
                           model_params=params_string(cli["model_params"]))
    shapes = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree_util.tree_leaves(shapes)) == (
        705_474_304)


def test_product_loss_and_routing_agree_with_the_reference_at_tiny_size():
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
    routing = json.loads([l for l in done.stderr.splitlines()
                          if l.startswith('{"routing')][-1])
    assert routing["routing_same_input"] >= routing["floor"]
    layers = json.loads([l for l in done.stderr.splitlines()
                         if l.startswith('{"layers')][-1])
    assert set(layers["layers_same_input"]) == {
        "attention", "shared_expert", "routed_experts"}
    assert max(layers["layers_same_input"].values()) <= layers["ceiling"]
    assert ("layer stack: pattern=wwaww lead=w period=waw periods=1 tail=w"
            in done.stderr)
    assert "shared_expert=64" in done.stderr
    assert ("attention block: rows=64 heads=4 kv_heads=2 head_dim=64 "
            "qk_norm=head gate=1 out_norms=1") in done.stderr


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
