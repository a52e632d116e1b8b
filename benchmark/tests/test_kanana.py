"""What the ``kanana-2-30b-a3b`` configuration brought: the named
latent-attention calls told from the equal-width kernels' and back,
their operations and bytes against hand counts, its two readers on a
fixture trace, the configuration's file against the catalog's numbers,
and its plain reference against the product at tiny sizes."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.lib import kernels, manifest, peaks

ROOT = os.path.dirname(manifest.BENCH_DIR)
BOOK = manifest.Manifest(ROOT)
NAME = "kanana-2-30b-a3b.seq16384"
CELL = BOOK.cell(NAME)

TAIL = 'custom-call(%a, %b, %c), custom_call_target="tpu_custom_call"'
OUT = "bf16[32,16384,128]{2,1,0}"
ROPE = "bf16[32,16384,64]{2,1,0}"
PART = "f32[32,16384,64]{2,1,0}"
STAT = "f32[32,1,16384]{2,1,0}"
# The calls of one step as the compiled program names them (operands cut).
LATENT = {
    "fwd": "%checkpoint_flash_fwd_qk192_v128__.2 = (" + ", ".join(
        [OUT, STAT, STAT]) + ") " + TAIL,
    "dq": "%flash_dq_qk192_v128.1 = (" + OUT + ", " + ROPE + ") " + TAIL,
    "dkv": "%transpose_jvp_flash_dkv_qk192_v128__.7 = (" + ", ".join(
        [OUT, OUT, PART]) + ") " + TAIL,
}
# the equal-width kernels' calls, a parent's, the other kernels'
FLASH = "%flash_fwd.3 = (" + ", ".join([OUT, STAT, STAT]) + ") " + TAIL
BANDED = "%flash_dq_w4096.5 = " + OUT + " " + TAIL
UNNAMED = "%custom-call.9 = " + OUT + " " + TAIL
GMM = "%gmm_nn.33 = bf16[24576,768]{1,0} " + TAIL
PAIRS = 134_225_920          # 16,384 x 16,385 / 2


def test_the_named_calls_are_told_by_kernel_and_widths():
    latent = manifest.load_named("kernels", "latent_attention")
    band = manifest.load_named("kernels", "banded_attention")
    for kind, hlo in LATENT.items():
        got = latent.classify(*kernels.parse_call(hlo), hlo=hlo, heads=32,
                              d_rope=64)
        assert got == (kind, latent.call(1, 32, 16384, 192, 128, kind, 64))
        # without the configuration's head count and RoPE width: one
        # sequence of 32, every key a head's own
        assert latent.classify(*kernels.parse_call(hlo), hlo=hlo) == (
            kind, latent.call(1, 32, 16384, 192, 128, kind, 0))
    for hlo in (FLASH, BANDED, UNNAMED, GMM):
        assert latent.classify(*kernels.parse_call(hlo), hlo=hlo) is None
    # the banded and the plain readers leave a name with widths alone
    # (PR 65; before, the banded pattern saw the base name and only the
    # configurations' lists kept the two apart)
    flash = manifest.load_named("kernels", "flash_attention")
    for other in (band, flash):
        assert other.classify(*kernels.parse_call(LATENT["fwd"]),
                              hlo=LATENT["fwd"]) is None
    assert "banded_attention" not in CELL["config"]["kernels"]
    # no text handed over, or a named call as an operand: nothing
    assert latent.classify(*kernels.parse_call(LATENT["fwd"])) is None
    operand = ("%fusion.4 = bf16[32,16384,128]{2,1,0} custom-call("
               '%flash_fwd_qk192_v128.3), '
               'custom_call_target="tpu_custom_call"')
    assert latent.classify(*kernels.parse_call(operand),
                           hlo=operand) is None


def test_a_calls_operations_and_bytes_by_hand():
    latent = manifest.load_named("kernels", "latent_attention")
    assert latent.pairs(16384) == PAIRS and latent.pairs(4) == 10
    rows, H = 16384, 32
    flops, nbytes = latent.call(1, H, rows, 192, 128, "fwd", 64)
    assert flops == 2 * H * PAIRS * (192 + 128)
    # q at 192 a head; k_nope a head and ONE RoPE key; v, o; l and m
    assert nbytes == 2 * rows * (H * 192 + H * 128 + 64 + 2 * H * 128) + (
        2 * 4 * rows * H)
    assert latent.call(1, H, rows, 192, 128, "dq", 64)[0] == (
        2 * H * PAIRS * (192 + 128 + 192))
    assert latent.call(1, H, rows, 192, 128, "dkv", 64)[0] == (
        2 * H * PAIRS * (192 + 128 + 128 + 192))
    # another RoPE width moves the keys' bytes: the configuration's is
    # handed down, the name carries the scores' whole width alone
    assert latent.call(1, H, rows, 192, 128, "fwd", 32)[1] - nbytes == (
        2 * rows * (H - 1) * 32)
    # the forward: 13.95 ms of the MXU's time on a v5e; compute-bound
    least, bound = peaks.roofline_seconds(flops, nbytes, "TPU v5 lite")
    assert bound == "compute"
    assert least * 1e3 == pytest.approx(
        2 * 32 * PAIRS * 320 / 197e12 * 1e3, rel=1e-3)
    # two sequences' calls are twice one's
    assert latent.call(2, H, rows, 192, 128, "dkv", 64) == tuple(
        2 * n for n in latent.call(1, H, rows, 192, 128, "dkv", 64))


def test_lm_mla_moe_counts_the_active_operations_of_a_record():
    module = manifest.load_named("opcounts", "lm_mla_moe")
    config = CELL["config"]
    E, T = 2048, 16384
    parts = module.per_token(config)
    assert parts == {
        "attention": 5 * (E * 6144 + E * 576 + 512 * 8192 + 4096 * E),
        "dense": 3 * E * 6144,
        "router": 4 * E * 128,
        "shared": 4 * 3 * E * 1536,
        "experts": 4 * (6 * 16 / 128) * 3 * E * 768,
        "head": E * 16032,
    }
    total = sum(parts.values())
    assert total == pytest.approx(255.3e6, rel=2e-3)
    assert parts["attention"] == pytest.approx(131.7e6, rel=2e-3)
    assert parts["experts"] == pytest.approx(14.2e6, rel=5e-3)
    scores = module.scores_per_sequence(config)
    assert scores == 5 * 32 * PAIRS * 320
    assert scores / T == pytest.approx(419.5e6, rel=2e-3)   # the issue's
    # the scores over the forward's multiply-adds: 62%
    assert scores / (T * total + scores) == pytest.approx(0.62, abs=0.005)
    assert module.train_flops(config) == 3 * 2 * (T * total + scores)
    assert module.train_flops(config) == pytest.approx(66.33e12, rel=2e-3)


def _run(custom_calls=None, config=None):
    trace = None if custom_calls is None else {
        "custom_calls": custom_calls, "busy_s": 6.0}
    return types.SimpleNamespace(
        trace=trace, config=config or CELL["config"],
        traffic=CELL["traffic"], cell={"chips": 1},
        device={"kind": "TPU v5 lite"})


# The fused backward (PR 41) as this cell's compiled program names it (my
# chip run, PR 53, layouts cut): dk, dv, each head's float32 part of the
# RoPE key's gradient, dq and dq's RoPE part.
BWD = ("%flash_bwd_qk192_v128.14 = (" + ", ".join(
    ["bf16[32,16384,128]", "bf16[32,16384,128]", "f32[32,16384,64]",
     "bf16[32,16384,128]", "bf16[32,16384,64]"]) + ") custom-call("
    "s32[136] %copy-done.245, s32[136] %copy-done.247, bf16[32,16384,128] "
    "%convolution_bitcast_fusion.14, bf16[32,16384,128] "
    "%convolution_bitcast_fusion.15, bf16[32,16384,128] %bitcast.1574, "
    "bf16[32,16384,128] %get-tuple-element.6852, f32[32,1,16384] "
    "%bitcast.1626, f32[32,1,16384] %reshape.5570, bf16[1,16384,64] "
    "%pad_maximum_fusion.14, bf16[32,16384,64] %bitcast.1575), "
    'custom_call_target="tpu_custom_call"')
JVP_FWD = ("%jvp_flash_fwd_qk192_v128_.1 = (" + ", ".join(
    [OUT, STAT, STAT]) + ") " + TAIL)


def test_the_fused_latent_backward_is_told_by_name_and_counted(capsys):
    latent = manifest.load_named("kernels", "latent_attention")
    H, rows = 32, 16384
    got = latent.classify(*kernels.parse_call(BWD), hlo=BWD, heads=32,
                          d_rope=64)
    assert got == ("bwd", latent.call(1, 32, 16384, 192, 128, "bwd", 64))
    assert latent.classify(*kernels.parse_call(JVP_FWD), hlo=JVP_FWD,
                           heads=32, d_rope=64)[0] == "fwd"
    flops, nbytes = got[1]
    # S, dQ, dK over 192 and dP, dV over 128: five products where the
    # forward has two and the split pair made seven
    assert flops == 2 * H * PAIRS * (3 * 192 + 2 * 128)
    fwd = latent.call(1, H, rows, 192, 128, "fwd", 64)[0]
    pair = sum(latent.call(1, H, rows, 192, 128, kind, 64)[0]
               for kind in ("dq", "dkv"))
    assert fwd * 832 == flops * 320 and flops < pair
    # q, dq at 192 a head; k, dk at 128 a head and ONE RoPE plane; v, o,
    # dO, dv at 128; the two statistics; the float32 parts of dk's RoPE
    k = rows * (H * 128 + 64)
    assert nbytes == 2 * (2 * rows * H * 192 + 2 * k + 4 * rows * H * 128
                          ) + 2 * 4 * rows * H + 4 * rows * H * 64
    least, bound = peaks.roofline_seconds(flops, nbytes, "TPU v5 lite")
    # 36.3 ms of the MXU's time on a v5e, under the 51.1 the call takes
    assert bound == "compute" and least * 1e3 == pytest.approx(36.28, rel=1e-3)
    # the readers on the ledger's PR 63 line of this cell: the share is
    # every flash_* second over the busy time (15.8% while the backward
    # went unread), forward and backward apart on stderr, none over 100%
    calls = {BWD: [1.991911361, 39.0],
             BWD.replace(".14 =", ".13 ="): [0.459672149, 9.0],
             LATENT["fwd"].replace("__.2 =", ".17 ="): [0.746472143, 36.0],
             JVP_FWD: [0.186619045, 9.0], GMM: [0.5, 10.0]}
    run = _run(custom_calls=calls)
    run.trace["busy_s"] = 5.97
    share = BOOK.reader("kernel.latent_attention_share")(run)
    assert share == pytest.approx(100 * 3.384674698 / 5.97)
    assert share > 50
    assert 60 < BOOK.reader("kernel.latent_attention_roofline")(run) < 100
    lines = [l for l in capsys.readouterr().err.splitlines()
             if "latent_attention" in l]
    assert [l.split()[2] for l in lines] == ["bwd:", "fwd:"]
    assert "48.0 calls" in lines[0] and "45.0 calls" in lines[1]


def test_the_readers_take_the_named_latent_calls_alone(capsys):
    latent = manifest.load_named("kernels", "latent_attention")
    roofline = BOOK.reader("kernel.latent_attention_roofline")
    share = BOOK.reader("kernel.latent_attention_share")
    least = {kind: peaks.roofline_seconds(
        *latent.call(1, 32, 16384, 192, 128, kind, 64), "TPU v5 lite")[0]
        for kind in LATENT}
    # 6 steps of 5 layers: the forward at half its roofline (and run
    # twice a step under remat), dq and dk-dv at a quarter
    times = {"fwd": 2, "dq": 4, "dkv": 4}
    counts = {"fwd": 60.0, "dq": 30.0, "dkv": 30.0}
    calls = {LATENT[k]: [counts[k] * least[k] * times[k], counts[k]]
             for k in LATENT}
    calls.update({GMM: [0.5, 10.0], UNNAMED: [0.5, 10.0],
                  FLASH: [0.5, 10.0]})
    run = _run(custom_calls=calls)
    floor = sum(counts[k] * least[k] for k in LATENT)
    taken = sum(counts[k] * least[k] * times[k] for k in LATENT)
    assert roofline(run) == pytest.approx(100 * floor / taken)
    assert share(run) == pytest.approx(100 * taken / 6.0)
    lines = [l for l in capsys.readouterr().err.splitlines()
             if "latent_attention" in l]
    assert [l.split()[2] for l in lines] == ["dkv:", "dq:", "fwd:"]
    assert "(25.0%)" in lines[0] and "30.0 calls" in lines[0]
    assert "(50.0%)" in lines[2] and "60.0 calls" in lines[2]
    # a parent (no named call), an untraced run, another configuration
    others = {GMM: [0.5, 10.0], UNNAMED: [0.5, 10.0], FLASH: [0.5, 10.0]}
    for reader in (roofline, share):
        assert reader(_run(custom_calls=others)) is None
        assert reader(_run()) is None
        other = BOOK.cell("smallthinker-21b-a3b.seq16384")["config"]
        assert reader(_run(custom_calls=calls, config=other)) is None


def test_the_cells_metrics_hold_the_new_ones_and_the_held_shares():
    """``>=``, not ``==``: a later PR may list the cell under more
    (PERF.md section 7 (14))."""
    mine = {m["name"] for m in CELL["per_layer"]}
    new = {"kernel.latent_attention_roofline",
           "kernel.latent_attention_share"}
    assert mine >= new | {"moe.dead_row_share",
                          "moe.held_load_max_over_mean",
                          "kernel.row_move_share", "trainer.mfu",
                          "trainer.peak_hbm_gb", "kernel.mosaic_share"}
    assert {m["name"] for m in CELL["end_to_end"]} >= {"records_per_s",
                                                       "setup_s"}
    # the two later cells with a latent head joined the two lists
    latent = (NAME, "xing4.0-29b-a4b.seq4096", "ling-3.0-flash.seq16384")
    for entry in BOOK.doc["workloads"]:
        theirs = {m["name"] for m in BOOK.cell(entry["name"])["per_layer"]}
        assert (theirs >= new) == (entry["name"] in latent), entry["name"]
        assert theirs >= new or not theirs & new, entry["name"]
    # the plain reader takes the names without widths: not this cell
    assert "kernel.flash_attention_roofline" not in mine
    assert set(CELL["config"]["kernels"]) >= {"latent_attention",
                                              "grouped_matmul"}
    assert CELL["chips"] == 1
    flags = CELL["traffic"]["flags"]
    assert (flags["batch_size"], flags["num_minibatches_per_task"],
            flags["num_workers"], flags["log_loss_steps"]) == (1, 4, 1, 8)
    assert CELL["traffic"]["generator"] == "tokens_zipf_fixed_ids"
    assert flags["batch_size"] * CELL["config"]["seq_len"] == 16384


def test_the_configuration_keeps_every_published_width():
    """The catalog row's numbers, key by key: only the three keys of
    ``reduced`` differ, each with its published value beside it; the
    model_params run those sizes."""
    catalog = {
        "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "kv_lora_rank": 512,
        "max_position_embeddings": 32768, "model_type": "deepseek_v3",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 128, "n_shared_experts": 2,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "num_hidden_layers": 48,
        "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_interleave": True,
        "rope_scaling": None, "rope_theta": 1000000,
        "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128256}
    config = CELL["config"]
    reduced = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert config["reduced"] == reduced
    entry = [e for e in BOOK.doc["configs"]
             if e["name"] == "kanana-2-30b-a3b"][0]
    assert entry["reduced"] == reduced
    assert entry["source"] == (
        "https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/"
        "blob/main/config.json")
    for key, value in catalog.items():
        if key in reduced:
            assert config["published"][key] == value, key
            assert config[key] != value, key
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 16, 128256 // 8)
    assert config["layers_kept"] == [0, 1, 2, 3, 4]
    p = config["cli"]["model_params"]
    assert (p["dim"], p["num_heads"], p["kv_latent_rank"], p["qk_nope_dim"],
            p["qk_rope_dim"], p["v_head_dim"], p["dense_ffn_dim"],
            p["ffn_dim"], p["moe_experts"], p["moe_top_k"],
            p["moe_shared_experts"], p["moe_route_scale"], p["rope_theta"],
            p["norm_eps"]) == (2048, 32, 512, 128, 64, 128, 6144, 768, 128,
                               6, 2, 2.448, 1000000, 1e-06)
    assert (p["moe_router"], p["moe_norm_topk"], p["moe_aux_weight"],
            p["tied_embeddings"], p["embed_scale"], p["remat"],
            p["warmup_steps"], p["dense_layers"]) == (
                "sigmoid_bias", True, 0, False, 1.0, True, 2000, 1)
    assert (p["num_layers"], p["moe_experts_held"],
            p["vocab_size"]) == tuple(config[k] for k in reduced)
    assert p["seq_len"] == config["seq_len"] == 16384
    # the arithmetic of the cut: 16 B a parameter
    attention = (2048 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048
                 + 512 + 2 * 2048)
    expert_layer = (attention + 2048 * 128 + 128 + 3 * 2048 * 1536
                    + 16 * 3 * 2048 * 768)
    total = (attention + 3 * 2048 * 6144 + 4 * expert_layer
             + 2 * 16032 * 2048 + 2048)
    assert total == 575_955_968
    assert 16 * total == pytest.approx(9.22e9, rel=1e-3)
    assert "576.0 M" in config["reduced_why"]
    assert "8 chips share each layer" in config["deployment"]
    for key in ("rope_layout", "router", "shared_experts", "balance_loss",
                "optimizer", "remat", "compute_dtype", "seq_len",
                "embedding"):
        assert key in config["assumed"], key


def test_the_products_tree_is_the_configurations_parameter_count():
    import jax

    from benchmark.lib.runner import params_string
    from elasticdl_tpu.models.spec import load_model_spec

    cli = CELL["config"]["cli"]
    spec = load_model_spec(cli["model_zoo"],
                           model_params=params_string(cli["model_params"]))
    shapes = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree_util.tree_leaves(shapes)) == (
        575_955_968)


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
    assert "layer stack: pattern=aaa lead=a period=a periods=2" in done.stderr
    assert "shared_expert=128" in done.stderr
    assert "latent attention: heads=4 t=64 rank=32" in done.stderr


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
