"""What the ``lfm2-24b-a2b`` configuration brought: the short
convolution's calls told from the flash and grouped-matmul kernels' and
back, its operation count against a hand count, its four readers on a
fixture log and trace, the configuration's file against the catalog's
numbers, and its plain reference against the product at tiny sizes."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.lib import kernels, manifest

ROOT = os.path.dirname(manifest.BENCH_DIR)
BOOK = manifest.Manifest(ROOT)
CELL = BOOK.cell("lfm2-24b-a2b.seq8192")

TAIL = 'custom-call(%a, %b, %c), custom_call_target="tpu_custom_call"'
# The calls of one step as the compiled program names them (operands cut).
SCONV = {
    "fwd": "%sconv_fwd.7 = bf16[32768,2048]{1,0:T(8,128)(2,1)} " + TAIL,
    "fwd remat": "%checkpoint_sconv_fwd__.3 = bf16[32768,2048]{1,0} " + TAIL,
    "bwd": "%transpose_jvp_sconv_bwd__.1 = (bf16[32768,6144]{1,0:T(8,128)"
           "(2,1)}, f32[512,2048]{1,0:T(8,128)S(1)}) " + TAIL,
}
GMM = {
    "fwd up": "%gmm_nn.33 = bf16[131072,1536]{1,0} " + TAIL,
    "dlhs": "%gmm_nt.6 = bf16[131072,2048]{1,0} " + TAIL,
    "drhs": "%gmm_tn.6 = bf16[16384,1536]{1,0} " + TAIL,
}
FLASH = {
    "fwd": "%checkpoint_flash_fwd__.4 = (bf16[128,8192,64]{2,1,0}, f32[128,1,8192]"
           "{2,1,0}, f32[128,1,8192]{2,1,0}) " + TAIL,
    "dq": "%flash_dq.9 = bf16[128,8192,64]{2,1,0} " + TAIL,
    "dkv": "%flash_dkv.10 = (bf16[128,8192,64]{2,1,0}, bf16[128,8192,64]"
           "{2,1,0}) " + TAIL,
}


def test_each_kernels_classify_leaves_the_others_calls_alone():
    flash = manifest.load_named("kernels", "flash_attention")
    gmm = manifest.load_named("kernels", "grouped_matmul")
    sconv = manifest.load_named("kernels", "short_conv")
    shapes = dict(rows=131072, widths=(2048, 1536), groups=8)
    for name, hlo in SCONV.items():
        parsed = kernels.parse_call(hlo)
        assert flash.classify(*parsed, hlo=hlo) is None, name
        assert gmm.classify(*parsed, hlo=hlo, **shapes) is None, name
        assert sconv.classify(*parsed, hlo=hlo)[0] == name.split()[0]
    for name, hlo in list(GMM.items()) + list(FLASH.items()):
        assert sconv.classify(*kernels.parse_call(hlo), hlo=hlo) is None, name
    for name, hlo in FLASH.items():
        assert flash.classify(*kernels.parse_call(hlo), hlo=hlo)[0] == name
    # the door lib/kernels.roofline_share uses hands no text over
    assert sconv.classify(*kernels.parse_call(SCONV["fwd"])) is None


def test_short_conv_calls_are_counted_from_their_shapes():
    sconv = manifest.load_named("kernels", "short_conv")
    rows, e = 32768, 2048
    fwd = sconv.classify(*kernels.parse_call(SCONV["fwd"]), hlo=SCONV["fwd"])
    bwd = sconv.classify(*kernels.parse_call(SCONV["bwd"]), hlo=SCONV["bwd"])
    # forward: B, C, u read and the result written; backward: those three
    # and dout read, dB, dC, du written
    assert fwd == ("fwd", (7 * rows * e, 4 * rows * e * 2))
    assert bwd == ("bwd", (21 * rows * e, 7 * rows * e * 2))
    # bound by memory on a v5e: 0.655 ms and 1.147 ms at 819 GB/s
    from benchmark.lib import peaks

    for (_, (flops, nbytes)), ms in ((fwd, 0.6554), (bwd, 1.1469)):
        least, bound = peaks.roofline_seconds(flops, nbytes, "TPU v5 lite")
        assert bound == "memory"
        assert least * 1e3 == pytest.approx(ms, rel=1e-3)
    assert sconv.call(10, 4, "fwd", taps=2) == (5 * 40, 4 * 40 * 2)


def test_lm_hybrid_moe_counts_the_active_operations_of_a_record():
    module = manifest.load_named("opcounts", "lm_hybrid_moe")
    config = CELL["config"]
    hidden, tokens = 2048, 8192
    parts = module.per_token(config)
    assert parts == {
        "conv": 4 * (hidden * 6144 + hidden * hidden),
        "attention": 2 * hidden * 2048 + 2 * hidden * 512,
        "dense": 3 * hidden * 11776,
        "router": 4 * hidden * 64,
        "experts": 4 * (4 * 8 / 64) * 3 * hidden * 1536,
        "head": hidden * 8192,
    }
    total = sum(parts.values())
    assert total == pytest.approx(186.2e6, rel=2e-3)   # the issue's count
    shares = {k: v / total for k, v in parts.items()}
    assert shares["dense"] == pytest.approx(0.39, abs=0.01)
    assert shares["conv"] == pytest.approx(0.36, abs=0.01)
    assert shares["experts"] == pytest.approx(0.10, abs=0.01)
    assert shares["head"] == pytest.approx(0.09, abs=0.01)
    attention = 2 * tokens * tokens * 32 * 64       # one attention layer
    assert module.train_flops(config) == 3 * (
        tokens * 2 * total + attention)
    assert module.train_flops(config) == pytest.approx(9.98e12, rel=2e-3)


LOG = """\
[2026-09-28 02:00:10,000] [INFO] [worker-0] [w:1:x] moe load: step=8 layers=4 rows=60000 max=9000 mean=1875.0 padded_rows=900 moved=524288
[2026-09-28 02:00:20,000] [INFO] [worker-0] [w:1:x] step 16 loss 9.1 (version 16)
[2026-09-28 02:00:20,001] [INFO] [worker-0] [w:1:x] moe load: step=16 layers=4 rows=65536 max=4096 mean=2048.0 padded_rows=1000 moved=524288
[2026-09-28 02:00:30,000] [INFO] [worker-0] [w:1:x] moe load: step=24 layers=4 rows=32768 max=4096 mean=1024.0 padded_rows=1100 moved=524288
[2026-09-28 02:00:50,000] [INFO] [worker-0] [w:1:x] moe load: step=40 layers=4 rows=1 max=1 mean=1.0 padded_rows=0 moved=524288
"""
# a program that holds every expert says no more than it did (a parent)
OLD_LOG = LOG.replace(" moved=524288", "")


def _run(log=LOG, custom_calls=None, config=None):
    from benchmark.lib import job

    at = lambda clock: job.stamp_seconds("[2026-09-28 %s,000] x" % clock)
    trace = None if custom_calls is None else {
        "custom_calls": custom_calls, "busy_s": 6.0}
    return types.SimpleNamespace(
        job=types.SimpleNamespace(text=log), trace=trace,
        times={"open": at("02:00:15"), "close": at("02:00:40")},
        config=config or CELL["config"], traffic=CELL["traffic"],
        cell={"chips": 1}, device={"kind": "TPU v5 lite"})


def test_the_load_readers_take_the_lines_that_say_moved():
    dead = BOOK.reader("moe.dead_row_share")
    spread = BOOK.reader("moe.held_load_max_over_mean")
    assert dead(_run()) == pytest.approx(
        100 * (1 - (65536 + 32768) / (2 * 524288)))
    assert spread(_run()) == pytest.approx((2.0 + 4.0) / 2)
    assert dead(_run(log=OLD_LOG)) is None
    assert spread(_run(log=OLD_LOG)) is None
    quiet = _run(log="[2026-09-28 02:00:20,000] [INFO] step 8 loss 1.0\n")
    assert dead(quiet) is None and spread(quiet) is None


def test_the_kernel_readers_take_the_short_conv_calls_alone(capsys):
    roofline = BOOK.reader("kernel.short_conv_roofline")
    share = BOOK.reader("kernel.short_conv_share")
    fwd = 4 * 32768 * 2048 * 2 / 819e9           # 0.655 ms
    bwd = 7 * 32768 * 2048 * 2 / 819e9
    calls = {SCONV["fwd"]: [8 * fwd, 4.0],        # 50%
             SCONV["fwd remat"]: [4 * fwd, 4.0],  # 100%
             SCONV["bwd"]: [16 * bwd, 4.0],       # 25%
             GMM["fwd up"]: [0.5, 10.0], FLASH["fwd"]: [0.5, 10.0]}
    run = _run(custom_calls=calls)
    least, taken = 8 * fwd + 4 * bwd, 12 * fwd + 16 * bwd
    assert roofline(run) == pytest.approx(100 * least / taken)
    assert share(run) == pytest.approx(100 * taken / 6.0)
    lines = [l for l in capsys.readouterr().err.splitlines()
             if "short_conv" in l]
    assert [l.split()[2].rstrip(":") for l in lines] == ["bwd", "fwd"]
    assert "memory-bound" in lines[0] and "(25.0%)" in lines[0]
    assert "(66.7%)" in lines[1] and "8.0 calls" in lines[1]
    # the flash reader counts flash calls alone, beside these
    flash = BOOK.reader("kernel.flash_attention_roofline")(run)
    assert 0 < flash < 100
    # a parent (no such call), an untraced run, a configuration without it
    others = {GMM["fwd up"]: [0.5, 10.0], FLASH["fwd"]: [0.5, 10.0]}
    assert roofline(_run(custom_calls=others)) is None
    assert share(_run(custom_calls=others)) is None
    assert roofline(_run()) is None and share(_run()) is None
    olmoe = BOOK.cell("olmoe1b7b.seq4096")["config"]
    assert roofline(_run(custom_calls=calls, config=olmoe)) is None


def test_the_new_metrics_are_the_new_cells_alone():
    mine = {m["name"] for m in CELL["per_layer"]}
    new = {"kernel.short_conv_roofline", "kernel.short_conv_share",
           "moe.dead_row_share", "moe.held_load_max_over_mean"}
    for other in ("olmo1b.seq2048", "olmo1b.seq2048-dp4",
                  "olmoe1b7b.seq4096"):
        theirs = {m["name"] for m in BOOK.cell(other)["per_layer"]}
        assert not theirs & new, other
    dense = {m["name"] for m in BOOK.cell("olmo1b.seq2048")["per_layer"]}
    # .. and PR 34's row kernel's share, which the share cells list
    assert mine - dense == new | {"kernel.row_move_share"}
    assert dense - mine == set()
    # the held-share cell stays off the lists whose readers count every
    # expert's rows from the configuration (ISSUE 31, trap 1)
    olmoe_only = {m["name"] for m in BOOK.doc["per_layer"]
                  if m.get("workloads") == ["olmoe1b7b.seq4096"]}
    assert olmoe_only == {
        "kernel.grouped_matmul_roofline", "kernel.grouped_matmul_share",
        "moe.load_max_over_mean", "moe.padded_row_share"}
    assert CELL["chips"] == 1
    flags = CELL["traffic"]["flags"]
    assert flags["batch_size"] * CELL["config"]["seq_len"] == 32768
    assert flags["log_loss_steps"] % flags["num_minibatches_per_task"] == 0


def test_the_configuration_keeps_every_published_width():
    """The catalog row's numbers, key by key: only the four keys of
    ``reduced`` differ, each with its published value beside it; the
    model_params run those sizes."""
    catalog = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_dense_layers": 2,
        "num_experts": 64, "num_experts_per_tok": 4,
        "num_hidden_layers": 40, "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 65536}
    config = CELL["config"]
    reduced = ["num_hidden_layers", "num_dense_layers", "num_experts",
               "vocab_size"]
    assert config["reduced"] == reduced
    assert [e for e in BOOK.doc["configs"]
            if e["name"] == "lfm2-24b-a2b"][0]["reduced"] == reduced
    for key, value in catalog.items():
        if key in reduced:
            assert config["published"][key] == value, key
            assert config[key] != value, key
        else:
            assert config[key] == value, key
    kinds = config["layer_types"]
    assert len(kinds) == 40 and [i for i, k in enumerate(kinds)
                                 if k == "full_attention"] == list(
                                     range(2, 40, 4))
    assert config["layer_types_run"] == [kinds[i]
                                         for i in config["layers_kept"]]
    p = config["cli"]["model_params"]
    assert (p["dim"], p["num_heads"], p["num_kv_heads"], p["ffn_dim"],
            p["dense_ffn_dim"], p["moe_experts"], p["moe_top_k"],
            p["conv_kernel"]) == (2048, 32, 8, 1536, 11776, 64, 4, 3)
    assert p["layer_pattern"] == "".join(
        "a" if k == "full_attention" else "c"
        for k in config["layer_types_run"])
    assert (p["num_layers"], p["dense_layers"], p["moe_experts_held"],
            p["vocab_size"]) == tuple(config[k] for k in reduced)
    assert set(config["kernels"]) == {"flash_attention", "grouped_matmul",
                                      "short_conv"}


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
    assert 0.5 < routing["routing_end_to_end"] <= 1.0
