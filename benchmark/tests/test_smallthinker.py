"""What the ``smallthinker-21b-a3b`` configuration brought: the named
flash calls told by their window and from the other kernels' and back,
their operation count against hand counts, its three readers on a
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
NAME = "smallthinker-21b-a3b.seq16384"
CELL = BOOK.cell(NAME)

TAIL = 'custom-call(%a, %b, %c), custom_call_target="tpu_custom_call"'
OUT = "bf16[28,16384,128]{2,1,0}"
STAT = "f32[28,1,16384]{2,1,0}"
# The calls of one step as the compiled program names them (operands cut).
FLASH = {
    ("fwd", 0): "%flash_fwd.3 = (" + ", ".join([OUT, STAT, STAT]) + ") "
                + TAIL,
    ("dq", 0): "%flash_dq.1 = " + OUT + " " + TAIL,
    ("dkv", 0): "%flash_dkv.1 = (" + OUT + ", " + OUT + ") " + TAIL,
    ("fwd", 4096): "%checkpoint_flash_fwd_w4096__.2 = (" + ", ".join(
        [OUT, STAT, STAT]) + ") " + TAIL,
    ("dq", 4096): "%flash_dq_w4096.5 = " + OUT + " " + TAIL,
    ("dkv", 4096): "%transpose_jvp_flash_dkv_w4096__.7 = (" + OUT + ", "
                   + OUT + ") " + TAIL,
}
# a parent's calls carry no name; the other kernels' carry their own
UNNAMED = "%custom-call.9 = " + OUT + " " + TAIL
GMM = "%gmm_nn.33 = bf16[49152,768]{1,0} " + TAIL
SCONV = "%sconv_fwd.7 = bf16[32768,2048]{1,0} " + TAIL
FULL_PAIRS, BAND_PAIRS = 134_225_920, 58_722_304     # the issue's counts


def test_the_named_calls_are_told_by_kernel_and_window():
    band = manifest.load_named("kernels", "banded_attention")
    flash = manifest.load_named("kernels", "flash_attention")
    gmm = manifest.load_named("kernels", "grouped_matmul")
    for (kind, window), hlo in FLASH.items():
        got = band.classify(*kernels.parse_call(hlo), hlo=hlo)
        assert got[0] == kind and got[2] == window, (kind, window)
        assert got[1] == band.call(28, 16384, 128, kind, window)
        # the plain reader takes a full causal layer's names alone
        plain = flash.classify(*kernels.parse_call(hlo), hlo=hlo)
        assert plain is None if window else plain == (kind, got[1])
        assert gmm.classify(*kernels.parse_call(hlo), hlo=hlo, rows=98304,
                            widths=(2560, 768), groups=16) is None
    for hlo in (UNNAMED, GMM, SCONV):
        assert band.classify(*kernels.parse_call(hlo), hlo=hlo) is None
    # the door lib/kernels.roofline_share uses hands no text over
    assert band.classify(*kernels.parse_call(FLASH[("fwd", 0)])) is None
    # an operand that is a named call's result names nothing
    operand = ("%fusion.4 = bf16[28,16384,128]{2,1,0} custom-call("
               '%flash_fwd.3), custom_call_target="tpu_custom_call"')
    assert band.classify(*kernels.parse_call(operand), hlo=operand) is None


def test_a_bands_pairs_and_a_calls_operations_by_hand():
    band = manifest.load_named("kernels", "banded_attention")
    assert band.pairs(16384) == FULL_PAIRS
    assert band.pairs(16384, 4096) == BAND_PAIRS
    assert band.pairs(4, 2) == 1 + 2 + 2 + 2          # by hand
    assert band.pairs(4, 4) == band.pairs(4) == band.pairs(4, 9) == 10
    assert BAND_PAIRS / FULL_PAIRS == pytest.approx(0.4375, abs=1e-3)
    flops, nbytes = band.call(28, 16384, 128, "fwd", 4096)
    assert flops == 2 * 2 * 28 * BAND_PAIRS * 128
    # q, k, v, o and the two float32 row statistics
    assert nbytes == 4 * 28 * 16384 * 128 * 2 + 2 * 4 * 28 * 16384
    assert band.call(28, 16384, 128, "dq")[0] == 3 * 2 * 28 * FULL_PAIRS * 128
    assert band.call(28, 16384, 128, "dkv")[0] == 4 * 2 * 28 * (
        FULL_PAIRS * 128)
    # the full forward: 9.77 ms of the MXU's time on a v5e; compute-bound
    least, bound = peaks.roofline_seconds(
        *band.call(28, 16384, 128, "fwd"), "TPU v5 lite")
    assert bound == "compute" and least * 1e3 == pytest.approx(9.768, rel=1e-3)


def test_lm_banded_moe_counts_the_active_operations_of_a_record():
    module = manifest.load_named("opcounts", "lm_banded_moe")
    config = CELL["config"]
    hidden, tokens = 2560, 16384
    parts = module.per_token(config)
    assert parts == {
        "attention": 4 * (2 * hidden * 3584 + 2 * hidden * 512),
        "router": 4 * hidden * 64,
        "experts": 4 * (6 * 16 / 64) * 3 * hidden * 768,
        "head": hidden * 37984,
    }
    total = sum(parts.values())
    assert total == pytest.approx(217.2e6, rel=2e-3)   # the issue's count
    shares = {k: v / total for k, v in parts.items()}
    assert shares["head"] == pytest.approx(0.45, abs=0.01)
    assert shares["attention"] == pytest.approx(0.39, abs=0.01)
    assert shares["experts"] == pytest.approx(0.16, abs=0.01)
    assert module.layer_pairs(config) == [FULL_PAIRS] + [BAND_PAIRS] * 3
    scores = (FULL_PAIRS + 3 * BAND_PAIRS) * 2 * 28 * 128   # multiply-adds
    assert scores / tokens == pytest.approx(135.8e6, rel=2e-3)
    assert module.train_flops(config) == 3 * (
        tokens * 2 * total + 2 * scores)
    assert module.train_flops(config) == pytest.approx(34.71e12, rel=2e-3)
    # attention's scores over the forward's operations
    assert scores / (tokens * total + scores) == pytest.approx(0.385,
                                                               abs=0.005)


def _run(custom_calls=None, config=None):
    trace = None if custom_calls is None else {
        "custom_calls": custom_calls, "busy_s": 6.0}
    return types.SimpleNamespace(
        trace=trace, config=config or CELL["config"],
        traffic=CELL["traffic"], cell={"chips": 1},
        device={"kind": "TPU v5 lite"})


def test_the_readers_take_the_named_flash_calls_alone(capsys):
    band = manifest.load_named("kernels", "banded_attention")
    roofline = BOOK.reader("kernel.banded_attention_roofline")
    share = BOOK.reader("kernel.banded_attention_share")
    ratio = BOOK.reader("attn.window_over_full_time")
    least = {key: peaks.roofline_seconds(
        *band.call(28, 16384, 128, *key), "TPU v5 lite")[0] for key in FLASH}
    # 12 steps: a full call at half its roofline, a windowed one at a
    # quarter, so a windowed layer takes 2 x 0.4375 of a full layer's time
    calls = {FLASH[key]: [12 * least[key] * (4 if key[1] else 2), 12.0 * (
        3 if key[1] else 1)] for key in FLASH}
    for key in FLASH:
        if key[1]:      # three windowed layers a step
            calls[FLASH[key]][0] *= 3
    calls.update({GMM: [0.5, 10.0], UNNAMED: [0.5, 10.0]})
    run = _run(custom_calls=calls)
    full = sum(v for k, v in least.items() if not k[1])
    banded = sum(v for k, v in least.items() if k[1])
    want = (full + 3 * banded) / (2 * full + 12 * banded)
    assert roofline(run) == pytest.approx(100 * want)
    assert share(run) == pytest.approx(
        100 * 12 * (2 * full + 12 * banded) / 6.0)
    assert ratio(run) == pytest.approx(4 * banded / (2 * full))
    assert ratio(run) == pytest.approx(2 * 0.4375, rel=1e-3)
    lines = [l for l in capsys.readouterr().err.splitlines()
             if "banded_attention" in l]
    assert [tuple(l.split()[2:4]) for l in lines] == [
        ("full", "dkv:"), ("full", "dq:"), ("full", "fwd:"),
        ("window=4096", "dkv:"), ("window=4096", "dq:"),
        ("window=4096", "fwd:")]
    assert "(50.0%)" in lines[0] and "12.0 calls" in lines[0]
    assert "(25.0%)" in lines[3] and "36.0 calls" in lines[3]
    # a parent (no named call), an untraced run, another configuration
    others = {GMM: [0.5, 10.0], UNNAMED: [0.5, 10.0]}
    for reader in (roofline, share, ratio):
        assert reader(_run(custom_calls=others)) is None
        assert reader(_run()) is None
        lfm2 = BOOK.cell("lfm2-24b-a2b.seq8192")["config"]
        assert reader(_run(custom_calls=calls, config=lfm2)) is None
    # one kind of layer alone gives no ratio
    full_only = {FLASH[key]: [1.0, 1.0] for key in FLASH if not key[1]}
    assert ratio(_run(custom_calls=full_only)) is None
    assert roofline(_run(custom_calls=full_only)) is not None


# Since PR 41 a layer's backward is ONE call with three results, as many
# as the forward's.  The texts of the traces the issue names (layouts
# cut): ``chiprun_out/c1/traced_detail.json`` (a suffix the lowering
# gave, 32 query heads on 4) and this cell's own (ledger, PR 63: 28 on 4).
def _fused(name, q, kv, stat):
    fwd = name.startswith(("flash_fwd", "jvp_flash_fwd"))
    results = [q, stat, stat] if fwd else [kv, kv, q]
    operands = [q, kv, kv] if fwd else [kv, kv, q, q, stat, stat]
    return "%" + name + " = (" + ", ".join(results) + ") custom-call(" + (
        "s32[80] %constant.1544, s32[80] %constant.1545, " + ", ".join(
            "%s %%bitcast.%d" % (shape, 4700 + i)
            for i, shape in enumerate(operands))
        + '), custom_call_target="tpu_custom_call"')


Q28, KV4, ROWS28 = "bf16[28,16384,128]", "bf16[4,16384,128]", (
    "f32[28,1,16384]")
SUFFIXED = {name: _fused(name, "bf16[32,16384,128]", KV4, "f32[32,1,16384]")
            for name in ("flash_bwd_b4.14", "flash_fwd_b4.48")}


@pytest.mark.parametrize("name, kind, window, heads", [
    ("flash_bwd_b4.14", "bwd", 0, (32, 4)),
    ("flash_fwd_b4.48", "fwd", 0, (32, 4)),
    ("flash_bwd.2", "bwd", 0, (28, 4)),
    ("flash_bwd_w4096.6", "bwd", 4096, (28, 4)),
    ("flash_fwd_w4096.26", "fwd", 4096, (28, 4)),
    ("jvp_flash_fwd_w4096_.3", "fwd", 4096, (28, 4)),
    ("checkpoint_flash_fwd__.8", "fwd", 0, (28, 4)),
])
def test_a_call_is_told_by_its_name_whatever_stands_round_it(
        name, kind, window, heads):
    band = manifest.load_named("kernels", "banded_attention")
    flash = manifest.load_named("kernels", "flash_attention")
    hlo = SUFFIXED.get(name) or _fused(name, Q28, KV4, ROWS28)
    results, operands = kernels.parse_call(hlo)
    assert len(results) == 3                  # whichever kind it is
    assert flash.name_of(hlo) == (kind, window, None)
    assert flash.heads_of(results, hlo, 16384) == heads
    work = band.call(heads[0], 16384, 128, kind, window, kv_heads=heads[1])
    assert band.classify(results, operands, hlo=hlo) == (kind, work, window)
    plain = flash.classify(results, operands, hlo=hlo)
    assert plain is None if window else plain == (kind, work)
    # a grouped-query backward's operations: 2.5 x its forward's
    fwd = band.call(heads[0], 16384, 128, "fwd", window, kv_heads=heads[1])
    bwd = band.call(heads[0], 16384, 128, "bwd", window, kv_heads=heads[1])
    assert 2 * bwd[0] == 5 * fwd[0]
    assert bwd[0] == 5 * 2 * heads[0] * band.pairs(16384, window) * 128
    # K, V and their cotangents at the K/V heads: under the bytes of as
    # many K/V heads as query heads
    assert bwd[1] < band.call(heads[0], 16384, 128, "bwd", window)[1]


def test_the_readers_read_this_cells_fused_trace(capsys):
    """The calls of the ledger's PR 63 line of this cell: the share was
    9.25% while ``flash_bwd*`` went unread, and the ratio nothing."""
    roofline = BOOK.reader("kernel.banded_attention_roofline")
    share = BOOK.reader("kernel.banded_attention_share")
    ratio = BOOK.reader("attn.window_over_full_time")
    text = lambda name: _fused(name, Q28, KV4, ROWS28)
    calls = {text("flash_bwd.2"): [0.450639246, 14.0],
             text("flash_fwd.8"): [0.220200153, 14.0],
             text("flash_bwd_w4096.6"): [0.220261194, 14.0],
             text("flash_bwd_w4096.7"): [0.2202574, 14.0],
             text("flash_bwd_w4096.8"): [0.220257388, 14.0],
             text("flash_fwd_w4096.26"): [0.114666605, 14.0],
             text("flash_fwd_w4096.24"): [0.108297586, 14.0],
             text("flash_fwd_w4096.25"): [0.108297586, 14.0],
             GMM: [0.5, 10.0], UNNAMED: [0.5, 10.0]}
    run = _run(custom_calls=calls)
    flash_s = sum(v[0] for k, v in calls.items() if "flash_" in k)
    assert share(run) == pytest.approx(100 * flash_s / 6.0)
    assert share(run) > 25
    # a windowed layer over a full one: the tiles say 0.51, the pairs 0.44
    assert ratio(run) == pytest.approx(
        (0.220261194 + 0.2202574 + 0.220257388 + 0.114666605
         + 2 * 0.108297586) / 3 / (0.450639246 + 0.220200153))
    assert 0.44 < ratio(run) < 0.55
    assert 60 < roofline(run) < 100
    lines = [l for l in capsys.readouterr().err.splitlines()
             if "banded_attention" in l]
    assert [tuple(l.split()[2:4]) for l in lines] == [
        ("full", "bwd:"), ("full", "fwd:"),
        ("window=4096", "bwd:"), ("window=4096", "fwd:")]
    for line in lines:
        assert float(line.split("(")[1].split("%")[0]) <= 100.0, line
    # a full layer's backward gone from the trace: no ratio
    del calls[text("flash_bwd.2")]
    assert ratio(_run(custom_calls=calls)) is None


# the older metrics of a held share of the experts that list the cell
HELD_SHARE = {"moe.dead_row_share", "moe.held_load_max_over_mean"}


def test_the_held_shares_older_readers_read_the_cell():
    """The cell is on the lists of the two older metrics that read a
    held share's ``moe load:`` lines (those that say ``moved=``); the
    grouped matmul's keep to their lists (``test_lfm2.py`` holds them
    to ``olmoe1b7b.seq4096`` alone, and their reader asks the
    configuration for sizes under another model's names)."""
    from benchmark.lib import job

    stamp = "[2026-09-28 16:06:%02d,000] [INFO] [worker-0] moe load: "
    text = "\n".join([
        stamp % 10 + "step=8 layers=4 rows=100000 max=6000 mean=1562.5 "
        "padded_rows=7897 moved=196608 spilled=0",
        stamp % 20 + "step=16 layers=4 rows=120000 max=5625 mean=1875.0 "
        "padded_rows=7920 moved=196608 spilled=0",
        stamp % 50 + "step=24 layers=4 rows=1 max=1 mean=1.0 "
        "padded_rows=0 moved=196608 spilled=0"])        # past the window
    at = job.stamp_seconds(stamp % 0)
    run = types.SimpleNamespace(
        job=types.SimpleNamespace(text=text),
        times={"open": at + 5, "close": at + 30})
    assert {m["name"] for m in CELL["per_layer"]} >= HELD_SHARE
    assert BOOK.reader("moe.dead_row_share")(run) == pytest.approx(
        100 * (1 - 220000 / 393216))
    assert BOOK.reader("moe.held_load_max_over_mean")(run) == pytest.approx(
        (6000 / 1562.5 + 5625 / 1875.0) / 2)
    assert not {"kernel.grouped_matmul_roofline",
                "kernel.grouped_matmul_share"} & {
        m["name"] for m in CELL["per_layer"]}


def test_the_new_metrics_are_the_new_cells_alone():
    mine = {m["name"] for m in CELL["per_layer"]}
    new = {"kernel.banded_attention_roofline",
           "kernel.banded_attention_share", "attn.window_over_full_time"}
    earlier = ("olmo1b.seq2048", "olmo1b.seq2048-dp4", "olmoe1b7b.seq4096",
               "lfm2-24b-a2b.seq8192")
    for other in earlier:
        theirs = {m["name"] for m in BOOK.cell(other)["per_layer"]}
        assert not theirs & new, other
        assert "kernel.flash_attention_roofline" in theirs
    # flash's older reader counts every call as full causal: the cell
    # with windowed calls is off its list (ISSUE 33, the trap)
    assert "kernel.flash_attention_roofline" not in mine
    flash = [m for m in BOOK.doc["per_layer"]
             if m["name"] == "kernel.flash_attention_roofline"][0]
    # .. and since PR 65, which made it tell calls by name, the three
    # cells whose stacks call the plain kernel beside a scan
    assert flash["workloads"] == list(earlier) + [
        "olmo-hybrid-7b.seq16384", "solar-open2-250b.seq16384",
        "nemotron-3-nano-30b-a3b.seq16384"]
    dense = {m["name"] for m in BOOK.cell("olmo1b.seq2048")["per_layer"]}
    # .. and PR 34's row kernel's share, which the share cells list
    assert mine - dense == new | HELD_SHARE | {"kernel.row_move_share"}
    assert dense - mine == {"kernel.flash_attention_roofline"}
    assert set(CELL["config"]["kernels"]) == {"banded_attention",
                                              "grouped_matmul"}
    assert CELL["chips"] == 1
    flags = CELL["traffic"]["flags"]
    assert (flags["batch_size"], flags["num_minibatches_per_task"],
            flags["num_workers"], flags["log_loss_steps"]) == (1, 4, 1, 8)
    assert "fused_steps" not in flags
    assert CELL["traffic"]["params"] == {
        "sequences": 512, "exponent": 1.1, "dtype": "uint16"}
    assert flags["batch_size"] * CELL["config"]["seq_len"] == 16384


def test_the_frequent_ids_are_the_same_whatever_the_seed(tmp_path):
    """``tokens_zipf_fixed_ids``: the same seed gives the same bytes,
    another seed other bytes of the same size, and the same ids are the
    frequent ones under both (under ``tokens_zipf`` they are not)."""
    import numpy as np

    def top(generator, seed, where):
        out = tmp_path / where
        out.mkdir()
        origin = manifest.load_named("generators", generator).generate(
            str(out), seed, sequences=16, seq_len=1024, vocab_size=37984)
        assert origin == "tokens:%s:1024:uint16" % (out / "tokens.bin")
        tokens = np.fromfile(out / "tokens.bin", dtype=np.uint16)
        assert tokens.size == 16 * 1024 and tokens.max() < 37984
        ids, counts = np.unique(tokens, return_counts=True)
        return tokens, list(ids[np.argsort(-counts)][:3]), counts.max()

    fixed = "tokens_zipf_fixed_ids"
    a, top_a, most = top(fixed, 2147483659, "a")
    b, top_b, _ = top(fixed, 7, "b")
    again, _, _ = top(fixed, 2147483659, "again")
    assert np.array_equal(a, again) and not np.array_equal(a, b)
    assert top_a == top_b
    assert most / a.size == pytest.approx(0.147, abs=0.02)   # rank 1
    _, seeded_a, _ = top("tokens_zipf", 2147483659, "c")
    _, seeded_b, _ = top("tokens_zipf", 7, "d")
    assert seeded_a != seeded_b
    assert CELL["traffic"]["generator"] == fixed


def test_the_configuration_keeps_every_published_width():
    """The catalog row's numbers, key by key: only the three keys of
    ``reduced`` differ, each with its published value beside it; the
    model_params run those sizes."""
    layout = [0, 1, 1, 1] * 13
    catalog = {
        "head_dim": 128, "hidden_size": 2560,
        "max_position_embeddings": 16384,
        "model_name": "smallthinker_21b_instruct",
        "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
        "moe_num_primary_experts": 64,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "num_attention_heads": 28, "num_hidden_layers": 52,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_layout": layout, "rope_scaling": None, "rope_theta": 1500000,
        "sliding_window_layout": layout, "sliding_window_size": 4096,
        "tie_word_embeddings": False, "vocab_size": 151936}
    config = CELL["config"]
    reduced = ["num_hidden_layers", "moe_num_primary_experts", "vocab_size"]
    assert config["reduced"] == reduced
    entry = [e for e in BOOK.doc["configs"]
             if e["name"] == "smallthinker-21b-a3b"][0]
    assert entry["reduced"] == reduced
    assert entry["source"] == (
        "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/"
        "blob/main/config.json")
    for key, value in catalog.items():
        if key in reduced:
            assert config["published"][key] == value, key
            assert config[key] != value, key
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["moe_num_primary_experts"],
            config["vocab_size"]) == (4, 16, 151936 // 4)
    kept = config["layers_kept"]
    assert kept == [0, 1, 2, 3]      # one whole period of both layouts
    p = config["cli"]["model_params"]
    assert (p["dim"], p["num_heads"], p["num_kv_heads"], p["head_dim"],
            p["ffn_dim"], p["moe_experts"], p["moe_top_k"], p["window"],
            p["rope_theta"], p["norm_eps"]) == (
                2560, 28, 4, 128, 768, 64, 6, 4096, 1500000, 1e-06)
    assert p["num_heads"] * p["head_dim"] == 3584 != p["dim"]
    assert p["layer_pattern"] == "".join(
        "w" if config["sliding_window_layout"][i] else "a" for i in kept)
    assert set(p["rope_kinds"]) == {
        letter for letter, i in zip(p["layer_pattern"], kept)
        if config["rope_layout"][i]}
    assert (p["ffn_activation"], p["moe_route_before_op"], p["moe_router"],
            p["moe_norm_topk"], p["tied_embeddings"], p["embed_scale"]) == (
                "relu", True, "softmax", True, False, 1.0)
    assert (p["num_layers"], p["moe_experts_held"],
            p["vocab_size"]) == tuple(config[k] for k in reduced)
    assert p["seq_len"] == config["seq_len"] == 16384
    # the arithmetic of the cut: 16 B a parameter
    layer = (2 * 2560 * 3584 + 2 * 2560 * 512 + 2560 * 64 + 2 * 2560
             + 16 * 3 * 2560 * 768)
    total = 4 * layer + 2 * 37984 * 2560 + 2560
    assert total == pytest.approx(656.5e6, rel=1e-3)
    assert 16 * total == pytest.approx(10.50e9, rel=1e-3)
    assert "656.5 M" in config["reduced_why"]
    assert "4 chips share each layer" in config["deployment"]
    for key in ("router_input", "balance_loss", "optimizer", "remat",
                "compute_dtype", "seq_len", "embedding"):
        assert key in config["assumed"], key


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
    assert "layer stack: pattern=awww" in done.stderr
    assert "a:window=0,rope=0 w:window=16,rope=1" in done.stderr
