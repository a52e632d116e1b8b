"""What the ``olmo-hybrid-7b`` configuration brought: the gated delta
rule's readers on its calls (15 heads x 16,384 x 96 | 192), the work
counted by the recurrence and not by a chunk, its operation count
against hand counts, the configuration's file against the catalog's
numbers, and its plain reference against the product at tiny sizes."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.lib import kernels, manifest, peaks

ROOT = os.path.dirname(manifest.BENCH_DIR)
BOOK = manifest.Manifest(ROOT)
NAME = "olmo-hybrid-7b.seq16384"
CELL = BOOK.cell(NAME)

TAIL = 'custom-call(%a, %b, %c, %d), custom_call_target="tpu_custom_call"'
KEY, VALUE = "bf16[15,16384,96]{2,1,0}", "bf16[15,16384,192]{2,1,0}"
STATES = "f32[15,256,96,192]{3,2,1,0}"
GATES = "f32[15,256,2,64]{3,2,1,0}"
# The calls of one step as the compiled program names them (operands cut).
CALLS = {
    "fwd": "%gdn_fwd.12 = (" + VALUE + ", " + STATES + ") " + TAIL,
    "fwd_again": "%checkpoint_gdn_fwd__.15 = (" + VALUE + ", " + STATES
                 + ") " + TAIL,
    "bwd": "%gdn_bwd.6 = (" + ", ".join([KEY, KEY, VALUE, GATES]) + ") "
           + TAIL,
}
CONV = "%sconv_silu_fwd.12 = bf16[16384,5760]{1,0} " + TAIL
FLASH = ("%flash_fwd.4 = (bf16[15,16384,128]{2,1,0}, f32[15,1,16384]{2,1,0}"
         ", f32[15,1,16384]{2,1,0}) " + TAIL)
TOKENS = 15 * 16384


def test_the_work_is_the_recurrences_whatever_the_chunk():
    module = manifest.load_named("kernels", "gated_delta")
    # 3 d_k d_v multiply-adds a token a head forward, 9 backward
    flops, nbytes = module.call(1, 15, 16384, 96, 192, "fwd")
    assert flops == 2 * 3 * TOKENS * 96 * 192 == 27_179_089_920
    assert nbytes == TOKENS * ((2 * 96 + 2 * 192) * 2 + 2 * 4)
    least, bound = peaks.roofline_seconds(flops, nbytes, "TPU v5 lite")
    assert bound == "memory"          # 0.35 ms of bytes, 0.14 of operations
    assert least * 1e3 == pytest.approx(0.348, rel=1e-2)
    flops, nbytes = module.call(1, 15, 16384, 96, 192, "bwd")
    assert flops == 2 * 9 * TOKENS * 96 * 192
    assert nbytes == TOKENS * ((4 * 96 + 4 * 192) * 2 + 4 * 4)
    assert peaks.roofline_seconds(flops, nbytes, "TPU v5 lite")[1] == (
        "memory")
    for kind, hlo in CALLS.items():
        got = module.classify(*kernels.parse_call(hlo), hlo=hlo)
        assert got == (kind[:3], module.call(1, 15, 16384, 96, 192,
                                             kind[:3])), kind
    # another chunk writes other states and other gates: the same work
    other = CALLS["fwd"].replace("f32[15,256,96,192]", "f32[15,128,96,192]")
    assert module.classify(*kernels.parse_call(other), hlo=other) == (
        module.classify(*kernels.parse_call(CALLS["fwd"]), hlo=CALLS["fwd"]))
    for hlo in (CONV, FLASH):
        assert module.classify(*kernels.parse_call(hlo), hlo=hlo) is None
    # and the short convolution's reader leaves the SiLU calls alone
    conv = manifest.load_named("kernels", "short_conv")
    import re
    assert not re.search(conv.PATTERN, CONV)


def _run(custom_calls=None, config=None):
    trace = None if custom_calls is None else {
        "custom_calls": custom_calls, "busy_s": 6.0}
    return types.SimpleNamespace(
        trace=trace, config=config or CELL["config"],
        traffic=CELL["traffic"], cell={"chips": 1},
        device={"kind": "TPU v5 lite"})


def test_the_two_readers_read_the_cells_calls(capsys):
    module = manifest.load_named("kernels", "gated_delta")
    roofline = BOOK.reader("kernel.gated_delta_roofline")
    share = BOOK.reader("kernel.gated_delta_share")
    least = {kind: peaks.roofline_seconds(
        *module.call(1, 15, 16384, 96, 192, kind), "TPU v5 lite")[0]
        for kind in ("fwd", "bwd")}
    # 7 steps of three delta layers: a forward at a twentieth of its
    # roofline, once in the forward and once again under remat; a
    # backward at a tenth
    calls = {CALLS["fwd"]: [21 * 20 * least["fwd"], 21.0],
             CALLS["fwd_again"]: [21 * 20 * least["fwd"], 21.0],
             CALLS["bwd"]: [21 * 10 * least["bwd"], 21.0],
             CONV: [0.5, 21.0], FLASH: [0.5, 7.0]}
    run = _run(custom_calls=calls)
    taken = 21 * (40 * least["fwd"] + 10 * least["bwd"])
    assert roofline(run) == pytest.approx(
        100 * 21 * (2 * least["fwd"] + least["bwd"]) / taken)
    assert share(run) == pytest.approx(100 * taken / 6.0)
    lines = [l for l in capsys.readouterr().err.splitlines()
             if "gated_delta" in l]
    assert [l.split()[2] for l in lines] == ["bwd:", "fwd:"]
    assert "(10.00%)" in lines[0] and "21.0 calls" in lines[0]
    assert "(5.00%)" in lines[1] and "42.0 calls" in lines[1]
    # nothing where the program makes no such call (a parent), or in a
    # configuration that does not list the kernel
    assert roofline(_run(custom_calls={FLASH: [0.5, 7.0]})) is None
    assert share(_run(custom_calls={FLASH: [0.5, 7.0]})) is None
    assert roofline(_run()) is None and share(_run()) is None
    other = BOOK.cell("trinity-mini.seq16384")["config"]
    assert roofline(_run(custom_calls=calls, config=other)) is None
    # no reading can pass 100%: a call at its floor reads exactly that
    at_floor = {CALLS["fwd"]: [least["fwd"], 1.0]}
    assert roofline(_run(custom_calls=at_floor)) == pytest.approx(100.0)


def test_lm_hybrid_delta_counts_the_operations_of_a_record():
    module = manifest.load_named("opcounts", "lm_hybrid_delta")
    config = CELL["config"]
    E, T = 3840, 16384
    parts = module.per_token(config)
    assert parts == {
        "mlp": 4 * 3 * E * 11008,
        # q, k, v of 15 heads; the gate and W_o; the two [E, 15]
        "delta_projections": 3 * (E * 5760 + 2 * E * 2880 + 2 * E * 15),
        "delta_scan": 3 * 3 * 15 * 96 * 192,
        "attention_projections": 4 * E * 1920,
        "head": E * 12544,
    }
    assert parts["mlp"] == pytest.approx(507.2e6, rel=1e-3)     # the issue's
    assert parts["delta_projections"] == pytest.approx(133.0e6, rel=1e-3)
    assert parts["attention_projections"] == pytest.approx(29.5e6, rel=1e-3)
    assert parts["head"] == pytest.approx(48.2e6, rel=1e-3)
    assert parts["delta_scan"] == 2_488_320
    scores = module.scores_per_sequence(config)
    assert scores == 134_225_920 * 15 * 256
    assert scores / T == pytest.approx(31.46e6, rel=1e-3)
    total = sum(parts.values()) + scores / T
    assert total == pytest.approx(751.9e6, rel=1e-3)
    # the MLP 67% of the forward, the full layer's scores 4%, the scan
    # a third of a percent
    assert parts["mlp"] / total == pytest.approx(0.674, abs=0.005)
    assert scores / T / total == pytest.approx(0.042, abs=0.002)
    assert parts["delta_scan"] / total == pytest.approx(0.0033, abs=0.0003)
    assert module.train_flops(config) == 3 * 2 * (
        T * sum(parts.values()) + scores)
    assert module.train_flops(config) == pytest.approx(73.92e12, rel=1e-3)


def test_the_cells_metrics_hold_the_two_new_ones_and_no_flash_reader():
    mine = {m["name"] for m in CELL["per_layer"]}
    assert mine >= {
        "kernel.gated_delta_roofline", "kernel.gated_delta_share",
        "trainer.mfu", "trainer.peak_hbm_gb", "kernel.mosaic_share",
        "loop.step_interval_ms", "setup.compile_or_load_s"}
    assert {m["name"] for m in CELL["end_to_end"]} >= {"records_per_s",
                                                       "setup_s"}
    for entry in BOOK.doc["workloads"]:
        if entry["name"] != NAME:
            theirs = {m["name"] for m in BOOK.cell(
                entry["name"])["per_layer"]}
            assert not theirs & {"kernel.gated_delta_roofline",
                                 "kernel.gated_delta_share"}, entry["name"]
    # the short convolution's reader counts a gated epilogue's passes,
    # and nothing here routes; the flash reader knows ``flash_bwd``
    # since PR 65 and reads the full layer's two calls
    assert not {name for name in mine if name.startswith(
        ("kernel.banded", "kernel.short_conv", "moe.",
         "kernel.row_move", "kernel.grouped"))}
    assert "kernel.flash_attention_roofline" in mine
    assert CELL["config"]["kernels"] == ["gated_delta", "flash_attention"]
    assert CELL["chips"] == 1
    flags = CELL["traffic"]["flags"]
    assert (flags["batch_size"], flags["num_minibatches_per_task"],
            flags["num_workers"], flags["log_loss_steps"]) == (1, 4, 1, 8)
    assert flags["batch_size"] * CELL["config"]["seq_len"] == 16384
    for entry in BOOK.doc["per_layer"]:
        if entry["name"].startswith("kernel.gated_delta"):
            assert entry["workloads"] == [NAME]
            assert (entry["layer"], entry["moves"], entry["source"]) == (
                "kernels", "records_per_s", "device_trace")


def test_the_configuration_keeps_every_published_width():
    """The catalog row's numbers, key by key: only the six keys of
    ``reduced`` differ, each with its published value beside it; the
    model_params run those sizes."""
    catalog = {
        "model_type": "olmo_hybrid", "vocab_size": 100352,
        "hidden_size": 3840, "intermediate_size": 11008,
        "num_hidden_layers": 32, "num_attention_heads": 30,
        "num_key_value_heads": 30, "hidden_act": "silu",
        "max_position_embeddings": 65536, "attention_bias": False,
        "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
        "layer_types": (["linear_attention"] * 3 + ["full_attention"]) * 8,
        "linear_num_key_heads": 30, "linear_num_value_heads": 30,
        "linear_key_head_dim": 96, "linear_value_head_dim": 192,
        "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None}}
    config = CELL["config"]
    reduced = ["num_hidden_layers", "num_attention_heads",
               "num_key_value_heads", "linear_num_key_heads",
               "linear_num_value_heads", "vocab_size"]
    assert config["reduced"] == reduced
    entry = [c for c in BOOK.doc["configs"]
             if c["name"] == "olmo-hybrid-7b"][0]
    assert entry["reduced"] == reduced
    assert entry["source"] == (
        "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/"
        "config.json")
    assert "catalog row Olmo-Hybrid-7B" in entry["why"]
    for key, value in catalog.items():
        if key in reduced:
            assert config["published"][key] == value, key
            assert config[key] != value, key
        else:
            assert config[key] == value, key
    assert [config[key] for key in reduced] == [4, 15, 15, 15, 15,
                                                100352 // 8]
    assert (config["head_dim"], config["layers_kept"]) == (128, [0, 1, 2, 3])
    p = config["cli"]["model_params"]
    letters = {"linear_attention": "d", "full_attention": "a"}
    assert p["layer_pattern"] == "".join(
        letters[config["layer_types"][i]] for i in config["layers_kept"])
    assert (p["dim"], p["num_heads"], p["num_kv_heads"], p["head_dim"],
            p["delta_key_dim"], p["delta_value_dim"], p["conv_kernel"],
            p["ffn_dim"], p["norm_eps"], p["head_shares"]) == (
                3840, 15, 15, 128, 96, 192, 4, 11008, 1e-06, 2)
    assert (p["rope_kinds"], p["qk_norm"], p["post_norms"], p["pre_norms"],
            p["delta_neg_eigval"], p["tied_embeddings"], p["embed_scale"],
            p["remat"]) == ("w", True, True, False, True, False, 1.0, True)
    assert "warmup_steps" not in p
    assert (p["num_layers"], p["vocab_size"]) == (4, 12544)
    assert p["num_heads"] * p["head_shares"] == 30
    assert p["seq_len"] == config["seq_len"] == 16384
    # the arithmetic of the cut: 16 B a parameter
    E = 3840
    delta = (E * 5760 + 5760 * 4 + 2 * E * 15 + 2 * 15 + E * 2880 + 192
             + 2880 * E)
    full = 4 * E * 1920 + 2 * 1920
    mlp = 3 * E * 11008 + 2 * E              # and the two output norms
    total = 3 * delta + full + 4 * mlp + 2 * 12544 * E + E
    assert total == 766_241_946
    assert delta == pytest.approx(44.38e6, rel=1e-3)
    assert 16 * total == pytest.approx(12.26e9, rel=1e-3)
    assert "766.2 M" in config["reduced_why"]
    assert "2 chips share each layer's heads" in config["deployment"]
    assert "WITHOUT its exchange" in config["deployment"]
    assert "whole" in config["deployment"]
    for key in ("norm_placement", "qk_norm", "nope_on_full_layers",
                "gated_delta_layer", "conv_bias", "l2_and_output_norm_eps",
                "decay_draw", "initializer_range", "optimizer",
                "compute_dtype", "remat", "seq_len"):
        assert key in config["assumed"], key
    assert "NOT kept" in config["assumed"]["optimizer"]
    for key in ("norm_placement", "qk_norm"):
        assert "family's published convention" in config["assumed"][key]
    for key in ("gated_delta_layer", "conv_bias", "decay_draw"):
        assert "as recalled" in config["assumed"][key]


def test_the_products_tree_is_the_configurations_parameter_count():
    import jax

    from benchmark.lib.runner import params_string
    from elasticdl_tpu.models.spec import load_model_spec

    cli = CELL["config"]["cli"]
    spec = load_model_spec(cli["model_zoo"],
                           model_params=params_string(cli["model_params"]))
    shapes = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree_util.tree_leaves(shapes)) == (
        766_241_946)


def test_the_reference_imports_nothing_of_the_scans_op():
    with open(os.path.join(manifest.BENCH_DIR, "reference",
                           "olmo-hybrid-7b.py")) as fh:
        text = fh.read()
    imports = [l for l in text.splitlines()
               if l.lstrip().startswith(("import ", "from "))]
    assert not [l for l in imports if "gated_delta" in l or "ops" in l]
    assert "lax.scan(token" in text


def test_product_loss_and_layers_agree_with_the_reference_at_tiny_size():
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
    layers = json.loads([l for l in done.stderr.splitlines()
                         if l.startswith('{"layers')][-1])
    assert set(layers["layers_same_input"]) == {"delta", "attention", "mlp"}
    assert max(layers["layers_same_input"].values()) <= layers["ceiling"]
    assert ("layer stack: pattern=ddda lead=- period=ddda periods=1 tail=-"
            in done.stderr)
    assert "heads_held=2/4" in done.stderr
    assert ("delta scan: rows=64 heads=2 key_dim=16 value_dim=32 chunk=64 "
            "conv_taps=4 neg_eigval=1") in done.stderr


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
