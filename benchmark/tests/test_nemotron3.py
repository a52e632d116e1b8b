"""What the ``nemotron-3-nano-30b-a3b`` configuration brought: its three
readers and the accepted ones it joined on fixture runs, its operation
count against a loop over the layers, the scan's operations and bytes
against hand counts, the configuration's file against the catalog's
row, and its plain reference against the product at the rehearsal's
size."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.lib import kernels, manifest, peaks

ROOT = os.path.dirname(manifest.BENCH_DIR)
BOOK = manifest.Manifest(ROOT)
NAME = "nemotron-3-nano-30b-a3b.seq16384"
CELL = BOOK.cell(NAME)
CONFIG = CELL["config"]
ROW = "/opt/skills/guides/model-configs/architectures.jsonl"

TAIL = 'custom-call(%a, %b, %c, %d), custom_call_target="tpu_custom_call"'
SSD_FWD = ("%checkpoint_ssd_fwd__.2 = (bf16[1,16384,4096]{2,1,0}, "
           "f32[1,8,128,512,128]{4,3,2,1,0}) " + TAIL)
SSD_BWD = ("%ssd_bwd.7 = (bf16[1,16384,4096]{2,1,0}, "
           "bf16[1,16384,1024]{2,1,0}, bf16[1,16384,1024]{2,1,0}, "
           "f32[1,64,128,2,128]{4,3,2,1,0}) " + TAIL)
CONV = "%sconv_silu_fwd.4 = bf16[16384,6144]{1,0} " + TAIL
GMM = "%gmm_nn.33 = bf16[12288,1920]{1,0} " + TAIL


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
    assert {"kernel.ssd_roofline", "kernel.ssd_share", "ssm.chunk_keep",
            "moe.dead_row_share", "moe.held_load_max_over_mean",
            "remat.estimate_over_gb", "trainer.mfu",
            "trainer.peak_hbm_gb"} <= names
    # the row kernel does not take rows of 2,688 x bfloat16 (1,344
    # words, 10.5 lane tiles): ``moe dispatch: .. rows=reference``, and
    # the accepted readers that would find nothing are not this cell's
    assert not names & {"kernel.row_move_share",
                        "kernel.grouped_matmul_roofline",
                        "kernel.grouped_matmul_share"}
    # its six attention layers call the plain ``flash_fwd`` / ``flash_bwd``,
    # which the flash reader tells by name since PR 65
    assert "kernel.flash_attention_roofline" in names
    assert {m["name"] for m in CELL["end_to_end"]} == {
        "records_per_s", "setup_s"}
    assert CELL["traffic"]["flags"]["batch_size"] == 1
    assert CELL["chips"] == 1
    assert CONFIG["kernels"] == ["ssd", "flash_attention", "grouped_matmul"]
    for name, layer in (("kernel.ssd_roofline", "kernels"),
                        ("kernel.ssd_share", "kernels"),
                        ("ssm.chunk_keep", "model")):
        entry = next(m for m in BOOK.doc["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [NAME] and entry["layer"] == layer
    assert len(BOOK.doc["workloads"]) == 12
    assert sum(w["chips"] == 4 for w in BOOK.doc["workloads"]) == 1


def test_the_scans_work_is_the_recurrences_from_shapes_alone():
    """Forward 2 x 128 x 64 multiply-adds a token a head, x and y [T, 64
    x 64], B and C [T, 8 x 128] ONCE a group, the decay and the step
    float32 a head: 343.9 MB and 34.4 GFLOP a layer's forward at 16,384
    tokens, HBM-bound at 0.42 ms; the backward six, by
    ``kernels/gated_delta.py``'s rule."""
    ssd = manifest.load_named("kernels", "ssd")
    T, H, P, G, N = 16384, 64, 64, 8, 128
    flops, nbytes = ssd.call(1, T, H * P, G * N, N, H, "fwd")
    assert flops == 2 * 2 * N * P * T * H == 34359738368
    assert nbytes == T * (2 * (2 * H * P + 2 * G * N) + 2 * 4 * H)
    assert nbytes == 343932928
    seconds, bound = peaks.roofline_seconds(flops, nbytes, "TPU v5 lite")
    assert bound == "memory" and seconds == pytest.approx(0.42e-3, rel=0.01)
    flops, nbytes = ssd.call(1, T, H * P, G * N, N, H, "bwd")
    assert flops == 3 * 34359738368
    assert nbytes == T * (2 * (3 * H * P + 4 * G * N) + 4 * 4 * H)
    # a chunk's states or another chunk change nothing: the count reads
    # y's, B's and the gates' shapes
    again = SSD_FWD.replace("f32[1,8,128,512,128]", "f32[1,8,64,512,128]")
    want = ssd.call(1, T, H * P, G * N, N, H, "fwd")
    for hlo in (SSD_FWD, again):
        assert ssd.classify(*kernels.parse_call(hlo), hlo=hlo, heads=H,
                            state=N) == ("fwd", want)
    assert ssd.classify(*kernels.parse_call(SSD_BWD), hlo=SSD_BWD,
                        state=N) == ("bwd", (flops, nbytes))
    # not the convolution's, not the grouped matmul's
    for hlo in (CONV, GMM):
        assert ssd.classify(*kernels.parse_call(hlo), hlo=hlo) is None


def test_the_trace_readers_take_the_scans_calls_and_no_others():
    ssd = manifest.load_named("kernels", "ssd")
    fwd = ssd.call(1, 16384, 4096, 1024, 128, 64, "fwd")
    bwd = ssd.call(1, 16384, 4096, 1024, 128, 64, "bwd")
    calls = {SSD_FWD: [0.1, 56.0], SSD_BWD: [0.16, 54.0], CONV: [0.2, 8.0],
             GMM: [0.3, 40.0]}
    layer = manifest.load_named("layers", "kernel.ssd_roofline")
    assert sorted(layer.calls(_run(custom_calls=calls))) == sorted([
        ("fwd", fwd, 0.1, 56.0), ("bwd", bwd, 0.16, 54.0)])
    least = lambda work: peaks.roofline_seconds(*work, "TPU v5 lite")[0]
    share = BOOK.reader("kernel.ssd_roofline")(_run(custom_calls=calls))
    assert share == pytest.approx(
        100 * (56 * least(fwd) + 54 * least(bwd)) / 0.26)
    assert 0 < share < 100
    assert BOOK.reader("kernel.ssd_share")(
        _run(custom_calls=calls)) == pytest.approx(100 * 0.26 / 6.0)
    # a parent makes no such call, a configuration may not list the
    # kernel: nothing, and no raise
    for run in (_run(custom_calls={GMM: [0.3, 40.0]}),
                _run(custom_calls=calls, config=dict(CONFIG, kernels=[])),
                _run()):
        assert BOOK.reader("kernel.ssd_roofline")(run) is None
        assert BOOK.reader("kernel.ssd_share")(run) is None


STAMP = "[2026-10-04 10:59:%02d,545] [INFO] [worker-0] [w:1:f] "


def test_chunk_keep_is_read_inside_the_window():
    from benchmark.lib import job

    keep = BOOK.reader("ssm.chunk_keep")
    loss = STAMP + "step %d loss %s (version %d) chunk_keep=%s"
    load = (STAMP + "moe load: step=%d layers=4 rows=25000 max=9000 "
            "mean=781.2 padded_rows=100 moved=49152 spilled=0")
    text = "\n".join([
        loss % (10, 8, "9.9", 8, "0.900000"),     # before the window
        load % (10, 8),
        loss % (20, 16, "11.0", 16, "0.050000"),
        load % (20, 16),
        loss % (30, 24, "11.0", 24, "0.070000"),
        load % (30, 24),
        loss % (40, 32, "9.9", 32, "0.900000"),    # after it
    ])
    at = lambda second: job.stamp_seconds(STAMP % second)
    run = _run(text=text, window=(at(15), at(35)))
    assert keep(run) == pytest.approx(0.06)
    assert BOOK.reader("moe.dead_row_share")(run) == pytest.approx(
        100 * (1 - 25000 / 49152))
    assert BOOK.reader("moe.held_load_max_over_mean")(run) == pytest.approx(
        9000 / 781.2)
    # a parent's lines carry no such field: nothing, and no raise
    run = _run(text=text.replace(" chunk_keep=", " x="),
               window=(at(15), at(35)))
    assert keep(run) is None


def test_the_operation_count_is_a_loop_over_the_layers():
    module = manifest.load_named("opcounts", CONFIG["opcounts"])
    E, T = 2688, 16384
    total = pairs = 0
    for letter in "MEMEM*EME":
        if letter == "M":       # z | x | B | C | dt, the way back, the scan
            total += E * (4096 + 4096 + 1024 + 1024 + 64) + 4096 * E
            total += 2 * 128 * 64 * 64
        elif letter == "*":     # q, k, v, o; the scores by the sequence
            total += 2 * E * 32 * 128 + 2 * E * 2 * 128
            pairs += 32 * 2 * 128 * (T * (T + 1) // 2)
        else:                   # router, shared, 6 * 8 / 128 held experts
            total += E * 128 + 2 * E * 3712 + 0.375 * 2 * E * 1856
    total += E * 16384          # the head
    parts = module.per_token(CONFIG)
    assert sum(parts.values()) == pytest.approx(total)
    assert module.scores_per_sequence(CONFIG) == pairs
    per_token = total + pairs / T
    assert per_token == pytest.approx(389.7e6, rel=1e-3)
    assert module.train_flops(CONFIG) == pytest.approx(6 * T * per_token)
    # the shares the issue states: Mamba-2 41%, experts 25%, attention
    # 23%, the head 11%
    share = lambda *names: sum(parts[n] for n in names) / per_token
    assert share("mamba_projections", "mamba_scan") == pytest.approx(
        0.41, abs=0.005)
    assert share("router", "shared", "experts") == pytest.approx(
        0.25, abs=0.005)
    assert share("head") == pytest.approx(0.11, abs=0.005)
    assert parts["mamba_scan"] == 4 * 1048576
    # the mfu reader on a window of 2.2 records a second
    run = _run()
    run.window = {"records_per_s": 2.2}
    assert BOOK.reader("trainer.mfu")(run) == pytest.approx(
        100 * module.train_flops(CONFIG) * 2.2 / 197e12, rel=1e-3)


def test_the_configuration_keeps_every_width_of_the_catalogs_row():
    with open(ROW) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    entry = next(c for c in BOOK.doc["configs"]
                 if c["name"] == "nemotron-3-nano-30b-a3b")
    assert entry["source"] == row["source_url"]
    assert CONFIG["source"].startswith(row["source_url"])
    assert "catalog row NVIDIA-Nemotron-3-Nano-30B-A3B-BF16" in CONFIG[
        "source"]
    changed = {"num_hidden_layers": 9, "n_routed_experts": 8,
               "vocab_size": 16384}
    assert sorted(entry["reduced"]) == sorted(changed)
    assert sorted(CONFIG["reduced"]) == sorted(changed)
    for key, value in row["config"].items():
        assert CONFIG[key] == changed.get(key, value), key
        assert CONFIG["published"].get(key, value) == value, key
    assert set(CONFIG["published"]) == set(changed)
    # no width among the changed keys
    assert not [key for key in changed if key.endswith(("_dim", "_rank"))
                or ("size" in key and key != "vocab_size")]
    assert CONFIG["layers_kept"] == list(range(9))
    params = CONFIG["cli"]["model_params"]
    letters = {"M": "m", "E": "e", "*": "a"}
    assert params["layer_pattern"] == "".join(
        letters[CONFIG["hybrid_override_pattern"][i]]
        for i in CONFIG["layers_kept"])
    assert params["mixer_ffn"] is False
    assert (params["ssm_heads"], params["ssm_head_dim"], params["ssm_state"],
            params["ssm_groups"], params["conv_kernel"],
            params["conv_bias"]) == (
        CONFIG["mamba_num_heads"], CONFIG["mamba_head_dim"],
        CONFIG["ssm_state_size"], CONFIG["n_groups"], CONFIG["conv_kernel"],
        CONFIG["use_conv_bias"])
    assert (params["ffn_activation"], params["ffn_dim"],
            params["moe_shared_experts"] * params["ffn_dim"]) == (
        CONFIG["mlp_hidden_act"], CONFIG["moe_intermediate_size"],
        CONFIG["moe_shared_expert_intermediate_size"])
    assert (params["moe_experts"], params["moe_experts_held"],
            params["moe_top_k"], params["moe_route_scale"]) == (
        CONFIG["published"]["n_routed_experts"], CONFIG["n_routed_experts"],
        CONFIG["num_experts_per_tok"], CONFIG["routed_scaling_factor"])
    assert (params["num_heads"], params["num_kv_heads"], params["head_dim"],
            params["dim"], params["norm_eps"]) == (
        CONFIG["num_attention_heads"], CONFIG["num_key_value_heads"],
        CONFIG["head_dim"], CONFIG["hidden_size"], CONFIG["norm_eps"])
    assert list(params)[0] == "dim"
    # a parent's first TypeError names a field it lacks: among the keys
    assert {"ssm_heads", "mixer_ffn", "conv_bias"} <= set(params)
    for reading in ("no_rope", "mamba_width", "gated_norm", "router"):
        said = CONFIG["assumed"][reading]
        assert "not taken" in said or "not from" in said or (
            "differ by" in said), reading
    for word in ("16 chips", "by 8", "pipeline stages",
                 "WITHOUT the exchange"):
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
    assert set(said[1]["layers_same_input"]) == {
        "mamba", "shared_expert", "routed_experts", "ssm_state",
        "ssm_decays"}
    assert max(said[1]["layers_same_input"].values()) < 1e-4
    assert said[2]["loss"] > 0
    assert "ssm scan: rows=" in done.stderr
