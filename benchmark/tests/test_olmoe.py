"""What the ``olmoe1b7b`` configuration brought: the grouped-matmul calls
told from the flash kernels' and back, its operation count against a
hand count, its four readers on a fixture log and trace, and its plain
reference against the product at tiny sizes."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.lib import kernels, manifest

ROOT = os.path.dirname(manifest.BENCH_DIR)
BOOK = manifest.Manifest(ROOT)
CELL = BOOK.cell("olmoe1b7b.seq4096")

# The calls of one step as the compiled program names them (operands cut).
GMM = {
    "fwd up": "%gmm_nn.33 = bf16[131072,1024]{1,0:T(8,128)(2,1)} "
              "custom-call(%a, %b, %c, %d, %e, %f), "
              'custom_call_target="tpu_custom_call"',
    "fwd down": "%gmm_nn.35 = bf16[131072,2048]{1,0} custom-call("
                "%a, %b, %c, %d, %e, %f), "
                'custom_call_target="tpu_custom_call"',
    "dlhs": "%gmm_nt.6 = bf16[131072,1024]{1,0} custom-call("
            "%a, %b, %c, %d, %e, %f), custom_call_target=\"tpu_custom_call\"",
    "drhs down": "%gmm_tn.6 = bf16[65536,2048]{1,0} custom-call("
                 "%a, %b, %c, %d, %e, %f), "
                 'custom_call_target="tpu_custom_call"',
    "drhs up": "%transpose_jvp_gmm_tn__.1 = bf16[131072,1024]{1,0} "
               "custom-call(%a, %b, %c, %d, %e, %f), "
               'custom_call_target="tpu_custom_call"',
}
FLASH = {
    "fwd": "%checkpoint_flash_fwd__.4 = (bf16[64,4096,128]{2,1,0}, f32[64,1,4096]"
           "{2,1,0}, f32[64,1,4096]{2,1,0}) custom-call(%q, %k, %v, %t, %u),"
           ' custom_call_target="tpu_custom_call"',
    "dq": "%flash_dq.9 = bf16[64,4096,128]{2,1,0} custom-call(%q, %k), "
          'custom_call_target="tpu_custom_call"',
    "dkv": "%flash_dkv.10 = (bf16[64,4096,128]{2,1,0}, bf16[64,4096,128]"
           "{2,1,0}) custom-call(%q, %k), "
           'custom_call_target="tpu_custom_call"',
}
SHAPES = dict(rows=131072, widths=(2048, 1024), groups=64)


def test_each_kernels_classify_leaves_the_others_calls_alone():
    flash = manifest.load_named("kernels", "flash_attention")
    gmm = manifest.load_named("kernels", "grouped_matmul")
    for name, hlo in GMM.items():
        assert flash.classify(*kernels.parse_call(hlo), hlo=hlo) is None, name
        assert gmm.classify(*kernels.parse_call(hlo), hlo=hlo,
                            **SHAPES) is not None, name
    for name, hlo in FLASH.items():
        assert flash.classify(*kernels.parse_call(hlo), hlo=hlo)[0] == name
        assert gmm.classify(*kernels.parse_call(hlo), hlo=hlo,
                            **SHAPES) is None, name
    # the door lib/kernels.roofline_share uses hands no text over
    assert gmm.classify(*kernels.parse_call(GMM["dlhs"])) is None


def test_grouped_matmul_calls_are_counted_from_their_shapes():
    gmm = manifest.load_named("kernels", "grouped_matmul")
    kinds = {name: gmm.classify(*kernels.parse_call(hlo), hlo=hlo, **SHAPES)
             for name, hlo in GMM.items()}
    assert [kinds[n][0] for n in GMM] == ["fwd", "fwd", "dlhs", "drhs",
                                          "drhs"]
    flops = 2 * 131072 * 2048 * 1024
    moved = 2 * (131072 * 2048 + 131072 * 1024 + 64 * 2048 * 1024)
    for name in GMM:            # the five differ in shape, not in work
        assert kinds[name][1] == (flops, moved), name
    assert gmm.call(10, 4, 6, "fwd", groups=3) == (480, 2 * (40 + 60 + 72))


def test_lm_moe_counts_the_active_operations_of_a_record():
    flops = manifest.load_named("opcounts", "lm_moe").train_flops
    config = CELL["config"]
    tokens, hidden, heads_x_dim = 4096, 2048, 16 * 128
    per_token = 2 * (
        4 * hidden * heads_x_dim            # wq, wk, wv, wo
        + hidden * 64                        # the router, every expert
        + 8 * 3 * hidden * 1024              # 8 of 64 experts, 3 matmuls
        + hidden * 50304)                    # the head, once
    attention = 2 * tokens * tokens * heads_x_dim
    assert flops(config) == 3 * (tokens * per_token + attention)
    assert flops(config) == pytest.approx(4.39e12, rel=2e-3)
    deeper = dict(config, num_hidden_layers=2)
    assert flops(deeper) - flops(config) == 3 * (
        tokens * 2 * (4 * hidden * heads_x_dim + hidden * 64
                      + 8 * 3 * hidden * 1024) + attention)


LOG = """\
[2026-09-27 02:00:10,000] [INFO] [worker-0] [w:1:x] moe load: step=40 layers=1 rows=131072 max=4096 mean=2048.0 padded_rows=30720
[2026-09-27 02:00:20,000] [INFO] [worker-0] [w:1:x] step 80 loss 10.5 (version 80)
[2026-09-27 02:00:20,001] [INFO] [worker-0] [w:1:x] moe load: step=80 layers=1 rows=131072 max=3072 mean=2048.0 padded_rows=32768
[2026-09-27 02:00:30,000] [INFO] [worker-0] [w:1:x] moe load: step=120 layers=1 rows=131072 max=6144 mean=2048.0 padded_rows=31744
[2026-09-27 02:00:50,000] [INFO] [worker-0] [w:1:x] moe load: step=200 layers=1 rows=131072 max=99999 mean=2048.0 padded_rows=0
"""


def _run(log=LOG, custom_calls=None, config=None):
    from benchmark.lib import job

    at = lambda clock: job.stamp_seconds("[2026-09-27 %s,000] x" % clock)
    trace = None if custom_calls is None else {
        "custom_calls": custom_calls, "busy_s": 6.0}
    return types.SimpleNamespace(
        job=types.SimpleNamespace(text=log), trace=trace,
        times={"open": at("02:00:15"), "close": at("02:00:40")},
        config=config or CELL["config"], traffic=CELL["traffic"],
        cell={"chips": 1}, device={"kind": "TPU v5 lite"})


def test_the_load_readers_take_the_lines_inside_the_window():
    spread = BOOK.reader("moe.load_max_over_mean")
    padded = BOOK.reader("moe.padded_row_share")
    assert spread(_run()) == pytest.approx((1.5 + 3.0) / 2)
    assert padded(_run()) == pytest.approx(100 * 64512 / 262144)
    dense = _run(log="[2026-09-27 02:00:20,000] [INFO] step 80 loss 1.0\n")
    assert spread(dense) is None and padded(dense) is None


def test_the_kernel_readers_take_the_grouped_matmul_calls_alone(capsys):
    roofline = BOOK.reader("kernel.grouped_matmul_roofline")
    share = BOOK.reader("kernel.grouped_matmul_share")
    least = 2 * 131072 * 2048 * 1024 / 197e12        # MXU-bound, 2.79 ms
    calls = {GMM["fwd up"]: [4 * least, 2.0],         # 50%
             GMM["dlhs"]: [least, 1.0],               # 100%
             GMM["drhs down"]: [4 * least, 1.0],      # 25%
             FLASH["fwd"]: [0.5, 10.0]}
    run = _run(custom_calls=calls)
    assert roofline(run) == pytest.approx(100 * 4 / 9)
    assert share(run) == pytest.approx(100 * 9 * least / 6.0)
    lines = [l for l in capsys.readouterr().err.splitlines()
             if "grouped_matmul" in l]
    assert [l.split()[2].rstrip(":") for l in lines] == ["dlhs", "drhs",
                                                         "fwd"]
    assert "(50.0%)" in lines[2] and "compute-bound" in lines[2]
    # flash's own reader counts flash calls only
    flash = BOOK.reader("kernel.flash_attention_roofline")(run)
    assert 0 < flash < 100
    assert "flash_attention fwd" in capsys.readouterr().err
    # a parent (no such call), an untraced run, a configuration without it
    assert roofline(_run(custom_calls={FLASH["fwd"]: [0.5, 10.0]})) is None
    assert share(_run(custom_calls={FLASH["fwd"]: [0.5, 10.0]})) is None
    assert roofline(_run()) is None and share(_run()) is None
    dense = BOOK.cell("olmo1b.seq2048")["config"]
    assert roofline(_run(custom_calls=calls, config=dense)) is None


def test_the_new_metrics_are_the_new_cells_alone():
    mine = {m["name"] for m in CELL["per_layer"]}
    old = {m["name"] for m in BOOK.cell("olmo1b.seq2048")["per_layer"]}
    assert mine - old == {
        "kernel.grouped_matmul_roofline", "kernel.grouped_matmul_share",
        "moe.load_max_over_mean", "moe.padded_row_share"}
    assert old - mine == set()
    assert CELL["chips"] == 1 and CELL["config"]["reduced"] == [
        "num_hidden_layers"]
    flags = CELL["traffic"]["flags"]
    assert flags["batch_size"] * CELL["config"]["seq_len"] == 16384
    assert flags["log_loss_steps"] % flags["num_minibatches_per_task"] == 0


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
