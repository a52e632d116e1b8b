"""Operation and byte functions against hand counts."""

import json
import os

import pytest

from benchmark.lib import kernels, manifest, peaks

lm_dense = manifest.load_named("opcounts", "lm_dense")
flash = manifest.load_named("kernels", "flash_attention")


def _config(name):
    with open(os.path.join(manifest.BENCH_DIR, "configs",
                           name + ".json")) as fh:
        return json.load(fh)


def test_lm_flops_by_hand_on_a_small_model():
    cfg = {"hidden_size": 4, "num_hidden_layers": 2, "intermediate_size": 8,
           "vocab_size": 10, "seq_len": 3, "num_attention_heads": 2,
           "head_dim": 2}
    # per layer: q,k,v,o 4*4*4 = 64; mlp 3*4*8 = 96 -> 160 weights; two
    # layers 320; head 4*10 = 40 -> 360 multiply-adds a token, 720 ops;
    # 3 tokens 2160.  attention: per layer 2 matmuls of T*T*E = 36
    # multiply-adds, 72 ops each, causal half -> 72; two layers 144.
    # forward 2304; with the backward x3 = 6912.
    assert lm_dense.train_flops(cfg) == 6912


def test_lm_flops_at_the_cells_widths():
    cfg = _config("olmo1b")
    L, E, F, V, T = (cfg["num_hidden_layers"], 2048, 8192, 50304, 2048)
    per_layer = 4 * E * E + 3 * E * F
    assert per_layer == 67_108_864            # the issue's 67.1 M a layer
    want = 3 * (T * 2 * (L * per_layer + E * V) + L * 2 * T * T * E)
    assert lm_dense.train_flops(cfg) == want
    # about 6 x weights x tokens: attention adds under a tenth
    assert want / (6 * (L * per_layer + E * V) * T) == pytest.approx(
        1.05, abs=0.05)


def test_flash_attention_call_counts():
    flops, nbytes = flash.call(8 * 16, 2048, 128, "fwd")
    assert flash.pairs(2048) == 2048 * 2049 // 2
    assert flops == 2 * 2 * 8 * 16 * flash.pairs(2048) * 128
    # q, k, v, o and the two float32 row statistics
    assert nbytes == 4 * 8 * 16 * 2048 * 128 * 2 + 2 * 4 * 8 * 16 * 2048
    dq = flash.call(8 * 16, 2048, 128, "dq")[0]
    dkv = flash.call(8 * 16, 2048, 128, "dkv")[0]
    assert (dq, dkv) == (flops * 3 // 2, flops * 2)
    # the fused backward: five products where the pair made seven
    bwd, bwd_bytes = flash.call(8 * 16, 2048, 128, "bwd")
    assert bwd == flops * 5 // 2 < dq + dkv
    assert bwd_bytes == 8 * 8 * 16 * 2048 * 128 * 2 + 2 * 4 * 8 * 16 * 2048
    seconds, bound = peaks.roofline_seconds(flops, nbytes, "TPU v5 lite")
    assert bound == "compute"
    assert seconds == pytest.approx(flops / 197e12)


def test_a_call_of_few_operations_is_memory_bound():
    seconds, bound = peaks.roofline_seconds(8e6, 819e9, "TPU_v5_lite")
    assert bound == "memory" and seconds == pytest.approx(1.0)


def test_an_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="TPU v5 lite"):
        peaks.peaks_of("TPU v9")


def test_kernel_calls_are_told_apart_by_their_names():
    fwd = ('%flash_fwd.71 = (bf16[128,2048,128]{2,1,0}, f32[128,2048,8]'
           '{2,1,0}, f32[128,2048,8]{2,1,0}) custom-call(bf16[128,2048,128]'
           '{2,1,0} %a, bf16[128,2048,128]{2,1,0} %b, bf16[128,2048,128]'
           '{2,1,0} %c), custom_call_target="tpu_custom_call"')
    dq = ('%checkpoint_flash_dq__.23 = bf16[128,2048,128]{2,1,0:T(8,128)(2,1)} '
          'custom-call(bf16[128,2048,128]{2,1,0} %a, bf16[128,2048,128]'
          '{2,1,0} %b), custom_call_target="tpu_custom_call"')
    results, operands = kernels.parse_call(fwd)
    assert len(results) == 3 and operands == 3
    kind, (flops, _) = flash.classify(results, operands, hlo=fwd)
    assert kind == "fwd"
    assert flops == flash.call(8 * 16, 2048, 128, "fwd")[0]
    assert flash.classify(*kernels.parse_call(dq), hlo=dq)[0] == "dq"
    # a count of results tells nothing: the fused backward has the
    # forward's three, and a call that carries no name is nobody's
    bwd = fwd.replace("%flash_fwd.71", "%flash_bwd.2").replace("f32", "bf16")
    assert flash.classify(*kernels.parse_call(bwd), hlo=bwd)[0] == "bwd"
    for other in (fwd.replace("%flash_fwd.71", "%pallas_call.71"),
                  dq.replace("%checkpoint_flash_dq__.23", "%checkpoint.23")):
        assert flash.classify(*kernels.parse_call(other), hlo=other) is None
    assert flash.classify(results, operands) is None     # no text, no name
    assert kernels.parse_call("%fusion.1 = f32[8]{0} fusion(%a)") is None


def test_roofline_share_of_the_flash_calls_in_a_trace():
    import types

    fwd = ('%flash_fwd.71 = (bf16[128,2048,128]{2,1,0}, f32[128,2048,8]'
           '{2,1,0}, f32[128,2048,8]{2,1,0}) custom-call(bf16[128,2048,128]'
           '{2,1,0} %a, bf16[128,2048,128]{2,1,0} %b, bf16[128,2048,128]'
           '{2,1,0} %c), custom_call_target="tpu_custom_call"')
    least = flash.call(8 * 16, 2048, 128, "fwd")[0] / 197e12
    run = types.SimpleNamespace(
        config=_config("olmo1b"), device={"kind": "TPU v5 lite"},
        trace={"custom_calls": {
            fwd: [3 * 4 * least, 3.0],      # three calls at a quarter
            '%other = f32[8]{0} custom-call(f32[8]{0} %a), '
            'custom_call_target="tpu_custom_call"': [1.0, 1.0]}})
    assert kernels.roofline_share(run, "flash_attention") == pytest.approx(
        25.0)
    run.config = dict(run.config, kernels=[])
    assert kernels.roofline_share(run, "flash_attention") is None
