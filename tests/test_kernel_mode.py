"""Which kernel runs is one decision behind ``ops/`` (``ops/mode.py``):
one mode, one switch, asked by the ops themselves; a tracing context
where no kernel can run; no caller that computes an ``interpret=``."""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.models import transformer as tfm
from elasticdl_tpu.ops import flash_attention as fa
from elasticdl_tpu.ops import group_norm as gn
from elasticdl_tpu.ops import grouped_matmul as gm
from elasticdl_tpu.ops.mode import SWITCH, kernel_mode, kernels_off
from elasticdl_tpu.parallel.mesh import build_mesh
from elasticdl_tpu.parallel.ring_attention import ring_attention

PACKAGE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "elasticdl_tpu")


def _pallas_calls(fn, *args):
    """"" / "interpret" / "compiled": what the Pallas calls in fn's
    jaxpr (custom_vjp bodies included) are."""
    text = str(jax.make_jaxpr(fn)(*args))
    if "pallas_call" not in text:
        return ""
    return "interpret" if "interpret=True" in text else "compiled"


def _rand(*shape, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape),
                       jnp.float32)


def _flash():
    q = _rand(1, 2, 128, 64)
    return (lambda q: fa.flash_attention(q, q, q)), (q,), (
        lambda q: fa._attention_ref(q, q, q, True, 64 ** -0.5))


def _grouped_matmul():
    lhs, rhs = _rand(256, 128), _rand(4, 128, 128, seed=1)
    sizes = jnp.asarray([100, 0, 56, 100], jnp.int32)
    return (lambda lhs, rhs: gm.grouped_matmul(lhs, rhs, sizes)), (
        lhs, rhs), (lambda lhs, rhs: gm.grouped_matmul_ref(lhs, rhs, sizes))


def _group_norm():
    x, scale, bias = _rand(2, 4, 4, 32), jnp.ones(32), jnp.zeros(32)
    return (lambda x: gn.fused_group_norm(x, scale, bias, 8)), (x,), (
        lambda x: gn._group_norm_ref(x, scale, bias, 8, 1e-6, False))


def test_auto_is_off_on_the_cpu(monkeypatch):
    monkeypatch.delenv(SWITCH, raising=False)
    assert kernel_mode() == "off"
    monkeypatch.setenv(SWITCH, "auto")
    assert kernel_mode() == "off"


def test_an_unknown_value_raises(monkeypatch):
    """``tpuu`` used to mean "off" in silence: on the chip, the slow
    path nobody asked for."""
    monkeypatch.setenv(SWITCH, "tpuu")
    with pytest.raises(ValueError, match="tpuu"):
        kernel_mode()
    with pytest.raises(ValueError, match=SWITCH):
        fa.flash_attention(*[_rand(1, 1, 128, 64)] * 3)


@pytest.mark.parametrize("mode", ["interpret", "off"])
@pytest.mark.parametrize("op", [_flash, _grouped_matmul, _group_norm])
def test_the_one_switch_moves_every_op(monkeypatch, op, mode):
    """``ELASTICDL_FLASH`` alone, no second variable: interpret puts the
    op on its kernel in interpret mode, off on its reference."""
    monkeypatch.setenv(SWITCH, mode)
    fn, args, ref = op()
    assert _pallas_calls(fn, *args) == ("interpret" if mode == "interpret"
                                        else "")
    np.testing.assert_allclose(fn(*args), ref(*args), rtol=2e-5, atol=2e-5)


def _moe_cfg():
    return tfm.TransformerConfig(
        vocab_size=64, dim=128, num_heads=2, num_layers=1, max_seq_len=128,
        dtype="float32", ffn_dim=128, moe_experts=4, moe_top_k=2)


def _layer_call(cfg):
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    w = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    x = _rand(2, 128, cfg.dim)
    positions = jnp.arange(128)
    return x, w, positions


@pytest.mark.parametrize("half", ["attention", "moe"])
def test_inside_kernels_off_a_layer_issues_no_pallas_call(
        monkeypatch, half):
    """The fact of the tracing context wins over the switch, for the
    attention and for how the MoE multiplies; outside it both reach
    their kernels."""
    monkeypatch.setenv(SWITCH, "interpret")
    cfg = _moe_cfg()
    x, w, positions = _layer_call(cfg)

    def fn():   # a new function each time: a trace is cached by its own
        if half == "attention":
            return lambda x: tfm._attention(x, w, cfg, None, positions)[0]
        return lambda x: tfm._ffn(x, w, cfg, None)[0]

    assert _pallas_calls(fn(), x) == "interpret"
    with kernels_off():
        assert kernel_mode() == "off"
        assert _pallas_calls(fn(), x) == ""
        with kernels_off(False):    # says nothing, undoes nothing
            assert kernel_mode() == "off"
    assert kernel_mode() == "interpret"


def test_a_model_parallel_mesh_keeps_the_moe_on_ragged_dot(monkeypatch):
    monkeypatch.setenv(SWITCH, "interpret")
    cfg = _moe_cfg()
    x, w, _ = _layer_call(cfg)
    mesh = build_mesh(dp=2, devices=jax.devices()[:2])
    assert _pallas_calls(lambda x: tfm._ffn(x, w, cfg, mesh)[0], x) == ""


def test_a_pipeline_stage_issues_no_pallas_call(monkeypatch):
    """``forward_pipelined``'s stage body runs under auto dp/tp axes:
    it enters ``kernels_off()`` itself, no argument says so."""
    monkeypatch.setenv(SWITCH, "interpret")
    cfg = dataclasses.replace(_moe_cfg(), num_layers=2, moe_experts=0)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((4, 128), jnp.int32)
    mesh = build_mesh(pp=2, devices=jax.devices()[:2])
    assert _pallas_calls(
        lambda p: tfm.forward_pipelined(p, tokens, cfg, mesh, 2),
        params) == ""
    assert _pallas_calls(
        lambda p: tfm.forward(p, tokens, cfg), params) == "interpret"


def test_ring_attention_off_a_mesh_it_cannot_divide_takes_the_reference(
        monkeypatch):
    """3 sequences over dp=2: no shard_map can hold the kernel, so the
    reference runs, through the same context."""
    monkeypatch.setenv(SWITCH, "interpret")
    q = _rand(3, 128, 2, 64)
    mesh = build_mesh(dp=2, devices=jax.devices()[:2])
    assert _pallas_calls(
        lambda q: ring_attention(q, q, q, mesh), q) == ""
    even = _rand(2, 128, 2, 64)
    assert _pallas_calls(
        lambda q: ring_attention(q, q, q, mesh), even) == "interpret"


@pytest.mark.parametrize("mode", ["interpret", "off"])
def test_one_chip_attention_is_the_op_itself(monkeypatch, mode):
    """``_attention`` without a mesh calls ``ops.flash_attention``
    directly; the sequence-parallel module's ``mesh=None`` door gives
    the same numbers."""
    monkeypatch.setenv(SWITCH, mode)
    cfg = dataclasses.replace(_moe_cfg(), moe_experts=0)
    x, w, positions = _layer_call(cfg)
    got, _ = tfm._attention(x, w, cfg, None, positions)
    h = tfm._rmsnorm(x, w["ln1"], cfg.norm_eps)
    # head-major from the projections; the sequence-parallel module
    # takes token-major operands
    q, k, v = (a.transpose(0, 2, 1, 3)
               for a in tfm._project_qkv(h, w, cfg, positions))
    attn = ring_attention(q, k, v, None, causal=True)
    want = x + attn.reshape(2, 128, cfg.dim) @ w["wo"]
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_nothing_outside_ops_names_the_mode_or_the_switch():
    """The lint gate of the seam.  ``worker/main.py`` is the one module
    allowed to ask: it states the mode in its ``worker device:`` line
    and decides nothing by it.  And ``utils/`` imports nothing from
    ``ops/``: the arrow points down."""
    named = re.compile(r"kernel_mode|ELASTICDL_FLASH|ops\.mode import "
                       r"(?!kernels_off\b)|\bresolve\(")
    offenders = []
    for folder, _, files in os.walk(PACKAGE):
        for name in files:
            path = os.path.join(folder, name)
            rel = os.path.relpath(path, PACKAGE)
            if not name.endswith(".py") or rel.startswith("ops" + os.sep):
                continue
            text = open(path).read()
            if rel != os.path.join("worker", "main.py") and named.search(
                    text):
                offenders.append(rel)
            if rel.startswith("utils" + os.sep) and re.search(
                    r"elasticdl_tpu\.ops|from elasticdl_tpu import ops",
                    text):
                offenders.append(rel + " imports ops")
    assert offenders == []
    switch_readers = [
        name for name in os.listdir(os.path.join(PACKAGE, "ops"))
        if name.endswith(".py") and re.search(
            r"environ.*(SWITCH|ELASTICDL_FLASH)",
            open(os.path.join(PACKAGE, "ops", name)).read())]
    assert switch_readers == ["mode.py"]


def test_the_worker_device_line_keeps_its_fields(monkeypatch):
    """``benchmark/lib/job.py`` and ``chip_smoke.py`` parse it: the
    fields and their order, ``flash=`` and ``fused_gn=`` both stating
    the one mode."""
    from elasticdl_tpu.worker.main import device_line

    monkeypatch.setenv(SWITCH, "interpret")
    items = [item.split("=", 1) for item in device_line().split()]
    assert [key for key, _ in items] == [
        "platform", "device_kind", "local_devices", "global_devices",
        "device_ids", "visible_chips", "flash", "fused_gn",
        "peak_bytes_in_use", "peak_bytes_reserved"]
    assert dict(items)["flash"] == dict(items)["fused_gn"] == "interpret"
