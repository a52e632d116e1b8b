"""The gated short convolution (ops/short_conv.py): the Pallas kernels in
interpret mode against the plain reference, forward and all four
gradients, at a sequence's start, across row-tile edges and with more
than one sequence; what the op does where no kernel runs.  Float32 and
bfloat16 on the CPU at tiny widths; the kernels through the TPU's
compiler are tests/test_flash_compile_tpu.py's."""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import flash_attention as fa
from elasticdl_tpu.ops import short_conv as sc


def _operands(b, t, e, taps=3, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    bcu = jnp.asarray(rng.standard_normal((b, t, 3 * e)), dtype)
    w = jnp.asarray(rng.standard_normal((e, taps)), jnp.float32)
    cot = jnp.asarray(rng.standard_normal((b, t, e)), jnp.float32)
    return bcu, w, cot


def _by_hand(bcu, w):
    """The definition, a loop over positions and taps in numpy."""
    bcu, w = np.asarray(bcu, np.float64), np.asarray(w, np.float64)
    e, taps = w.shape
    b, c, u = bcu[..., :e], bcu[..., e:2 * e], bcu[..., 2 * e:]
    g = b * u
    out = np.zeros_like(g)
    for t in range(g.shape[1]):
        for k in range(taps):
            src = t - (taps - 1 - k)
            if src >= 0:
                out[:, t] += w[:, k] * g[:, src]
    return c * out


@pytest.fixture
def small_tiles(monkeypatch):
    """16-row tiles: 32 or 64 positions are several tiles a sequence."""
    monkeypatch.setattr(sc, "ROW_TILES", (16,))


def test_the_reference_is_the_definition():
    bcu, w, _ = _operands(2, 9, 8)
    np.testing.assert_allclose(sc.short_conv_ref(bcu, w), _by_hand(bcu, w),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b,t,e,taps,dtype", [
    (3, 32, 128, 3, jnp.float32),     # two tiles a sequence, 3 sequences
    (2, 64, 256, 3, jnp.float32),     # four tiles, two channel tiles
    (1, 16, 128, 3, jnp.float32),     # one tile: the start alone
    (2, 32, 128, 4, jnp.float32),     # another tap count
    (2, 64, 128, 3, jnp.bfloat16),
])
def test_kernel_forward_matches_reference(small_tiles, b, t, e, taps, dtype):
    bcu, w, _ = _operands(b, t, e, taps, dtype)
    got = sc.short_conv(bcu, w, interpret=True)
    assert got.dtype == dtype and got.shape == (b, t, e)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(sc.short_conv_ref(bcu, w), np.float32),
        rtol=tol, atol=tol)


@pytest.mark.parametrize("which", ["dB", "dC", "du", "dw"])
def test_kernel_gradients_match_reference(small_tiles, which):
    """Each of the custom_vjp's four gradients, three sequences of two
    tiles: the taps run backwards across the tile edge and stop at a
    sequence's end."""
    b, t, e = 3, 32, 128
    bcu, w, cot = _operands(b, t, e, seed=3)
    loss = lambda fn: lambda bcu, w: (fn(bcu, w) * cot).sum()
    kernel = lambda bcu, w: sc.short_conv(bcu, w, interpret=True)
    got = jax.grad(loss(kernel), argnums=(0, 1))(bcu, w)
    want = jax.grad(loss(sc.short_conv_ref), argnums=(0, 1))(bcu, w)
    if which == "dw":
        pair = got[1], want[1]
    else:
        at = "BCu".index(which[1]) * e
        pair = got[0][..., at:at + e], want[0][..., at:at + e]
    assert float(jnp.abs(pair[1]).max()) > 0.1
    np.testing.assert_allclose(*pair, rtol=2e-5, atol=2e-5)


def test_no_sequence_leaks_into_the_next(small_tiles):
    """Sequence 1's result and gradients are those of sequence 1 alone,
    whatever sequence 0 holds; sequence 0's last positions get no
    gradient from sequence 1's first."""
    bcu, w, cot = _operands(2, 32, 128, seed=5)
    other = bcu.at[0].set(bcu[0] * 7.0 + 1.0)
    kernel = lambda bcu, w: sc.short_conv(bcu, w, interpret=True)
    value = lambda bcu: jax.value_and_grad(
        lambda bcu: (kernel(bcu, w) * cot).sum())(bcu)
    (_, g1), (_, g2) = value(bcu), value(other)
    np.testing.assert_array_equal(kernel(bcu, w)[1], kernel(other, w)[1])
    np.testing.assert_array_equal(g1[1], g2[1])
    alone = kernel(bcu[1:], w)
    np.testing.assert_allclose(kernel(bcu, w)[1], alone[0], rtol=1e-6)
    # only sequence 1's cotangent: nothing reaches sequence 0
    only = jax.grad(lambda bcu: (kernel(bcu, w)[1] * cot[1]).sum())(bcu)
    assert float(jnp.abs(only[0]).max()) == 0.0
    assert float(jnp.abs(only[1]).max()) > 0.0


def test_every_result_of_the_calls_is_two_dimensional(small_tiles):
    """The benchmark tells the flash kernels by their 3-D results
    (benchmark/kernels/flash_attention.py): these calls have none, and
    carry their names."""
    bcu, w, cot = _operands(2, 32, 128)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda bcu, w: (sc.short_conv(bcu, w, interpret=True) * cot).sum(),
        argnums=(0, 1)))(bcu, w)
    calls = []

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                calls.append(eqn)
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    walk(inner)

    walk(jaxpr.jaxpr)
    names = sorted(c.params["name"] for c in calls)
    assert names == ["sconv_bwd", "sconv_fwd"]
    assert all(len(v.aval.shape) == 2 for c in calls for v in c.outvars)


def test_shapes_the_kernel_does_not_tile_take_the_reference(monkeypatch):
    """T not a multiple of 16, or channels not of 128: the reference's
    result, and under the compiled mode a line that says so."""
    lines = []
    monkeypatch.setattr(fa, "_announce_once",
                        lambda *a: lines.append(a))
    for b, t, e in ((2, 10, 128), (2, 32, 96)):
        bcu, w, _ = _operands(b, t, e)
        np.testing.assert_array_equal(
            sc.short_conv(bcu, w, interpret=True), sc.short_conv_ref(bcu, w))
    assert not lines                       # interpret: no kernel asked for
    assert sc.tiles(10, 128) is None and sc.tiles(32, 96) is None
    assert sc.tiles(8192, 2048) == (512, 512)
    # the compiled mode says why (the reference itself runs anywhere)
    bcu, w, _ = _operands(2, 10, 128)
    out = sc.short_conv(bcu, w, interpret=False)
    np.testing.assert_array_equal(out, sc.short_conv_ref(bcu, w))
    assert [a[0] for a in lines] == ["short_conv"]


def test_off_mode_is_the_reference_and_the_line_says_which(monkeypatch):
    monkeypatch.setenv("ELASTICDL_FLASH", "off")
    sc.announce_conv.cache_clear()
    seen = []
    handler = logging.Handler()
    handler.emit = lambda record: seen.append(record.getMessage())
    fa.logger.addHandler(handler)
    try:
        bcu, w, _ = _operands(2, 32, 128)
        for _ in range(2):                  # once per compiled shape
            out = jax.jit(sc.short_conv)(bcu, w)
    finally:
        fa.logger.removeHandler(handler)
    np.testing.assert_allclose(out, sc.short_conv_ref(bcu, w), rtol=1e-5,
                               atol=1e-5)
    assert [l for l in seen if l.startswith("short conv:")] == [
        "short conv: rows=64 channels=128 tile=- kernel=off"]
