"""Sliding-window attention across the whole stack (VERDICT r2 #3).

The round-2 regression shipped because no test passed window != 0
anywhere.  This file covers the band in every implementation: the Pallas
flash kernel (fwd + both backwards), the partial kernel ring attention
folds, the ring dispatch (skip / full / banded blocks), Ulysses, and the
transformer config plumbing — all against the dense reference
``_attention_ref(window=...)``.

Window values are chosen to hit the tile-arithmetic edges at t=384
(tile 128 -> a 3x3 block grid): W < tile, W not a multiple of 128, and
W >= t (must equal full causal).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops.flash_attention import (
    _attention_ref,
    flash_attention,
    flash_attention_partial,
)
from elasticdl_tpu.ops.mode import kernels_off
from elasticdl_tpu.parallel.mesh import build_mesh
from elasticdl_tpu.parallel.ring_attention import (
    attention_local,
    ring_attention,
)
from elasticdl_tpu.parallel.ulysses import ulysses_attention

WINDOWS = [64, 200, 1000]  # < tile; not a multiple of 128; >= t
# (t, window) where the band's lower edge crosses major tiles of several
# sub-tiles: t=1536 takes the 512 tile (3x3 grid of 4x4 sub-tiles) and a
# window of 600 ends in the tile one below the diagonal, which so has
# interior, edge and skipped sub-tiles in one branch, while the tile two
# below is no grid step at all; t=1024 is one 8x8 major tile with both
# edges in it.
TILE_EDGE_WINDOWS = [(1536, 600), (1024, 200)]


def make_bhtd(b=1, h=2, t=384, d=64, seed=0):
    rng = np.random.RandomState(seed)
    shape = (b, h, t, d)
    return tuple(
        jnp.asarray(rng.randn(*shape).astype(np.float32)) for _ in range(3)
    )


def make_bthd(b=2, t=64, h=4, d=16, seed=0):
    rng = np.random.RandomState(seed)
    shape = (b, t, h, d)
    return tuple(
        jnp.asarray(rng.randn(*shape).astype(np.float32)) for _ in range(3)
    )


@pytest.mark.parametrize(
    "t,window", [(384, w) for w in WINDOWS] + TILE_EDGE_WINDOWS)
def test_flash_window_forward(t, window):
    q, k, v = make_bhtd(h=1 if t > 384 else 2, t=t)
    ref = _attention_ref(q, k, v, True, q.shape[-1] ** -0.5,
                         window=window)
    out = flash_attention(q, k, v, causal=True, interpret=True,
                          window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    if window >= q.shape[2]:
        full = flash_attention(q, k, v, causal=True, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                                   rtol=2e-5, atol=2e-5)


def test_flash_window_requires_causal():
    q, k, v = make_bhtd(t=128)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, causal=False, window=64)
    with pytest.raises(ValueError):
        flash_attention_partial(q, k, v, causal=False, window=64)
    with pytest.raises(ValueError):
        ring_attention(q, k, v, None, causal=False, window=64)
    qs, ks, vs = make_bthd(t=16)
    with pytest.raises(ValueError):
        attention_local(qs, ks, vs, causal=False, window=8)
    with pytest.raises(ValueError):
        ulysses_attention(qs, ks, vs, None, causal=False, window=8)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, causal=True, window=-1)


def test_banded_partial_matches_dense():
    """_partial_banded (the ring's straddling-block path, blockwise with
    checkpoint) == the dense banded reference, values and grads."""
    from elasticdl_tpu.ops.flash_attention import (
        _partial_banded,
        _partial_ref,
    )

    q, k, v = make_bhtd(b=1, h=1, t=256, d=32, seed=9)
    scale = q.shape[-1] ** -0.5
    for k_offset, window in ((-256, 300), (-128, 200), (0, 64)):
        dense = _partial_ref(q, k, v, True, scale, k_offset,
                             window=window)
        blockwise = _partial_banded(q, k, v, scale, k_offset, window)
        for a, b in zip(dense, blockwise):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-5)

    def norm_out(fn):
        def f(q, k, v):
            acc, l, m = fn(q, k, v)
            return (acc / jnp.maximum(l, 1e-30)[..., None]).sum()
        return f

    gd = jax.grad(norm_out(
        lambda q, k, v: _partial_ref(q, k, v, True, scale, -128,
                                     window=200)), argnums=(0, 1, 2),
    )(q, k, v)
    gb = jax.grad(norm_out(
        lambda q, k, v: _partial_banded(q, k, v, scale, -128, 200)),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(gd, gb):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_ring_window_blockwise_banded():
    """Shards long enough (T/sp=256, two 128-blocks) that the straddling
    ring step takes the blockwise _partial_banded path, not the dense
    fallback."""
    q, k, v = make_bthd(b=1, t=1024, h=1, d=32, seed=11)
    mesh = build_mesh(sp=4, devices=jax.devices()[:4])
    for window in (300, 700):
        with kernels_off():
            ref = attention_local(q, k, v, causal=True, window=window)
        out = ring_attention(q, k, v, mesh, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize(
    "t,window", [(384, w) for w in WINDOWS] + TILE_EDGE_WINDOWS)
def test_flash_window_pallas_bwd(t, window, monkeypatch):
    """The Pallas backward under a window — the live-tile tables and
    the in-kernel band mask on the edge sub-tiles."""
    import elasticdl_tpu.ops.flash_attention as fa

    called = {}
    orig = fa._pallas_bwd

    def spy(*args, **kwargs):
        called["yes"] = True
        return orig(*args, **kwargs)

    monkeypatch.setattr(fa, "_pallas_bwd", spy)
    q, k, v = make_bhtd(h=1 if t > 384 else 2, t=t, seed=window)

    def loss_flash(q, k, v):
        return (
            flash_attention(q, k, v, causal=True, interpret=True,
                            window=window) ** 2
        ).sum()

    def loss_ref(q, k, v):
        return (
            _attention_ref(q, k, v, True, q.shape[-1] ** -0.5,
                           window=window) ** 2
        ).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    assert called.get("yes"), "pallas bwd was not invoked"
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("t,window,tile,blocks_of_last_tile", [
    # tiles of 128: the last query tile's first rows reach 199 keys
    # back, into key block 1 of four
    (512, 200, 128, [1, 2, 3]),
    # tiles of 512 (4 x 4 sub-tiles, some skipped): the last of three
    # reaches into key block 0
    (1536, 600, 512, [0, 1, 2]),
])
def test_fused_backward_dq_across_a_windows_key_blocks(t, window, tile,
                                                       blocks_of_last_tile):
    """A query tile whose first and last key block differ, under a
    window: its dq is the sum of parts made at grid steps with other
    tiles' steps between them (keys resident: a key block's query tiles
    in a row), each head's in its own accumulator."""
    import elasticdl_tpu.ops.flash_attention as fa

    plan = fa._tile_plan(t, tile, True, window)
    last = plan.num - 1
    assert [ki for qi, ki in plan.k_major if qi == last] == \
        blocks_of_last_tile
    steps = [s for s, (qi, _) in enumerate(plan.k_major) if qi == last]
    assert max(b - a for a, b in zip(steps, steps[1:])) > 1
    q, k, v = make_bhtd(b=2, h=2, t=t, seed=window)
    g = make_bhtd(b=2, h=2, t=t, seed=1)[0]

    def grads(op):
        return jax.grad(lambda q, k, v: (op(q, k, v) * g).sum(),
                        (0, 1, 2))(q, k, v)

    got = grads(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=True, window=window))
    want = grads(lambda q, k, v: _attention_ref(
        q, k, v, True, q.shape[-1] ** -0.5, window=window))
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("window", [64, 200])
def test_partial_window_matches_reference(window):
    """Windowed partial (acc, l, m) normalizes to the windowed dense
    output — the diagonal block of a windowed ring."""
    q, k, v = make_bhtd(t=256, seed=5)
    acc, l, m = flash_attention_partial(
        q, k, v, causal=True, interpret=True, window=window
    )
    out = np.asarray(acc) / np.maximum(np.asarray(l), 1e-30)[..., None]
    ref = _attention_ref(q, k, v, True, q.shape[-1] ** -0.5,
                         window=window)
    np.testing.assert_allclose(out, np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [64, 200])
def test_partial_window_grads(window):
    """The stats-based partial backward recomputes windowed scores."""
    from elasticdl_tpu.ops.flash_attention import _partial_ref

    q, k, v = make_bhtd(t=256, seed=7)
    scale = q.shape[-1] ** -0.5
    rng = np.random.RandomState(1)
    cot = (
        jnp.asarray(rng.randn(*q.shape).astype(np.float32)),
        jnp.asarray(rng.randn(*q.shape[:3]).astype(np.float32)),
        jnp.asarray(rng.randn(*q.shape[:3]).astype(np.float32)),
    )
    _, vjp_d = jax.vjp(
        lambda q, k, v: _partial_ref(q, k, v, True, scale, 0,
                                     window=window),
        q, k, v,
    )
    _, vjp_f = jax.vjp(
        lambda q, k, v: flash_attention_partial(
            q, k, v, causal=True, interpret=True, window=window
        ),
        q, k, v,
    )
    for a, b in zip(vjp_d(cot), vjp_f(cot)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=2e-3)


# -- ring / ulysses (layout [B, T, H, D], 8 virtual CPU devices) ------------


@pytest.mark.parametrize("sp", [2, 4])
# shard C = 64/sp: windows hit (inside-shard, straddling, multi-shard,
# >= T) so the skip / banded / full dispatch arms all run.
@pytest.mark.parametrize("window", [8, 20, 40, 100])
def test_ring_window_matches_local(sp, window):
    q, k, v = make_bthd()
    mesh = build_mesh(dp=2, tp=1, sp=sp, devices=jax.devices()[: 2 * sp])
    ref = attention_local(q, k, v, causal=True, window=window)
    out = ring_attention(q, k, v, mesh, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=2e-5, atol=2e-5)


def test_ring_window_grad():
    q, k, v = make_bthd(b=1, t=32, h=2, d=16, seed=2)
    mesh = build_mesh(dp=1, tp=1, sp=4, devices=jax.devices()[:4])

    def loss(q, k, v):
        return ring_attention(q, k, v, mesh, causal=True, window=12).sum()

    def loss_ref(q, k, v):
        return attention_local(q, k, v, causal=True, window=12).sum()

    g = jax.jit(jax.grad(loss))(q, k, v)
    g_ref = jax.grad(loss_ref)(q, k, v)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-4)


def test_ring_window_grad_banded_scan():
    """Gradients THROUGH _partial_banded's checkpoint+scan branch.

    The sp=4/T=32 grad test above has shard T_k=8, so banded falls back
    to _partial_ref and the scan branch's backward was never covered
    (ADVICE r3).  Here shard T_k = 512/2 = 256 = 2 x 128-blocks, and
    window=300 makes the delta=1 ring step a straddling block: the
    multi-block scan + jax.checkpoint backward is on the grad path.
    """
    q, k, v = make_bthd(b=1, t=512, h=1, d=32, seed=9)
    mesh = build_mesh(dp=1, tp=1, sp=2, devices=jax.devices()[:2])

    def loss(q, k, v):
        out = ring_attention(q, k, v, mesh, causal=True, window=300)
        return (out * out).sum()

    def loss_ref(q, k, v):
        with kernels_off():
            out = attention_local(q, k, v, causal=True, window=300)
        return (out * out).sum()

    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_ring_window_flash_fold(monkeypatch):
    """Windowed ring with the Pallas partial kernel on the diagonal
    (interpret mode) — the windowed-kernel + banded-jnp mix."""
    monkeypatch.setenv("ELASTICDL_FLASH", "interpret")
    q, k, v = make_bthd(b=1, t=512, h=1, d=64, seed=4)
    mesh = build_mesh(sp=4, devices=jax.devices()[:4])
    for window in (100, 300):
        with kernels_off():
            ref = attention_local(q, k, v, causal=True, window=window)
        out = ring_attention(q, k, v, mesh, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("sp", [2, 4])
@pytest.mark.parametrize("window", [8, 20, 100])
def test_ulysses_window_matches_local(sp, window):
    q, k, v = make_bthd(seed=6)
    mesh = build_mesh(dp=2, tp=1, sp=sp, devices=jax.devices()[: 2 * sp])
    ref = attention_local(q, k, v, causal=True, window=window)
    out = ulysses_attention(q, k, v, mesh, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_sp1_fallback_honors_window():
    """ADVICE r2 medium: the no-sp fallback used to silently drop the
    window."""
    q, k, v = make_bthd(seed=8)
    mesh = build_mesh(dp=2, tp=1, sp=1, devices=jax.devices()[:2])
    ref = attention_local(q, k, v, causal=True, window=16)
    out = ulysses_attention(q, k, v, mesh, causal=True, window=16)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=2e-5, atol=2e-5)


def test_transformer_window_config():
    """cfg.window reaches the attention stack: a windowed forward
    differs from full causal and matches between ring and ulysses."""
    from elasticdl_tpu.models import transformer as tfm

    base = dict(vocab_size=64, dim=64, num_heads=4, num_layers=2,
                max_seq_len=64, dtype="float32")
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, 64, size=(2, 64)), jnp.int32
    )
    mesh = build_mesh(dp=1, tp=1, sp=2, devices=jax.devices()[:2])
    cfg_full = tfm.TransformerConfig(**base)
    cfg_ring = tfm.TransformerConfig(window=16, **base)
    cfg_uly = tfm.TransformerConfig(window=16, attention_impl="ulysses",
                                    **base)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg_full)
    full = tfm.forward(params, tokens, cfg_full, mesh=mesh)
    ring = tfm.forward(params, tokens, cfg_ring, mesh=mesh)
    uly = tfm.forward(params, tokens, cfg_uly, mesh=mesh)
    assert not np.allclose(np.asarray(full), np.asarray(ring),
                           atol=1e-3), "window had no effect"
    np.testing.assert_allclose(np.asarray(ring), np.asarray(uly),
                               rtol=2e-4, atol=2e-4)
