"""Pallas flash attention vs the jnp reference (interpret mode on CPU)."""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops.flash_attention import (
    _attention_ref,
    flash_attention,
)


def make_qkv(b=2, h=2, t=256, d=64, seed=0, g=None):
    """q at ``h`` heads, k and v at ``g`` (None: ``h``)."""
    rng = np.random.RandomState(seed)
    return tuple(
        jnp.asarray(rng.randn(b, heads, t, d).astype(np.float32))
        for heads in (h, g or h, g or h)
    )


def _on_repeated(k, v, h):
    """K and V spread to ``h`` query heads the long way, group order
    consecutive: what the kernels' ``head // group`` index stands for.
    A gradient taken through it is the group's sum."""
    return tuple(jnp.repeat(x, h // x.shape[1], axis=1) for x in (k, v))


@pytest.mark.parametrize("window", [0, 200])
@pytest.mark.parametrize("h,g,d", [(4, 2, 64), (7, 1, 128), (8, 1, 64),
                                   (8, 4, 128)])
def test_grouped_forward_reads_kv_head_of_its_group(h, g, d, window):
    """K and V at their own head count (groups of 2, 7 and 8 query
    heads; two batch rows, so that ``(b * H + h) // group`` crosses a
    row): the kernel's output is the reference's on K/V repeated the
    long way, and the reference given G heads repeats inside itself."""
    q, k, v = make_qkv(b=2, h=h, t=384, d=d, seed=h + d, g=g)
    scale = d ** -0.5
    want = _attention_ref(q, *_on_repeated(k, v, h), True, scale,
                          window=window)
    np.testing.assert_array_equal(
        np.asarray(_attention_ref(q, k, v, True, scale, window=window)),
        np.asarray(want))
    out = flash_attention(q, k, v, window=window, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_kv_heads_that_do_not_divide_the_queries_are_refused():
    q, k, v = make_qkv(h=4, t=128, g=3)
    with pytest.raises(ValueError, match="divides"):
        flash_attention(q, k, v, interpret=True)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_reference(causal):
    q, k, v = make_qkv()
    ref = _attention_ref(q, k, v, causal, q.shape[-1] ** -0.5)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("t", [1024, 1536, 2048])
def test_flash_multi_k_block_grid(causal, t):
    # Every tile class and the carry across grid steps, none of which
    # engage when t is one sub-tile.  t=1024 is ONE major tile of 8x8
    # sub-tiles (interior, edge and skipped inside one step); t=1536
    # takes the 512 tile (3 does not divide by 1024): a 3x3 grid of
    # which causal runs 6 steps, the diagonal ones in the branch with
    # edges and the others in the interior branch, with the scratch
    # carry and the init/finish gating across a query block's steps;
    # t=2048 is the same on the 1024 tile (3 of 4 steps).
    q, k, v = make_qkv(b=1, h=1, t=t, d=64, seed=3)
    ref = _attention_ref(q, k, v, causal, q.shape[-1] ** -0.5)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_unaligned_seq_falls_back_and_says_so(monkeypatch):
    # T = 192 is no multiple of the 128 lanes of the kernel's stats
    # tiles: the op must take the reference path (and still be
    # numerically right), and in the compiled mode say so.
    import elasticdl_tpu.ops.flash_attention as fa

    monkeypatch.setenv("ELASTICDL_FLASH", "tpu")
    q, k, v = make_qkv(t=192, d=64)
    ref = _attention_ref(q, k, v, True, q.shape[-1] ** -0.5)
    fa._announce_once.cache_clear()
    with mock.patch(
        "elasticdl_tpu.ops.flash_attention._flash",
        side_effect=AssertionError("kernel must not run for T=192"),
    ), mock.patch.object(fa.logger, "warning") as warning:
        out = flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    message = warning.call_args[0][0] % warning.call_args[0][1:]
    assert message.startswith(fa.FALLBACK_PREFIX) and "seq 192" in message


def test_flash_grad_matches_reference():
    q, k, v = make_qkv(t=128)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, interpret=True).sum()

    def loss_ref(q, k, v):
        return _attention_ref(q, k, v, True, q.shape[-1] ** -0.5).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_unfriendly_shapes_fall_back():
    q, k, v = make_qkv(t=100, d=48)
    out = flash_attention(q, k, v)  # no crash: reference path
    assert out.shape == q.shape


@pytest.mark.parametrize("t", [128, 1536])
def test_partial_matches_reference_stats(t):
    """flash_attention_partial returns (acc, l, m) that normalize to the
    reference output — the ring-fold building block.  t=1536: the
    narrow [1, tile] stats rows of a 3x3 grid, causal (6 steps, edges)
    and not (9 steps, every sub-tile interior)."""
    from elasticdl_tpu.ops.flash_attention import (
        _partial_ref,
        flash_attention_partial,
    )

    q, k, v = make_qkv(b=1, t=t)
    for causal in (True, False):
        acc, l, m = flash_attention_partial(
            q, k, v, causal=causal, interpret=True
        )
        acc_r, l_r, m_r = _partial_ref(
            q, k, v, causal, q.shape[-1] ** -0.5, 0
        )
        np.testing.assert_allclose(
            np.asarray(m + jnp.log(l)), np.asarray(m_r + jnp.log(l_r)),
            rtol=2e-5, atol=2e-5)
        out = acc / np.maximum(np.asarray(l), 1e-30)[..., None]
        out_r = acc_r / np.maximum(np.asarray(l_r), 1e-30)[..., None]
        np.testing.assert_allclose(np.asarray(out), np.asarray(out_r),
                                   rtol=2e-5, atol=2e-5)
        ref = _attention_ref(q, k, v, causal, q.shape[-1] ** -0.5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("t,d", [(256, 64), (384, 64), (1536, 64),
                                 (256, 128)])
def test_pallas_bwd_matches_reference(causal, t, d, monkeypatch):
    """The default backward is the one Pallas call (dk, dv and dq from
    one rebuild of each score tile) — it must be the path taken and
    match reference gradients.  t=384 forces tile=128 -> a 3x3 block
    grid, exercising the cross-step scratch accumulation and the
    live-tile tables (t=256 is a single-block grid where init/finish
    coincide); t=1536 is a 3x3 grid of 4x4 sub-tiles: the diagonal
    tiles' branch skips the sub-tiles above the diagonal and masks the
    four on it, the others' branch masks nothing."""
    import elasticdl_tpu.ops.flash_attention as fa

    called = {}
    orig = fa._pallas_bwd

    def spy(*args, **kwargs):
        called["yes"] = True
        return orig(*args, **kwargs)

    monkeypatch.setattr(fa, "_pallas_bwd", spy)
    q, k, v = make_qkv(b=1, t=t, d=d)

    def loss_flash(q, k, v):
        return (
            fa.flash_attention(q, k, v, causal=causal,
                               interpret=True) ** 2
        ).sum()

    def loss_ref(q, k, v):
        return (
            fa._attention_ref(q, k, v, causal,
                              q.shape[-1] ** -0.5) ** 2
        ).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    assert called.get("yes"), "pallas bwd was not invoked"
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)


def _backward_calls(fn, *args):
    """Names of the Pallas calls in the traced gradient of ``fn``."""
    from tests.test_mixed_stack import _eqns

    jaxpr = jax.make_jaxpr(jax.grad(fn, tuple(range(len(args)))))(*args)
    return sorted(str(e.params["name"]) for e in _eqns(jaxpr.jaxpr)
                  if e.primitive.name == "pallas_call")


@pytest.mark.parametrize("case,b,h,g,t,d,causal,window,dtype,tol", [
    # three key blocks a head (tile 128): query tile 2's dq is added to
    # at grid steps 2, 4 and 5 of (0,0) (1,0) (2,0) (1,1) (2,1) (2,2)
    ("three-key-blocks", 2, 3, 3, 384, 128, True, 0, "float32", 1e-4),
    # every query tile ends in the last key block: the head's dq is
    # written whole at the head's last step
    ("not-causal", 1, 2, 2, 384, 64, False, 0, "float32", 1e-4),
    # rows over 512 bytes take the 512 tile: two key blocks of 4 x 4
    ("d256-float32", 1, 2, 2, 1024, 256, True, 0, "float32", 1e-4),
    # sub-tiles stacked a key chunk at a time across a 1024 tile's
    # diagonal, in the storage dtype the cells run
    ("bfloat16-two-tiles", 1, 2, 2, 2048, 64, True, 0, "bfloat16", 2e-2),
    # K and V at their own head count: a K/V head's dk and dv planes are
    # started by its group's first query head, added to by the middle
    # ones and written by the last, a key block's rows at a time (three
    # key blocks a head), across two batch rows
    ("group-2", 2, 4, 2, 384, 64, True, 0, "float32", 1e-4),
    ("group-7-window", 1, 7, 1, 384, 128, True, 200, "float32", 1e-4),
    ("group-8", 2, 8, 1, 384, 128, True, 0, "float32", 1e-4),
    ("group-8-window-d64", 1, 8, 1, 384, 64, True, 200, "float32", 1e-4),
    ("group-4-not-causal", 1, 8, 2, 256, 64, False, 0, "float32", 1e-4),
    # the one rounding of the float32 sum, over two key blocks of 1024
    ("group-2-bfloat16-two-tiles", 1, 2, 1, 2048, 128, True, 0, "bfloat16",
     2e-2),
])
def test_fused_backward_matches_reference(case, b, h, g, t, d, causal,
                                          window, dtype, tol):
    """dq, dk and dv of the fused backward against ``_attention_ref``
    on K and V repeated to the query heads the long way (dk and dv, at
    K's and V's own head count, against the sum over the group that the
    repeat's transpose takes), each head against its own reference: a
    head's dq accumulator is revisited across grid steps that are not
    adjacent, and zeroed at the head's first step (batch x heads > 1,
    every head's values its own)."""
    import elasticdl_tpu.ops.flash_attention as fa

    q, k, v = (x.astype(dtype) for x in make_qkv(b=b, h=h, t=t, d=d,
                                                 seed=t + d, g=g))
    cot = make_qkv(b=b, h=h, t=t, d=d, seed=7)[0].astype(dtype)

    def loss(op):
        return lambda q, k, v: (op(q, k, v).astype(jnp.float32)
                                * cot.astype(jnp.float32)).sum()

    flash = loss(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=causal, window=window, interpret=True))
    ref = loss(lambda q, k, v: fa._attention_ref(
        q, *_on_repeated(k, v, h), causal, d ** -0.5, window=window))
    suffix = "_w%d" % window if window else ""
    assert _backward_calls(flash, q, k, v) == ["flash_bwd" + suffix,
                                               "flash_fwd" + suffix]
    got = jax.grad(flash, (0, 1, 2))(q, k, v)
    want = jax.grad(ref, (0, 1, 2))(q, k, v)
    for name, a, w in zip("qkv", got, want):
        assert a.shape == w.shape == (b, g if name in "kv" else h, t, d)
        a, w = (np.asarray(x, np.float32).reshape(-1, t, d)
                for x in (a, w))
        for head, (ah, wh) in enumerate(zip(a, w)):
            # a head's error over ITS largest
            assert np.abs(ah - wh).max() <= tol * np.abs(wh).max(), (
                case, name, head)


@pytest.mark.parametrize("t,d,d_rope,itemsize,group,want", [
    (2048, 128, 0, 2, 1, ("fused", "dq_acc_mb=2")),
    (8192, 64, 0, 2, 1, ("fused", "dq_acc_mb=8")),
    (16384, 128, 0, 2, 1, ("fused", "dq_acc_mb=16")),
    (16384, 128, 64, 2, 1, ("fused", "dq_acc_mb=28")),
    (65536, 128, 0, 2, 1, ("fused", "dq_acc_mb=64")),
    (131072, 128, 0, 2, 1, ("pair", "why=dq_acc_mb_128_over_80")),
    (65536, 128, 0, 4, 1, ("pair", "why=dq_acc_mb_96_over_80")),
    # the three grouped cells': 32 on 4 and 28 on 4 at 16,384 x 128, 32
    # on 8 at 8,192 x 64 (a plane's 64 lanes padded to 128)
    (16384, 128, 0, 2, 8, ("fused", "dq_acc_mb=16 dkv_acc_mb=32")),
    (16384, 128, 0, 2, 7, ("fused", "dq_acc_mb=16 dkv_acc_mb=32")),
    (8192, 64, 0, 2, 4, ("fused", "dq_acc_mb=8 dkv_acc_mb=16")),
    # dq alone would fit: the group's planes beside it do not
    (32768, 128, 0, 2, 8,
     ("pair", "why=dq_acc_mb_32_dkv_acc_mb_64_over_80")),
])
def test_the_backward_is_chosen_from_the_shapes_alone(t, d, d_rope,
                                                      itemsize, group, want):
    """Fused wherever a head's float32 dq and its output block's two
    buffers (and, with a group of query heads to a K/V head, that
    head's float32 dk and dv planes and their blocks' buffers) fit the
    VMEM the call may hold; every cell's shape does."""
    import elasticdl_tpu.ops.flash_attention as fa

    assert fa._backward_plan(t, d, d_rope, itemsize, group) == want


@pytest.mark.parametrize("h,g,budget_kb,why", [
    # t=384 at d=64 in float32: a head's dq wants 576 KiB
    (2, 2, 64, "why=dq_acc_mb_1_over_0"),
    # ... which fits 1 MiB, but not with a K/V head's dk and dv planes
    # (1,152 KiB) beside it
    (4, 2, 1024, "why=dq_acc_mb_1_dkv_acc_mb_2_over_1"),
    (7, 1, 1024, "why=dq_acc_mb_1_dkv_acc_mb_2_over_1"),
])
def test_a_dq_too_long_for_vmem_keeps_the_two_passes(monkeypatch, h, g,
                                                     budget_kb, why):
    """Past the budget the backward is the dk-dv pass, the fused body
    without dq's part, and the dq pass, and the gradients are the fused
    call's; ``_backward_plan`` says which accumulators did not fit.
    With a group both passes still read K/V head ``head // group``, and
    the dk-dv pass's per-query-head results are summed outside."""
    import elasticdl_tpu.ops.flash_attention as fa

    q, k, v = make_qkv(b=1, h=h, t=384, d=64, seed=3, g=g)
    loss = lambda q, k, v: (fa.flash_attention(
        q, k, v, window=200, interpret=True) ** 2).sum()
    assert fa._backward_plan(384, 64, 0, 4, h // g)[0] == "fused"
    fused = jax.grad(loss, (0, 1, 2))(q, k, v)
    monkeypatch.setattr(fa, "_DQ_VMEM", budget_kb * 1024)
    assert fa._backward_plan(384, 64, 0, 4, h // g) == ("pair", why)
    assert _backward_calls(loss, q, k, v) == ["flash_dkv_w200",
                                              "flash_dq_w200",
                                              "flash_fwd_w200"]
    pair = jax.grad(loss, (0, 1, 2))(q, k, v)
    for a, b in zip(fused, pair):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_partial_stats_bwd_matches_dense(causal, monkeypatch):
    """The partial custom-vjp's stats-based blockwise backward must give
    the same (acc, l, m) cotangent pullbacks as differentiating the
    dense reference — including the l/m cotangents a ring fold
    produces."""
    import elasticdl_tpu.ops.flash_attention as fa

    called = {}
    orig = fa._partial_stats_bwd

    def spy(*args, **kwargs):
        called["yes"] = True
        return orig(*args, **kwargs)

    monkeypatch.setattr(fa, "_partial_stats_bwd", spy)

    q, k, v = make_qkv(b=1, h=2, t=512, d=64, seed=7)
    scale = q.shape[-1] ** -0.5
    rng = np.random.RandomState(1)
    cot = (
        jnp.asarray(rng.randn(1, 2, 512, 64).astype(np.float32)),
        jnp.asarray(rng.randn(1, 2, 512).astype(np.float32)),
        jnp.asarray(rng.randn(1, 2, 512).astype(np.float32)),
    )

    outs_d, vjp_d = jax.vjp(
        lambda q, k, v: fa._partial_ref(q, k, v, causal, scale, 0),
        q, k, v,
    )
    outs_f, vjp_f = jax.vjp(
        lambda q, k, v: fa.flash_attention_partial(
            q, k, v, causal=causal, interpret=True
        ),
        q, k, v,
    )
    grads_f = vjp_f(cot)
    assert called.get("yes"), "stats-based partial bwd was not invoked"
    for a, b in zip(outs_d, outs_f):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
    for a, b in zip(vjp_d(cot), grads_f):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=2e-3)


def test_transformer_hits_flash_path(monkeypatch):
    """With ELASTICDL_FLASH=interpret the flagship transformer's
    attention goes through the Pallas kernel (VERDICT r1: the kernel was
    an orphan nothing called)."""
    import elasticdl_tpu.ops.flash_attention as fa
    from elasticdl_tpu.models import transformer as tfm

    monkeypatch.setenv("ELASTICDL_FLASH", "interpret")
    called = {}
    orig = fa._flash_forward

    def spy(*args, **kwargs):
        called["yes"] = True
        return orig(*args, **kwargs)

    monkeypatch.setattr(fa, "_flash_forward", spy)
    cfg = tfm.TransformerConfig(
        vocab_size=128, dim=128, num_heads=2, num_layers=2,
        max_seq_len=128, dtype="float32",
    )
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, 128, size=(2, 128)), jnp.int32
    )
    logits = tfm.forward(params, tokens, cfg, mesh=None)
    assert called.get("yes"), "transformer did not reach the flash kernel"
    # and the flash-backed forward matches the jnp-backed forward
    monkeypatch.setenv("ELASTICDL_FLASH", "off")
    logits_ref = tfm.forward(params, tokens, cfg, mesh=None)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(logits_ref),
                               rtol=2e-4, atol=2e-4)


def _closed_form_census(t, tile, causal, window):
    """(steps, full grid, interior, edge, skipped) by counting whole
    diagonals: sub-tile diagonal k (k sub-tiles below the main one) has
    t/128 - k sub-tiles; it is an edge at k = 0 and, under a window of
    w/128 = W whole sub-tiles, at k = W; interior strictly between;
    skipped above the main diagonal and below the window's."""
    n_sub, n_tile, per = t // 128, t // tile, tile // 128
    if not causal:
        return n_tile ** 2, n_tile ** 2, n_sub ** 2, 0, 0
    last = window // 128 if window else n_sub    # last live diagonal
    edge = n_sub + (n_sub - last if window else 0)
    interior = sum(n_sub - k for k in range(1, min(last, n_sub)))
    # a tile dt below the diagonal holds sub-tile diagonals
    # dt*per - (per-1) .. dt*per + (per-1)
    steps = sum(n_tile - dt for dt in range(n_tile)
                if dt * per - (per - 1) <= last)
    return (steps, n_tile ** 2, interior, edge,
            n_sub ** 2 - interior - edge)


@pytest.mark.parametrize("t,causal,window,expected", [
    (2048, True, 0,
     "flash tiles: bh=128 t=2048 d=128 causal window=0 tile=1024 "
     "subtile=128 steps=3/4 sub=120 interior + 16 edge + 120 skipped"),
    (2048, True, 512,
     "flash tiles: bh=128 t=2048 d=128 causal window=512 tile=1024 "
     "subtile=128 steps=3/4 sub=42 interior + 28 edge + 186 skipped"),
    (1024, False, 0,
     "flash tiles: bh=128 t=1024 d=128 full window=0 tile=1024 "
     "subtile=128 steps=1/1 sub=64 interior + 0 edge + 0 skipped"),
])
def test_tile_census_is_the_closed_form(t, causal, window, expected):
    """The census line the worker logs once per compiled shape, against
    a count made a different way (whole diagonals), at the benchmark's
    shape (bf16, d=128: the 1024 tile) and at the 512 tile."""
    import elasticdl_tpu.ops.flash_attention as fa

    tile = fa._major_tile(t, 128 * 2)
    assert fa.tile_census(128, t, 128, tile, causal, window) == expected
    for tile in (512, tile):
        steps, grid, interior, edge, skipped = _closed_form_census(
            t, tile, causal, window)
        line = fa.tile_census(128, t, 128, tile, causal, window)
        assert "tile=%d subtile=128 steps=%d/%d sub=%d interior + %d " \
            "edge + %d skipped" % (tile, steps, grid, interior, edge,
                                   skipped) in line, line
        plan = fa._tile_plan(t, tile, causal, window)
        assert len(plan.q_major) == len(plan.k_major) == steps
        # the kernels' init / finish gates are the ends of each row
        for qi in range(plan.num):
            row = [ki for q_, ki in plan.q_major if q_ == qi]
            assert row == list(range(max(0, qi - plan.dt_max),
                                     min(plan.num - 1,
                                         qi - plan.dt_min) + 1))
        for ki in range(plan.num):
            col = [qi for qi, k_ in plan.k_major if k_ == ki]
            assert col == list(range(max(0, ki + plan.dt_min),
                                     min(plan.num - 1,
                                         ki + plan.dt_max) + 1))


def test_tile_census_is_announced_once_per_shape():
    import elasticdl_tpu.ops.flash_attention as fa

    fa.announce_tiles.cache_clear()
    with mock.patch.object(fa.logger, "info") as info:
        for _ in range(3):
            fa.announce_tiles(4, 1024, 64, 1024, True, 0)
        fa.announce_tiles(4, 1024, 64, 1024, True, 256)
    assert [c.args[0] for c in info.call_args_list] == [
        fa.tile_census(4, 1024, 64, 1024, True, 0) + " kv_heads=4 group=1",
        fa.tile_census(4, 1024, 64, 1024, True, 256)
        + " kv_heads=4 group=1"]


def test_the_line_says_which_backward_the_shape_got(monkeypatch):
    """``kv_heads=<batch x K/V heads> group=<query heads to each>``
    behind the census, then ``backward=fused dq_acc_mb=<n>`` (and
    ``dkv_acc_mb=<n>`` where a group's dk and dv planes are resident)
    or ``backward=pair why=<reason>``: once per compiled shape, from
    the forward that traces it (the interpreter logs nothing)."""
    import elasticdl_tpu.ops.flash_attention as fa

    fa.announce_tiles.cache_clear()
    monkeypatch.setattr(fa.pl, "pallas_call", lambda *a, **kw: (
        lambda *operands: tuple(jnp.zeros(s.shape, s.dtype)
                                for s in kw["out_shape"])))
    x = jnp.zeros((1, 2, 2048, 128), jnp.bfloat16)
    one = x[:, :1]
    with mock.patch.object(fa.logger, "info") as info:
        for _ in range(2):
            fa._flash_forward(x, x, x, True, 1.0, False)
        fa._flash_forward(x, one, one, True, 1.0, False)
        monkeypatch.setattr(fa, "_DQ_VMEM", 2 ** 20)
        fa._flash_forward(x, x, x, True, 1.0, False, window=512)
        fa._flash_forward(x, x, x, True, 1.0, False, normalize=False)
    census = fa.tile_census(2, 2048, 128, 1024, True, 0)
    assert [c.args[0] for c in info.call_args_list] == [
        census + " kv_heads=2 group=1 backward=fused dq_acc_mb=2",
        census + " kv_heads=1 group=2 backward=fused dq_acc_mb=2 "
        "dkv_acc_mb=4",
        fa.tile_census(2, 2048, 128, 1024, True, 512)
        + " kv_heads=2 group=1 backward=pair why=dq_acc_mb_2_over_1",
        census + " kv_heads=2 group=1 backward=scan why=ring_partial"]
