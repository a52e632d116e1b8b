#!/usr/bin/env python3
"""Compile, run and compare every default-path Pallas kernel at the
shapes the two flagship models use at full width.

    python3 chip_check.py                  # on the chip, every case
    python3 chip_check.py resnet_step      # cases whose name holds a word
    python3 chip_check.py --tiny flash/    # CPU, interpret mode

On the chip every kernel is jitted with ``interpret=False`` and compared,
forward and gradient, against its reference (``_attention_ref``,
``_partial_ref``, ``_group_norm_ref``, one matmul per group for the
grouped matmul) evaluated in float32 at highest
matmul precision on the same bf16 values; then the full ResNet-50
(batch 128, bf16) takes two ``CollectiveTrainer`` steps in the default
mode (``ops/mode.py``: the kernel on a TPU), and two more with the
model's GroupNorm swapped for the reference, and the losses must agree.
``--tiny`` is the same code at toy shapes through the Pallas interpreter
(tests/test_chip_bringup.py drives it, so the checker itself is
exercised without chip time).  One JSON line per case, a summary line
last; exit 1 if any case failed.  The process owns the chip for its
whole life: run it alone (``chip_smoke.py``'s ``kernels`` leg does).
"""

import contextlib
import json
import os
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from elasticdl_tpu.ops import flash_attention as fa
from elasticdl_tpu.ops import group_norm as gn
from elasticdl_tpu.ops import grouped_matmul as gm
from elasticdl_tpu.ops import row_moves
from elasticdl_tpu.ops.mode import SWITCH, kernel_mode
from elasticdl_tpu.utils.device import device_report, place_compile_cache

# Errors are taken relative to the reference's largest value, so a
# masking or tiling bug (an O(1) error) cannot hide under them.
# Outputs in bf16 and gradients w.r.t. bf16 operands: bf16 rounds at
# 2^-8, and p and ds are rounded once more before their matmuls
# (measured 1.1e-3..5.1e-3 — my chip run, PR 21).
_BF16_FWD, _BF16_GRAD = 2e-2, 4e-2
# Quantities kernel and reference both accumulate in float32 from the
# same bf16 values (measured 1.1e-7..8.1e-7, same run).  This is the
# tolerance that sees a float32 contraction done in bf16 passes: that
# defect put dscale off by 1e-3 and dbias by 1.7e-2.
_F32 = 1e-4
TOLERANCES = {
    "fwd": _BF16_FWD, "o": _BF16_FWD,
    "dq": _BF16_GRAD, "dk": _BF16_GRAD, "dv": _BF16_GRAD,
    "dx": _BF16_GRAD,
    # latent attention's RoPE parts; the one key's gradient against the
    # sum of the parts the same kernel writes a head (bf16 roundings of
    # float32 partials on both sides)
    "dq_rope": _BF16_GRAD, "dk_rope": _BF16_GRAD,
    "dk_rope_sum": _BF16_FWD,
    # grouped matmul: bf16 results of float32 sums over the same bf16
    # values, so one rounding of the result is all that may differ.
    "dlhs": _BF16_FWD, "drhs": _BF16_FWD,
    "dscale": _F32, "dbias": _F32, "lse": _F32,
    # head_loss: a mean of losses over logits rounded to bf16, and the
    # gradients of two bf16 matmuls fed a bf16 cotangent.
    "loss": _BF16_FWD, "dhead": _BF16_GRAD,
}
# ResNet step.  The head is zero-initialised, so the first loss is
# ln(classes) whatever the kernels compute ...
TOLERANCES["loss0_minus_ln_classes"] = 1e-2
# ... and the second has been through every GroupNorm forward and
# backward and one update: how far the loss moved with the fused
# GroupNorm against the same update with the reference GroupNorm,
# relative to the latter (measured 3.5e-3 on ResNet-50 b128 bf16:
# 6.90775 -> 6.41835 fused, -> 6.42008 reference — my chip run, PR 21;
# 5.7e-4 in --tiny on the CPU).
TOLERANCES["step_vs_reference_gn"] = 2e-2
# ZeRO-1 against the replicated update, 12 steps of a bf16 LM: the same
# arithmetic on the same values.  On one chip (one shard) the losses are
# the same bits (``zero1_steps_differing`` 0); over the four chips of a
# v5e host 11 of 12 differ, by at most 3.1e-5 (my chip run, PR 28), and
# on 4 virtual CPU devices 9 of 12, 4.3e-5 (--tiny): a last-ulp
# difference in a float32 parameter now and then flips a bf16 rounding
# downstream (tests/test_zero1.py::LAST_ULPS).  A wrong slice or a
# missing sum moves the loss by far more than this.
TOLERANCES["zero1_rel_diff"] = 1e-3
# remat=true with the names the room holds kept against nothing kept:
# the kept values are the ones the second forward would have produced,
# by the same kernels on the same values.  What differs is where XLA
# rounds to bf16 inside the fusions it forms around them: in --tiny on
# the CPU every gradient leaf moves by ~1.3e-2 of its norm and the loss
# by 2e-5, while the float32 stacks of tests/test_remat_keep.py agree
# to 1e-7.  ``keep_grad`` is the worst leaf's max error over its max,
# ``keep_grad_l2`` the whole tree's difference over its norm.
TOLERANCES["keep_loss"] = 1e-4
TOLERANCES["keep_grad"] = TOLERANCES["keep_grad_l2"] = _BF16_GRAD
# short_conv: bf16 results of float32 arithmetic on the same bf16
# values (one rounding of the result); the taps' gradient is a float32
# sum over every row of products of values rounded to bf16 nowhere.
TOLERANCES["dbcu"] = _BF16_FWD
TOLERANCES["dw"] = _F32
# A stack whose layers differ, holding a share of its experts: bf16
# through the kernels against (a) the same program in bf16 through the
# jnp references (``stack_grad_l2``, the whole tree's difference over
# its norm: 0.9e-2 in --tiny on the CPU, 4.2e-2 on the chip at the
# published widths, where the two paths' bf16 roundings send a token in
# fifty to other experts; a reference path that left the rows of absent
# experts to the backend read 1.0 there: my chip runs, PR 31; the gated,
# output-normed ``wwaww`` stack 3.7e-2, and 2.2e-4 in its loss: my chip
# run, PR 40) and
# (b) the loss of the same weights in float32 through the references
# (``stack_loss``, 7e-4 in --tiny).  Gradients are not held to the
# float32 path: bf16's roundings through five layers, and a token's
# experts flipping on them, put the bf16 reference path itself 0.10-0.13
# of the tree's norm from it in --tiny, and three gradient trees of the
# published widths do not fit a chip.  In float32, kernels and
# references agree to 2e-6 (tests/test_mixed_stack.py).
# The row kernel (ops/row_moves.py) against its jnp reference on the
# same values: a gather moves bits (0 exactly); a float32 sum of at most
# K products differs by its order alone; a bf16 result of a float32
# product by one rounding.
TOLERANCES["rows_gather"] = 0.0
TOLERANCES["rows_sum"] = TOLERANCES["rows_sum_back"] = _F32
TOLERANCES["rows_dots"] = _F32
TOLERANCES["rows_gather_back"] = _BF16_FWD
# The embedding table's gradient by the kernel (ops/embed_rows.py)
# against one float32 scatter-add of the same bf16 rows: float32 sums
# of the same values, whose order alone differs.
TOLERANCES["embed_grad"] = _F32
# A sublayer's maps by the kernels (ops/hyper_mix.hyper_maps) against
# ``maps_of`` differentiated by JAX: float32 both, the rounds' sums in
# another order and a reciprocal and a product for a quotient.
for _name in ("hyper_maps", "hyper_maps_err", "hyper_maps_dz",
              "hyper_maps_dbias"):
    TOLERANCES[_name] = 1e-5
TOLERANCES["stack_loss"] = 2e-3
TOLERANCES["stack_grad_l2"] = 2 * _BF16_GRAD


def _rel_err(got, want):
    """max|got - want| / max|want|, reduced on the device (the full-size
    operands are hundreds of MB; only the scalar crosses to the host)."""
    if got.shape != want.shape:
        raise AssertionError("shape %s != %s" % (got.shape, want.shape))
    got = got.astype(jnp.float32)
    want = want.astype(jnp.float32)
    if not bool(jnp.isfinite(got).all()):
        raise AssertionError("non-finite values in kernel output")
    return float(
        jnp.abs(got - want).max() / jnp.maximum(jnp.abs(want).max(), 1e-6)
    )


def _f32(*arrays):
    return tuple(a.astype(jnp.float32) for a in arrays)


def _qkv(b, h, t, d, seed, kv_heads=None):
    """q, k, v and a cotangent; k and v at ``kv_heads`` (None: ``h``)."""
    rng = np.random.RandomState(seed)
    g = kv_heads or h
    return tuple(
        jnp.asarray(rng.randn(b, heads, t, d), jnp.bfloat16)
        for heads in (h, g, g, h)
    )


def _kernel_vs_corner(kernel, ref, loss, operands, ref_slice):
    """Kernel at the full shape, reference on a [batch, head] corner of
    the same values (attention is independent per head, and the dense
    [T, T] reference at B8·H16·T2048 would not fit beside the kernel's
    operands).  Returns (kernel out, reference out, gradient errors)."""
    sb, sh = ref_slice
    out = jax.jit(kernel)(*operands[:3])
    grads = jax.jit(jax.grad(loss(kernel), argnums=(0, 1, 2)))(*operands)
    # K and V at their own head count: the corner's query heads are
    # whole groups, and its K/V heads the ones they read
    group = operands[0].shape[1] // operands[1].shape[1]
    heads = lambda a: sh if a.shape[1] == operands[0].shape[1] \
        else sh // group
    corner = tuple(a[:sb, :heads(a)] for a in _f32(*operands))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref)(*corner[:3])
        want_grads = jax.jit(
            jax.grad(loss(ref), argnums=(0, 1, 2)))(*corner)
    errs = {
        name: _rel_err(g[:sb, :heads(g)], wg)
        for name, g, wg in zip(("dq", "dk", "dv"), grads, want_grads)
    }
    return out, want, errs


def check_flash(b, h, t, d, window, interpret, ref_slice=(1, 2),
                kv_heads=None):
    """``kv_heads`` < ``h``: K and V at their own head count, read by
    the kernels as ``head // group`` and repeated by the reference;
    ``ref_slice`` then names whole groups of query heads."""
    scale = d ** -0.5
    sb, sh = ref_slice

    def loss(fn):
        return lambda q, k, v, w: (
            fn(q, k, v).astype(jnp.float32) * w.astype(jnp.float32)
        ).sum()

    out, want, errs = _kernel_vs_corner(
        lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, scale=scale, interpret=interpret,
            window=window),
        lambda q, k, v: fa._attention_ref(q, k, v, True, scale,
                                          window=window),
        loss, _qkv(b, h, t, d, seed=t + d + window, kv_heads=kv_heads),
        ref_slice)
    return {"fwd": _rel_err(out[:sb, :sh], want), **errs}


def check_latent(b, h, t, interpret, widths=(128, 64, 128), ref_slice=2):
    """``latent_attention`` (scores over D_nope + D_rope, values of
    D_v, ONE RoPE key a sequence) at the full shape against the jnp
    math, a head's key put together the long way, on the first
    ``ref_slice`` heads of the same values; the RoPE key's gradient is
    the sum over every head, so it is held to the sum of the float32
    parts the backward kernel writes a head (``_pallas_bwd``'s last
    result), and the first heads' parts to the reference's with the key
    spread to the heads.  Where there are
    more chips than one and ``b`` is a multiple of them, the kernel
    runs a sequence a chip, its operands sharded over the chips under
    the trainer's ``batch_axis`` (``ops/batch_shard.per_batch_shard``)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from elasticdl_tpu.ops.batch_shard import batch_axis, per_batch_shard

    dn, dr, dv = widths
    rng = np.random.RandomState(t + dn + dr)
    draw = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.bfloat16)
    args = (draw(b, h, t, dn), draw(b, h, t, dr), draw(b, h, t, dn),
            draw(b, t, dr), draw(b, h, t, dv))
    w = draw(b, h, t, dv)
    scale = (dn + dr) ** -0.5
    every = tuple(range(5))

    def loss(fn):
        return lambda *a: (fn(*a[:5]).astype(jnp.float32)
                           * a[5].astype(jnp.float32)).sum()

    chips = jax.device_count()
    mesh = Mesh(np.array(jax.devices()), ("data",)) if (
        chips > 1 and b % chips == 0) else None

    def kernel(*a):
        with batch_axis(mesh, "data"):
            return fa.latent_attention(*a, interpret=interpret)

    def head_parts(q_nope, q_rope, k_nope, k_rope, v, g):
        def one(q_nope, q_rope, k_nope, k_rope, v, g):
            static = (True, scale, bool(interpret))
            _, res = fa._latent_fwd(q_nope, q_rope, k_nope, k_rope, v,
                                    *static)
            return fa._pallas_bwd(q_nope, k_nope, v, *res[5:], g, *static,
                                  rope=(q_rope, k_rope))[4]

        with batch_axis(mesh, "data"):
            return per_batch_shard(
                one, (q_nope, q_rope, k_nope, k_rope, v, g))

    def jit(fn):     # a sequence a chip where a mesh is
        return jax.jit(fn) if mesh is None else jax.jit(
            fn, in_shardings=NamedSharding(mesh, P("data")))

    out = jit(kernel)(*args)
    grads = jit(jax.grad(loss(kernel), every))(*args, w)
    parts = jit(head_parts)(*args, w)
    sh = ref_slice
    corner = tuple(a if a.ndim == 3 else a[:, :sh] for a in _f32(*args, w))
    corner = corner[:3] + (jnp.broadcast_to(
        corner[3][:, None], corner[1].shape),) + corner[4:]
    # the key spread to the corner's heads, so that the reference's
    # gradient of it is a head's own part
    ref = lambda q_nope, q_rope, k_nope, k_spread, v: fa._attention_ref(
        jnp.concatenate([q_nope, q_rope], axis=-1),
        jnp.concatenate([k_nope, k_spread], axis=-1), v, True, scale)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref)(*corner[:5])
        want_grads = jax.jit(jax.grad(loss(ref), every))(*corner)
    errs = {"fwd": _rel_err(out[:, :sh], want)}
    for name, g, wg in zip(("dq", "dq_rope", "dk", "dk_rope", "dv"),
                           grads, want_grads):
        if name == "dk_rope":     # a head's part, then the heads' sum
            errs["dk_rope"] = _rel_err(parts[:, :sh], wg)
            errs["dk_rope_sum"] = _rel_err(g, parts.sum(axis=1))
        else:
            errs[name] = _rel_err(g[:, :sh], wg)
    return errs


def check_grouped_matmul(rows, k, n, groups, skew, interpret):
    """``grouped_matmul`` forward and both gradients against one plain
    matmul per group on the same bf16 values, float32 at the highest
    precision.  ``skew``: ``zipf`` (shares ~ rank ** -1.1, no group a
    tile multiple) or ``empty`` (every other group empty, one holding
    half the rows)."""
    rng = np.random.RandomState(rows + k + groups)
    if skew == "zipf":
        share = np.arange(1, groups + 1, dtype=np.float64) ** -1.1
    else:
        share = np.where(np.arange(groups) % 2, 1.0, 0.0)
        share[1] = share.sum()
    sizes = np.floor(rng.permutation(share / share.sum()) * rows).astype(
        np.int32)
    sizes[np.argmax(sizes)] += rows - sizes.sum()
    lhs, cot = (jnp.asarray(rng.randn(rows, w), jnp.bfloat16)
                for w in (k, n))
    rhs = jnp.asarray(rng.randn(groups, k, n) * k ** -0.5, jnp.bfloat16)
    starts = np.concatenate([[0], np.cumsum(sizes)])

    def kernel(lhs, rhs):
        return gm.grouped_matmul(lhs, rhs, jnp.asarray(sizes),
                                 interpret=interpret)

    def reference(lhs, rhs):
        return jnp.concatenate([
            lhs[starts[g]:starts[g + 1]] @ rhs[g] for g in range(groups)])

    loss = lambda fn: lambda lhs, rhs, cot: (
        fn(lhs, rhs).astype(jnp.float32) * cot.astype(jnp.float32)).sum()
    out = jax.jit(kernel)(lhs, rhs)
    grads = jax.jit(jax.grad(loss(kernel), argnums=(0, 1)))(lhs, rhs, cot)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(reference)(*_f32(lhs, rhs))
        want_grads = jax.jit(jax.grad(loss(reference), argnums=(0, 1)))(
            *_f32(lhs, rhs, cot))
    return {"fwd": _rel_err(out, want),
            "dlhs": _rel_err(grads[0], want_grads[0]),
            "drhs": _rel_err(grads[1], want_grads[1])}


def check_row_moves(n, w, k, bound, live, interpret):
    """A share's four row moves by the row kernel against
    ``row_sum_ref`` on the same values, at a cell's shapes: ``bound``
    rows of which the first ``live`` are some token's (each of n tokens
    has k choices); the rows of every source that no index names are
    NaN, and must reach no result."""
    rng = np.random.default_rng(n + w + k)
    claims = np.full(bound, n * k, np.int64)
    claims[:live] = rng.permutation(n * k)[:live]
    pos = np.full(n * k, -1, np.int64)
    pos[claims[:live]] = np.arange(live)
    named = jnp.asarray(np.arange(bound) < live)[:, None]
    tok = jnp.asarray(np.where(np.arange(bound) < live, claims // k, n),
                      jnp.int32)[:, None]
    pos = jnp.asarray(pos.reshape(n, k), jnp.int32)
    normal = lambda rows, dtype: jnp.asarray(
        rng.standard_normal((rows, w), np.float32), dtype)
    taken = jnp.zeros((n,), bool).at[tok[:live, 0]].set(True)[:, None]
    x = jnp.where(taken, normal(n, jnp.bfloat16), jnp.nan)
    g = jnp.where(taken, normal(n, jnp.float32), jnp.nan)
    y = jnp.where(named, normal(bound, jnp.bfloat16), jnp.nan)
    gates = jnp.asarray(rng.random((n, k), np.float32))
    scale = jnp.asarray(rng.random((bound, 1), np.float32))
    count = jnp.int32(live)
    errs = {}
    for name, (src, idx, weight, other, rows, dtype) in {
            "rows_gather": (x, tok, None, None, None, None),
            "rows_sum": (y, pos, gates, None, count, jnp.float32),
            "rows_gather_back": (g, tok, scale, y, None, jnp.bfloat16),
            "rows_sum_back": (y, pos, None, None, count, None)}.items():
        got = jax.jit(lambda *a, rows=rows, dtype=dtype: row_moves.row_sum(
            *a, live=rows, out_dtype=dtype, interpret=interpret))(
                src, idx, weight, other)
        want = jax.jit(lambda *a, dtype=dtype: row_moves.row_sum_ref(
            *a, out_dtype=dtype))(src, idx, weight, other)
        errs[name] = _rel_err(got[0], want[0])
        if other is not None:
            errs["rows_dots"] = _rel_err(got[1], want[1])
    return errs


def check_embed_rows(n, vocab, dim, interpret):
    """The embedding table's gradient by the kernel against
    ``rows_added_ref``: ``n`` Zipf ids as the benchmark draws them (one
    id a seventh of them) and their bfloat16 rows."""
    from elasticdl_tpu.ops import embed_rows
    from tools.head_loss_on_chip import draw_ids

    tokens = jnp.asarray(draw_ids("zipf", vocab, n).reshape(1, n), jnp.int32)
    g = jnp.asarray(np.random.default_rng(n + vocab + dim).standard_normal(
        (1, n, dim), np.float32), jnp.bfloat16)
    got = jax.jit(lambda t, g: embed_rows.rows_added(
        t, g, vocab, interpret=interpret))(tokens, g)
    want = jax.jit(lambda t, g: embed_rows.rows_added_ref(
        t, g, vocab))(tokens, g)
    return {"embed_grad": _rel_err(got, want)}


def check_hyper_maps(b, t, n, iters, interpret):
    """``hyper_maps`` (value, the error it states, both gradients)
    against ``maps_of``: logits of a few units, some beyond the clamps,
    a cotangent on every column."""
    from elasticdl_tpu.ops import hyper_mix

    rng = np.random.default_rng(b * t + iters)
    width = hyper_mix.columns(n)
    z = np.zeros((b, t, hyper_mix.LANES), np.float32)
    z[..., :width] = 3.0 * rng.standard_normal((b, t, width))
    z[0, :8, 2 * n:width] = 40.0 * np.sign(z[0, :8, 2 * n:width])
    bias = jnp.asarray(0.5 * rng.standard_normal(width), jnp.float32)
    weigh = jnp.asarray(rng.standard_normal(z.shape), jnp.float32)

    def both(maps):
        def loss(z, bias):
            out, err = maps(z, bias)
            return (out * weigh).sum(), (out, err)

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                          has_aux=True))

    z = jnp.asarray(z)
    (_, (got, got_err)), got_grads = both(
        lambda z, bias: hyper_mix.hyper_maps(
            z, bias, n, iters, 1e-6, interpret=interpret))(z, bias)
    (_, (want, want_err)), want_grads = both(
        lambda z, bias: hyper_mix.maps_of(z, bias, n, iters, 1e-6))(z, bias)
    return {"hyper_maps": _rel_err(got, want),
            "hyper_maps_err": abs(float(got_err) - float(want_err)),
            "hyper_maps_dz": _rel_err(got_grads[0], want_grads[0]),
            "hyper_maps_dbias": _rel_err(got_grads[1], want_grads[1])}


def check_short_conv(b, t, e, taps, interpret):
    """``short_conv`` forward and both gradients against the plain
    ``short_conv_ref`` in float32 on the same bf16 values; b > 1 and t a
    multiple of the row tile, so sequence starts and tile edges are both
    inside.  Compiled, also the host's clock on forward + backward of
    the kernels and of XLA's fusion of the plain path (stderr)."""
    from elasticdl_tpu.ops import short_conv as sc

    rng = np.random.RandomState(b + t + e)
    bcu = jnp.asarray(rng.randn(b, t, 3 * e), jnp.bfloat16)
    cot = jnp.asarray(rng.randn(b, t, e), jnp.bfloat16)
    w = jnp.asarray(rng.randn(e, taps) * taps ** -0.5, jnp.float32)
    kernel = lambda bcu, w: sc.short_conv(bcu, w, interpret=interpret)
    loss = lambda fn: lambda bcu, w, cot: (
        fn(bcu, w).astype(jnp.float32) * cot.astype(jnp.float32)).sum()
    both = lambda fn: jax.jit(lambda bcu, w, cot: (
        fn(bcu, w), jax.grad(loss(fn), argnums=(0, 1))(bcu, w, cot)))
    out, grads = both(kernel)(bcu, w, cot)
    want, want_grads = both(sc.short_conv_ref)(*_f32(bcu), w, *_f32(cot))
    if not interpret:
        for name, fn, args in (("kernel", both(kernel), (bcu, w, cot)),
                               ("plain", both(sc.short_conv_ref),
                                (bcu, w, cot))):
            jax.block_until_ready(fn(*args))
            t0 = time.perf_counter()
            for _ in range(20):
                last = fn(*args)
            jax.block_until_ready(last)
            print(json.dumps({
                "short_conv_fwd_bwd_ms": name, "shape": [b, t, e],
                "ms": 1e3 * (time.perf_counter() - t0) / 20}),
                file=sys.stderr, flush=True)
    return {"fwd": _rel_err(out, want),
            "dbcu": _rel_err(grads[0], want_grads[0]),
            "dw": _rel_err(grads[1], want_grads[1])}


# The stacks ``check_mixed_stack`` runs: (--tiny sizes, the cell's
# widths, what both share).
MIXED_STACKS = {
    # benchmark/configs/lfm2-24b-a2b.json at a sequence of 2,048: the
    # attention reference holds all [32, T, T] float32 scores at once
    "caccc": (
        dict(vocab_size=256, dim=128, num_heads=4, num_kv_heads=2,
             seq_len=128, dense_ffn_dim=192, ffn_dim=128, moe_experts=8,
             moe_top_k=2, moe_experts_held=2),
        dict(vocab_size=8192, dim=2048, num_heads=32, num_kv_heads=8,
             seq_len=2048, dense_ffn_dim=11776, ffn_dim=1536,
             moe_experts=64, moe_top_k=4, moe_experts_held=8),
        dict(num_layers=5, layer_pattern="caccc", dense_layers=1,
             moe_router="sigmoid_bias", moe_aux_weight=0, qk_norm="head",
             rope_theta=1e6, norm_eps=1e-5)),
    # benchmark/configs/smallthinker-21b-a3b.json at a sequence of 2,048
    # and an eighth of the slice of the vocabulary, the window cut from
    # 4,096 to 512 so that it bites inside what the reference can hold
    # (the band at 4,096 in 16,384 is the cell's own comparison)
    "awww": (
        dict(vocab_size=256, dim=128, num_heads=4, num_kv_heads=2,
             head_dim=64, seq_len=256, window=128, ffn_dim=128,
             moe_experts=8, moe_top_k=2, moe_experts_held=2),
        dict(vocab_size=4748, dim=2560, num_heads=28, num_kv_heads=4,
             head_dim=128, seq_len=2048, window=512, ffn_dim=768,
             moe_experts=64, moe_top_k=6, moe_experts_held=16),
        dict(num_layers=4, layer_pattern="awww", rope_kinds="w",
             rope_theta=1.5e6, ffn_activation="relu",
             moe_route_before_op=True, moe_aux_weight=0,
             tied_embeddings=False, embed_scale=1.0)),
    # benchmark/configs/trinity-mini.json at a sequence of 2,048 and an
    # eighth of the slice of the vocabulary, the window cut from 2,048
    # to 512 so that it bites inside what the reference can hold.  In
    # --tiny every token takes all 8 experts: with a choice of 2, 256
    # tokens through five layers of attention kernels are few enough
    # that the two paths' bf16 roundings flip choices worth 8-13% of the
    # tree's norm (2.3e-2 without a choice; on the chip, 8 of 128 chosen
    # at the published widths, 3.7e-2: my chip run, PR 40)
    "wwaww": (
        dict(vocab_size=256, dim=128, num_heads=4, num_kv_heads=2,
             head_dim=64, seq_len=256, window=128, dense_ffn_dim=192,
             ffn_dim=128, moe_experts=8, moe_top_k=8, moe_experts_held=2,
             embed_multiplier=128 ** 0.5),
        dict(vocab_size=3128, dim=2048, num_heads=32, num_kv_heads=4,
             head_dim=128, seq_len=2048, window=512, dense_ffn_dim=6144,
             ffn_dim=1024, moe_experts=128, moe_top_k=8,
             moe_experts_held=16, embed_multiplier=2048 ** 0.5),
        dict(num_layers=5, layer_pattern="wwaww", rope_kinds="w",
             rope_theta=10000, qk_norm="head", attn_gate=True,
             post_norms=True, dense_layers=1, moe_shared_experts=1,
             moe_router="sigmoid_bias", moe_norm_topk=True,
             moe_route_scale=2.826, moe_aux_weight=0, norm_eps=1e-5,
             tied_embeddings=False)),
    # benchmark/configs/olmo-hybrid-7b.json at a sequence of 2,048 (32
    # chunks of the scan) and an eighth of the slice of the vocabulary:
    # three gated-delta layers and a full NoPE layer, 15 of 30 heads
    # held, a dense SwiGLU, norms on the sublayers' outputs alone
    "ddda": (
        # --tiny runs one layer of each kind: the two bf16 paths'
        # roundings grow ~3.5 times through every delta layer's backward
        # at these widths (1.8e-2 through ``da``, 5.8e-2 ``dda``, 0.24
        # ``ddda``, and the twin's own path reads 0.10-0.16 from float32
        # there), while on the same operands the kernels' output and five
        # gradients stand 3-6e-3 from the float32 recurrence
        dict(vocab_size=256, dim=128, num_heads=2, num_kv_heads=2,
             head_dim=64, seq_len=256, ffn_dim=192, delta_key_dim=32,
             delta_value_dim=64, num_layers=2, layer_pattern="da"),
        dict(vocab_size=1568, dim=3840, num_heads=15, num_kv_heads=15,
             head_dim=128, seq_len=2048, ffn_dim=11008, delta_key_dim=96,
             delta_value_dim=192),
        dict(num_layers=4, layer_pattern="ddda", rope_kinds="w",
             qk_norm=True, post_norms=True, pre_norms=False,
             delta_neg_eigval=True, conv_kernel=4, head_shares=2,
             tied_embeddings=False, embed_scale=1.0)),
    # benchmark/configs/solar-open2-250b.json at a sequence of 2,048 and
    # an eighth of the slice of the vocabulary: a gated NoPE layer at 8
    # query heads on 1 K/V head then three Kimi Delta Attention layers
    # (a decay a channel, the low-rank pairs) at 8 of 64 heads, every
    # FFN 8 of 320 experts behind a sigmoid router beside a shared one
    "addd": (
        # --tiny runs one layer of each kind, as ``ddda`` does and for
        # its reason, every token taking all 8 experts, as ``wwaww``
        dict(vocab_size=256, dim=128, num_heads=2, num_kv_heads=1,
             head_dim=64, seq_len=256, ffn_dim=128, delta_key_dim=32,
             delta_value_dim=32, delta_rank=16, moe_experts=8, moe_top_k=8,
             moe_experts_held=2, num_layers=2, layer_pattern="ad"),
        dict(vocab_size=3072, dim=4096, num_heads=8, num_kv_heads=1,
             head_dim=128, seq_len=2048, ffn_dim=1280, delta_key_dim=128,
             delta_value_dim=128, delta_rank=128, moe_experts=320,
             moe_top_k=8, moe_experts_held=8),
        dict(num_layers=4, layer_pattern="addd", rope_kinds="w",
             attn_gate=True, delta_kind="kda", delta_neg_eigval=True,
             conv_kernel=4, head_shares=8, moe_shared_experts=1,
             moe_router="sigmoid_bias", moe_norm_topk=True,
             moe_aux_weight=0, norm_eps=1e-5, tied_embeddings=False,
             embed_scale=1.0)),
}


def check_mixed_stack(tiny, spill=False, stack="caccc"):
    """One loss-and-gradients evaluation of a stack whose layers differ
    (``MIXED_STACKS``: a leading short-convolution layer with a dense
    MLP, then attention and short convolutions with a share of the
    experts behind a sigmoid router, the ``lfm2-24b-a2b`` cell's model;
    or full attention without positional encoding then windowed
    attention with RoPE, heads wider than the hidden size divides into,
    the router read before attention, ReGLU over a share of the
    experts, the ``smallthinker-21b-a3b`` cell's; or the gated,
    output-normed block over windowed-RoPE and full-NoPE layers at 32
    query heads on 4, a per-head QK norm, a muP-scaled embedding and a
    shared expert beside the share, the ``trinity-mini`` cell's; or
    three gated-delta layers to one full NoPE layer over a chip's share
    of the heads, no experts, the ``olmo-hybrid-7b`` cell's; or a gated
    NoPE layer then three Kimi Delta Attention layers, a decay a
    channel, over a chip's share of the heads AND of the experts, the
    ``solar-open2-250b`` cell's; each at one short sequence), bf16
    through the kernels, against the same weights in float32 through
    the references at the highest matmul precision (the loss), and the
    same bf16 program through the references (the gradients).
    ``spill``: ``expert_bias`` raised on the held experts, so every row
    of every dispatch is theirs, four times ``moe_dispatch.row_bound``:
    each layer runs all four of its blocks."""
    from elasticdl_tpu.models import transformer as tfm
    from elasticdl_tpu.ops.mode import kernels_off

    small, full, shared = MIXED_STACKS[stack]
    sizes = small if tiny else full
    common = dict(shared, remat=True, **sizes)
    spec = tfm.model_spec(**common)
    exact = tfm.model_spec(dtype="float32", **common)
    params = jax.jit(spec.init_fn)(jax.random.PRNGKey(5))
    # logits that matter: an untied head's by its own weights, a tied
    # one's through its embedding
    if "lm_head" in params:
        params["lm_head"] = params["lm_head"] * 5.0
    else:
        params["embed"] = params["embed"] * 25.0
    if spill:
        held = sizes["moe_experts_held"]
        params = jax.tree_util.tree_map_with_path(
            lambda path, a: (a.at[..., :held].add(5.0) if getattr(
                path[-1], "key", None) == "expert_bias" else a), params)
    tokens = jnp.asarray(np.random.RandomState(7).randint(
        0, sizes["vocab_size"], size=(1, sizes["seq_len"])), jnp.int32)
    if spec.step_stats_fn is not None:      # a stack with experts
        stats = spec.step_stats_fn(jax.jit(
            lambda p: spec.apply_fn(p, tokens, True))(params))
        if bool((stats["moe_spilled"] > 0).all()) != spill:
            raise AssertionError("blocks run a layer: %s" % (
                stats["moe_moved"],))

    def evaluate(spec, grads=True):
        loss = lambda p: spec.loss_fn(
            spec.apply_fn(p, tokens, True), tokens).mean()
        return jax.jit(jax.value_and_grad(loss) if grads else loss)(params)

    leaves = jax.tree_util.tree_leaves
    norm = lambda trees: float(jnp.sqrt(sum(
        jnp.sum(jnp.square(t.astype(jnp.float32))) for t in trees)))
    apart = lambda got, want: norm([g - b for g, b in zip(
        leaves(got), leaves(want))]) / norm(leaves(want))
    loss, grads = evaluate(spec)
    if not all(bool(jnp.isfinite(g).all()) for g in leaves(grads)):
        raise AssertionError("non-finite gradients")
    with kernels_off():
        _, plain_grads = evaluate(spec)
    errs = {"stack_grad_l2": apart(grads, plain_grads)}
    del grads, plain_grads
    with kernels_off(), jax.default_matmul_precision("highest"):
        want = evaluate(exact, grads=False)
    errs["stack_loss"] = abs(float(loss) - float(want)) / abs(float(want))
    return errs


def check_head_loss(b, t, dim, vocab, tied):
    """``head_loss`` (bf16 operands, bf16 logits) and its gradients
    against ``next_token_loss`` of float32 logits at the highest matmul
    precision on the same values; the last example weighs zero, as a
    padded record does."""
    from elasticdl_tpu.models.transformer import next_token_loss
    from elasticdl_tpu.ops.head_loss import head_loss

    rng = np.random.RandomState(t + vocab)
    x = jnp.asarray(rng.randn(b, t, dim), jnp.bfloat16)
    head = jnp.asarray(rng.randn(*((vocab, dim) if tied else (dim, vocab)))
                       * dim ** -0.5, jnp.bfloat16)
    tokens = jnp.asarray(rng.randint(0, vocab, (b, t)), jnp.int32)
    weights = jnp.asarray([1.0] * (b - 1) + [0.0], jnp.float32)

    def reference(x, head, tokens, tied):
        return next_token_loss(x @ (head.T if tied else head), tokens)

    def both(fn):
        def f(x, head):
            per_example = fn(x, head, tokens, tied)
            return (per_example * weights).sum() / weights.sum(), per_example
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1), has_aux=True))

    (_, loss), (dx, dhead) = both(head_loss)(x, head)
    with jax.default_matmul_precision("highest"):
        (_, want), (want_dx, want_dhead) = both(reference)(*_f32(x, head))
    return {"loss": _rel_err(loss, want), "dx": _rel_err(dx, want_dx),
            "dhead": _rel_err(dhead, want_dhead)}


def check_flash_partial(b, h, t, d, causal, interpret, ref_slice=(1, 2)):
    """``flash_attention_partial`` the way ring attention calls it: the
    diagonal block causal, lower blocks non-causal, (acc, l, m) out and
    cotangents on all three coming back."""
    scale = d ** -0.5
    sb, sh = ref_slice

    def loss(fn):
        def f(q, k, v, w):
            acc, l, m = fn(q, k, v)
            # the ring's fold: every output reaches the loss
            o = acc / jnp.maximum(l, 1e-30)[..., None]
            return (o * w.astype(jnp.float32)).sum() + (
                jnp.log(jnp.maximum(l, 1e-30)) + m).mean()
        return f

    (acc, l, m), (want_acc, want_l, want_m), errs = _kernel_vs_corner(
        lambda q, k, v: fa.flash_attention_partial(
            q, k, v, causal=causal, scale=scale, interpret=interpret),
        lambda q, k, v: fa._partial_ref(q, k, v, causal, scale, 0),
        loss, _qkv(b, h, t, d, seed=7 * t + d + causal), ref_slice)
    # acc and l carry exp(s - m): compare the normalized output and the
    # log-sum-exp, which do not depend on which row max was subtracted.
    norm = lambda a, l: a / jnp.maximum(l, 1e-30)[..., None]
    return {
        "o": _rel_err(norm(acc, l)[:sb, :sh], norm(want_acc, want_l)),
        "lse": _rel_err((jnp.log(l) + m)[:sb, :sh],
                        jnp.log(want_l) + want_m),
        **errs,
    }


def check_group_norm(batch, hw, channels, groups, relu, interpret,
                     ref_batch=4):
    rng = np.random.RandomState(hw + channels)
    x = jnp.asarray(rng.randn(batch, hw, channels) * 2.0 + 0.5,
                    jnp.bfloat16)
    dy = jnp.asarray(rng.randn(batch, hw, channels), jnp.bfloat16)
    scale = jnp.asarray(1.0 + 0.1 * rng.randn(channels), jnp.float32)
    bias = jnp.asarray(0.1 * rng.randn(channels), jnp.float32)
    eps = 1e-6

    def loss(fn):
        return lambda x, s, b, dy: (
            fn(x, s, b).astype(jnp.float32) * dy.astype(jnp.float32)
        ).sum()

    kernel = lambda x, s, b: gn._fused(x, s, b, groups, eps, relu,
                                       interpret)
    ref = lambda x, s, b: gn._group_norm_ref(x, s, b, groups, eps, relu)
    out = jax.jit(kernel)(x, scale, bias)
    dx, dscale, dbias = jax.jit(
        jax.grad(loss(kernel), argnums=(0, 1, 2)))(x, scale, bias, dy)
    # dscale/dbias sum over the batch, so their reference needs the whole
    # batch; in f32 that is 4 bytes x B·HW·C, which fits (<= 0.8 GB).
    xf, dyf = _f32(x, dy)
    want = jax.jit(ref)(xf[:ref_batch], scale, bias)
    want_dx, want_dscale, want_dbias = jax.jit(
        jax.grad(loss(ref), argnums=(0, 1, 2)))(xf, scale, bias, dyf)
    if relu:
        # An element whose pre-activation rounds to the other side of
        # zero flips its ReLU mask, and kernel and reference associate
        # the affine differently: of 1e8 elements a handful sit within
        # rounding of zero.  Each flip is a legitimate O(1) difference in
        # dx at that element, so dx is compared outside that band.
        pre = jax.jit(lambda x, s, b: gn._group_norm_ref(
            x, s, b, groups, eps, False))(xf, scale, bias)
        settled = jnp.abs(pre) > 1e-4
        dx = jnp.where(settled, dx, 0)
        want_dx = jnp.where(settled, want_dx, 0)
    return {
        "fwd": _rel_err(out[:ref_batch], want),
        "dx": _rel_err(dx, want_dx),
        "dscale": _rel_err(dscale, want_dscale),
        "dbias": _rel_err(dbias, want_dbias),
    }


@contextlib.contextmanager
def _model_group_norm(fn):
    """Swap the GroupNorm the ResNet model calls for ``fn``."""
    from elasticdl_tpu.models import resnet

    real = resnet.fused_group_norm
    resnet.fused_group_norm = fn
    try:
        yield
    finally:
        resnet.fused_group_norm = real


def _reference_group_norm(x, scale, bias, num_groups, eps=1e-6,
                          relu=False):
    return gn._group_norm_ref(x, scale, bias, num_groups, eps, relu)


def resnet_group_norm_shapes(variant, image_size, batch):
    """Every distinct (HW, C, groups, relu) the model hands to
    ``fused_group_norm``, recorded from an abstract trace of the model
    itself rather than listed by hand."""
    from elasticdl_tpu.models import resnet

    seen = []

    def record(x, scale, bias, num_groups, eps=1e-6, relu=False):
        key = (int(np.prod(x.shape[1:-1])), x.shape[-1], num_groups, relu)
        if key not in seen:
            seen.append(key)
        return _reference_group_norm(x, scale, bias, num_groups, eps, relu)

    spec = resnet.model_spec(variant=variant, image_size=image_size)
    with _model_group_norm(record):
        params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
        jax.eval_shape(
            lambda p, x: spec.apply_fn(p, x, True), params,
            jax.ShapeDtypeStruct(
                (batch, image_size, image_size, 3), jnp.bfloat16),
        )
    return seen


def _resnet_losses(variant, image_size, batch, num_classes):
    """Two real trainer steps from the seeded init on seeded data."""
    from elasticdl_tpu.models import resnet
    from elasticdl_tpu.worker.collective_trainer import CollectiveTrainer

    spec = resnet.model_spec(variant=variant, num_classes=num_classes,
                             image_size=image_size, learning_rate=0.1)
    trainer = CollectiveTrainer(spec, batch_size=batch,
                                use_bf16_compute=True)
    rng = np.random.RandomState(0)
    xs = rng.rand(batch, image_size, image_size, 3).astype(np.float32)
    ys = rng.randint(0, num_classes, size=batch).astype(np.int32)
    losses = [float(trainer.train_minibatch(xs, ys)[0]) for _ in range(2)]
    if not all(np.isfinite(losses)):
        raise AssertionError("non-finite loss %s" % losses)
    return losses


def check_resnet_step(variant, image_size, batch, num_classes):
    """The whole model through the trainer with whatever
    ``ops/mode.py`` resolves to by default, then again with the
    reference GroupNorm in the model."""
    args = (variant, image_size, batch, num_classes)
    fused = _resnet_losses(*args)
    print(json.dumps({"resnet_step": "fused", "losses": fused,
                      "fused_gn": kernel_mode()}), flush=True)
    with _model_group_norm(_reference_group_norm):
        ref = _resnet_losses(*args)
    moved, ref_moved = fused[1] - fused[0], ref[1] - ref[0]
    return {
        "loss0_minus_ln_classes": abs(fused[0] - np.log(num_classes)),
        "loss1": fused[1],
        "loss1_reference_gn": ref[1],
        "step_vs_reference_gn": abs(moved - ref_moved) / abs(ref_moved),
    }


def check_zero1(steps=12):
    """ZeRO-1 (``--zero1``: optimizer state and update sharded over the
    data axis) against the replicated update, a small LM under AdamW over
    every device this process has, same seed, same batches.  ``worker/
    collective_trainer._zero1_apply`` pins its numerics so that the two
    trajectories can be the same bits; whether they are is the backend's
    to say, so this reports ``zero1_steps_differing`` and holds the
    losses to the last ulps."""
    from jax.sharding import Mesh

    from elasticdl_tpu.models import transformer as tfm
    from elasticdl_tpu.worker.collective_trainer import CollectiveTrainer

    devices = jax.devices()
    spec = tfm.model_spec(vocab_size=1024, dim=256, num_heads=2,
                          num_layers=2, seq_len=256)
    batch = 2 * len(devices)
    tokens = np.random.RandomState(5).randint(
        0, 1024, size=(batch, 256)).astype(np.int32)
    mesh = Mesh(np.array(devices), axis_names=("data",))
    losses = []
    for zero1 in (False, True):
        trainer = CollectiveTrainer(spec, batch_size=batch, mesh=mesh,
                                    rng_seed=3, zero1=zero1)
        losses.append([float(trainer.train_minibatch(tokens, tokens)[0])
                       for _ in range(steps)])
    base, sharded = (np.asarray(l, np.float32) for l in losses)
    print(json.dumps({"zero1": "losses", "devices": len(devices),
                      "replicated": losses[0], "zero1_on": losses[1]}),
          flush=True)
    return {"zero1_rel_diff": float(np.max(np.abs(sharded - base) / base)),
            "zero1_steps_differing": int((sharded != base).sum())}


def check_remat_keep(tiny):
    """One loss-and-gradients evaluation of an LM with ``remat=true``
    through the trainer (``chip_smoke.py``'s ``lm_local`` model, over
    every device this process has), with what ``models/remat_keep.py``
    keeps in the room the trainer states here, against the same
    evaluation with no room stated (nothing kept).  ``--tiny`` states a
    room itself: the CPU has no limit."""
    from jax.sharding import Mesh

    from elasticdl_tpu.models import remat_keep, transformer as tfm
    from elasticdl_tpu.ops.batch_shard import DeviceRoom
    from elasticdl_tpu.worker.collective_trainer import CollectiveTrainer

    devices = jax.devices()
    sizes = (dict(vocab_size=256, dim=128, num_heads=2, num_layers=2,
                  seq_len=256) if tiny else
             dict(vocab_size=32768, dim=1024, num_heads=16, num_layers=24,
                  seq_len=2048))
    per_device = 1 if tiny else 4
    spec = tfm.model_spec(remat=True, **sizes)
    batch = per_device * len(devices)
    tokens = np.random.RandomState(9).randint(
        0, sizes["vocab_size"], size=(batch, sizes["seq_len"])
    ).astype(np.int32)
    mesh = Mesh(np.array(devices), axis_names=("data",))
    trainer = CollectiveTrainer(spec, batch_size=batch, mesh=mesh,
                                rng_seed=3)
    prepared = trainer.prepare_batch(tokens, tokens)
    room = DeviceRoom(10 ** 12, 10 ** 12 - 1) if tiny else trainer._room
    if room is None:
        raise AssertionError("the backend states no bytes_limit")
    names, kept, budget, peak = remat_keep.choose(
        spec.config, trainer._params, per_device * sizes["seq_len"], room)
    print(json.dumps({"remat_keep": "chosen", "devices": len(devices),
                      "names": names, "bytes": kept, "budget": budget,
                      "predicted_peak": peak, "room": list(room)}),
          flush=True)
    if not names:
        raise AssertionError("nothing fits the room %s" % (room,))

    def evaluate(room):
        trainer._room = room        # read where the step is traced
        loss, grads, _ = jax.jit(trainer._loss_and_grads)(
            trainer._params, prepared.features, prepared.labels,
            prepared.weights)
        return float(loss), grads

    loss, grads = evaluate(room)
    base_loss, base_grads = evaluate(None)
    pairs = list(zip(jax.tree_util.tree_leaves(grads),
                     jax.tree_util.tree_leaves(base_grads)))
    norm = lambda trees: float(jnp.sqrt(sum(
        jnp.sum(jnp.square(t.astype(jnp.float32))) for t in trees)))
    return {"keep_loss": abs(loss - base_loss) / abs(base_loss),
            "keep_grad": max(_rel_err(g, b) for g, b in pairs),
            "keep_grad_l2": norm([g - b for g, b in pairs])
            / norm([b for _, b in pairs])}


def _cases(tiny):
    interpret = tiny
    if tiny:
        b, h, t = 1, 2, 256
        resnet_args = ("resnet_small_cifar10", 32, 8, 10)
    else:
        b, h, t = 8, 16, 2048
        resnet_args = ("resnet50", 224, 128, 1000)
    for d in (64, 128):
        for window in (0, t // 4):
            yield ("flash/B%d.H%d.T%d.D%d.window%d" % (b, h, t, d, window),
                   lambda d=d, window=window: check_flash(
                       b, h, t, d, window, interpret))
    # kanana-2-30b-a3b's attention (benchmark/configs/kanana-2-30b-a3b
    # .json): 32 heads, scores over 128 + 64, values of 128; at its
    # cell's 16,384 positions and at 4,096, two sequences on one chip
    # and a sequence a chip where there are more
    for lb, lh, lt in ((1, 2, 256),) if tiny else (
            (1, 32, 16384), (max(2, jax.device_count()), 32, 4096)):
        yield ("latent/B%d.H%d.T%d.QK192.V128" % (lb, lh, lt),
               lambda lb=lb, lh=lh, lt=lt: check_latent(
                   lb, lh, lt, interpret, ref_slice=1 + (lt < 16384)))
    for causal in (True, False):
        yield ("flash_partial/B%d.H%d.T%d.D64.causal%d" % (b, h, t, causal),
               lambda causal=causal: check_flash_partial(
                   b, h, t, 64, causal, interpret))
    # OLMoE's expert block (benchmark/configs/olmoe1b7b.json): 4 x 4096
    # tokens x 8 choices sorted over 64 experts, up- and down-projection.
    rows, hidden, width, experts = (
        (512, 128, 256, 8) if tiny else (131072, 2048, 1024, 64))
    for k, n, skew in ((hidden, width, "zipf"), (width, hidden, "empty")):
        yield ("grouped_matmul/M%d.K%d.N%d.X%d.%s"
               % (rows, k, n, experts, skew),
               lambda k=k, n=n, skew=skew: check_grouped_matmul(
                   rows, k, n, experts, skew, interpret))
    # LFM2's expert block (benchmark/configs/lfm2-24b-a2b.json): 4 x 8192
    # tokens x 4 choices, 8 of 64 experts held; flash at its head of 64
    # and T = 8192; the short convolution at 4 sequences of 8192.
    if not tiny:
        yield ("grouped_matmul/M131072.K2048.N1536.X8.zipf",
               lambda: check_grouped_matmul(131072, 2048, 1536, 8, "zipf",
                                            interpret))
        yield ("flash/B1.H32.T8192.D64.window0",
               lambda: check_flash(1, 32, 8192, 64, 0, interpret,
                                   ref_slice=(1, 1)))
    # Grouped-query attention as the three GQA cells run it: K and V at
    # their own head count (32 on 4 under a window, 28 on 4, 32 on 8 at
    # heads of 64), dk and dv summed over the group in the backward
    # call; the reference on the first group of the first batch row.
    for gb, gh, gg, gt, gd, gw in ((2, 4, 2, 256, 64, 128),) if tiny else (
            (2, 32, 4, 4096, 128, 1024), (1, 28, 4, 4096, 128, 0),
            (1, 32, 8, 8192, 64, 0)):
        yield ("flash/B%d.H%d.G%d.T%d.D%d.window%d"
               % (gb, gh, gg, gt, gd, gw),
               lambda gb=gb, gh=gh, gg=gg, gt=gt, gd=gd, gw=gw: check_flash(
                   gb, gh, gt, gd, gw, interpret,
                   ref_slice=(1, gh // gg), kv_heads=gg))
    # Four share cells' row moves (tokens x width, choices, bound): the
    # third a 16-bit row of 21 lane tiles, moved as 1,408 words (tiny: 3
    # tiles as 256), the last the thinnest share's, 8 of 320 experts
    # held: most groups of sixteen tokens sum one term of their eight.
    for n, w, k, bound in ((96, 256, 4, 128), (96, 384, 6, 128)) if tiny else (
            (32768, 2048, 4, 32768), (16384, 2560, 6, 49152),
            (16384, 2688, 6, 12288), (16384, 4096, 8, 6656)):
        yield ("row_moves/N%d.W%d.K%d.C%d" % (n, w, k, bound),
               lambda n=n, w=w, k=k, bound=bound: check_row_moves(
                   n, w, k, bound, bound * 9 // 16, interpret))
    # The embedding's gradient at two cells' tables (tokens, ids, width).
    for n, vocab, dim in ((300, 96, 256),) if tiny else (
            (16384, 37984, 2560), (16384, 24576, 4096)):
        yield ("embed_rows/N%d.V%d.E%d" % (n, vocab, dim),
               lambda n=n, vocab=vocab, dim=dim: check_embed_rows(
                   n, vocab, dim, interpret))
    # xing4.0-29b-a4b.seq4096's maps: 2 x 4,096 tokens, 4 streams, 20
    # Sinkhorn rounds.
    mb, mt = (2, 128) if tiny else (2, 4096)
    yield ("hyper_maps/B%d.T%d.N4.R20" % (mb, mt),
           lambda: check_hyper_maps(mb, mt, 4, 20, interpret))
    cb, ct, ce = (3, 64, 128) if tiny else (4, 8192, 2048)
    yield ("short_conv/B%d.T%d.E%d.K3" % (cb, ct, ce),
           lambda: check_short_conv(cb, ct, ce, 3, interpret))
    yield ("mixed_stack/caccc.share", lambda: check_mixed_stack(tiny))
    yield ("mixed_stack/caccc.share.spill",
           lambda: check_mixed_stack(tiny, spill=True))
    yield ("mixed_stack/awww.share",
           lambda: check_mixed_stack(tiny, stack="awww"))
    yield ("mixed_stack/wwaww.gated.share",
           lambda: check_mixed_stack(tiny, stack="wwaww"))
    yield ("mixed_stack/ddda.share",
           lambda: check_mixed_stack(tiny, stack="ddda"))
    yield ("mixed_stack/addd.kda.share",
           lambda: check_mixed_stack(tiny, stack="addd"))
    # The benchmark's two heads: OLMoE's untied, OLMo's tied embedding.
    hdim, vocab, heads = (64, 256, ((2, 24, False), (2, 24, True))) if tiny \
        else (2048, 50304, ((4, 4096, False), (8, 2048, True)))
    for hb, ht, tied in heads:
        yield ("head_loss/B%d.T%d.E%d.V%d.%s"
               % (hb, ht, hdim, vocab, "tied" if tied else "untied"),
               lambda hb=hb, ht=ht, tied=tied: check_head_loss(
                   hb, ht, hdim, vocab, tied))
    variant, image_size, batch, classes = resnet_args
    for hw, c, groups, relu in resnet_group_norm_shapes(
            variant, image_size, batch):
        yield ("group_norm/B%d.HW%d.C%d.G%d.relu%d"
               % (batch, hw, c, groups, relu),
               lambda hw=hw, c=c, groups=groups, relu=relu:
               check_group_norm(batch, hw, c, groups, relu, interpret))
    yield ("resnet_step/%s.b%d" % (variant, batch),
           lambda: check_resnet_step(variant, image_size, batch, classes))
    yield ("zero1/lm.adamw.%ddevices" % jax.device_count(), check_zero1)
    yield ("remat_keep/lm.%ddevices" % jax.device_count(),
           lambda: check_remat_keep(tiny))


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    tiny = "--tiny" in argv
    wanted = [a for a in argv if not a.startswith("--")]
    place_compile_cache()
    if tiny:
        os.environ[SWITCH] = "interpret"
    report = device_report()
    print(json.dumps({"device": report}), flush=True)
    if not tiny and report["platform"] != "tpu":
        print("chip_check: platform is %r, not tpu (use --tiny for the "
              "interpreter)" % report["platform"], file=sys.stderr)
        return 1
    failed, ran = [], 0
    for name, run in _cases(tiny):
        if wanted and not any(word in name for word in wanted):
            continue
        ran += 1
        t0 = time.perf_counter()
        row = {"case": name}
        try:
            errs = run()
            row["errs"] = errs
            # "not <=" so that a NaN is over tolerance too
            bad = {k: e for k, e in errs.items()
                   if not e <= TOLERANCES.get(k, float("inf"))}
            if bad:
                raise AssertionError("over tolerance: %s" % bad)
            row["ok"] = True
        except Exception as e:  # noqa: BLE001 — report every case
            row["ok"] = False
            row["error"] = "%s: %s" % (type(e).__name__, str(e)[-1500:])
            traceback.print_exc()
            failed.append(name)
        row["secs"] = round(time.perf_counter() - t0, 1)
        print(json.dumps(row), flush=True)
    if not ran:
        failed.append("no case matches %s" % wanted)
    print(json.dumps({"chip_check": "kernels", "ok": not failed,
                      "cases": ran, "failed": failed, "device": report}),
          flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
