"""Fused GroupNorm(+ReLU) Pallas kernel for TPU.

GroupNorm is the normalization of the ResNet family here (models/
resnet.py — BatchNorm needs cross-replica batch-stats sync; GroupNorm
doesn't), and it is HBM-bound: XLA computes stats and normalizes in
separate passes over the activation, and the benchmark ablation showed
it costing ~14.5 ms of the 54.5 ms ResNet-50 step (BENCHMARKS.md).
This kernel does the whole op — stats, normalize, affine, optional
ReLU — in ONE pass over HBM: each grid step holds one batch row
[HW, C] in VMEM, reduces it, and writes the normalized output back.

Backward is a second Pallas kernel (custom VJP): recomputes x-hat from
the saved group stats broadcast per channel (two small [B, 1, C] f32
residuals — the activation itself is never re-saved), applies the
closed-form GroupNorm pullback, and accumulates dscale/dbias across
the sequential TPU grid in a revisited output block.  Inside a grid
step the HW axis is walked in chunks (``_row_chunk``) so the f32
temporaries fit scoped VMEM even for the 112x112 stem map.

Layouts: channels-last [..., C] (the conv layout everywhere in this
framework); stats are over (spatial..., C/G) per group, matching
flax.linen.GroupNorm semantics (models/resnet.py used nn.GroupNorm
before this kernel).  Kernel, interpreter or the jnp reference:
``ops/mode.py``'s answer, the one every Pallas op here asks.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.ops.batch_shard import per_batch_shard
from elasticdl_tpu.ops.mode import kernel_mode


def _group_norm_ref(x, scale, bias, num_groups, eps, relu):
    """jnp reference (identical math to flax.linen.GroupNorm)."""
    B = x.shape[0]
    C = x.shape[-1]
    xf = x.astype(jnp.float32).reshape(B, -1, num_groups,
                                       C // num_groups)
    mean = xf.mean(axis=(1, 3), keepdims=True)
    var = ((xf - mean) ** 2).mean(axis=(1, 3), keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    y = y.reshape(x.shape) * scale.astype(jnp.float32) \
        + bias.astype(jnp.float32)
    if relu:
        y = jax.nn.relu(y)
    return y.astype(x.dtype)


# -- forward kernel ---------------------------------------------------------


def _membership(L, num_groups, logical_C):
    """[L, G] one-hot lane->group matrix.  Group reductions become two
    small MXU matmuls ([1,L]@[L,G] then [1,G]@[G,L]) — Mosaic has no
    efficient lowering for the [C]->[G, C/G] reshape (C/G can be 2,
    far below the 128-lane tile), matmuls it always has.

    L may be a lane-folded layout (narrow C folds rows into lanes so
    C=64 doesn't waste half of every 128-lane vector register and
    every DMA): physical lane l holds logical channel l % logical_C.
    """
    gsz = logical_C // num_groups
    chan = jax.lax.broadcasted_iota(jnp.int32, (L, num_groups), 0) \
        % logical_C
    grp = jax.lax.broadcasted_iota(jnp.int32, (L, num_groups), 1)
    return (chan // gsz == grp).astype(jnp.float32)


def _group_mean_c(row, memb, n):
    """row [1, C] -> per-group mean broadcast back to [1, C].

    HIGHEST precision: Mosaic's default contracts f32 operands in bf16
    passes, which left the group mean and rstd good to ~1e-3 on the chip
    (my chip run, PR 21: dscale off by 1e-3 against the f32 reference,
    3e-7 with this).  The operands are one row, so the exact contraction
    costs nothing."""
    exact = jax.lax.Precision.HIGHEST
    return jnp.dot(
        jnp.dot(row, memb, preferred_element_type=jnp.float32,
                precision=exact),
        memb.T, preferred_element_type=jnp.float32, precision=exact,
    ) / n


def _row_chunk(HW, C):
    """Rows per in-kernel chunk: cap the f32 temporaries at ~2 MB while
    keeping the chunk count a clean divisor of HW (halving only while
    even), so big feature maps fit scoped VMEM."""
    chunk = HW
    while chunk * C * 4 > 2 * 1024 * 1024 and chunk % 2 == 0:
        chunk //= 2
    return chunk


def _fwd_kernel(x_ref, scale_ref, bias_ref, out_ref, mean_ref, rstd_ref,
                csum_ref, csumsq_ref, *, num_groups, eps, relu, chunk,
                logical_C):
    L = x_ref.shape[-1]
    HW = x_ref.shape[1]
    gsz = logical_C // num_groups
    n = HW * (L // logical_C) * gsz      # logical elements per group
    memb = _membership(L, num_groups, logical_C)

    # Pass 1 over VMEM (chunked so f32 temps stay small): channel sums
    # -> group means.
    csum_ref[...] = jnp.zeros_like(csum_ref)

    def mean_body(i, _):
        xs = x_ref[0, pl.ds(i * chunk, chunk), :].astype(jnp.float32)
        csum_ref[...] += jnp.sum(xs, axis=0, keepdims=True)
        return 0

    jax.lax.fori_loop(0, HW // chunk, mean_body, 0)
    mean_c = _group_mean_c(csum_ref[...], memb, n)       # [1, C]

    # Pass 2: CENTERED second moment.  E[x^2]-E[x]^2 catastrophically
    # cancels in f32 when |mean| >> std (un-normalized inputs); the
    # data is already resident in VMEM, so the extra pass costs no HBM
    # traffic and matches nn.GroupNorm's two-pass variance exactly.
    csumsq_ref[...] = jnp.zeros_like(csumsq_ref)

    def var_body(i, _):
        xs = x_ref[0, pl.ds(i * chunk, chunk), :].astype(jnp.float32)
        d = xs - mean_c
        csumsq_ref[...] += jnp.sum(d * d, axis=0, keepdims=True)
        return 0

    jax.lax.fori_loop(0, HW // chunk, var_body, 0)
    var_c = _group_mean_c(csumsq_ref[...], memb, n)
    rstd_c = jax.lax.rsqrt(var_c + eps)
    mean_ref[0] = mean_c
    rstd_ref[0] = rstd_c
    a = rstd_c * scale_ref[...].astype(jnp.float32)
    b = bias_ref[...].astype(jnp.float32) - mean_c * a

    # Pass 2 over VMEM: normalize + affine (+ ReLU).
    def norm_body(i, _):
        xs = x_ref[0, pl.ds(i * chunk, chunk), :].astype(jnp.float32)
        y = xs * a + b
        if relu:
            y = jnp.maximum(y, 0.0)
        out_ref[0, pl.ds(i * chunk, chunk), :] = y.astype(out_ref.dtype)
        return 0

    jax.lax.fori_loop(0, HW // chunk, norm_body, 0)


def _fold(x3):
    """Fold rows into lanes while C < 128 (keeps every 128-wide vector
    register and DMA fully populated).  Returns (folded, logical_C)."""
    B, HW, C = x3.shape
    while C < 128 and HW % 2 == 0:
        HW //= 2
        C *= 2
    return x3.reshape(B, HW, C), x3.shape[-1]


def _fwd_pallas(x3, scale, bias, num_groups, eps, relu, interpret):
    x3, logical_C = _fold(x3)
    B, HW, C = x3.shape
    r = C // logical_C
    scale = jnp.tile(scale.reshape(1, logical_C), (1, r))
    bias = jnp.tile(bias.reshape(1, logical_C), (1, r))
    out, mean, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, num_groups=num_groups, eps=eps,
                          relu=relu, chunk=_row_chunk(HW, C),
                          logical_C=logical_C),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, HW, C), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, C), lambda b: (0, 0)),
            pl.BlockSpec((1, C), lambda b: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, HW, C), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, C), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, C), lambda b: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, HW, C), x3.dtype),
            jax.ShapeDtypeStruct((B, 1, C), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, C), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, C), jnp.float32),
            pltpu.VMEM((1, C), jnp.float32),
        ],
        interpret=interpret,
    )(x3, scale, bias)
    return out, mean, rstd


# -- backward kernel --------------------------------------------------------


def _bwd_kernel(x_ref, dy_ref, scale_ref, bias_ref, mean_ref, rstd_ref,
                dx_ref, dscale_ref, dbias_ref, s1_ref, s2_ref,
                *, num_groups, eps, relu, chunk, logical_C):
    L = x_ref.shape[-1]
    HW = x_ref.shape[1]
    gsz = logical_C // num_groups
    n = HW * (L // logical_C) * gsz
    memb = _membership(L, num_groups, logical_C)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        dscale_ref[...] = jnp.zeros_like(dscale_ref)
        dbias_ref[...] = jnp.zeros_like(dbias_ref)

    scale = scale_ref[...].astype(jnp.float32)   # [1, C]
    bias = bias_ref[...].astype(jnp.float32)
    mean_c = mean_ref[0]                         # [1, C]
    rstd_c = rstd_ref[0]
    # Same a/b association order as the forward so the ReLU mask is
    # bit-identical on boundary elements (y == 0).
    a_c = rstd_c * scale
    b_c = bias - mean_c * a_c

    # Pass 1 (chunked): s1 = sum(dy), s2 = sum(dy * xhat) per channel
    # (dy already ReLU-masked).
    s1_ref[...] = jnp.zeros_like(s1_ref)
    s2_ref[...] = jnp.zeros_like(s2_ref)

    def stats_body(i, _):
        sl = pl.ds(i * chunk, chunk)
        xs = x_ref[0, sl, :].astype(jnp.float32)
        xhat = (xs - mean_c) * rstd_c
        dy = dy_ref[0, sl, :].astype(jnp.float32)
        if relu:
            dy = jnp.where(xs * a_c + b_c > 0.0, dy, 0.0)
        s1_ref[...] += jnp.sum(dy, axis=0, keepdims=True)
        s2_ref[...] += jnp.sum(dy * xhat, axis=0, keepdims=True)
        return 0

    jax.lax.fori_loop(0, HW // chunk, stats_body, 0)
    dscale_ref[...] += s2_ref[...]
    dbias_ref[...] += s1_ref[...]
    # GroupNorm pullback: dx = rstd*(g - mean_g(g) - xhat*mean_g(g*xhat))
    # with g = dy*scale; the group means come from the channel sums.
    gsum_c = _group_mean_c(s1_ref[...] * scale, memb, n)     # [1, C]
    gxsum_c = _group_mean_c(s2_ref[...] * scale, memb, n)

    def dx_body(i, _):
        sl = pl.ds(i * chunk, chunk)
        xs = x_ref[0, sl, :].astype(jnp.float32)
        xhat = (xs - mean_c) * rstd_c
        dy = dy_ref[0, sl, :].astype(jnp.float32)
        if relu:
            dy = jnp.where(xs * a_c + b_c > 0.0, dy, 0.0)
        dx = rstd_c * (dy * scale - gsum_c - xhat * gxsum_c)
        dx_ref[0, sl, :] = dx.astype(dx_ref.dtype)
        return 0

    jax.lax.fori_loop(0, HW // chunk, dx_body, 0)


def _bwd_pallas(x3, dy3, scale, bias, mean, rstd, num_groups, eps, relu,
                interpret):
    orig_shape = x3.shape
    x3, logical_C = _fold(x3)
    dy3 = dy3.reshape(x3.shape)
    B, HW, C = x3.shape
    r = C // logical_C
    scale_p = jnp.tile(scale.reshape(1, logical_C), (1, r))
    bias_p = jnp.tile(bias.reshape(1, logical_C), (1, r))
    dx, dscale, dbias = pl.pallas_call(
        functools.partial(_bwd_kernel, num_groups=num_groups, eps=eps,
                          relu=relu, chunk=_row_chunk(HW, C),
                          logical_C=logical_C),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, HW, C), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, HW, C), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, C), lambda b: (0, 0)),
            pl.BlockSpec((1, C), lambda b: (0, 0)),
            pl.BlockSpec((1, 1, C), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, C), lambda b: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, HW, C), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, C), lambda b: (0, 0)),
            pl.BlockSpec((1, C), lambda b: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, HW, C), x3.dtype),
            jax.ShapeDtypeStruct((1, C), jnp.float32),
            jax.ShapeDtypeStruct((1, C), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, C), jnp.float32),
            pltpu.VMEM((1, C), jnp.float32),
        ],
        interpret=interpret,
    )(x3, dy3, scale_p, bias_p, mean, rstd)
    # Un-fold the lane-tiled affine grads back to logical channels.
    dscale = dscale.reshape(r, logical_C).sum(axis=0)
    dbias = dbias.reshape(r, logical_C).sum(axis=0)
    return dx.reshape(orig_shape), dscale, dbias


# -- custom-VJP wrapper -----------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _fused(x, scale, bias, num_groups, eps, relu, interpret):
    return _fused_fwd(x, scale, bias, num_groups, eps, relu,
                      interpret)[0]


def _fused_fwd(x, scale, bias, num_groups, eps, relu, interpret):
    B, C = x.shape[0], x.shape[-1]
    x3 = x.reshape(B, -1, C)
    y3, mean, rstd = _fwd_pallas(x3, scale, bias, num_groups, eps, relu,
                                 interpret)
    return y3.reshape(x.shape), (x3, scale, bias, mean, rstd, x.shape)


def _fused_bwd(num_groups, eps, relu, interpret, res, dy):
    x3, scale, bias, mean, rstd, xshape = res
    dy3 = dy.reshape(x3.shape)
    dx3, dscale, dbias = _bwd_pallas(
        x3, dy3, scale, bias, mean, rstd, num_groups, eps, relu,
        interpret,
    )
    return (dx3.reshape(xshape), dscale.astype(scale.dtype),
            dbias.astype(bias.dtype))


_fused.defvjp(
    lambda x, scale, bias, num_groups, eps, relu, interpret: _fused_fwd(
        x, scale, bias, num_groups, eps, relu, interpret
    ),
    _fused_bwd,
)


def fused_group_norm(x, scale, bias, num_groups, eps=1e-6, relu=False):
    """GroupNorm + affine (+ ReLU) over the trailing channel axis.

    x: [B, spatial..., C]; scale/bias: [C].  The Pallas kernel where
    ``ops/mode.py`` allows one, else the jnp reference.
    """
    C = x.shape[-1]
    if C % num_groups:
        raise ValueError(
            "channels %d not divisible by %d groups" % (C, num_groups)
        )
    mode = kernel_mode()
    if mode != "off":
        return per_batch_shard(
            lambda x, scale, bias: _fused(x, scale, bias, num_groups, eps,
                                          relu, mode == "interpret"),
            (x,), (scale, bias),
        )
    return _group_norm_ref(x, scale, bias, num_groups, eps, relu)
