"""Gated short convolution (Pallas TPU): the operator of a layer that
mixes a sequence with a depthwise causal convolution a few taps long.

``short_conv(bcu [B, T, 3E], w [E, K]) -> [B, T, E]``: ``bcu`` is the
input projection's output, whose three column blocks are B, C and u;
``g = B * u``; ``c_t = sum_k w[:, k] * g_(t - (K - 1 - k))`` per channel
(causal, zero before the sequence's start); the result is ``C * c``.
One pass: a call reads B, C and u as column blocks of the one array (no
split copies), keeps ``g`` and ``c`` in VMEM, and writes the result; the
taps before a row tile's first row come from the ``HALO`` rows above it,
and a tile that starts a sequence takes zeros (a row tile never spans
two sequences: it divides T).

The ``custom_vjp`` is one more call of the same shape: it rebuilds ``g``
and ``c`` from the saved ``bcu``, runs the taps the other way over
``dc = dout * C`` (the rows below the tile are its halo, zero past the
sequence's end) and writes dB, dC and du as the three column blocks of
one ``[rows, 3E]`` array, a block a grid step, beside each row tile's
part of ``dw`` (summed outside: ``[tiles * 8, E]`` float32).  Every
result of these calls is 2-D (the benchmark tells the flash kernels
apart by their 3-D results) and the calls carry their names into the
trace, ``sconv_fwd`` and ``sconv_bwd`` (``benchmark/kernels/``).

The op is bound by memory: 4 passes of ``[rows, E]`` forward, 7 backward.

A second entry point, ``conv_silu(x [B, T, E], w [E, K]) -> [B, T,
E]``, runs the same depthwise causal taps over ``x`` itself and puts a
SiLU behind them, ``silu(conv(x))``: what a Gated DeltaNet layer puts
between its q, k, v projection and its scan (models/transformer.py).
Its calls are ``sconv_silu_fwd`` (2 passes) and ``sconv_silu_bwd``, which
rebuilds ``conv`` of the tile and of the HALO rows below it for the
SiLU's slope (5 passes); the tiles, the halo and the taps' layout are
the gated op's.  With a ``bias`` [E] (a Mamba-2 layer's ``use_conv_bias``)
it is ``silu(conv(x) + bias)``: the bias rides as row ``BIAS_ROW`` of the
taps' [8, E] block, and its gradient comes back in that row of ``dw``'s
parts.

Reference: ``short_conv_ref``, plain ``jax.numpy`` differentiated by JAX,
which is also what ``short_conv`` returns wherever ``ops/mode.py``
answers ``off``, or for shapes the kernel does not tile (it says so:
``announce_fallback``).  An explicit ``interpret=`` forces the kernels.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.ops import flash_attention
from elasticdl_tpu.ops.batch_shard import per_batch_shard, shards
from elasticdl_tpu.ops.mode import resolve

# ``checkpoint_name``s of what the backward reads, for
# models/remat_keep.py: the projection's output (named by the model,
# where it is made) and the op's result (the output projection's
# operand).
KEEP_IN, KEEP_OUT = "conv_in", "conv_out"

# Rows of the block a tile's neighbouring rows are read in: bfloat16's
# least tile height.  The kernel has K - 1 <= HALO.
HALO = 16
ROW_TILES = (512, 256, 128, 64, 32, 16)
CHANNEL_TILES = (512, 256, 128)
VMEM_LIMIT = 64 * 1024 * 1024


def short_conv_ref(bcu, w):
    """The same in plain ``jax.numpy`` (float32 inside, bcu's dtype
    out); bcu [..., T, 3E], w [E, K]."""
    b, c, u = jnp.split(bcu.astype(jnp.float32), 3, axis=-1)
    return (c * _taps_ref(b * u, w)).astype(bcu.dtype)


def _taps_ref(g, w):
    """``conv`` [..., T, E] float32 of g and the taps w [E, K]."""
    t, taps = g.shape[-2], w.shape[1]
    pad = [(0, 0)] * (g.ndim - 2) + [(taps - 1, 0), (0, 0)]
    padded = jnp.pad(g, pad)
    return sum(w[:, k].astype(jnp.float32)
               * lax.slice_in_dim(padded, k, k + t, axis=-2)
               for k in range(taps))


def conv_silu_ref(x, w, bias=None):
    """``silu(conv(x) + bias)`` in plain ``jax.numpy`` (float32 inside,
    x's dtype out); x [..., T, E], w [E, K], bias [E] or None."""
    conv = _taps_ref(x.astype(jnp.float32), w)
    if bias is not None:
        conv = conv + bias.astype(jnp.float32)
    return jax.nn.silu(conv).astype(x.dtype)


def tiles(seq_len, channels):
    """(row tile, channel tile) of the kernels for sequences of
    ``seq_len`` rows, or None where they do not tile."""
    tm = next((t for t in ROW_TILES if seq_len % t == 0), None)
    tc = next((t for t in CHANNEL_TILES if channels % t == 0), None)
    return None if tm is None or tc is None else (tm, tc)


def _shifted(g, edge, back):
    """g's rows moved ``back`` rows down (up for ``back`` < 0), the rows
    that enter from outside the tile taken from ``edge`` [HALO, tc]: the
    rows above the tile (their last ones enter) or below it (their
    first)."""
    tm = g.shape[0]
    row = lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    out = pltpu.roll(g, back % tm, 0)
    for i in range(abs(back)):
        if back > 0:      # row i takes the halo's row HALO - back + i
            at, src = i, HALO - back + i
        else:             # row tm - |back| + i takes the halo's row i
            at, src = tm + back + i, i
        out = jnp.where(row == at, edge[src:src + 1, :], out)
    return out


def _gate(b_ref, u_ref):
    return b_ref[...].astype(jnp.float32) * u_ref[...].astype(jnp.float32)


def _conv(g, above, w_ref, taps):
    """c [tm, tc] float32 of the tile's g and the HALO rows above."""
    conv = w_ref[taps - 1:taps, :] * g
    for k in range(taps - 1):
        conv += w_ref[k:k + 1, :] * _shifted(g, above, taps - 1 - k)
    return conv


def _fwd_kernel(b_ref, c_ref, u_ref, b_up_ref, u_up_ref, w_ref, out_ref,
                *, taps, tiles_a_sequence):
    # the rows above a sequence's first tile are another sequence's
    starts = pl.program_id(0) % tiles_a_sequence == 0
    above = jnp.where(starts, 0.0, _gate(b_up_ref, u_up_ref))
    conv = _conv(_gate(b_ref, u_ref), above, w_ref, taps)
    out_ref[...] = (c_ref[...].astype(jnp.float32) * conv).astype(
        out_ref.dtype)


def _bwd_kernel(b_ref, c_ref, u_ref, b_up_ref, u_up_ref, w_ref, dout_ref,
                dout_dn_ref, c_dn_ref, dbcu_ref, dw_ref, dc_keep, du_keep,
                *, taps, tiles_a_sequence):
    part = pl.program_id(2)
    starts = pl.program_id(0) % tiles_a_sequence == 0
    ends = (pl.program_id(0) + 1) % tiles_a_sequence == 0

    @pl.when(part == 0)
    def _():
        g = _gate(b_ref, u_ref)
        above = jnp.where(starts, 0.0, _gate(b_up_ref, u_up_ref))
        dout = dout_ref[...].astype(jnp.float32)
        dc_keep[...] = (dout * _conv(g, above, w_ref, taps)).astype(
            dc_keep.dtype)
        dconv = dout * c_ref[...].astype(jnp.float32)
        below = jnp.where(ends, 0.0, dout_dn_ref[...].astype(jnp.float32)
                          * c_dn_ref[...].astype(jnp.float32))
        dg = w_ref[taps - 1:taps, :] * dconv
        dw = [None] * taps
        dw[taps - 1] = jnp.sum(dconv * g, axis=0, keepdims=True)
        for k in range(taps - 1):
            back = taps - 1 - k
            dg += w_ref[k:k + 1, :] * _shifted(dconv, below, -back)
            dw[k] = jnp.sum(dconv * _shifted(g, above, back), axis=0,
                            keepdims=True)
        row = lax.broadcasted_iota(jnp.int32, dw_ref.shape, 0)
        dw_ref[...] = sum(jnp.where(row == k, dw[k], 0.0)
                          for k in range(taps))
        dbcu_ref[...] = (dg * u_ref[...].astype(jnp.float32)).astype(
            dbcu_ref.dtype)
        du_keep[...] = (dg * b_ref[...].astype(jnp.float32)).astype(
            du_keep.dtype)

    @pl.when(part == 1)
    def _():
        dbcu_ref[...] = dc_keep[...]

    @pl.when(part == 2)
    def _():
        dbcu_ref[...] = du_keep[...]


def _specs(tm, tc, e, rows):
    """BlockSpecs of B, C, u (column blocks of one [rows, 3E] array),
    of B's and u's HALO rows above the tile, and of the taps, for a grid
    that leads with (row tile, channel tile)."""
    blocks = e // tc
    per_halo = tm // HALO

    def column(which):
        return pl.BlockSpec((tm, tc),
                            lambda i, j, *_: (i, which * blocks + j))

    def above(which):
        return pl.BlockSpec(
            (HALO, tc), lambda i, j, *_: (
                jnp.maximum(i * per_halo - 1, 0), which * blocks + j))

    def below(which):
        return pl.BlockSpec(
            (HALO, tc), lambda i, j, *_: (
                jnp.minimum((i + 1) * per_halo, rows // HALO - 1),
                which * blocks + j))

    taps = pl.BlockSpec((8, tc), lambda i, j, *_: (0, j))
    return column, above, below, taps


# The row of the taps' [8, E] block that holds a bias on the taps.
BIAS_ROW = 7


def _taps(w, bias=None):
    """[8, E] float32: row k is tap k of every channel; row ``BIAS_ROW``
    the ``bias`` where there is one."""
    e, taps = w.shape
    rows = jnp.zeros((8, e), jnp.float32).at[:taps].set(
        w.astype(jnp.float32).T)
    if bias is not None:
        rows = rows.at[BIAS_ROW].set(bias.astype(jnp.float32))
    return rows


def _fwd_call(bcu, w, seq_len, interpret, tm, tc):
    rows, e = bcu.shape[0], w.shape[0]
    column, above, _below, taps = _specs(tm, tc, e, rows)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, taps=w.shape[1],
                          tiles_a_sequence=seq_len // tm),
        out_shape=jax.ShapeDtypeStruct((rows, e), bcu.dtype),
        grid=(rows // tm, e // tc),
        in_specs=[column(0), column(1), column(2), above(0), above(2),
                  taps],
        out_specs=pl.BlockSpec((tm, tc), lambda i, j: (i, j)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="sconv_fwd",
    )(bcu, bcu, bcu, bcu, bcu, _taps(w))


def _bwd_call(bcu, w, dout, seq_len, interpret, tm, tc):
    """(dbcu [rows, 3E], dw's parts [tiles * 8, E] float32)."""
    rows, e = bcu.shape[0], w.shape[0]
    column, above, below, taps = _specs(tm, tc, e, rows)
    blocks = e // tc
    return pl.pallas_call(
        functools.partial(_bwd_kernel, taps=w.shape[1],
                          tiles_a_sequence=seq_len // tm),
        out_shape=(jax.ShapeDtypeStruct((rows, 3 * e), bcu.dtype),
                   jax.ShapeDtypeStruct((rows // tm * 8, e), jnp.float32)),
        # the third axis writes dB, dC, du in turn: the inputs' blocks
        # stay where they are, so nothing is read again
        grid=(rows // tm, blocks, 3),
        in_specs=[column(0), column(1), column(2), above(0), above(2),
                  taps, column(0), below(0), below(1)],
        out_specs=(
            pl.BlockSpec((tm, tc), lambda i, j, p: (i, p * blocks + j)),
            pl.BlockSpec((8, tc), lambda i, j, p: (i, j))),
        scratch_shapes=[pltpu.VMEM((tm, tc), bcu.dtype),
                        pltpu.VMEM((tm, tc), bcu.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="sconv_bwd",
    )(bcu, bcu, bcu, bcu, bcu, _taps(w), dout, dout, bcu)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _sconv(bcu, w, seq_len, interpret, tm, tc):
    return _fwd_call(bcu, w, seq_len, interpret, tm, tc)


def _sconv_fwd(bcu, w, seq_len, interpret, tm, tc):
    return _fwd_call(bcu, w, seq_len, interpret, tm, tc), (bcu, w)


def _taps_gradient(parts, w):
    """dw [E, K] of the row tiles' parts [tiles * 8, E] float32."""
    dw = parts.reshape(-1, 8, w.shape[0]).sum(axis=0)[:w.shape[1]]
    return dw.T.astype(w.dtype)


def _sconv_bwd(seq_len, interpret, tm, tc, res, dout):
    bcu, w = res
    dbcu, parts = _bwd_call(bcu, w, dout, seq_len, interpret, tm, tc)
    return dbcu, _taps_gradient(parts, w)


_sconv.defvjp(_sconv_fwd, _sconv_bwd)


# -- taps and a SiLU ---------------------------------------------------------


def _silu_slope(conv):
    """(silu(conv), d silu / d conv)."""
    s = jax.nn.sigmoid(conv)
    return conv * s, s * (1.0 + conv * (1.0 - s))


def _biased(conv, w_ref, bias):
    return conv + w_ref[BIAS_ROW:BIAS_ROW + 1, :] if bias else conv


def _silu_fwd_kernel(x_ref, x_up_ref, w_ref, out_ref, *, taps,
                     tiles_a_sequence, bias=False):
    starts = pl.program_id(0) % tiles_a_sequence == 0
    above = jnp.where(starts, 0.0, x_up_ref[...].astype(jnp.float32))
    conv = _biased(_conv(x_ref[...].astype(jnp.float32), above, w_ref, taps),
                   w_ref, bias)
    out_ref[...] = _silu_slope(conv)[0].astype(out_ref.dtype)


def _silu_bwd_kernel(x_ref, x_up_ref, x_dn_ref, w_ref, dout_ref,
                     dout_dn_ref, dx_ref, dw_ref, *, taps,
                     tiles_a_sequence, bias=False):
    starts = pl.program_id(0) % tiles_a_sequence == 0
    ends = (pl.program_id(0) + 1) % tiles_a_sequence == 0
    x = x_ref[...].astype(jnp.float32)
    above = jnp.where(starts, 0.0, x_up_ref[...].astype(jnp.float32))
    dconv = dout_ref[...].astype(jnp.float32) * _silu_slope(
        _biased(_conv(x, above, w_ref, taps), w_ref, bias))[1]
    # the HALO rows below: their conv reads this tile's last rows
    conv_dn = _biased(_conv(x_dn_ref[...].astype(jnp.float32),
                            x[x.shape[0] - HALO:], w_ref, taps), w_ref, bias)
    below = jnp.where(ends, 0.0, dout_dn_ref[...].astype(jnp.float32)
                      * _silu_slope(conv_dn)[1])
    dx = w_ref[taps - 1:taps, :] * dconv
    dw = [None] * taps
    dw[taps - 1] = jnp.sum(dconv * x, axis=0, keepdims=True)
    for k in range(taps - 1):
        back = taps - 1 - k
        dx += w_ref[k:k + 1, :] * _shifted(dconv, below, -back)
        dw[k] = jnp.sum(dconv * _shifted(x, above, back), axis=0,
                        keepdims=True)
    row = lax.broadcasted_iota(jnp.int32, dw_ref.shape, 0)
    parts = sum(jnp.where(row == k, dw[k], 0.0) for k in range(taps))
    if bias:
        parts += jnp.where(row == BIAS_ROW,
                           jnp.sum(dconv, axis=0, keepdims=True), 0.0)
    dw_ref[...] = parts
    dx_ref[...] = dx.astype(dx_ref.dtype)


def _silu_call(x, w, seq_len, interpret, tm, tc, dout=None, bias=None):
    """``silu(conv(x))`` [rows, E] (``silu(conv(x) + bias)``), or with
    ``dout`` its backward: (dx [rows, E], dw's parts [tiles * 8, E]
    float32, the bias's in row ``BIAS_ROW``)."""
    rows, e = x.shape
    column, above, below, taps = _specs(tm, tc, e, rows)
    here = pl.BlockSpec((tm, tc), lambda i, j: (i, j))
    options = dict(
        grid=(rows // tm, e // tc),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret)
    static = dict(taps=w.shape[1], tiles_a_sequence=seq_len // tm)
    if bias is not None:
        static["bias"] = True
    if dout is None:
        return pl.pallas_call(
            functools.partial(_silu_fwd_kernel, **static),
            out_shape=jax.ShapeDtypeStruct((rows, e), x.dtype),
            in_specs=[column(0), above(0), taps], out_specs=here,
            name="sconv_silu_fwd", **options)(x, x, _taps(w, bias))
    return pl.pallas_call(
        functools.partial(_silu_bwd_kernel, **static),
        out_shape=(jax.ShapeDtypeStruct((rows, e), x.dtype),
                   jax.ShapeDtypeStruct((rows // tm * 8, e), jnp.float32)),
        in_specs=[column(0), above(0), below(0), taps, column(0),
                  below(0)],
        out_specs=(here, pl.BlockSpec((8, tc), lambda i, j: (i, j))),
        name="sconv_silu_bwd", **options)(x, x, x, _taps(w, bias), dout,
                                          dout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _sconv_silu(x, w, seq_len, interpret, tm, tc):
    return _silu_call(x, w, seq_len, interpret, tm, tc)


def _sconv_silu_fwd(x, w, seq_len, interpret, tm, tc):
    return _silu_call(x, w, seq_len, interpret, tm, tc), (x, w)


def _sconv_silu_bwd(seq_len, interpret, tm, tc, res, dout):
    x, w = res
    dx, parts = _silu_call(x, w, seq_len, interpret, tm, tc, dout)
    return dx, _taps_gradient(parts, w)


_sconv_silu.defvjp(_sconv_silu_fwd, _sconv_silu_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _sconv_silu_biased(x, w, bias, seq_len, interpret, tm, tc):
    return _silu_call(x, w, seq_len, interpret, tm, tc, bias=bias)


def _sconv_silu_biased_fwd(x, w, bias, seq_len, interpret, tm, tc):
    return _sconv_silu_biased(x, w, bias, seq_len, interpret, tm, tc), (
        x, w, bias)


def _sconv_silu_biased_bwd(seq_len, interpret, tm, tc, res, dout):
    x, w, bias = res
    dx, parts = _silu_call(x, w, seq_len, interpret, tm, tc, dout, bias)
    dbias = parts.reshape(-1, 8, w.shape[0]).sum(axis=0)[BIAS_ROW]
    return dx, _taps_gradient(parts, w), dbias.astype(bias.dtype)


_sconv_silu_biased.defvjp(_sconv_silu_biased_fwd, _sconv_silu_biased_bwd)


@functools.lru_cache(maxsize=None)
def announce_conv(rows, channels, tile, kernel, silu=False, bias=False):
    """Once per compiled shape, by the logger ``announce_tiles`` uses:
    what the op runs (of one shard of the trainer's data axis); with
    ``silu`` it is ``conv_silu``, and the line says so at its end, and
    ``bias=1`` where the taps carry one."""
    flash_attention.logger.info(
        "short conv: rows=%d channels=%d tile=%s kernel=%s%s%s", rows,
        channels, "%dx%d" % tile if tile else "-", kernel,
        " epilogue=silu" if silu else "", " bias=1" if bias else "")


def _plan(what, batch, seq_len, e, taps, interpret, silu=False,
          bias=False):
    """(mode, tile) an op of this file runs [batch, seq_len, e] in:
    the kernels where ``ops/mode.py`` allows them and the shape tiles,
    else ("off", None), said once."""
    mode = resolve(interpret)
    tile = None if mode == "off" else tiles(seq_len, e)
    if mode != "off" and (tile is None or taps > 8 - bias):
        flash_attention.announce_fallback(
            what, (batch, seq_len, e),
            "T %% %d, E %% %d or %d taps" % (ROW_TILES[-1],
                                             CHANNEL_TILES[-1], taps), mode)
        tile, mode = None, "off"
    if mode != "interpret":
        announce_conv(batch * seq_len // shards(), e, tile, mode, silu,
                      bias)
    return mode, tile


def short_conv(bcu, w, interpret=None):
    """bcu [B, T, 3E] (B, C, u side by side), w [E, K] -> [B, T, E] in
    bcu's dtype, causal within each of the B sequences.  Differentiable
    in both.  The kernels where ``ops/mode.py`` allows them and the
    shapes tile, else ``short_conv_ref``."""
    batch, seq_len, e3 = bcu.shape
    e, taps = w.shape
    mode, tile = _plan("short_conv", batch, seq_len, e, taps, interpret)
    if mode == "off":
        return checkpoint_name(short_conv_ref(bcu, w), KEEP_OUT)

    def op(bcu, w):
        rows = bcu.shape[0] * seq_len
        out = _sconv(bcu.reshape(rows, e3), w, seq_len,
                     mode == "interpret", *tile)
        return out.reshape(-1, seq_len, e)

    return checkpoint_name(per_batch_shard(op, (bcu,), (w,)), KEEP_OUT)


def conv_silu(x, w, interpret=None, bias=None):
    """x [B, T, E], w [E, K] -> ``silu(conv(x))`` [B, T, E] in x's
    dtype, the taps causal within each of the B sequences; with ``bias``
    [E], ``silu(conv(x) + bias)``.  Differentiable in all.  The kernels
    where ``ops/mode.py`` allows them and the shapes tile, else
    ``conv_silu_ref``."""
    batch, seq_len, e = x.shape
    mode, tile = _plan("conv_silu", batch, seq_len, e, w.shape[1],
                       interpret, silu=True, bias=bias is not None)
    if mode == "off":
        return conv_silu_ref(x, w, bias)

    def op(x, w, *bias):
        call = _sconv_silu_biased if bias else _sconv_silu
        out = call(x.reshape(-1, e), w, *bias, seq_len,
                   mode == "interpret", *tile)
        return out.reshape(-1, seq_len, e)

    return per_batch_shard(op, (x,), (w,) + (() if bias is None
                                             else (bias,)))
