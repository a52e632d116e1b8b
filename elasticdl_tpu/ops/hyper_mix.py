"""Manifold-constrained hyper-connections (Pallas TPU): a residual
stream ``n`` wide, read and written through learned maps.

A token's stream is ``X`` in R^{n x C}, held as ``[B, T, n * C]`` (stream
``i`` is columns ``i C .. (i + 1) C``: a ``[.., n, C]`` array's second
minor dimension of 4 would be tiled to 16 sublanes in HBM, four times
the bytes).  A sublayer ``F`` owns ``phi`` [n C, n + n + n^2], three
scalars ``alpha`` and a bias ``[n + n + n^2]`` (arXiv:2512.24880):

    x~ = vec(X) / rms(vec(X));  [p, q, r] = x~ phi
    H_pre  = sigmoid(alpha_pre p + b_pre)                      [n]
    H_post = 2 sigmoid(alpha_post q + b_post)                  [n]
    H_res  = SK(exp(clamp(alpha_res mat(r) + b_res)))          [n, n]
    u = sum_i H_pre[i] X[i];  y = F(norm(u))
    X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y

``SK``: ``iters`` rounds of rows over their sums, then columns over
their sums (``+ sk_eps``), float32.  ``pre(X, ..) -> (u, X, maps, err)``
and ``post(X, y, maps) -> X'`` are the two halves a block writes round
its sublayer; ``post_pre`` is a sublayer's ``post`` and the next
sublayer's ``pre`` of what it wrote as one; ``narrow`` is ``pre`` with
the first map alone (what a model reads the stream through after its
last layer).

Bound by memory: the stream is 4 C values a token where every other
operand of a block is C.  The kernels make the fewest passes of it
there are.  At a layer's edge, where a write and the next read lie on
two sides of a checkpoint: ``hc_pre_fwd`` reads X once (the logits'
matmul on the MXU from the same block in VMEM, ``alpha`` folded into
``phi`` outside, the normalization a float32 scale of the product's
rows), ``hc_post_fwd`` reads X and writes X'; three passes a sublayer.
Backward: ``hc_post_bwd`` reads X and dX', writes dX; ``hc_pre_bwd``
reads X and that dX and writes their sum with its own terms (``pre``
hands X through, so that the stream's two readers' cotangents meet in
the kernel and not in a pass of XLA's), and ``phi``'s gradient is one
matmul of XLA's that reads X once more: seven.  Inside a layer, between
its two sublayers (``post_pre``): ``hc_post_pre_fwd`` reads X, writes
X' and reads the block it has just stored, still in VMEM, for the next
sublayer's u' and logits: two passes where the pair made three;
``hc_pre_post_bwd`` reads X', its cotangent from the next write and X,
keeps the summed cotangent of X' in VMEM (rounded to the stream's dtype
where ``hc_pre_bwd`` rounds it for HBM) and writes dX: four where the
pair made six; with ``phi``'s matmul seven passes for the write and the
read together, which apart are ten.  The fused calls' results are the
separate calls' bit for bit.

The maps between (24 values a token: the sigmoids, the Sinkhorn rounds)
are one call each way, ``hyper_maps``: ``hc_maps_fwd`` reads a tile of
the logits, turns it so that the tokens ride the lanes and each of the
24 live columns is a dense ``[tile / 128, 128]`` plane, runs the rounds
on the 16 planes of ``H_res`` in registers (a sum over a row or a
column of ``H_res`` is three adds of whole planes, no lane or sublane
is crossed) and turns the 128-lane tile ``post`` takes back out;
``hc_maps_bwd`` reads the logits and the maps' cotangent, runs the
rounds again keeping each half round's planes in VMEM (40 x 20 planes,
3.3 MB at a tile of 1,024), walks them back and writes the logits'
cotangent.  Nothing of the rounds reaches HBM, and the compiled step
holds no loop for them.  ``maps_of``, the same in ``jax.numpy`` on
``[n, n, rows]`` with the rounds one ``lax.scan`` that JAX
differentiates, is the reference, and what runs wherever the op cannot.

The calls carry their names into the compiled program and a device
trace: ``hc_pre_fwd``, ``hc_post_fwd``, ``hc_pre_bwd``, ``hc_post_bwd``
(``benchmark/kernels/hyper_mix.py``), the pair's ``hc_post_pre_fwd``,
``hc_pre_post_bwd`` (``benchmark/layers/kernel.hyper_fused_share.py``),
and the maps' ``hc_maps_fwd``, ``hc_maps_bwd``.  Reference: ``pre_ref``,
``maps_of`` and ``post_ref``, plain ``jax.numpy`` differentiated by JAX,
which is also what runs wherever ``ops/mode.py`` answers ``off`` or the
shape does not tile (it says so: ``announce_fallback``).
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

# ``checkpoint_name``s of what a second forward need not make again
# (models/remat_keep.py): a sublayer's mixed input and its logits.
KEEP_U, KEEP_Z = "hc_u", "hc_z"

# The maps' columns as the kernels take them: one 128-lane tile.
LANES = 128
ROW_TILES = (128, 64, 32, 16)
# The maps' tokens a block: whole 128-lane rows of a plane, 8 of them
# a full vreg.
MAPS_TILES = (1024, 512, 256, 128)
VMEM_LIMIT = 64 * 1024 * 1024
CLAMP = (-30.0, 30.0)


def columns(n):
    """How many logits a token has: H_pre, H_post, H_res."""
    return 2 * n + n * n


def sinkhorn(m, iters, eps):
    """m [n, n, rows], positive -> the same after ``iters`` rounds of
    each row (axis 1) over its sum, then each column (axis 0) over its
    sum (``+ eps``).  The token axis is the minor one: a round is two
    sums over four planes and two divisions, whatever n.  The
    reference's form (``hyper_maps`` runs the rounds in one kernel): a
    loop of the program's and not of Python's, because unrolled the
    benchmark's cell compiled 5,000 small fusions more into an
    executable past the compile cache's 192 MiB an entry."""
    def one_round(m, _):
        m = m / (m.sum(axis=1, keepdims=True) + eps)
        return m / (m.sum(axis=0, keepdims=True) + eps), None

    return lax.scan(one_round, m, None, length=iters)[0]


def maps_of(z, bias, n, iters, eps, sinkhorn_dtype=jnp.float32):
    """(maps [.., LANES] float32: H_post in columns n .. 2n, H_res row
    major in 2n .. 2n + n^2, zeros elsewhere; err: the largest ``|row or
    column sum - 1|`` of any token's H_res, no gradient) of the logits
    ``z`` [.., >= 2n + n^2] float32 (``alpha`` already in them) and
    ``bias``.  ``sinkhorn_dtype``: what the rounds run in (float32; a
    precision tool shows what a lower one costs)."""
    z = z[..., :columns(n)] + bias
    lead = z.shape[:-1]
    h_post = 2.0 * jax.nn.sigmoid(z[..., n:2 * n])
    logits = jnp.clip(z[..., 2 * n:], *CLAMP)
    h_res = sinkhorn(
        jnp.exp(jnp.moveaxis(logits, -1, 0).reshape(n, n, -1)).astype(
            sinkhorn_dtype), iters, eps).astype(jnp.float32)
    err = lax.stop_gradient(jnp.maximum(
        jnp.max(jnp.abs(h_res.sum(axis=1) - 1.0)),
        jnp.max(jnp.abs(h_res.sum(axis=0) - 1.0))))
    flat = jnp.moveaxis(h_res.reshape(n * n, *lead), 0, -1)
    maps = jnp.concatenate([jnp.zeros_like(h_post), h_post, flat], axis=-1)
    pad = [(0, 0)] * (maps.ndim - 1) + [(0, LANES - maps.shape[-1])]
    return jnp.pad(maps, pad), err


def _streams(x, n):
    """The n streams of x [.., n C], float32."""
    c = x.shape[-1] // n
    return [x[..., i * c:(i + 1) * c].astype(jnp.float32) for i in range(n)]


def logits_ref(x, phi, eps):
    """``(x / rms(x)) phi`` [.., M] float32 as the kernel rounds it: the
    product of x and phi in x's dtype, summed in float32, its rows
    scaled by the float32 ``1 / rms``."""
    xf = x.astype(jnp.float32)
    inv = lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return jnp.einsum("...k,km->...m", x, phi.astype(x.dtype),
                      preferred_element_type=jnp.float32) * inv


def pre_ref(x, phi, bias, n, eps):
    """(u [.., C] in x's dtype, z [.., M] float32) of x [.., n C], phi
    [n C, M] (``alpha`` folded in) and bias [M] in plain ``jax.numpy``:
    the logits and the streams mixed by ``H_pre``."""
    z = logits_ref(x, phi, eps)
    h_pre = jax.nn.sigmoid(z[..., :n] + bias[:n])
    u = sum(h_pre[..., i:i + 1] * xi for i, xi in enumerate(_streams(x, n)))
    return u.astype(x.dtype), z


def post_ref(x, y, maps, n):
    """X' [.., n C] in x's dtype of x, y [.., C] and maps [.., LANES]."""
    xs, yf = _streams(x, n), y.astype(jnp.float32)
    out = []
    for i in range(n):
        acc = maps[..., n + i, None] * yf
        for j in range(n):
            acc = acc + maps[..., 2 * n + i * n + j, None] * xs[j]
        out.append(acc.astype(x.dtype))
    return jnp.concatenate(out, axis=-1)


# -- kernels -----------------------------------------------------------------


def _column(a, i):
    """Column i of a [rows, LANES] value, [rows, 1]."""
    return a[:, i:i + 1]


def _inv_rms(x_ref, n, c, eps):
    ss = None
    for i in range(n):
        xi = x_ref[:, i * c:(i + 1) * c].astype(jnp.float32)
        part = jnp.sum(xi * xi, axis=1, keepdims=True)
        ss = part if ss is None else ss + part
    return lax.rsqrt(ss / (n * c) + eps)


def _pre_fwd_kernel(x_ref, phi_ref, bias_ref, u_ref, z_ref, *, n, c, eps):
    z = jnp.dot(x_ref[...], phi_ref[...],
                preferred_element_type=jnp.float32,
                precision=(lax.Precision.HIGHEST
                           if x_ref.dtype == jnp.float32 else None))
    z = z * _inv_rms(x_ref, n, c, eps)
    z_ref[...] = z
    h = jax.nn.sigmoid(z + bias_ref[...])
    u = None
    for i in range(n):
        part = _column(h, i) * x_ref[:, i * c:(i + 1) * c].astype(
            jnp.float32)
        u = part if u is None else u + part
    u_ref[...] = u.astype(u_ref.dtype)


def _pre_bwd_kernel(x_ref, phi_ref, bias_ref, z_ref, du_ref, dz_ref,
                    dx_in_ref, dx_ref, ds_ref, dzs_ref, *, n, c, eps):
    """dx = dx_in + h_i du + (ds phi^T) + x coef; ds = dz inv (phi's
    gradient is x^T ds), dzs = dz with the pre columns' part (the
    bias's gradient is its column sums)."""
    inv = _inv_rms(x_ref, n, c, eps)
    z = z_ref[...]
    h = jax.nn.sigmoid(z + bias_ref[...])
    du = du_ref[...].astype(jnp.float32)
    lane = lax.broadcasted_iota(jnp.int32, z.shape, 1)
    dh = jnp.zeros_like(z)
    for i in range(n):
        xi = x_ref[:, i * c:(i + 1) * c].astype(jnp.float32)
        dh = dh + jnp.where(lane == i,
                            jnp.sum(du * xi, axis=1, keepdims=True), 0.0)
    dz = dz_ref[...] + jnp.where(lane < n, dh * h * (1.0 - h), 0.0)
    dzs_ref[...] = dz
    ds = dz * inv
    ds_ref[...] = ds
    # z = s inv, inv = (mean(x^2) + eps)^-1/2: the norm's part of dx
    coef = -inv * inv * jnp.sum(dz * z, axis=1, keepdims=True) / (n * c)
    low = ds.astype(x_ref.dtype)
    for i in range(n):
        xi = x_ref[:, i * c:(i + 1) * c].astype(jnp.float32)
        through = lax.dot_general(
            low, phi_ref[i * c:(i + 1) * c, :], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=(lax.Precision.HIGHEST
                       if x_ref.dtype == jnp.float32 else None))
        dx = (dx_in_ref[:, i * c:(i + 1) * c].astype(jnp.float32)
              + _column(h, i) * du + through + coef * xi)
        dx_ref[:, i * c:(i + 1) * c] = dx.astype(dx_ref.dtype)


def _post_fwd_kernel(x_ref, y_ref, maps_ref, out_ref, *, n, c):
    maps = maps_ref[...]
    y = y_ref[...].astype(jnp.float32)
    xs = [x_ref[:, j * c:(j + 1) * c].astype(jnp.float32) for j in range(n)]
    for i in range(n):
        acc = _column(maps, n + i) * y
        for j in range(n):
            acc = acc + _column(maps, 2 * n + i * n + j) * xs[j]
        out_ref[:, i * c:(i + 1) * c] = acc.astype(out_ref.dtype)


def _post_bwd_kernel(x_ref, y_ref, maps_ref, dout_ref, dx_ref, dy_ref,
                     dmaps_ref, *, n, c):
    maps = maps_ref[...]
    y = y_ref[...].astype(jnp.float32)
    lane = lax.broadcasted_iota(jnp.int32, maps.shape, 1)
    dmaps = jnp.zeros_like(maps)
    dy = None
    dxs = [None] * n
    for i in range(n):
        dout = dout_ref[:, i * c:(i + 1) * c].astype(jnp.float32)
        part = _column(maps, n + i) * dout
        dy = part if dy is None else dy + part
        dmaps = dmaps + jnp.where(
            lane == n + i, jnp.sum(dout * y, axis=1, keepdims=True), 0.0)
        for j in range(n):
            xj = x_ref[:, j * c:(j + 1) * c].astype(jnp.float32)
            k = 2 * n + i * n + j
            dmaps = dmaps + jnp.where(
                lane == k, jnp.sum(dout * xj, axis=1, keepdims=True), 0.0)
            part = _column(maps, k) * dout
            dxs[j] = part if dxs[j] is None else dxs[j] + part
    for j in range(n):
        dx_ref[:, j * c:(j + 1) * c] = dxs[j].astype(dx_ref.dtype)
    dy_ref[...] = dy.astype(dy_ref.dtype)
    dmaps_ref[...] = dmaps


def _post_pre_fwd_kernel(x_ref, y_ref, maps_ref, phi_ref, bias_ref, out_ref,
                         u_ref, z_ref, *, n, c, eps):
    """A sublayer's write, then the next sublayer's read of the block
    just written: ``out_ref`` holds X' in the stream's dtype, so u' and
    z' are what ``hc_pre_fwd`` makes of the X' that reaches HBM."""
    _post_fwd_kernel(x_ref, y_ref, maps_ref, out_ref, n=n, c=c)
    _pre_fwd_kernel(out_ref, phi_ref, bias_ref, u_ref, z_ref, n=n, c=c,
                    eps=eps)


def _pre_post_bwd_kernel(out_ref, phi_ref, bias_ref, z_ref, du_ref, dz_ref,
                         dout_in_ref, x_ref, y_ref, maps_ref, dx_ref, dy_ref,
                         dmaps_ref, ds_ref, dzs_ref, dout, *, n, c, eps):
    """The pair's way back: the read's backward leaves X's summed
    cotangent in ``dout`` (VMEM, the stream's dtype: rounded where
    ``hc_pre_bwd`` rounds it on its way to HBM), the write's backward
    takes it from there."""
    _pre_bwd_kernel(out_ref, phi_ref, bias_ref, z_ref, du_ref, dz_ref,
                    dout_in_ref, dout, ds_ref, dzs_ref, n=n, c=c, eps=eps)
    _post_bwd_kernel(x_ref, y_ref, maps_ref, dout, dx_ref, dy_ref, dmaps_ref,
                     n=n, c=c)


def _call(kernel, name, tm, interpret, ins, outs, whole=(), scratch=()):
    """One call over row blocks of ``tm``: ``ins`` and ``outs`` are
    [rows, width] (arrays, ShapeDtypeStructs), blocked by rows (an
    output of fewer rows, a block's own few, evenly); the positions
    ``whole`` of ``ins`` are given to every block entire; ``scratch``:
    the (shape, dtype) of what the kernel takes last, in VMEM."""
    grid = outs[0].shape[0] // tm
    block = lambda a: pl.BlockSpec((a.shape[0] // grid, a.shape[1]),
                                   lambda r: (r, 0))
    entire = lambda a: pl.BlockSpec(a.shape, lambda r: (0, 0))
    return pl.pallas_call(
        kernel, out_shape=outs, grid=(grid,),
        in_specs=[entire(a) if i in whole else block(a)
                  for i, a in enumerate(ins)],
        out_specs=[block(a) for a in outs],
        scratch_shapes=[pltpu.VMEM(*held) for held in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        # The HLO instruction's name, so the trace's.
        name=name,
    )(*ins)


def _shape(rows, width, dtype):
    return jax.ShapeDtypeStruct((rows, width), dtype)


# Every call's forward and backward is jitted, so that a stack's
# sublayers and their second forward under remat, which trace them at the
# same shapes, trace and lower a kernel once (a worker lowers its step at
# every start, and the maps' kernels unroll 20 rounds).
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _pre(x, phi, bias, n, eps, tm, interpret):
    return _pre_fwd(x, phi, bias, n, eps, tm, interpret)[0]


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _pre_fwd(x, phi, bias, n, eps, tm, interpret):
    rows, width = x.shape
    c = width // n
    u, z = _call(
        functools.partial(_pre_fwd_kernel, n=n, c=c, eps=eps),
        "hc_pre_fwd", tm, interpret, (x, phi, bias),
        (_shape(rows, c, x.dtype), _shape(rows, LANES, jnp.float32)),
        whole=(1, 2))
    return (u, z, x), (x, phi, bias, z)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _pre_bwd(n, eps, tm, interpret, residuals, cotangents):
    x, phi, bias, z = residuals
    du, dz, dx_in = cotangents
    rows, width = x.shape
    dx, ds, dzs = _call(
        functools.partial(_pre_bwd_kernel, n=n, c=width // n, eps=eps),
        "hc_pre_bwd", tm, interpret, (x, phi, bias, z, du, dz, dx_in),
        (_shape(rows, width, x.dtype), _shape(rows, LANES, jnp.float32),
         _shape(rows, LANES, jnp.float32)), whole=(1, 2))
    return dx, *_read_grads(x, phi, ds, dzs, n)


def _read_grads(x, phi, ds, dzs, n):
    """(phi's gradient, the bias's) of a read of x: ``x^T ds``, one
    matmul of XLA's, and the pre columns' sums of ``dzs``."""
    dphi = jnp.einsum("rk,rm->km", x, ds.astype(x.dtype),
                      preferred_element_type=jnp.float32)
    lane = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    dbias = jnp.where(lane < n, dzs.sum(axis=0, keepdims=True), 0.0)
    return dphi.astype(phi.dtype), dbias


_pre.defvjp(_pre_fwd, _pre_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _post(x, y, maps, n, tm, interpret):
    return _post_fwd(x, y, maps, n, tm, interpret)[0]


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _post_fwd(x, y, maps, n, tm, interpret):
    out = _call(
        functools.partial(_post_fwd_kernel, n=n, c=y.shape[1]),
        "hc_post_fwd", tm, interpret, (x, y, maps),
        (_shape(*x.shape, x.dtype),))[0]
    return out, (x, y, maps)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _post_bwd(n, tm, interpret, residuals, dout):
    x, y, maps = residuals
    return tuple(_call(
        functools.partial(_post_bwd_kernel, n=n, c=y.shape[1]),
        "hc_post_bwd", tm, interpret, (x, y, maps, dout),
        (_shape(*x.shape, x.dtype), _shape(*y.shape, y.dtype),
         _shape(*maps.shape, jnp.float32))))


_post.defvjp(_post_fwd, _post_bwd)


# A write and the next sublayer's read of what it wrote, one call each
# way: X' goes to HBM once and is not read back, its cotangent not at
# all.  ``tiles``: the forward's rows a block, the backward's.
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _post_pre(x, y, maps, phi, bias, n, eps, tiles, interpret):
    return _post_pre_fwd(x, y, maps, phi, bias, n, eps, tiles, interpret)[0]


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _post_pre_fwd(x, y, maps, phi, bias, n, eps, tiles, interpret):
    rows, width = x.shape
    c = width // n
    out, u, z = _call(
        functools.partial(_post_pre_fwd_kernel, n=n, c=c, eps=eps),
        "hc_post_pre_fwd", tiles[0], interpret, (x, y, maps, phi, bias),
        (_shape(rows, width, x.dtype), _shape(rows, c, x.dtype),
         _shape(rows, LANES, jnp.float32)), whole=(3, 4))
    return (out, u, z), (x, y, maps, out, phi, bias, z)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _post_pre_bwd(n, eps, tiles, interpret, residuals, cotangents):
    x, y, maps, out, phi, bias, z = residuals
    dout, du, dz = cotangents
    rows, width = x.shape
    dx, dy, dmaps, ds, dzs = _call(
        functools.partial(_pre_post_bwd_kernel, n=n, c=width // n, eps=eps),
        "hc_pre_post_bwd", tiles[1], interpret,
        (out, phi, bias, z, du, dz, dout, x, y, maps),
        (_shape(rows, width, x.dtype), _shape(*y.shape, y.dtype))
        + (_shape(rows, LANES, jnp.float32),) * 3, whole=(1, 2),
        scratch=(((tiles[1], width), x.dtype),))
    return dx, dy, dmaps, *_read_grads(out, phi, ds, dzs, n)


_post_pre.defvjp(_post_pre_fwd, _post_pre_bwd)


# -- the maps' kernels --------------------------------------------------------
#
# A block of ``tile`` tokens is turned 128 by 128 into a VMEM scratch
# ``[tile, LANES]`` whose row ``c * LANES + k`` holds column k of the
# block's c-th 128 tokens; column k's plane, ``[tile / 128, 128]`` with
# every lane and (at a tile of 1,024) every sublane a token, is that
# scratch read at a stride of 128 rows.  The way back is the same.


def _planes_in(ref, bias_ref, turn, first, count):
    """Columns ``first .. first + count`` of the block ``ref`` (``+
    bias``), a dense plane each."""
    s = turn.shape[0] // LANES
    for c in range(s):
        rows = slice(c * LANES, (c + 1) * LANES)
        block = ref[rows, :]
        turn[rows, :] = (block if bias_ref is None
                         else block + bias_ref[...]).T
    return [turn[pl.ds(first + k, s, stride=LANES), :] for k in range(count)]


def _planes_out(planes, first, turn, ref):
    """The block ``ref``: ``planes`` in columns ``first ..``, zeros in
    the others."""
    s = turn.shape[0] // LANES
    for k, plane in enumerate(planes):
        turn[pl.ds(first + k, s, stride=LANES), :] = plane
    lane = lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
    live = (lane >= first) & (lane < first + len(planes))
    for c in range(s):
        rows = slice(c * LANES, (c + 1) * LANES)
        ref[rows, :] = jnp.where(live, turn[rows, :].T, 0.0)


def _halves(n, iters):
    """The rounds' half rounds in order, each the groups of H_res's
    planes (row major) it sums over: the rows, then the columns."""
    by_row = [[i * n + j for j in range(n)] for i in range(n)]
    by_column = [[i * n + j for i in range(n)] for j in range(n)]
    return [by_row, by_column] * iters


def _total(planes):
    return functools.reduce(lambda a, b: a + b, planes)


def _normalized(m, groups, eps):
    """Half a round: each group's planes over their sum ``+ eps``, as
    one reciprocal a group and a product a plane -> (planes, the
    groups' reciprocals)."""
    out, recips = list(m), []
    for group in groups:
        recip = 1.0 / (_total([m[k] for k in group]) + eps)
        recips.append(recip)
        for k in group:
            out[k] = m[k] * recip
    return out, recips


def _maps_fwd_kernel(z_ref, bias_ref, maps_ref, err_ref, turn, *, n, iters,
                     eps):
    z = _planes_in(z_ref, bias_ref, turn, n, n + n * n)
    h_post = [2.0 * jax.nn.sigmoid(p) for p in z[:n]]
    m = [jnp.exp(jnp.clip(p, *CLAMP)) for p in z[n:]]
    halves = _halves(n, iters)
    for groups in halves:
        m = _normalized(m, groups, eps)[0]
    off = functools.reduce(jnp.maximum, [
        jnp.abs(_total([m[k] for k in group]) - 1.0)
        for group in halves[0] + halves[1]])
    err_ref[...] = jnp.broadcast_to(jnp.max(off, axis=0, keepdims=True),
                                    err_ref.shape)
    _planes_out(h_post + m, n, turn, maps_ref)


def _maps_bwd_kernel(z_ref, bias_ref, dmaps_ref, dz_ref, dsum_ref, turn,
                     kept, kept_recips, *, n, iters, eps):
    """With y = m r, r = 1 / (sum of the group's m + eps) a half round:
    dm = r (dy - sum of the group's dy y), so the walk back reads each
    half round's y and r, which the rounds run again here leave in
    ``kept`` / ``kept_recips``.  A logit on a clamp passes nothing on
    (JAX halves it there)."""
    z = _planes_in(z_ref, bias_ref, turn, n, n + n * n)
    halves = _halves(n, iters)
    first = m = [jnp.exp(jnp.clip(p, *CLAMP)) for p in z[n:]]
    for t, groups in enumerate(halves):
        m, recips = _normalized(m, groups, eps)
        for k, plane in enumerate(m):
            kept[t * n * n + k] = plane
        for g, recip in enumerate(recips):
            kept_recips[t * n + g] = recip
    d = _planes_in(dmaps_ref, None, turn, n, n + n * n)
    d_post = []
    for p, dp in zip(z[:n], d[:n]):
        sig = jax.nn.sigmoid(p)
        d_post.append(dp * (2.0 * sig * (1.0 - sig)))
    dy = d[n:]
    for t in reversed(range(len(halves))):
        dm = list(dy)
        for g, group in enumerate(halves[t]):
            inner = _total([dy[k] * kept[t * n * n + k] for k in group])
            for k in group:
                dm[k] = kept_recips[t * n + g] * (dy[k] - inner)
        dy = dm
    d_res = [jnp.where((p > CLAMP[0]) & (p < CLAMP[1]), g * e, 0.0)
             for p, g, e in zip(z[n:], dy, first)]
    _planes_out(d_post + d_res, n, turn, dz_ref)
    # the bias's gradient is dz's column sums: this block's, 8 rows of
    # partial sums
    dsum_ref[...] = _total([dz_ref[r:r + 8, :]
                            for r in range(0, dz_ref.shape[0], 8)])


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _maps(z, bias, n, iters, eps, tile, interpret):
    return _maps_fwd(z, bias, n, iters, eps, tile, interpret)[0]


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _maps_fwd(z, bias, n, iters, eps, tile, interpret):
    """(maps [rows, LANES], the blocks' largest errors [8 a block,
    LANES]) of z [rows, LANES] and bias [1, LANES]."""
    rows = z.shape[0]
    out = _call(
        functools.partial(_maps_fwd_kernel, n=n, iters=iters, eps=eps),
        "hc_maps_fwd", tile, interpret, (z, bias),
        (_shape(rows, LANES, jnp.float32),
         _shape(rows // tile * 8, LANES, jnp.float32)),
        whole=(1,), scratch=(((tile, LANES), jnp.float32),))
    return tuple(out), (z, bias)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _maps_bwd(n, iters, eps, tile, interpret, residuals, cotangents):
    z, bias = residuals
    rows, halves = z.shape[0], 2 * iters
    dz, dsum = _call(
        functools.partial(_maps_bwd_kernel, n=n, iters=iters, eps=eps),
        "hc_maps_bwd", tile, interpret, (z, bias, cotangents[0]),
        (_shape(rows, LANES, jnp.float32),
         _shape(rows // tile * 8, LANES, jnp.float32)),
        whole=(1,), scratch=[(shape, jnp.float32) for shape in (
            (tile, LANES), (halves * n * n, tile // LANES, LANES),
            (halves * n, tile // LANES, LANES))])
    return dz, dsum.sum(axis=0, keepdims=True)


_maps.defvjp(_maps_fwd, _maps_bwd)


# -- the op ------------------------------------------------------------------


# What ran, as the ``hyper residual:`` and ``hyper pair:`` lines say it.
FORMS = {"tpu": "kernel", "interpret": "interpreter", "off": "reference"}


@functools.lru_cache(maxsize=None)
def announce_hyper(rows, n, c, nbytes, tile, mode, why):
    """Once per compiled shape, by the logger ``announce_tiles`` uses:
    the stream one shard of the data axis mixes, and by what."""
    flash_attention.logger.info(
        "hyper residual: tokens=%d streams=%d width=%d stream_bytes=%d "
        "tile=%s %s%s", rows, n, c, nbytes, tile or "-", FORMS[mode],
        " (%s)" % why if why else "")


def hyper_mode(rows, n, c, interpret=None):
    """(mode, row tile, why not the kernel) for a stream of ``rows``
    tokens a shard, n x c wide."""
    mode = resolve(interpret)
    if mode == "off":
        return mode, None, ""
    tile = next((tm for tm in ROW_TILES if rows % tm == 0), None)
    if tile is None or c % LANES or columns(n) > LANES:
        return "off", None, "rows %% %d, width %% %d or %d > %d logits" % (
            ROW_TILES[-1], LANES, columns(n), LANES)
    return mode, tile, ""


def _plan(x, n, interpret):
    batch, seq_len, width = x.shape
    rows = batch * seq_len // shards()
    mode, tile, why = hyper_mode(rows, n, width // n, interpret)
    if why:
        flash_attention.announce_fallback("hyper_mix", x.shape, why,
                                          resolve(interpret))
    if mode != "interpret":
        announce_hyper(rows, n, width // n, rows * width * x.dtype.itemsize,
                       tile, mode, why)
    return mode, tile


def _folded(phi, alpha, n):
    """phi [n C, M] with each map's ``alpha`` in its columns, float32."""
    sizes = (n, n, n * n)[:alpha.shape[0]]
    return phi.astype(jnp.float32) * jnp.concatenate(
        [jnp.broadcast_to(a, (size,))
         for a, size in zip(alpha.astype(jnp.float32), sizes)])


def _tiled(phi, bias, dtype):
    """phi and the bias as the kernels take them: the logits' columns
    a 128-lane tile, phi in the stream's dtype."""
    pad = LANES - phi.shape[1]
    return (jnp.pad(phi, ((0, 0), (0, pad))).astype(dtype),
            jnp.pad(bias, (0, pad))[None])


def _logits(x, phi, alpha, bias, n, eps, interpret):
    """(u [B, T, C], z [B, T, >= M] float32, x handed through) of the
    stream x [B, T, n C]: ``pre_ref``, by the kernel where it runs."""
    mode, tile = _plan(x, n, interpret)
    phi = _folded(phi, alpha, n)
    bias = bias.astype(jnp.float32)
    if mode == "off":
        u, z = pre_ref(x, phi, bias, n, eps)
        return u, z, x
    phi, bias = _tiled(phi, bias, x.dtype)

    def op(x, phi, bias):
        b, t, width = x.shape
        u, z, through = _pre(x.reshape(b * t, width), phi, bias, n, eps,
                             tile, mode == "interpret")
        return (u.reshape(b, t, -1), z.reshape(b, t, LANES),
                through.reshape(b, t, width))

    return per_batch_shard(op, (x,), (phi, bias))


@functools.lru_cache(maxsize=None)
def announce_maps(rows, n, iters, tile, mode):
    """Once per compiled shape, beside ``announce_hyper``: the maps one
    shard of the data axis makes, and by what."""
    flash_attention.logger.info(
        "hyper maps: rows=%d n=%d iters=%d tile=%s form=%s", rows, n, iters,
        tile or "-", {"tpu": "kernel", "interpret": "interpreter",
                      "off": "jnp"}[mode])


def maps_mode(rows, n, width, interpret=None):
    """(mode, tokens a block, why not the kernel) for the maps of
    ``rows`` tokens a shard whose logits are ``width`` wide."""
    mode = resolve(interpret)
    if mode == "off":
        return mode, None, ""
    tile = next((t for t in MAPS_TILES if rows % t == 0), None)
    if tile is None or width != LANES or columns(n) > LANES:
        return "off", None, "maps: rows %% %d, logits %d wide or %d > %d" % (
            MAPS_TILES[-1], width, columns(n), LANES)
    return mode, tile, ""


def hyper_maps(z, bias, n, iters, eps, interpret=None):
    """``maps_of(z, bias, n, iters, eps)`` of the logits z [B, T, >= 2n
    + n^2] float32, one call forward and one backward where a kernel
    may run; ``maps_of`` itself where none may, or the logits are not
    ``pre``'s 128-lane tile."""
    batch, seq_len, width = z.shape
    rows = batch * seq_len // shards()
    mode, tile, why = maps_mode(rows, n, width, interpret)
    if why:
        flash_attention.announce_fallback("hyper_mix", z.shape, why,
                                          resolve(interpret))
    announce_maps(rows, n, iters, tile, mode)
    if mode == "off":
        return maps_of(z, bias, n, iters, eps)

    def op(z, bias):
        b, t, _ = z.shape
        maps, off = _maps(z.reshape(b * t, LANES), bias, n, iters, eps, tile,
                          mode == "interpret")
        return maps.reshape(b, t, LANES), off

    maps, off = per_batch_shard(
        op, (z,), (jnp.pad(bias, (0, LANES - bias.shape[0]))[None],))
    return maps, lax.stop_gradient(off.max())


def pre(x, phi, alpha, bias, streams, iters, eps, sk_eps, interpret=None,
        sinkhorn_dtype=jnp.float32):
    """A sublayer's read of the stream x [B, T, n C] -> (u [B, T, C]:
    the streams mixed by H_pre; x handed through: what ``post`` takes;
    maps [B, T, LANES] float32 and err: ``hyper_maps``, or ``maps_of``
    in a ``sinkhorn_dtype`` that is not float32).  phi [n C, 2n + n^2],
    alpha [3], bias [2n + n^2], float32."""
    u, z, x = _logits(x, phi, alpha, bias, streams, eps, interpret)
    u, z = checkpoint_name(u, KEEP_U), checkpoint_name(z, KEEP_Z)
    bias = bias.astype(jnp.float32)
    if sinkhorn_dtype != jnp.float32:
        return u, x, *maps_of(z, bias, streams, iters, sk_eps, sinkhorn_dtype)
    return u, x, *hyper_maps(z, bias, streams, iters, sk_eps, interpret)


@functools.lru_cache(maxsize=None)
def announce_pair(rows, n, c, tiles, mode):
    """Once per compiled shape, beside ``announce_hyper``: a write and
    the read behind it as one call each way, or (``reference``) apart
    by ``post_ref`` and ``pre_ref``."""
    flash_attention.logger.info(
        "hyper pair: tokens=%d streams=%d width=%d tile=%s %s", rows, n, c,
        "/".join(map(str, tiles)) if tiles else "-", FORMS[mode])


def back_tile(rows, n, c, itemsize, tile):
    """The rows a block of the fused pair's backward where
    ``hyper_mode`` gave ``tile``.  It holds four wide blocks (X', its
    cotangent in, X, its cotangent out) and three narrow ones twice
    each, the summed cotangent between its halves once, its float32 sums
    (two wide blocks' worth) and ``phi`` twice: the largest of
    ``ROW_TILES`` under ``tile`` that leaves it inside ``VMEM_LIMIT``
    (61 MB of 64 at 128 rows of 4 x 3,584 bfloat16, which the TPU's
    compiler takes), the smallest there is where none does."""
    def held(tm):
        wide = tm * n * c
        return (2 * (4 * wide + 3 * tm * c) * itemsize + wide * itemsize
                + 2 * wide * 4 + 2 * n * c * LANES * itemsize
                + 12 * tm * LANES * 4)

    fits = [tm for tm in ROW_TILES
            if tm <= tile and rows % tm == 0 and held(tm) <= VMEM_LIMIT]
    return (fits or ROW_TILES[-1:])[0]


def post_pre(x, y, maps, phi, alpha, bias, streams, iters, eps, sk_eps,
             keep, interpret=None):
    """A sublayer's write and the next sublayer's read of what it
    wrote: ``pre(post(x, y, maps, ..), phi, alpha, bias, ..)`` with the
    next sublayer's phi, alpha and bias -> (u', X', maps', err'), X'
    (under the ``checkpoint_name`` ``keep``) leaving one call forward
    (``hc_post_pre_fwd``) and its cotangent none backward
    (``hc_pre_post_bwd``) where the kernels run; the two ops one after
    the other where they do not."""
    batch, seq_len, width = x.shape
    rows, c = batch * seq_len // shards(), width // streams
    mode, tile, _ = hyper_mode(rows, streams, c, interpret)
    tiles = None if mode == "off" else (
        tile, back_tile(rows, streams, c, x.dtype.itemsize, tile))
    announce_pair(rows, streams, c, tiles, mode)
    if mode == "off":
        out = checkpoint_name(post(x, y, maps, streams, interpret), keep)
        return pre(out, phi, alpha, bias, streams, iters, eps, sk_eps,
                   interpret)
    bias = bias.astype(jnp.float32)

    def op(x, y, maps, phi, bias):
        b, t, _ = x.shape
        out, u, z = _post_pre(
            x.reshape(b * t, width), y.reshape(b * t, -1),
            maps.reshape(b * t, LANES), phi, bias, streams, eps, tiles,
            mode == "interpret")
        return (out.reshape(b, t, width), u.reshape(b, t, -1),
                z.reshape(b, t, LANES))

    out, u, z = per_batch_shard(
        op, (x, y, maps),
        _tiled(_folded(phi, alpha, streams), bias, x.dtype))
    u, z = checkpoint_name(u, KEEP_U), checkpoint_name(z, KEEP_Z)
    return (u, checkpoint_name(out, keep),
            *hyper_maps(z, bias, streams, iters, sk_eps, interpret))


def narrow(x, phi, alpha, bias, streams, eps, interpret=None):
    """The stream x [B, T, n C] read through one H_pre-like map (phi
    [n C, n], alpha [1], bias [n]) -> [B, T, C]."""
    return _logits(x, phi, alpha, bias, streams, eps, interpret)[0]


def post(x, y, maps, streams, interpret=None):
    """A sublayer's write: x [B, T, n C] (``pre``'s second result), the
    sublayer's result y [B, T, C], ``pre``'s maps -> X' [B, T, n C]."""
    mode, tile, _ = hyper_mode(x.shape[0] * x.shape[1] // shards(), streams,
                               y.shape[-1], interpret)
    if mode == "off":
        return post_ref(x, y, maps, streams)

    def op(x, y, maps):
        b, t, width = x.shape
        out = _post(x.reshape(b * t, width), y.reshape(b * t, -1),
                    maps.reshape(b * t, LANES), streams, tile,
                    mode == "interpret")
        return out.reshape(b, t, width)

    return per_batch_shard(op, (x, y, maps))
