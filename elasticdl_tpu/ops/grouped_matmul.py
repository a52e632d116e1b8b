"""Grouped matmul (Pallas TPU): rows sorted by group against per-group
weights — the multiply of a dropless mixture-of-experts FFN.

``grouped_matmul(lhs [M, K], rhs [X, K, N], group_sizes [X]) -> [M, N]``:
rows ``offset[g] .. offset[g + 1]`` of ``lhs`` (``offset`` the running
sum of ``group_sizes``, which must add up to M) are multiplied by
``rhs[g]``.  With ``zero_tail`` the sizes may add up to less (a chip that
holds a share of the experts sorts their rows first): the kernels visit
no tile past the groups and write nothing there, so the op itself zeroes
those rows, of the product and of the input's gradient alike, and no
undefined value leaves it.  The algorithm is the public one of
``jax.experimental.pallas.ops.tpu.megablox``: the grid walks (column
tile, work item), a work item being one (row tile, group) pair that
share rows; which tile and which group each item is comes from the group
sizes at run time and is scalar-prefetched, so the weights' block index
follows the data.  A row tile that straddles two groups is one item for
each, computed in blocks of 128 rows of which an item takes those that
hold rows of its group and masks the other group's rows out of the
store, so the rows computed beyond the real ones are at most ``(X - 1)
* 128`` (``padded_rows``), whatever the routing.  Unlike megablox the whole
contraction is resident: one item is one ``[tm, K] @ [K, tn]``, so a
group's weights are read from HBM once per column tile and not once per
row tile; shapes whose operands do not fit the VMEM budget that way take
the reference path and say so (``announce_fallback``).

The ``custom_vjp``: the input gradient is the same kernel on the
transposed weights (``transpose_rhs``: the block is read as ``[tn, K]``
and contracted over its second axis, nothing is transposed in HBM), the
weight gradient is the transposed grouped product ``lhs[rows of g]^T @
dout[rows of g]``, accumulated over a group's row tiles in VMEM and
written as ``[X * K, N]`` (every result of these calls is 2-D: the
benchmark tells the flash kernels apart by their 3-D results).

Reference: ``grouped_matmul_ref`` (``lax.ragged_dot``), which is also
what ``grouped_matmul`` itself returns wherever ``ops/mode.py`` answers
``off`` (no TPU, the switch, a model-parallel mesh or a pipeline stage:
``kernels_off()``).  An explicit ``interpret=`` forces the kernels, in
Pallas interpret mode or compiled (tests, ``chip_check.py``).  On a
v5e, alone, at M = 131,072, 64 groups, 2048 x 1024: see PERF.md
section 5 (``tools/grouped_matmul_on_chip.py``).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.ops.flash_attention import announce_fallback
from elasticdl_tpu.ops.mode import resolve

ROW_TILE = 512
# Rows of the blocks a tile shared by two groups is computed in: the
# MXU's height.
SUB_ROWS = 128
# Scoped VMEM asked of Mosaic (a v5e core has 128 MiB; 16 MiB is only
# the default), and what the double-buffered blocks of one call may
# take of it: the rest is the matmul's own float32 result.
VMEM_LIMIT = 64 * 1024 * 1024
_VMEM_BLOCKS = 40 * 1024 * 1024

_NN = (((1,), (0,)), ((), ()))   # a @ b
_NT = (((1,), (1,)), ((), ()))   # a @ b^T
_TN = (((0,), (0,)), ((), ()))   # a^T @ b


def grouped_matmul_ref(lhs, rhs, group_sizes, transpose_rhs=False,
                       zero_tail=False):
    """The same product by ``lax.ragged_dot`` (float32 accumulation).
    With ``zero_tail`` a last group of zero weights takes the rows past
    the others: what ``ragged_dot`` and its transposes leave in rows no
    group covers is the backend's business (zeros on the CPU, and from
    one call alone on a TPU; inside a whole step there they were not:
    PERF.md section 6, PR 31)."""
    if transpose_rhs:
        rhs = rhs.swapaxes(1, 2)
    sizes = group_sizes.astype(jnp.int32)
    if zero_tail:
        sizes = jnp.concatenate([sizes, lhs.shape[0] - sizes.sum(
            keepdims=True)])
        rhs = jnp.concatenate([rhs, jnp.zeros_like(rhs[:1])])
    return lax.ragged_dot(
        lhs, rhs, sizes,
        preferred_element_type=jnp.float32).astype(lhs.dtype)


def _zero_tail(out, group_sizes):
    """``out`` with the rows past the groups' total zeroed."""
    live = lax.broadcasted_iota(jnp.int32, (out.shape[0], 1), 0) \
        < group_sizes.sum()
    return jnp.where(live, out, jnp.zeros((), out.dtype))


def row_tile(m):
    """Rows of one work item: ROW_TILE, or all of a shorter input
    rounded up to the 128 rows of an MXU pass."""
    return min(ROW_TILE, -(-m // 128) * 128)


def work_items(group_sizes, m, tm, visit_empty=False):
    """The (row tile, group) pairs a call walks, from the group sizes.

    Returns ``(offsets [X + 1], group_ids [S], tile_ids [S], count)``
    with S = m // tm + X - 1, the most there can be: a group owns the
    tiles its rows touch, so consecutive groups share at most one tile.
    Items past ``count`` repeat the last one (same blocks: no DMA) and
    the kernels skip them.  ``visit_empty`` gives an empty group one
    item (the weight gradient has to write its zeros)."""
    x = group_sizes.shape[0]
    tiles_m = m // tm
    steps = tiles_m + x - 1
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends + tm - 1) // tm - first,
                      1 if visit_empty else 0)
    count = tiles.sum()
    step = jnp.minimum(jnp.arange(steps, dtype=jnp.int32), count - 1)
    group_ids = jnp.repeat(jnp.arange(x, dtype=jnp.int32), tiles,
                           total_repeat_length=steps)[step]
    step0 = jnp.cumsum(tiles) - tiles
    tile_ids = jnp.clip(first[group_ids] + step - step0[group_ids],
                        0, tiles_m - 1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, group_ids, tile_ids.astype(jnp.int32), count


def padded_rows(group_sizes, m):
    """Rows the forward kernel computes beyond the m real ones: the
    SUB_ROWS-row blocks its groups touch, less the rows in them."""
    sub = min(SUB_ROWS, row_tile(m))
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    blocks = jnp.where(sizes > 0,
                       (ends + sub - 1) // sub - (ends - sizes) // sub, 0)
    return blocks.sum() * sub - sizes.sum()


def _lane_tiles(n):
    """The multiples of 128 up to 1024 that divide ``n``, widest first:
    the column tiles of a width of an ODD number of 128-lane tiles
    (2,688 = 21, 1,920 = 15), which no power of two above 128 divides."""
    whole = n // 128
    return sorted((128 * d for d in range(1, min(whole, 8) + 1)
                   if whole % d == 0), reverse=True)


def _cut(t):
    """``t`` over its smallest factor that leaves whole 128-lane tiles
    (a half where it has one), or 0 where it has none."""
    whole = t // 128
    if t % 128:
        return 0
    return next((t // f for f in range(2, whole + 1) if whole % f == 0), 0)


def _column_tile(tm, k, n, itemsize):
    """Widest column tile (all of n up to 1024, else a multiple of 128
    that divides it) whose double-buffered blocks fit the budget, or
    None."""
    tiles = [n] * (n <= 1024) + [t for t in (1024, 512, 256, 128)
                                 if t < n and n % t == 0]
    if n > 1024 and n % 256 == 128:
        tiles = _lane_tiles(n)
    for tn in tiles:
        if 2 * itemsize * (tm * k + k * tn + tm * tn) <= _VMEM_BLOCKS:
            return tn
    return None


def _item_rows(offsets, group_ids, tile_ids, item, tm):
    """(first row of the item's tile, first and one past the last row of
    its group)."""
    group = group_ids[item]
    return tile_ids[item] * tm, offsets[group], offsets[group + 1]


def _sub_blocks(tm, row0, start, end, body):
    """``body(slice of the tile's rows, mask of the rows in it that are
    the group's)`` for each SUB_ROWS-row block of the tile that holds
    rows of the group: a tile two groups share costs each of them its
    own blocks, not the tile."""
    sub = min(SUB_ROWS, tm)
    for s in range(tm // sub):
        lo = row0 + s * sub

        @pl.when((lo < end) & (lo + sub > start))
        def _(s=s, lo=lo):
            rows = lo + lax.broadcasted_iota(jnp.int32, (sub, 1), 0)
            body(pl.ds(s * sub, sub), (rows >= start) & (rows < end))


def _gmm_kernel(offsets, group_ids, tile_ids, count, lhs_ref, rhs_ref,
                out_ref, *, tm, dims):
    item = pl.program_id(1)
    row0, start, end = _item_rows(offsets, group_ids, tile_ids, item, tm)
    live = item < count[0]
    whole = (start <= row0) & (end >= row0 + tm)

    def product(rows):
        return lax.dot_general(lhs_ref[rows, :], rhs_ref[...], dims,
                               preferred_element_type=jnp.float32)

    @pl.when(live & whole)
    def _():
        out_ref[...] = product(slice(None)).astype(out_ref.dtype)

    @pl.when(live & jnp.logical_not(whole))
    def _():
        # Rows of the tile's other groups are theirs to write: kept.
        def block(rows, mine):
            out_ref[rows, :] = jnp.where(
                mine, product(rows).astype(out_ref.dtype), out_ref[rows, :])

        _sub_blocks(tm, row0, start, end, block)


def _gmm_call(lhs, rhs, group_sizes, transpose_rhs, interpret, tm, tn):
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    items = work_items(group_sizes, m, tm)
    steps = items[1].shape[0]
    if transpose_rhs:
        rhs_spec = pl.BlockSpec(
            (None, tn, k), lambda j, i, off, gid, tid, cnt: (gid[i], j, 0))
    else:
        rhs_spec = pl.BlockSpec(
            (None, k, tn), lambda j, i, off, gid, tid, cnt: (gid[i], 0, j))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm,
                          dims=_NT if transpose_rhs else _NN),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, steps),
            in_specs=[
                pl.BlockSpec((tm, k),
                             lambda j, i, off, gid, tid, cnt: (tid[i], 0)),
                rhs_spec,
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda j, i, off, gid, tid, cnt: (tid[i], j)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=interpret,
        # The HLO instruction's name, so the trace's: the benchmark
        # tells the calls apart by it (benchmark/kernels/).
        name="gmm_nt" if transpose_rhs else "gmm_nn",
    )(*items[:3], items[3].reshape(1), lhs, rhs)


def _tgmm_kernel(offsets, group_ids, tile_ids, count, lhs_ref, dout_ref,
                 out_ref, acc_ref, *, tm):
    item = pl.program_id(2)
    last = count[0] - 1
    live = item <= last
    group = group_ids[item]
    before = group_ids[jnp.maximum(item - 1, 0)]
    after = group_ids[jnp.minimum(item + 1, last)]
    row0, start, end = _item_rows(offsets, group_ids, tile_ids, item, tm)
    whole = (start <= row0) & (end >= row0 + tm)

    @pl.when(live & ((item == 0) | (before != group)))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live & whole)
    def _():
        acc_ref[...] += lax.dot_general(
            lhs_ref[...], dout_ref[...], _TN,
            preferred_element_type=jnp.float32)

    @pl.when(live & jnp.logical_not(whole))
    def _():     # an empty group's one item finds no block of its own
        def block(rows, mine):
            # Zeroing one side's foreign rows drops them from the sum.
            lhs = jnp.where(mine, lhs_ref[rows, :],
                            jnp.zeros((), lhs_ref.dtype))
            acc_ref[...] += lax.dot_general(
                lhs, dout_ref[rows, :], _TN,
                preferred_element_type=jnp.float32)

        _sub_blocks(tm, row0, start, end, block)

    @pl.when(live & ((item == last) | (after != group)))
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _tgmm_tiles(tm, k, n, itemsize):
    """(tk, tn) of the weight gradient's [k, n] blocks: whole if the
    float32 accumulator and the blocks fit, else halved (cut by its
    smallest factor that leaves whole 128-lane tiles: ``_cut``), k
    first."""
    tk, tn = k, n
    while True:
        blocks = (4 * tk * tn + 2 * itemsize * (tk * tn + tm * (tk + tn)))
        if blocks <= _VMEM_BLOCKS:
            return tk, tn
        if tk >= tn and _cut(tk):
            tk = _cut(tk)
        elif _cut(tn):
            tn = _cut(tn)
        else:
            return None


def _tgmm_call(lhs, dout, group_sizes, interpret, tm, tk, tn):
    """[X * k, n]: ``lhs[rows of g]^T @ dout[rows of g]`` for each g."""
    m, k = lhs.shape
    n = dout.shape[1]
    x = group_sizes.shape[0]
    items = work_items(group_sizes, m, tm, visit_empty=True)
    steps = items[1].shape[0]
    k_tiles = k // tk
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((x * k, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, k_tiles, steps),
            in_specs=[
                pl.BlockSpec(
                    (tm, tk),
                    lambda j, c, i, off, gid, tid, cnt: (tid[i], c)),
                pl.BlockSpec(
                    (tm, tn),
                    lambda j, c, i, off, gid, tid, cnt: (tid[i], j)),
            ],
            out_specs=pl.BlockSpec(
                (tk, tn),
                lambda j, c, i, off, gid, tid, cnt: (
                    gid[i] * k_tiles + c, j)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=interpret,
        name="gmm_tn",
    )(*items[:3], items[3].reshape(1), lhs, dout)


def _pad_rows(a, m_pad):
    return a if a.shape[0] == m_pad else jnp.pad(
        a, ((0, m_pad - a.shape[0]), (0, 0)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _gmm(lhs, rhs, group_sizes, transpose_rhs, interpret, tm, zero_tail):
    m = lhs.shape[0]
    m_pad = -(-m // tm) * tm
    k = lhs.shape[1]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tn = _column_tile(tm, k, n, lhs.dtype.itemsize)
    out = _gmm_call(_pad_rows(lhs, m_pad), rhs, group_sizes,
                    transpose_rhs, interpret, tm, tn)[:m]
    return _zero_tail(out, group_sizes) if zero_tail else out


def _gmm_fwd(lhs, rhs, group_sizes, transpose_rhs, interpret, tm,
             zero_tail):
    return (_gmm(lhs, rhs, group_sizes, transpose_rhs, interpret, tm,
                 zero_tail), (lhs, rhs, group_sizes))


def _gmm_bwd(transpose_rhs, interpret, tm, zero_tail, res, dout):
    lhs, rhs, group_sizes = res
    m_pad = -(-lhs.shape[0] // tm) * tm
    dlhs = _gmm(dout, rhs, group_sizes, not transpose_rhs, interpret, tm,
                zero_tail)
    # rhs is [X, a, b] and its gradient a^T-side @ b-side of the rows.
    a, b = (dout, lhs) if transpose_rhs else (lhs, dout)
    tk, tn = _tgmm_tiles(tm, a.shape[1], b.shape[1], lhs.dtype.itemsize)
    drhs = _tgmm_call(_pad_rows(a, m_pad), _pad_rows(b, m_pad),
                      group_sizes, interpret, tm, tk, tn)
    return dlhs, drhs.reshape(rhs.shape).astype(rhs.dtype), None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def _unfriendly(k, n, tm, itemsize):
    """Why the kernels cannot take these widths, or ""."""
    for a, b in ((k, n), (n, k)):   # forward, and the input gradient
        if _column_tile(tm, a, b, itemsize) is None:
            return ("a [%d, %d] tile beside a [%d, >=128] weight block "
                    "does not fit %d MiB of VMEM" % (
                        tm, a, a, _VMEM_BLOCKS >> 20))
    if None in (_tgmm_tiles(tm, k, n, itemsize),
                _tgmm_tiles(tm, n, k, itemsize)):
        return "no [k, n] block of %dx%d fits the VMEM budget" % (k, n)
    return ""


def grouped_matmul(lhs, rhs, group_sizes, transpose_rhs=False,
                   interpret=None, row_tile_rows=None, zero_tail=False):
    """lhs [M, K] (rows sorted by group), rhs [X, K, N] (or [X, N, K]
    with ``transpose_rhs``), group_sizes [X] int32 adding up to M (to
    at most M with ``zero_tail``: the rows past them come back zero) ->
    [M, N] in lhs's dtype, float32 accumulation.  Differentiable in lhs
    and rhs.  The kernels where ``ops/mode.py`` allows them and the
    widths fit, else ``grouped_matmul_ref``.  ``row_tile_rows``
    overrides the row tile (tests)."""
    mode = resolve(interpret)
    if mode == "off":
        return grouped_matmul_ref(lhs, rhs, group_sizes, transpose_rhs,
                                  zero_tail)
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm = row_tile_rows or row_tile(m)
    why = _unfriendly(k, n, tm, lhs.dtype.itemsize)
    if not why and mode == "tpu" and (k % 128 or n % 128):
        why = "widths %dx%d are not multiples of the 128 lanes" % (k, n)
    if why:
        announce_fallback("grouped_matmul", (m, k, n), why, mode)
        return grouped_matmul_ref(lhs, rhs, group_sizes, transpose_rhs,
                                  zero_tail)
    return _gmm(lhs, rhs.astype(lhs.dtype), group_sizes.astype(jnp.int32),
                transpose_rhs, mode == "interpret", tm, zero_tail)
