"""The routed FFN of a dropless mixture of experts: sort, gather, three
grouped matmuls (two where the experts are MLPs of two matrices, an
activation without a gate product: ``GATELESS``), un-sort
(``models/transformer._moe_ffn`` routes and weighs the statistics; this
is the multiply).

``moe_experts`` is the op: it asks ``ops/mode.py`` which product runs
(the Pallas ``grouped_matmul`` kernels, per shard of the trainer's data
axis, or ``lax.ragged_dot``) and says so once per compiled shape (``moe
dispatch:``, which the benchmark and ``chip_smoke.py`` read).

With all the experts held every buffer has n * K rows.  With a share of
them (``held != total``) every buffer between the sort and the
token-shaped result has ``row_bound`` rows: the held experts' rows are a
prefix of the sort, the first ``row_bound`` of them are one block, and
the blocks after it run while held rows are left (``_further_blocks``),
so nothing is dropped and the work follows the data.  Where a kernel
may run, a share's rows move by ``ops/row_moves.py``: rows gathered for
the held rows alone, and a token's rows summed in token order, with no
float32 buffer of ``row_bound`` rows and no scatter; the jnp row moves
below are the reference, and what a chip runs only where a row is no
whole number of 128 lanes or too wide for the kernel's slots
(``rows=kernel`` | ``rows=reference`` on the ``moe dispatch:`` line;
every share cell of the benchmark reads ``rows=kernel``).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from elasticdl_tpu.ops import flash_attention, grouped_matmul as gm
from elasticdl_tpu.ops import row_moves
from elasticdl_tpu.ops.batch_shard import per_batch_shard
from elasticdl_tpu.ops.mode import kernel_mode


# ``checkpoint_name``s of what the dispatch's backward reads, where it
# is made (models/remat_keep.py picks among them): the sort's int32
# results, the sorted rows, the two products before the activation, the
# down product (un-sorted where all experts are held, else the first
# block's ``row_bound`` rows as the grouped matmul gives them).
KEEP_SORT, KEEP_ROWS = "moe_sort", "moe_rows"
KEEP_GATE, KEEP_UP, KEEP_OUT = "moe_gate", "moe_up", "moe_out"

# Rows of a share's buffers over the rows a balanced router gives the
# held experts.  2 is the usual receive capacity of an expert-parallel
# exchange, and well above what a layer of the benchmark's cell holds
# at random weights (13,233-19,456 of a bound of 32,768 over forty layer
# dispatches of ten seeds: PERF.md section 6, PR 32).  Rows past it are
# multiplied in further blocks, so the factor sets a cost, never a
# result.
BOUND_FACTOR = 2

# The gate's activation by the model's name for it
# (``TransformerConfig.ffn_activation``): act(gate) * up is SwiGLU with
# "silu", ReGLU with "relu".  A name of ``GATELESS`` is an MLP of TWO
# matrices, ``act(h W_up) W_down`` with no gate product and no
# ``w_gate`` anywhere: "relu2", the squared ReLU.
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu,
               "relu2": lambda x: jnp.square(jax.nn.relu(x))}
GATELESS = ("relu2",)


def gated(activation, gate, up, limit=0.0):
    """``act(gate) * up`` of a gated MLP's two products; with a
    ``limit`` L > 0 the clamped form, ``act(min(gate, L)) * clip(up,
    -L, L)``: no gradient reaches a value past its bound.  ``gate``
    None: an MLP of two matrices (``GATELESS``), ``act(up)``."""
    if gate is None:
        return ACTIVATIONS[activation](up)
    if limit:
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    return ACTIVATIONS[activation](gate) * up


def row_bound(rows, held, total):
    """Rows of every buffer of a dispatch that holds ``held`` of the
    ``total`` experts its ``rows`` assignments are routed over:
    ``BOUND_FACTOR`` times the balanced share, a whole number of the
    grouped matmul's row tiles, and never more than ``rows`` (all of
    them where all experts are held)."""
    if held == total:
        return rows
    need = -(-BOUND_FACTOR * rows * held // total)
    tile = gm.row_tile(need)
    return min(rows, -(-need // tile) * tile)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _take_rows(k, x, order, inverse):
    """``x[order // k]``: row i of the result is the row of x that
    ``order[i]`` of the n * k (row, choice) assignments claims, and
    ``inverse`` undoes ``order``, so the pullback is a gather and a sum
    over a row's k claims, not a scatter-add.  With k = 1 it permutes
    rows, by gathers both ways."""
    return x[order // k]


def _take_rows_bwd(k, inverse, g):
    claims = g[inverse].reshape(-1, k, g.shape[-1])
    return claims.astype(jnp.float32).sum(axis=1).astype(g.dtype), None, None


_take_rows.defvjp(
    lambda k, x, order, inverse: (x[order // k], inverse), _take_rows_bwd)


# -- a share's rows: C of them to and from n tokens ------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def tokens_to_rows(n, x, tok):
    """x [n, w], tok [C] int32 in 0 .. n -> [C, w]: row i is token
    ``tok[i]``'s.  ``tok[i] == n`` marks a row that is no token's (past
    the held rows): what it gets is not read.  The transpose of
    ``rows_to_tokens`` with no scale."""
    return jnp.take(x, tok, axis=0, mode="clip")


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def rows_to_tokens(n, y, tok, scale):
    """y [C, w], tok [C], scale [C] float32 or None -> float32 [n, w]:
    token t's row is the sum of ``scale[i] * y[i]`` over its rows i
    (``tok[i] == t``), zeros where it has none; a row that is no
    token's (``tok[i] == n``) is dropped whatever it holds.

    A scatter-add of C rows: in the job's trace on a v5e it takes 3.5 ms
    at 32,768 x 2,048, where the scatter-free form (rows gathered into
    token order, K - 1 shifted adds, the tokens gathering their first
    rows) took ~6 (PERF.md section 6, PR 32)."""
    z = y.astype(jnp.float32)
    if scale is not None:
        z = z * scale[:, None]
    return jnp.zeros((n, y.shape[1]), jnp.float32).at[tok].add(
        z, mode="drop")


def _tokens_to_rows_fwd(n, x, tok):
    return tokens_to_rows(n, x, tok), tok


def _tokens_to_rows_bwd(n, tok, g):
    return rows_to_tokens(n, g, tok, None).astype(g.dtype), None


def _rows_to_tokens_fwd(n, y, tok, scale):
    return rows_to_tokens(n, y, tok, scale), (y, tok, scale)


def _rows_to_tokens_bwd(n, res, g):
    y, tok, scale = res
    rows = tokens_to_rows(n, g, tok)
    live = (tok < n)[:, None]
    dy = rows if scale is None else rows * scale[:, None]
    dscale = None if scale is None else jnp.where(
        live, y.astype(jnp.float32) * rows, 0.0).sum(axis=1)
    return jnp.where(live, dy, 0.0).astype(y.dtype), None, dscale


tokens_to_rows.defvjp(_tokens_to_rows_fwd, _tokens_to_rows_bwd)
rows_to_tokens.defvjp(_rows_to_tokens_fwd, _rows_to_tokens_bwd)


# -- the same moves by the row kernel (ops/row_moves.py) --------------------
#
# ``tok`` [C] says whose a row is (as above); ``pos`` [n, K] is the other
# way round: where in the block the row of token t's j-th choice lies,
# -1 where it has none here (the expert is not held, or the row is in
# another block).  Each move's pullback is another call of the kernel.

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gather_rows(n, x, tok, pos, live, tokens):
    """``tokens_to_rows``: rows that are no token's (those from ``live``
    on) are not read, and come back zero.  ``tokens``: n, or 0 where
    the block holds no row (nothing of x is then packed)."""
    return row_moves.row_sum(x, tok[:, None], live=tokens)[0]


def _gather_rows_bwd(n, res, g):
    pos, live = res
    return (row_moves.row_sum(g, pos, live=live)[0],) + (None,) * 4


_gather_rows.defvjp(
    lambda n, x, tok, pos, live, tokens: (
        _gather_rows(n, x, tok, pos, live, tokens), (pos, live)),
    _gather_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _sum_rows(n, y, tok, pos, live, claims, gates, tokens):
    """``rows_to_tokens`` walked in token order: float32 [n, w], token
    t's row the sum over its choices j of ``gates[t, j] * y[pos[t, j]]``,
    summed in VMEM and written once.  ``claims`` [C] is the (token,
    choice) a row is the product of, n * K where it is none's: the
    pullback weighs a row by its gate.  ``tokens``: n, or 0 where the
    block holds no row (its pullback then packs no cotangent)."""
    return row_moves.row_sum(y, pos, gates, live=live,
                             out_dtype=jnp.float32)[0]


def _sum_rows_fwd(n, y, tok, pos, live, claims, gates, tokens):
    return _sum_rows(n, y, tok, pos, live, claims, gates, tokens), (
        y, tok, pos, claims, gates, tokens)


def _sum_rows_bwd(n, res, g):
    y, tok, pos, claims, gates, tokens = res
    scale = gates.reshape(-1).at[claims].get(mode="fill", fill_value=0)
    dy, dscale = row_moves.row_sum(g, tok[:, None], scale[:, None],
                                   other=y, live=tokens, out_dtype=y.dtype)
    dgates = jnp.where(pos >= 0, dscale.at[pos].get(mode="clip"), 0.0)
    return dy, None, None, None, None, dgates, None


_sum_rows.defvjp(_sum_rows_fwd, _sum_rows_bwd)


def rows_by_kernel(n, bound, width, dtype, k, mode=None):
    """Whether a share's rows of ``width`` x ``dtype``, ``bound`` of
    them for ``n`` tokens of ``k`` choices, move by the row kernel in
    ``mode`` (``kernel_mode()``'s answer): all four moves or none (rows
    of ``dtype`` gathered one and summed k a result row, the float32
    cotangent's gathered)."""
    mode = mode or kernel_mode()
    return mode != "off" and not any(
        row_moves.unfriendly(width, source, terms, mode, rows)
        for source, terms, rows in ((dtype, 1, n), (dtype, k, bound),
                                    (jnp.float32, 1, n)))


def held_positions(flat, sizes):
    """[rows] int32: where in the sort (held experts first, in their
    order, each one's assignments in the order they stand in ``flat``)
    each of the assignments ``flat`` (experts, the held ones 0 ..
    ``len(sizes)``) lies, -1 for an expert not held: the sort's inverse
    over the held experts, by counting and with no second sort."""
    held = sizes.shape[0]
    mine = flat[None, :] == jnp.arange(held, dtype=flat.dtype)[:, None]
    rank = jnp.cumsum(mine, axis=1, dtype=jnp.int32) - 1
    start = jnp.cumsum(sizes) - sizes
    at = jnp.where(mine, rank + start[:, None], 0).sum(axis=0)
    return jnp.where(flat < held, at, -1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _block(i, c, activation, limit, x, gates, weights, order, sizes,
           pos=None):
    """Float32 [n, e]: what the sorted rows ``i * c .. (i + 1) * c`` add
    to the tokens' results.  x [n, e], gates [n, k], order [blocks * c]
    (the sort, padded with n * k: no assignment), sizes [held] the held
    experts' rows (a prefix of the sort): the block multiplies those of
    them that fall inside it.  ``pos`` [n, k] (``held_positions``) where
    the row kernel moves the rows, None where the jnp moves do.

    The names are the first block's to keep: a further block's values
    never leave its loop.  Jitted, so that the layers of a stack and the
    blocks of a layer, which trace it at the same shapes, lower its
    kernels once (a worker lowers its step at every start, compile cache
    or not: PERF.md section 6, PR 32)."""
    n, k = gates.shape
    ends = jnp.cumsum(sizes)
    # n for a block that is run.  Said of i, so that no compiler takes
    # the packing of x, or of the cotangent, out of a loop of blocks
    # that never turns (XLA did: a pass over all n rows a layer)
    tokens = jnp.where(i * c < ends[-1], n, 0)
    inside = lambda at: jnp.clip(at - i * c, 0, c)
    sizes = inside(ends) - inside(ends - sizes)
    live = sizes.sum()
    claims = lax.dynamic_slice_in_dim(order, i * c, c)
    tok = jnp.where(jnp.arange(c) < live, claims // k, n)
    if pos is not None:
        pos = jnp.where((pos >= i * c) & (pos < i * c + live),
                        pos - i * c, -1)
    matmul = functools.partial(gm.grouped_matmul, zero_tail=True)
    *w_gate, w_up, w_down = weights
    xs = checkpoint_name(
        tokens_to_rows(n, x, tok) if pos is None
        else _gather_rows(n, x, tok, pos, live, tokens), KEEP_ROWS)
    gate = checkpoint_name(matmul(xs, w_gate[0], sizes),
                           KEEP_GATE) if w_gate else None
    up = checkpoint_name(matmul(xs, w_up, sizes), KEEP_UP)
    ys = checkpoint_name(
        matmul(gated(activation, gate, up, limit), w_down, sizes),
        KEEP_OUT)
    if pos is not None:
        return _sum_rows(n, ys, tok, pos, live, claims, gates, tokens)
    scale = gates.reshape(n * k).at[claims].get(mode="fill", fill_value=0)
    return rows_to_tokens(n, ys, tok, scale)


def _blocks(c, sizes):
    """The blocks of c rows that hold a held expert's row."""
    return -(-sizes.sum() // c)


def _sums_walk(c, pos, blocks):
    """int32 [2]: the slots the vector phase of a step's ``rows_sum``
    calls walks, and the tokens x K a call they would be without the
    ranks (``row_moves.sum_terms``), over the ``blocks`` that ran: two
    calls a block, ``_sum_rows`` and ``_gather_rows``' pullback, on the
    same ``pos`` (a held row's position is under the held rows' count,
    so block i's are those of ``i * c .. (i + 1) * c``)."""
    def add(i, walked):
        mine = jnp.where((pos >= i * c) & (pos < (i + 1) * c),
                         pos - i * c, -1)
        return walked + 2 * jnp.stack(row_moves.sum_terms(mine, c))

    return lax.fori_loop(jnp.int32(0), blocks, add, jnp.zeros(2, jnp.int32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _further_blocks(c, activation, limit, out, x, gates, weights, order,
                    sizes, pos):
    """``out`` + every block after the first that holds a held expert's
    row: a loop whose trip count is the data's (none at the balance the
    bound was taken from, all of them under a router collapsed onto the
    held experts).  Its backward is the same loop over each block's
    pullback, the block's forward run again: a block keeps nothing."""
    return lax.fori_loop(
        jnp.int32(1), _blocks(c, sizes),
        lambda i, out: out + _block(i, c, activation, limit, x, gates,
                                    weights, order, sizes, pos),
        out)


def _further_blocks_fwd(c, activation, limit, out, x, gates, weights,
                        order, sizes, pos):
    return (_further_blocks(c, activation, limit, out, x, gates, weights,
                            order, sizes, pos),
            (x, gates, weights, order, sizes, pos))


def _further_blocks_bwd(c, activation, limit, res, g):
    *operands, order, sizes, pos = res

    def add_block(i, grads):
        pull = jax.vjp(lambda *operands: _block(
            i, c, activation, limit, *operands, order, sizes, pos),
            *operands)[1]
        return jax.tree_util.tree_map(jnp.add, grads, pull(g))

    grads = lax.fori_loop(
        jnp.int32(1), _blocks(c, sizes), add_block,
        jax.tree_util.tree_map(jnp.zeros_like, tuple(operands)))
    return (g, *grads, None, None, None)


_further_blocks.defvjp(_further_blocks_fwd, _further_blocks_bwd)


def _moe_experts(h, gates, experts, *weights, total=None, first=0,
                 activation="silu", limit=0.0):
    """The routed FFN of the rows this device holds: sort the n * K
    (token, choice) assignments by expert, gather their rows, three
    grouped matmuls (``weights``: w_gate, w_up, w_down; two for an MLP
    of two matrices, w_up and w_down alone), un-sort and sum each
    token's K results weighted by
    its gates.  O(n * K * width) memory, no capacity, nothing dropped.
    Returns (out [b, T, E], load [1, X + 1]: rows per expert, then the
    rows the grouped matmul computes beyond the real ones).

    The weights may be a share of the ``total`` experts the tokens are
    routed over, ``first .. first + held``: the assignments are sorted
    with the held experts' first, and those rows alone are gathered,
    multiplied and summed into their tokens, ``row_bound`` rows a block
    (the module's docstring); the others' are never moved.  ``load``
    counts all ``total`` experts either way, and with a share ends in
    two more numbers: the rows the blocks that ran moved, and 1 if more
    than one ran; where the row kernel moves the rows, in two more
    behind them (``_sums_walk``)."""
    b, t, e = h.shape
    held, k = weights[0].shape[0], experts.shape[-1]
    x = total or held
    share = held != x
    n, rows = b * t, b * t * k
    bound = row_bound(rows, held, x)
    mode = kernel_mode()
    by_kernel = share and rows_by_kernel(n, bound, e, h.dtype, k, mode)
    if mode != "interpret":
        announce_dispatch(n, x, k, mode, (held, bound) if share else None,
                          by_kernel)
    flat = experts.reshape(rows)
    if share:    # expert ``first`` sorts as 0, the absent ones last
        flat = (flat - first) % x
    order = checkpoint_name(
        jnp.argsort(flat, stable=True).astype(jnp.int32), KEEP_SORT)
    if not share:    # a share's rows find their tokens themselves
        inverse = checkpoint_name(
            jnp.argsort(order).astype(jnp.int32), KEEP_SORT)
    sizes = checkpoint_name(
        (flat[:, None] == jnp.arange(x, dtype=flat.dtype)).sum(
            axis=0, dtype=jnp.int32), KEEP_SORT)
    counted = sizes          # every expert's rows, in the experts' order
    if share:
        counted, sizes = jnp.roll(sizes, first), sizes[:held]
    padded = (jnp.int32(0) if mode == "off"
              else gm.padded_rows(sizes, rows))
    if share:
        # whole blocks: the sort padded with assignments that are none
        order = jnp.pad(order, (0, -rows % bound), constant_values=rows)
        pos = checkpoint_name(
            held_positions(flat, sizes).reshape(n, k),
            KEEP_SORT) if by_kernel else None
        operands = (h.reshape(n, e), gates.reshape(n, k), weights, order,
                    sizes, pos)
        out = _further_blocks(
            bound, activation, limit,
            _block(jnp.int32(0), bound, activation, limit, *operands),
            *operands)
        blocks = jnp.maximum(_blocks(bound, sizes), 1)
        load = jnp.concatenate([counted, jnp.stack(
            [padded, blocks * bound, (blocks > 1).astype(jnp.int32)])] + (
                [_sums_walk(bound, pos, blocks)] if by_kernel else []))
        return out.astype(h.dtype).reshape(b, t, e), load[None]
    xs = checkpoint_name(
        _take_rows(k, h.reshape(n, e), order, inverse), KEEP_ROWS)
    *w_gate, w_up, w_down = weights
    gate = checkpoint_name(gm.grouped_matmul(xs, w_gate[0], sizes),
                           KEEP_GATE) if w_gate else None
    up = checkpoint_name(gm.grouped_matmul(xs, w_up, sizes), KEEP_UP)
    act = gated(activation, gate, up, limit)
    ys = checkpoint_name(_take_rows(
        1, gm.grouped_matmul(act, w_down, sizes), inverse, order), KEEP_OUT)
    out = jnp.einsum("nke,nk->ne", ys.reshape(n, k, e).astype(jnp.float32),
                     gates.reshape(n, k))
    load = jnp.concatenate([counted, padded.reshape(1)])[None]
    return out.astype(h.dtype).reshape(b, t, e), load


@functools.lru_cache(maxsize=None)
def announce_dispatch(tokens, experts, top_k, kernel, share=None,
                      by_kernel=False):
    """Once per compiled shape, by the logger ``announce_tiles`` uses:
    what the dispatch hands the grouped matmul (of one shard of the
    trainer's data axis, where there is one); ``held=`` and ``bound=``
    (``row_bound``: the rows of one block) where the weights are a
    ``share`` (held, bound) of the experts, and which row moves stand
    round them: ``rows=kernel`` (``ops/row_moves.py``) or
    ``rows=reference`` (the jnp moves: ``rows_by_kernel`` said no, and
    ``row_moves.unfriendly`` why)."""
    rows = tokens * top_k
    held, bound = share or (experts, rows)
    tile = gm.row_tile(bound)
    flash_attention.logger.info(
        "moe dispatch: tokens=%d experts=%d top_k=%d rows=%d row_tile=%d "
        "groups_tiles<=%d kernel=%s%s", tokens, experts, top_k, rows, tile,
        -(-bound // tile) + held - 1, kernel,
        "" if share is None else " held=%d bound=%d rows=%s" % (
            share + ("kernel" if by_kernel else "reference",)))


def whole_lanes(weights, lanes=128):
    """The experts' ``weights`` with their inner width (the columns of
    w_gate and w_up, the rows of w_down) padded by zeros to whole
    128-lane tiles, which the grouped matmul's kernels tile; as they
    are where it is one already.  An activation's value at 0 is 0
    (``ACTIVATIONS``), so a padded column's product is 0, it meets zero
    rows of w_down and adds nothing; the pad's pullback cuts the
    gradients back.  A width of 1,856 = 14.5 tiles runs as 1,920."""
    *ins, down = weights
    pad = -down.shape[1] % lanes
    if not pad:
        return tuple(weights)
    return tuple(jnp.pad(w, ((0, 0), (0, 0), (0, pad))) for w in ins) + (
        jnp.pad(down, ((0, 0), (0, pad), (0, 0))),)


def moe_experts(h, gates, experts, *weights, total=None, first=0,
                activation="silu", limit=0.0):
    """h [B, T, E], gates and experts [B, T, K], the three expert
    ``weights`` [X, ...] in h's dtype, w_gate, w_up, w_down, or the two
    of an MLP without a gate product, w_up, w_down (or the share
    ``first .. first + X``
    of ``total`` experts: ``_moe_experts``), the gate's ``activation``
    (a name of ``ACTIVATIONS``) and the layer's clamp on the two
    products (``gated``'s ``limit``) -> (out [B, T, E], load
    [shards, total + 1], with a share [shards, total + 3]).  Where a
    kernel runs, once per shard of the declared batch axis (weights
    whole on each); the reference partitions by itself."""
    fn = functools.partial(_moe_experts, total=total, first=first,
                           activation=activation, limit=limit)
    if len(weights) != 3 - (activation in GATELESS):
        raise ValueError(
            "activation=%s takes %d expert weights, got %d" % (
                activation, 3 - (activation in GATELESS), len(weights)))
    if kernel_mode() == "off":
        return fn(h, gates, experts, *weights)
    return per_batch_shard(fn, (h, gates, experts), whole_lanes(weights))
