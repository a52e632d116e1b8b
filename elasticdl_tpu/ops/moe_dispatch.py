"""The routed FFN of a dropless mixture of experts: sort, gather, three
grouped matmuls, un-sort (``models/transformer._moe_ffn`` routes and
weighs the statistics; this is the multiply).

``moe_experts`` is the op: it asks ``ops/mode.py`` which product runs
(the Pallas ``grouped_matmul`` kernels, per shard of the trainer's data
axis, or ``lax.ragged_dot``) and says so once per compiled shape (``moe
dispatch:``, which the benchmark and ``chip_smoke.py`` read).
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from elasticdl_tpu.ops import flash_attention, grouped_matmul as gm
from elasticdl_tpu.ops.batch_shard import per_batch_shard
from elasticdl_tpu.ops.mode import kernel_mode


# ``checkpoint_name``s of what the dispatch's backward reads, where it
# is made (models/remat_keep.py picks among them): the sort's three
# int32 results, the sorted rows, the two products before the
# activation, the un-sorted down product.
KEEP_SORT, KEEP_ROWS = "moe_sort", "moe_rows"
KEEP_GATE, KEEP_UP, KEEP_OUT = "moe_gate", "moe_up", "moe_out"


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


def _moe_experts(h, gates, experts, w_gate, w_up, w_down, total=None,
                 first=0):
    """The routed FFN of the rows this device holds: sort the n * K
    (token, choice) assignments by expert, gather their rows, three
    grouped matmuls, un-sort and sum each token's K results weighted by
    its gates.  O(n * K * width) memory, no capacity, nothing dropped.
    Returns (out [b, T, E], load [1, X + 1]: rows per expert, then the
    rows the grouped matmul computes beyond the real ones).

    The weights may be a share of the ``total`` experts the tokens are
    routed over, ``first .. first + held``: the assignments are sorted
    with the held experts' first, all n * K rows are moved as ever, the
    held experts' alone are multiplied, and the others' rows are zeros
    (``grouped_matmul(zero_tail=True)``) that the weighted sum adds as
    nothing.  ``load`` counts all ``total`` experts either way."""
    b, t, e = h.shape
    held, k = w_gate.shape[0], experts.shape[-1]
    x = total or held
    share = held != x
    n, rows = b * t, b * t * k
    mode = kernel_mode()
    if mode != "interpret":
        announce_dispatch(n, x, k, mode, held if share else None)
    flat = experts.reshape(rows)
    if share:    # expert ``first`` sorts as 0, the absent ones last
        flat = (flat - first) % x
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    inverse = jnp.argsort(order).astype(jnp.int32)
    sizes = (flat[:, None] == jnp.arange(x, dtype=flat.dtype)).sum(
        axis=0, dtype=jnp.int32)
    order, inverse, sizes = (
        checkpoint_name(a, KEEP_SORT) for a in (order, inverse, sizes))
    counted = sizes          # every expert's rows, in the experts' order
    if share:
        counted, sizes = jnp.roll(sizes, first), sizes[:held]
    padded = (jnp.int32(0) if mode == "off"
              else gm.padded_rows(sizes, rows))
    # asks the mode itself: as here
    matmul = functools.partial(gm.grouped_matmul, zero_tail=share)
    xs = checkpoint_name(
        _take_rows(k, h.reshape(n, e), order, inverse), KEEP_ROWS)
    gate = checkpoint_name(matmul(xs, w_gate, sizes), KEEP_GATE)
    up = checkpoint_name(matmul(xs, w_up, sizes), KEEP_UP)
    act = jax.nn.silu(gate) * up
    ys = checkpoint_name(
        _take_rows(1, matmul(act, w_down, sizes), inverse, order), KEEP_OUT)
    out = jnp.einsum("nke,nk->ne", ys.reshape(n, k, e).astype(jnp.float32),
                     gates.reshape(n, k))
    load = jnp.concatenate([counted, padded.reshape(1)])[None]
    return out.astype(h.dtype).reshape(b, t, e), load


@functools.lru_cache(maxsize=None)
def announce_dispatch(tokens, experts, top_k, kernel, held=None):
    """Once per compiled shape, by the logger ``announce_tiles`` uses:
    what the dispatch hands the grouped matmul (of one shard of the
    trainer's data axis, where there is one); ``held=`` where the
    weights are a share of the experts."""
    rows = tokens * top_k
    tile = gm.row_tile(rows)
    flash_attention.logger.info(
        "moe dispatch: tokens=%d experts=%d top_k=%d rows=%d row_tile=%d "
        "groups_tiles<=%d kernel=%s%s", tokens, experts, top_k, rows, tile,
        -(-rows // tile) + (held or experts) - 1, kernel,
        "" if held is None else " held=%d" % held)


def moe_experts(h, gates, experts, w_gate, w_up, w_down, total=None,
                first=0):
    """h [B, T, E], gates and experts [B, T, K], the three expert
    weights [X, ...] in h's dtype (or the share ``first .. first + X``
    of ``total`` experts: ``_moe_experts``) -> (out [B, T, E], load
    [shards, total + 1]).  Where a kernel runs, once per shard of the
    declared batch axis (weights whole on each); the reference
    partitions by itself."""
    fn = functools.partial(_moe_experts, total=total, first=first)
    if kernel_mode() == "off":
        return fn(h, gates, experts, w_gate, w_up, w_down)
    return per_batch_shard(fn, (h, gates, experts),
                           (w_gate, w_up, w_down))
