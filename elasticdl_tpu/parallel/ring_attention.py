"""Ring attention — sequence/context parallelism over the ICI ring.

Long-context support the reference lacks entirely (SURVEY.md §5.7).
Sequence is sharded over the ``sp`` mesh axis; each device holds a query
block and streams key/value blocks around the ring with ``ppermute``,
folding every block into a numerically-stable online softmax (the same
accumulation flash attention uses, distributed over devices).  Peak memory
per device is O(T/sp · T/sp) instead of O(T²), and the KV transfers ride
ICI concurrently with compute.

The per-shard block attention inside the fold is
``ops/flash_attention.flash_attention_partial``: the Pallas flash kernel
where ``ops/mode.py`` allows one, so even the per-device T/sp x T/sp
score matrix never materializes in the forward.  Causal folds dispatch
per ring step: the diagonal block runs the causal kernel, blocks from
lower ranks run the (cheaper) non-causal kernel, and blocks from higher
ranks are skipped outright — about half the ring FLOPs for causal LMs.

Layout convention: [batch, seq, heads, head_dim]; heads shard over ``tp``,
sequence over ``sp``, batch over ``dp``.
"""

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.ops.flash_attention import (
    _check_window,
    _partial_banded,
    announce_fallback,
    flash_attention,
    flash_attention_partial,
)
from elasticdl_tpu.ops.mode import kernels_off

_NEG_INF = -1e30


def _ring_attention_local(q, k, v, axis_name, causal, scale, window=0):
    """Per-device fold, [B, T/sp, H, D] shards in; the block math runs in
    [B, H, T, D] (the flash kernel's layout) and transposes back once."""
    axis_size = jax.lax.psum(1, axis_name)
    rank = jax.lax.axis_index(axis_name)
    b, tq, h, d = q.shape

    qT = q.transpose(0, 2, 1, 3)                         # [B,H,Tq,D]
    kT = k.transpose(0, 2, 1, 3)
    vT = v.transpose(0, 2, 1, 3)

    def partial(qT, kT, vT, block_causal, block_window=0):
        return flash_attention_partial(
            qT, kT, vT, causal=block_causal, scale=scale,
            window=block_window,
        )

    def skip_partial(qT):
        return (
            jnp.zeros(qT.shape, jnp.float32),
            jnp.zeros(qT.shape[:3], jnp.float32),
            jnp.full(qT.shape[:3], _NEG_INF, jnp.float32),
        )

    o = jnp.zeros((b, h, tq, d), jnp.float32)
    l = jnp.zeros((b, h, tq), jnp.float32)
    m = jnp.full((b, h, tq), _NEG_INF, jnp.float32)

    def fold(o, l, m, acc_i, l_i, m_i):
        m_new = jnp.maximum(m, m_i)
        alpha = jnp.exp(m - m_new)
        beta = jnp.exp(m_i - m_new)
        l = l * alpha + l_i * beta
        o = o * alpha[..., None] + acc_i * beta[..., None]
        return o, l, m_new

    def body(i, carry):
        o, l, m, kT, vT = carry
        src_rank = (rank - i) % axis_size
        if causal and window:
            # Sliding window: the ring distance delta = rank - src picks
            # the block's global diff range [delta*C - (C-1), delta*C +
            # C-1] (C = shard length).  Fully above the diagonal OR
            # entirely past the window -> skip; fully inside the band ->
            # plain non-causal kernel; diagonal -> windowed causal
            # kernel; straddling blocks (one or two consecutive ring
            # steps, since the straddle interval spans up to 2C-2 diffs)
            # run the blockwise banded partial with a rank-dependent
            # k offset — O(C·block_k) live, never the dense square.
            delta = rank - src_rank

            def banded(ops):
                return _partial_banded(ops[0], ops[1], ops[2], scale,
                                       -delta * tq, window)

            acc_i, l_i, m_i = jax.lax.cond(
                src_rank == rank,
                lambda ops: partial(*ops, block_causal=True,
                                    block_window=window),
                lambda ops: jax.lax.cond(
                    (src_rank > rank)
                    | (delta * tq - (tq - 1) >= window),
                    lambda o2: skip_partial(o2[0]),
                    lambda o2: jax.lax.cond(
                        delta * tq + tq - 1 < window,
                        lambda o3: partial(*o3, block_causal=False),
                        banded,
                        o2,
                    ),
                    ops,
                ),
                (qT, kT, vT),
            )
        elif causal:
            # diagonal -> causal kernel; lower source rank -> full
            # (non-causal) kernel; higher -> entirely masked, skip.
            acc_i, l_i, m_i = jax.lax.cond(
                src_rank == rank,
                lambda ops: partial(*ops, block_causal=True),
                lambda ops: jax.lax.cond(
                    src_rank < rank,
                    lambda ops2: partial(*ops2, block_causal=False),
                    lambda ops2: skip_partial(ops2[0]),
                    ops,
                ),
                (qT, kT, vT),
            )
        else:
            acc_i, l_i, m_i = partial(qT, kT, vT, block_causal=False)
        o, l, m = fold(o, l, m, acc_i, l_i, m_i)
        # pass our current KV block along the ring
        perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
        kT = jax.lax.ppermute(kT, axis_name, perm)
        vT = jax.lax.ppermute(vT, axis_name, perm)
        return o, l, m, kT, vT

    o, l, m, kT, vT = jax.lax.fori_loop(
        0, axis_size, body, (o, l, m, kT, vT)
    )
    o = o / jnp.maximum(l, 1e-30)[..., None]
    return o.transpose(0, 2, 1, 3).astype(q.dtype)


def attention_local(q, k, v, causal=True, scale=None, window=0):
    """``ops.flash_attention.flash_attention`` in ring layout
    [B, T, H, D]: what a ``shard_map`` body here and in ulysses.py runs
    on its shard.  ``window`` > 0 = sliding-window causal attention."""
    o = flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal, scale=scale,
        window=window,
    )
    return o.transpose(0, 2, 1, 3)


def ring_attention(q, k, v, mesh, causal=True, scale=None,
                   dp_axis="dp", sp_axis="sp", tp_axis="tp", window=0):
    """Sequence-parallel attention over mesh axis ``sp``.

    q, k, v: [batch, seq, heads, head_dim] global arrays (or sharded).
    Falls back to local attention when the mesh has no sp extent.
    ``window`` > 0 = sliding-window causal attention; ring steps whose
    shard lies entirely outside the band skip compute AND the fold.
    """
    _check_window(window, causal)
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if mesh is None:
        return attention_local(q, k, v, causal=causal, scale=scale,
                               window=window)
    if mesh.shape.get(sp_axis, 1) == 1:
        dp = mesh.shape.get(dp_axis, 1)
        tp = mesh.shape.get(tp_axis, 1)
        if q.shape[0] % dp == 0 and q.shape[2] % tp == 0:
            # A Pallas kernel must run INSIDE a manual shard_map over
            # dp/tp: pallas_call is opaque to the partitioner, and under
            # a multi-device GSPMD jit JAX 0.9.0 refuses to lower a
            # Mosaic kernel at all ("cannot be automatically
            # partitioned" — my chip run, PR 21).  ops/batch_shard.py is
            # the same repair for the trainer's batch-only mesh; this one
            # also splits heads over tp.
            spec = P(dp_axis, None, tp_axis, None)
            fn = shard_map(
                functools.partial(
                    attention_local, causal=causal, scale=scale,
                    window=window,
                ),
                mesh=mesh,
                in_specs=(spec, spec, spec),
                out_specs=spec,
                check_vma=False,
            )
            return fn(q, k, v)
        announce_fallback(
            "ring_attention", q.shape,
            "batch %d / heads %d do not divide dp=%d / tp=%d, and "
            "outside a shard_map the kernel cannot be partitioned"
            % (q.shape[0], q.shape[2], dp, tp),
        )
        with kernels_off():
            return attention_local(q, k, v, causal=causal, scale=scale,
                                   window=window)
    sp = mesh.shape[sp_axis]
    tp = mesh.shape.get(tp_axis, 1)
    dp = mesh.shape.get(dp_axis, 1)
    for name, arr in (("q", q), ("k", k), ("v", v)):
        if arr.shape[0] % dp or arr.shape[1] % sp or arr.shape[2] % tp:
            raise ValueError(
                "ring attention needs %s dims [batch=%d, seq=%d, "
                "heads=%d] divisible by [dp=%d, sp=%d, tp=%d]; pad the "
                "inputs or adjust the mesh"
                % (name, arr.shape[0], arr.shape[1], arr.shape[2],
                   dp, sp, tp)
            )
    spec = P(dp_axis, sp_axis, tp_axis, None)
    fn = shard_map(
        functools.partial(
            _ring_attention_local,
            axis_name=sp_axis, causal=causal, scale=scale, window=window,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
