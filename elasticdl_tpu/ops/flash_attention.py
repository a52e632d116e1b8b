"""Pallas flash attention for TPU.

The hot op of the long-context path.  One (batch*head, q-block) program
holds its query tile in VMEM and streams K/V tiles of the same head
through the MXU with the online-softmax accumulation, so the T x T score
matrix never materializes in HBM.

Forward emits the per-row softmax stats (l, m) alongside the output, and
the backward is a Pallas kernel pair: a dq pass (q/dO tiles resident,
K/V streamed) and a dk/dv pass (K/V resident, q/dO streamed), each
rebuilding its probability tiles from the saved stats IN VMEM — unlike
the older XLA ``lax.scan`` block-recompute (kept behind
``ELASTICDL_FLASH_BWD=xla``), the [T, block] p/ds tiles never make an
HBM round-trip between einsums.  Peak memory stays O(T·block), never
the full T x T.

``flash_attention_partial`` exposes the same kernel without the final
normalization, returning (acc, l, m) for one KV block — the building
block ring attention folds across ``ppermute`` hops
(parallel/ring_attention.py).  The ring's *forward* thereby skips the
dense per-shard score matrix; its backward is the hand-written
closed-form pullback ``_partial_stats_bwd`` (scans K blocks,
recomputing each [T, block_k] score tile), so each ring step's bwd is
O(T/sp x block_k) live, never the dense per-shard square.

Layout: [batch, heads, seq, head_dim].  The caller-facing block sizes
are a friendliness contract (seq divisible by them, 128-lane block_k);
the kernel chooses its own internal tiling (up to 512-wide q blocks and
K/V major tiles) to amortize per-grid-step overhead.  `flash_attention`
falls back to the reference implementation for unfriendly shapes, and in
the compiled mode says so once per shape (``announce_fallback``): on the
chip a silent reference path is a slow path nobody asked for.
Mode selection: ``ELASTICDL_FLASH=auto`` (default: compiled kernel on
TPU; jnp elsewhere), ``interpret`` (Pallas interpret mode, for tests),
``off``.  Forward and Pallas backward compile and match the reference at
B8·H16·T2048, D64 and D128, full causal and windowed, on a v5e (my chip
run, PR 21; ``chip_check.py`` at the repo root repeats it).
"""

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.ops.batch_shard import per_batch_shard
from elasticdl_tpu.utils.logging import get_logger

logger = get_logger(__name__)

NEG_INF = -1e30

# chip_smoke.py fails an LM leg on this prefix.
FALLBACK_PREFIX = "attention fallback:"


@functools.lru_cache(maxsize=None)
def announce_fallback(what, shape, why):
    """Once per (call site, shape, reason): the compiled kernel was asked
    for and a jnp path ran instead."""
    logger.warning("%s %s for shape %s took the jnp reference path: %s",
                   FALLBACK_PREFIX, what, shape, why)


def flash_mode():
    """"tpu" (compiled), "interpret", or "off" for the current config."""
    mode = os.environ.get("ELASTICDL_FLASH", "auto")
    if mode == "auto":
        return "tpu" if jax.default_backend() == "tpu" else "off"
    return mode


def _attention_ref(q, k, v, causal, scale, window=0):
    """jnp reference in the same [B, H, T, D] layout.  ``window`` > 0
    limits causal attention to the last ``window`` positions."""
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        tq, tk = q.shape[2], k.shape[2]
        diff = jnp.arange(tq)[:, None] - jnp.arange(tk)[None, :]
        mask = diff >= 0
        if window:
            mask &= diff < window
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "bhqk,bhkd->bhqd", p, v, preferred_element_type=jnp.float32
    ).astype(q.dtype)


STATS_LANES = 128  # Mosaic wants >=(8,128) tiles; stats ride 128 lanes
                   # broadcast, same layout as the in-tree TPU kernel.


def _lanes_bcast(x, head_dim):
    """[bq, 128] all-equal-lane stats -> [bq, head_dim]."""
    if head_dim == STATS_LANES:
        return x
    if head_dim < STATS_LANES:
        return x[:, :head_dim]
    return pltpu.repeat(x, head_dim // STATS_LANES, axis=1)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, l_ref, m_ref, acc_scr,
                  l_scr, m_scr, *, block_k, causal, scale, normalize,
                  window=0):
    # grid: (bh, num_q_blocks, num_k_blocks), K innermost.  Each grid
    # step sees ONE [1, block_k, D] K/V tile — Pallas's automatic
    # pipelining streams tiles HBM->VMEM overlapped with compute, so
    # VMEM never holds the full sequence (the fori_loop-over-resident-KV
    # variant OOMs scoped vmem at T=8k).  The running (acc, l, m) lives
    # in VMEM scratch, persistent across the K grid dimension.
    # Stats stay 2D [block_q, STATS_LANES] (every lane equal) so all
    # vector ops live on full (8, 128) tiles — Mosaic rejects 1D or
    # lane-1 output blocks.  Requires block_k == STATS_LANES so
    # `s - m` stays lane-aligned.
    block_q = q_ref.shape[1]
    block_k_major = k_ref.shape[1]
    head_dim = q_ref.shape[2]
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    num_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros(acc_scr.shape, acc_scr.dtype)
        l_scr[...] = jnp.zeros(l_scr.shape, l_scr.dtype)
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, m_scr.dtype)

    # Under causal masking, major blocks strictly above the diagonal
    # contribute nothing — skip their matmuls entirely.  A sliding
    # window additionally kills blocks entirely below the band.
    live = (
        ki * block_k_major <= qi * block_q + block_q - 1 if causal
        else ki >= 0
    )
    if causal and window:
        live &= (
            ki * block_k_major + block_k_major - 1
            >= qi * block_q - window + 1
        )

    @pl.when(live)
    def _major_step():
        # Keep the operands in their storage dtype (bf16 in the mixed-
        # precision path) and accumulate in f32 via preferred_element_type
        # — upcasting before the dot would push the MXU onto the ~4x
        # slower f32 path.  The scale folds into the f32 scores.
        q = q_ref[0]                                   # [bq, D]
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )

        # One [1, block_k_major, D] K/V tile is streamed per grid step
        # (enough work to amortize the per-step pipeline overhead); the
        # online-softmax update walks it in lane-width chunks.
        @pl.loop(0, block_k_major, step=block_k, unroll=True)
        def _inner(start):
            k = k_ref[0, pl.ds(start, block_k), :]     # [bk, D]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                  # [bq, bk] f32
            if causal:
                k_pos = (
                    ki * block_k_major + start
                    + jax.lax.broadcasted_iota(
                        jnp.int32, (block_q, block_k), 1
                    )
                )
                keep = q_pos >= k_pos
                if window:
                    keep &= q_pos - k_pos < window
                s = jnp.where(keep, s, NEG_INF)
            m_prev = m_scr[...]
            l_prev = l_scr[...]
            m_new = jnp.maximum(
                m_prev, s.max(axis=-1)[:, None]
            )                                          # [bq, LANES]
            alpha = jnp.exp(m_prev - m_new)            # [bq, LANES]
            p = jnp.exp(s - m_new)         # [bq, bk]; bk == STATS_LANES
            l_scr[...] = l_prev * alpha + p.sum(axis=-1)[:, None]
            m_scr[...] = m_new
            pv = jax.lax.dot_general(
                p.astype(v_ref.dtype),
                v_ref[0, pl.ds(start, block_k), :],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc_scr[...] = (
                acc_scr[...] * _lanes_bcast(alpha, head_dim) + pv
            )

    @pl.when(ki == num_k - 1)
    def _finish():
        acc = acc_scr[...]
        l = l_scr[...]
        if normalize:
            o_ref[0] = (
                acc / _lanes_bcast(jnp.maximum(l, 1e-30), head_dim)
            ).astype(o_ref.dtype)
        else:
            o_ref[0] = acc.astype(o_ref.dtype)
        l_ref[0] = l
        m_ref[0] = m_scr[...]


def _flash_forward(q, k, v, causal, scale, block_q, block_k, interpret,
                   normalize=True, window=0):
    """Returns (out, l, m); out is normalized iff ``normalize``."""
    b, h, t, d = q.shape
    bh = b * h
    qr = q.reshape(bh, t, d)
    kr = k.reshape(bh, t, d)
    vr = v.reshape(bh, t, d)
    # Work per grid step must amortize the per-step pipeline overhead:
    # widen the q block and stream a major K/V tile (the kernel's inner
    # loop walks it in block_k lane chunks), both capped by what
    # divides t.  The caller's block_q/block_k are a friendliness
    # contract (t divisible, 128 lanes) — the kernel owns its tiling.
    block_q = block_k_major = _major_tile(t)
    grid = (bh, t // block_q, t // block_k_major)
    if causal:
        # Dead blocks above the diagonal skip compute (pl.when in the
        # kernel) — also skip their HBM->VMEM DMA by clamping the K/V
        # index map to the last live block: a revisited block index is
        # deduped by the pipeline into no copy.
        def kv_index(i, j, ki):
            last_live = (j * block_q + block_q - 1) // block_k_major
            if window:
                first_live = jnp.maximum(
                    0, (j * block_q - window + 1) // block_k_major
                )
            else:
                first_live = 0
            return (i, jnp.clip(ki, first_live, last_live), 0)
    else:
        def kv_index(i, j, ki):
            return (i, ki, 0)
    out_dtype = q.dtype if normalize else jnp.float32
    out, l, m = pl.pallas_call(
        functools.partial(
            _flash_kernel, block_k=block_k, causal=causal, scale=scale,
            normalize=normalize, window=window,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((bh, t, d), out_dtype),
            jax.ShapeDtypeStruct((bh, t, STATS_LANES), jnp.float32),
            jax.ShapeDtypeStruct((bh, t, STATS_LANES), jnp.float32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, ki: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k_major, d), kv_index,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k_major, d), kv_index,
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, d), lambda i, j, ki: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, STATS_LANES),
                         lambda i, j, ki: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, STATS_LANES),
                         lambda i, j, ki: (i, j, 0),
                         memory_space=pltpu.VMEM),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, STATS_LANES), jnp.float32),
            pltpu.VMEM((block_q, STATS_LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qr, kr, vr)
    return (
        out.reshape(b, h, t, d),
        l[..., 0].reshape(b, h, t),
        m[..., 0].reshape(b, h, t),
    )


def _kv_blocks(k, v, block_k):
    """Split [B,H,Tk,D] K/V into scan-leading f32 blocks
    [num_k, B, H, block_k, D]."""
    b, h, tk, d = k.shape
    num_k = tk // block_k
    kb = jnp.moveaxis(
        k.reshape(b, h, num_k, block_k, d), 2, 0
    ).astype(jnp.float32)
    vb = jnp.moveaxis(
        v.reshape(b, h, num_k, block_k, d), 2, 0
    ).astype(jnp.float32)
    return num_k, kb, vb


def _masked_block_scores(qf, kf, ki, block_k, causal, scale, k_offset,
                         q_pos, window=0):
    """One [B,H,T,block_k] f32 score tile, causally masked against k
    rows offset by ``k_offset + ki*block_k``.  Returns (scores, mask)
    with mask None when not causal — the single source of truth both
    blockwise backwards recompute from."""
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", qf, kf,
        preferred_element_type=jnp.float32,
    ) * scale
    if causal:
        k_pos = k_offset + ki * block_k + jnp.arange(block_k)
        diff = q_pos[:, None] - k_pos[None, :]
        mask = diff >= 0
        if window:
            mask &= diff < window
        mask = mask[None, None]
        return jnp.where(mask, s, NEG_INF), mask
    return s, None


def _blockwise_bwd(q, k, v, out, l, m, g, causal, scale, block_k,
                   window=0):
    """Block-recompute backward: scan over K blocks rebuilding each
    [T, block_k] probability tile from the saved (l, m) stats.  Peak
    live memory O(B·H·T·block_k), never the T x T matrix."""
    _, _, tk, _ = k.shape
    qf = q.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    outf = out.astype(jnp.float32)
    # delta_i = sum_d dO_i O_i  (the usual flash-bwd row constant)
    delta = (gf * outf).sum(axis=-1)                    # [B,H,T]
    l_safe = jnp.maximum(l, 1e-30)
    q_pos = jnp.arange(q.shape[2])

    num_k, k_blocks, v_blocks = _kv_blocks(k, v, block_k)

    def body(carry, inputs):
        dq = carry
        ki, kf, vf = inputs
        s, _ = _masked_block_scores(
            qf, kf, ki, block_k, causal, scale, 0, q_pos, window=window
        )                                               # [B,H,T,bk]
        p = jnp.exp(s - m[..., None]) / l_safe[..., None]
        dv = jnp.einsum("bhqk,bhqd->bhkd", p, gf)
        dp = jnp.einsum("bhqd,bhkd->bhqk", gf, vf)
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, kf)
        dk = jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
        return dq, (dk, dv)

    dq0 = jnp.zeros(q.shape, jnp.float32)
    dq, (dk, dv) = jax.lax.scan(
        body, dq0, (jnp.arange(num_k), k_blocks, v_blocks)
    )
    dk = jnp.moveaxis(dk, 0, 2).reshape(k.shape)
    dv = jnp.moveaxis(dv, 0, 2).reshape(v.shape)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


STATS_OUT = 8  # lanes for stats arrays fed back into the bwd kernels


def _major_tile(t):
    """Shared fwd/bwd major-tile policy: widest of 128/256/512 dividing t
    (enough per-grid-step work to amortize pipeline overhead)."""
    return max(bs for bs in (128, 256, 512) if bs <= t and t % bs == 0)


def _bwd_dq_kernel(q_ref, o_ref, do_ref, k_ref, v_ref, l_ref, m_ref,
                   dq_ref, dq_scr, *, block_k, causal, scale,
                   window=0):
    """dq = sum_j ds_ij k_j.  Grid (bh, NQ, NK), K innermost: the q/o/dO
    tiles and stats stay resident while K/V tiles stream through VMEM;
    the [bq, block_k] probability/ds tiles never exist outside VMEM."""
    block_q = q_ref.shape[1]
    block_k_major = k_ref.shape[1]
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    num_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, dq_scr.dtype)

    live = (
        ki * block_k_major <= qi * block_q + block_q - 1 if causal
        else ki >= 0
    )
    if causal and window:
        live &= (
            ki * block_k_major + block_k_major - 1
            >= qi * block_q - window + 1
        )

    @pl.when(live)
    def _step():
        q = q_ref[0]                                   # [bq, D]
        do = do_ref[0]
        delta = (
            do.astype(jnp.float32) * o_ref[0].astype(jnp.float32)
        ).sum(axis=-1)[:, None]                        # [bq, 1]
        m = m_ref[0][:, 0:1]                           # [bq, 1]
        l = jnp.maximum(l_ref[0][:, 0:1], 1e-30)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )

        @pl.loop(0, block_k_major, step=block_k, unroll=True)
        def _inner(start):
            k = k_ref[0, pl.ds(start, block_k), :]     # [bk, D]
            v = v_ref[0, pl.ds(start, block_k), :]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                  # [bq, bk]
            if causal:
                k_pos = (
                    ki * block_k_major + start
                    + jax.lax.broadcasted_iota(
                        jnp.int32, (block_q, block_k), 1
                    )
                )
                keep = q_pos >= k_pos
                if window:
                    keep &= q_pos - k_pos < window
                s = jnp.where(keep, s, NEG_INF)
            p = jnp.exp(s - m) / l                     # normalized
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                          # [bq, bk]
            ds = (p * (dp - delta) * scale).astype(k_ref.dtype)
            dq_scr[...] += jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    @pl.when(ki == num_k - 1)
    def _finish():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(k_ref, v_ref, q_ref, o_ref, do_ref, l_ref, m_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr,
                    *, block_q, causal, scale, window=0):
    """dk_j = sum_i ds_ij^T q_i, dv_j = sum_i p_ij^T dO_i.  Grid
    (bh, NK, NQ), Q innermost: the K/V tiles and accumulators stay
    resident while q/o/dO tiles (and their stats) stream through."""
    block_k_major = k_ref.shape[1]
    block_q_major = q_ref.shape[1]
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    num_q = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, dk_scr.dtype)
        dv_scr[...] = jnp.zeros(dv_scr.shape, dv_scr.dtype)

    live = (
        qi * block_q_major + block_q_major - 1 >= kj * block_k_major
        if causal else qi >= 0
    )
    if causal and window:
        live &= (
            qi * block_q_major
            <= kj * block_k_major + block_k_major - 1 + window - 1
        )

    @pl.when(live)
    def _step():
        k = k_ref[0]                                   # [bkM, D]
        v = v_ref[0]
        if causal:
            k_pos = kj * block_k_major + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k_major), 1
            )

        @pl.loop(0, block_q_major, step=block_q, unroll=True)
        def _inner(start):
            q = q_ref[0, pl.ds(start, block_q), :]     # [qc, D]
            o = o_ref[0, pl.ds(start, block_q), :]
            do = do_ref[0, pl.ds(start, block_q), :]
            m = m_ref[0, pl.ds(start, block_q), :][:, 0:1]
            l = jnp.maximum(
                l_ref[0, pl.ds(start, block_q), :][:, 0:1], 1e-30
            )
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                  # [qc, bkM]
            if causal:
                q_pos = (
                    qi * block_q_major + start
                    + jax.lax.broadcasted_iota(
                        jnp.int32, (block_q, block_k_major), 0
                    )
                )
                keep = q_pos >= k_pos
                if window:
                    keep &= q_pos - k_pos < window
                s = jnp.where(keep, s, NEG_INF)
            p = jnp.exp(s - m) / l                     # [qc, bkM]
            pb = p.astype(do_ref.dtype)
            dv_scr[...] += jax.lax.dot_general(
                pb, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                          # [bkM, D]
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                          # [qc, bkM]
            delta = (
                do.astype(jnp.float32) * o.astype(jnp.float32)
            ).sum(axis=-1)[:, None]
            ds = (p * (dp - delta) * scale).astype(q_ref.dtype)
            dk_scr[...] += jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                          # [bkM, D]

    @pl.when(qi == num_q - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _pallas_bwd(q, k, v, out, l, m, g, causal, scale, interpret,
                window=0):
    """Pallas backward: dq in one pass (K streamed), dk/dv in another
    (Q streamed).  Same FLOPs as the XLA block-recompute path but the
    probability/ds tiles live only in VMEM — no [B,H,T,block] HBM
    round-trips between the einsums of a scan step."""
    b, h, t, d = q.shape
    bh = b * h
    tile = _major_tile(t)
    num = t // tile
    qr = q.reshape(bh, t, d)
    kr = k.reshape(bh, t, d)
    vr = v.reshape(bh, t, d)
    orr = out.reshape(bh, t, d)
    gr = g.astype(q.dtype).reshape(bh, t, d)
    l8 = jnp.broadcast_to(
        l.reshape(bh, t, 1), (bh, t, STATS_OUT)
    ).astype(jnp.float32)
    m8 = jnp.broadcast_to(
        m.reshape(bh, t, 1), (bh, t, STATS_OUT)
    ).astype(jnp.float32)

    qo_spec = pl.BlockSpec((1, tile, d), lambda i, j, kk: (i, j, 0),
                           memory_space=pltpu.VMEM)
    st_spec = pl.BlockSpec((1, tile, STATS_OUT),
                           lambda i, j, kk: (i, j, 0),
                           memory_space=pltpu.VMEM)
    if causal:
        # Dead blocks skip compute; clamp the streamed-side index map so
        # their HBM->VMEM copies dedupe away too.  (Equal fwd tile
        # sizes, so tile index arithmetic is 1:1.)
        win_tiles = (window + tile - 2) // tile if window else 0

        def kv_index(i, j, kk):
            lo = jnp.maximum(0, j - win_tiles) if window else 0
            return (i, jnp.clip(kk, lo, j), 0)

        def q_index(i, j, kk):
            hi = j + win_tiles if window else num - 1
            return (i, jnp.clip(kk, j, hi), 0)
    else:
        def kv_index(i, j, kk):
            return (i, kk, 0)

        def q_index(i, j, kk):
            return (i, kk, 0)
    kv_spec = pl.BlockSpec((1, tile, d), kv_index,
                           memory_space=pltpu.VMEM)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_k=128, causal=causal,
                          scale=scale, window=window),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        grid=(bh, num, num),
        in_specs=[qo_spec, qo_spec, qo_spec, kv_spec, kv_spec,
                  st_spec, st_spec],
        out_specs=qo_spec,
        scratch_shapes=[pltpu.VMEM((tile, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qr, orr, gr, kr, vr, l8, m8)

    kv_res_spec = pl.BlockSpec((1, tile, d), lambda i, j, kk: (i, j, 0),
                               memory_space=pltpu.VMEM)
    qs_spec = pl.BlockSpec((1, tile, d), q_index,
                           memory_space=pltpu.VMEM)
    sts_spec = pl.BlockSpec((1, tile, STATS_OUT), q_index,
                            memory_space=pltpu.VMEM)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=128, causal=causal,
                          scale=scale, window=window),
        out_shape=(
            jax.ShapeDtypeStruct((bh, t, d), k.dtype),
            jax.ShapeDtypeStruct((bh, t, d), v.dtype),
        ),
        grid=(bh, num, num),
        in_specs=[kv_res_spec, kv_res_spec, qs_spec, qs_spec, qs_spec,
                  sts_spec, sts_spec],
        out_specs=(kv_res_spec, kv_res_spec),
        scratch_shapes=[
            pltpu.VMEM((tile, d), jnp.float32),
            pltpu.VMEM((tile, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(kr, vr, qr, orr, gr, l8, m8)
    return (
        dq.reshape(b, h, t, d),
        dk.reshape(b, h, t, d),
        dv.reshape(b, h, t, d),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, scale, block_q, block_k, interpret,
           window=0):
    out, _, _ = _flash_forward(q, k, v, causal, scale, block_q, block_k,
                               interpret, window=window)
    return out


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
               window=0):
    out, l, m = _flash_forward(q, k, v, causal, scale, block_q, block_k,
                               interpret, window=window)
    return out, (q, k, v, out, l, m)


def _flash_bwd(causal, scale, block_q, block_k, interpret, window, res,
               g):
    q, k, v, out, l, m = res
    if os.environ.get("ELASTICDL_FLASH_BWD", "pallas") == "xla":
        # Escape hatch: the XLA block-recompute backward.
        return _blockwise_bwd(q, k, v, out, l, m, g, causal, scale,
                              block_k, window=window)
    return _pallas_bwd(q, k, v, out, l, m, g, causal, scale, interpret,
                       window=window)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _check_window(window, causal):
    if window and not causal:
        raise ValueError("sliding window requires causal attention")
    if window < 0:
        raise ValueError("window must be >= 0, got %d" % window)


def _unfriendly(t, d, block_q, block_k):
    """Why the kernel cannot take this shape, or "" when it can."""
    # block_k must equal STATS_LANES so the kernel's [bq, bk] score tile
    # is lane-aligned with the [bq, STATS_LANES] running stats.
    if block_k != STATS_LANES:
        return "block_k %d is not the %d-lane stats width" % (
            block_k, STATS_LANES)
    if t % block_q or t % block_k:
        return "seq %d is not a multiple of the %dx%d blocks" % (
            t, block_q, block_k)
    if d % 128 and d != 64:
        return "head_dim %d is neither 64 nor a multiple of 128" % d
    return ""


def flash_attention(q, k, v, causal=True, scale=None, block_q=128,
                    block_k=128, interpret=False, window=0):
    """q, k, v: [batch, heads, seq, head_dim].  ``window`` > 0 limits
    causal attention to the last ``window`` positions (O(T·W) compute:
    blocks outside the band skip both matmuls and DMA)."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    _check_window(window, causal)
    t = q.shape[2]
    d = q.shape[3]
    block_q = min(block_q, t)
    block_k = min(block_k, t)
    why = _unfriendly(t, d, block_q, block_k)
    if why:
        if not interpret:
            announce_fallback("flash_attention", q.shape, why)
        return _attention_ref(q, k, v, causal, scale, window=window)
    return per_batch_shard(
        lambda q, k, v: _flash(q, k, v, causal, scale, block_q, block_k,
                               interpret, window),
        (q, k, v),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_partial(q, k, v, causal, scale, block_q, block_k, interpret,
                   k_offset, window):
    # causal here means the diagonal (k_offset == 0) block, where the
    # kernel's absolute-position mask equals the local mask.
    out, l, m = _flash_forward(
        q, k, v, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, interpret=interpret, normalize=False,
        window=window,
    )
    return out, l, m


def _partial_ref(q, k, v, causal, scale, k_offset, window=0):
    """Unnormalized block attention in jnp (ring-fold fallback and the
    recompute target of the partial bwd).  Positions: q rows are local,
    k rows offset by ``k_offset`` (ring rotation); ``window`` > 0 keeps
    only q_pos - k_pos in [0, window)."""
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ) * scale
    if causal:
        tq, tk = q.shape[2], k.shape[2]
        diff = (
            jnp.arange(tq)[:, None] - (k_offset + jnp.arange(tk))[None, :]
        )
        mask = diff >= 0
        if window:
            mask &= diff < window
        s = jnp.where(mask[None, None], s, NEG_INF)
    m = s.max(axis=-1)
    p = jnp.exp(s - m[..., None])
    l = p.sum(axis=-1)
    acc = jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return acc, l, m


def _partial_banded(q, k, v, scale, k_offset, window, block_k=128):
    """Causal banded partial for a TRACED ``k_offset`` (the ring's
    window-straddling block, where the offset depends on the device
    rank).  Scans K blocks with the online-softmax fold and
    ``jax.checkpoint`` on the per-block math, so live memory is
    O(T·block_k) in both directions — never the dense [T, T_k] square
    the jnp reference would materialize.  Falls back to ``_partial_ref``
    when T_k doesn't divide into blocks."""
    b, h, tq, d = q.shape
    tk = k.shape[2]
    if tk % block_k or tk // block_k <= 1:
        return _partial_ref(q, k, v, True, scale, k_offset, window=window)
    qf = q.astype(jnp.float32)
    q_pos = jnp.arange(tq)
    num_k, k_blocks, v_blocks = _kv_blocks(k, v, block_k)

    @jax.checkpoint
    def block(ki, kb, vb):
        s, _ = _masked_block_scores(
            qf, kb, ki, block_k, True, scale, k_offset, q_pos,
            window=window,
        )
        m_i = s.max(axis=-1)
        p = jnp.exp(s - m_i[..., None])
        l_i = p.sum(axis=-1)
        acc_i = jnp.einsum(
            "bhqk,bhkd->bhqd", p, vb,
            preferred_element_type=jnp.float32,
        )
        return acc_i, l_i, m_i

    def body(carry, inputs):
        o, l, m = carry
        ki, kb, vb = inputs
        acc_i, l_i, m_i = block(ki, kb, vb)
        m_new = jnp.maximum(m, m_i)
        alpha = jnp.exp(m - m_new)
        beta = jnp.exp(m_i - m_new)
        return (
            o * alpha[..., None] + acc_i * beta[..., None],
            l * alpha + l_i * beta,
            m_new,
        ), None

    init = (
        jnp.zeros((b, h, tq, d), jnp.float32),
        jnp.zeros((b, h, tq), jnp.float32),
        jnp.full((b, h, tq), NEG_INF, jnp.float32),
    )
    (o, l, m), _ = jax.lax.scan(
        body, init, (jnp.arange(num_k), k_blocks, v_blocks)
    )
    return o, l, m


def _partial_stats_bwd(q, k, v, acc, l, ga, gl, gm, causal, scale,
                       k_offset, block_k, window=0):
    """Hand-written backward of ``(acc, l, m) = partial(q, k, v)`` that
    walks K in blocks, recomputing each [T, block_k] score tile — live
    memory is O(T x block_k) plus the O(T x D) grad accumulators, never
    the dense [T, T_k] square (nor scan-vjp carry residuals).

    With e_ij = exp(s_ij - m_i) the pullback of cotangents
    (ga, gl, gm) is
        ds_ij = e_ij (ga_i . v_j + gl_i) + (ind_ij / cnt_i) c_i,
        c_i   = gm_i - ga_i . acc_i - gl_i l_i,
        dv_j  = sum_i e_ij ga_i,   dq = scale ds k,   dk = scale ds^T q,
    where ind marks the row-max positions and cnt splits ties the way
    reduce_max's vjp does.  m is deliberately NOT taken from the saved
    kernel stats: it is recomputed (pass 1) from the same jnp scores
    pass 3 uses, so the ``s == m_re`` indicator compares bit-identical
    values (kernel-vs-jnp ulp differences would silently drop the gm
    cotangent).  Saved acc/l feed the c coefficient only.
    """
    b, h, tq, d = q.shape
    qf = q.astype(jnp.float32)
    q_pos = jnp.arange(tq)
    num_k, k_blocks, v_blocks = _kv_blocks(k, v, block_k)
    gaf = ga.astype(jnp.float32)

    def scores(ki, kb):
        return _masked_block_scores(
            qf, kb, ki, block_k, causal, scale, k_offset, q_pos,
            window=window,
        )

    # Pass 1: row max, recomputed so pass 3's indicator is exact.
    def max_body(m_c, inputs):
        ki, kb = inputs
        s, _ = scores(ki, kb)
        return jnp.maximum(m_c, s.max(axis=-1)), None

    m_re, _ = jax.lax.scan(
        max_body, jnp.full((b, h, tq), NEG_INF, jnp.float32),
        (jnp.arange(num_k), k_blocks),
    )

    # Pass 2: tie count at the max (reduce_max's vjp splits ties).
    def cnt_body(cnt, inputs):
        ki, kb = inputs
        s, _ = scores(ki, kb)
        return cnt + (s == m_re[..., None]).sum(axis=-1), None

    cnt, _ = jax.lax.scan(
        cnt_body, jnp.zeros((b, h, tq), jnp.int32),
        (jnp.arange(num_k), k_blocks),
    )

    c = (
        gm.astype(jnp.float32)
        - jnp.einsum("bhqd,bhqd->bhq", gaf, acc.astype(jnp.float32))
        - gl.astype(jnp.float32) * l.astype(jnp.float32)
    ) / jnp.maximum(cnt, 1).astype(jnp.float32)

    # Pass 3: grads, one K block at a time.
    def grad_body(dq, inputs):
        ki, kb, vb = inputs
        s, mask = scores(ki, kb)
        e = jnp.exp(s - m_re[..., None])               # [B,H,T,bk]
        ds = e * (
            jnp.einsum("bhqd,bhkd->bhqk", gaf, vb,
                       preferred_element_type=jnp.float32)
            + gl.astype(jnp.float32)[..., None]
        ) + jnp.where(s == m_re[..., None], c[..., None], 0.0)
        if mask is not None:
            # the dense vjp drops gradient at masked positions (the
            # `where` in the forward); mirror it for exact parity
            ds = jnp.where(mask, ds, 0.0)
        dv = jnp.einsum("bhqk,bhqd->bhkd", e, gaf)
        dk = jnp.einsum("bhqk,bhqd->bhkd", ds, qf) * scale
        dq = dq + jnp.einsum(
            "bhqk,bhkd->bhqd", ds, kb,
            preferred_element_type=jnp.float32,
        ) * scale
        return dq, (dk, dv)

    dq, (dk, dv) = jax.lax.scan(
        grad_body, jnp.zeros((b, h, tq, d), jnp.float32),
        (jnp.arange(num_k), k_blocks, v_blocks),
    )
    dk = jnp.moveaxis(dk, 0, 2).reshape(k.shape)
    dv = jnp.moveaxis(dv, 0, 2).reshape(v.shape)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _flash_partial_fwd(q, k, v, causal, scale, block_q, block_k,
                       interpret, k_offset, window):
    out = _flash_partial(q, k, v, causal, scale, block_q, block_k,
                         interpret, k_offset, window)
    acc, l, _ = out
    return out, (q, k, v, acc, l)


def _flash_partial_bwd(causal, scale, block_q, block_k, interpret,
                       k_offset, window, res, g):
    q, k, v, acc, l = res
    ga, gl, gm = g
    tk = k.shape[2]
    if tk % block_k == 0 and tk // block_k > 1:
        return _partial_stats_bwd(
            q, k, v, acc, l, ga, gl, gm, causal, scale, k_offset,
            block_k, window=window,
        )
    _, vjp = jax.vjp(
        lambda q, k, v: _partial_ref(q, k, v, causal, scale, k_offset,
                                     window=window),
        q, k, v,
    )
    return vjp((ga, gl, gm))


_flash_partial.defvjp(_flash_partial_fwd, _flash_partial_bwd)


def flash_attention_partial(q, k, v, causal=True, scale=None, k_offset=0,
                            block_q=128, block_k=128, interpret=False,
                            window=0):
    """Unnormalized online-softmax block attention: returns
    (acc [B,H,T,D] f32, l [B,H,T] f32, m [B,H,T] f32) for this KV block,
    ready to fold into a running (o, l, m) state — the per-shard step of
    ring attention.  Causal masking compares local q rows against k rows
    shifted by ``k_offset``.

    The Pallas kernel serves k_offset == 0 (the ring's diagonal block,
    where absolute and local positions coincide) and every non-causal
    block; a non-zero offset (not needed by the ring's dispatch, which
    routes lower blocks as non-causal and skips upper ones) uses the jnp
    reference."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    _check_window(window, causal)
    t, d = q.shape[2], q.shape[3]
    block_q = min(block_q, t)
    block_k = min(block_k, t)
    why = _unfriendly(t, d, block_q, block_k)
    if causal and k_offset != 0:
        why = "causal block with k_offset %d" % k_offset
    if why:
        if not interpret:
            announce_fallback("flash_attention_partial", q.shape, why)
        return _partial_ref(q, k, v, causal, scale, k_offset,
                            window=window)
    return _flash_partial(q, k, v, causal, scale, block_q, block_k,
                          interpret, k_offset, window)
