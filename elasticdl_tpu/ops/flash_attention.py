"""Pallas flash attention for TPU.

The hot op of the long-context path.  One (batch*head, live tile)
program holds a query tile in VMEM and streams the K/V tiles of the same
head through the MXU with the online-softmax accumulation, so the T x T
score matrix never materializes in HBM.

Each kernel does per score only what the score's place in the band asks
for.  The [T, T] square is cut into major tiles (``_major_tile``: 1024
where it divides T) of 128 x 128 sub-tiles, and a sub-tile is *interior*
(wholly inside the causal band or the window: no mask is built), *edge*
(crossed by the diagonal or by the window's lower edge: masked) or
*skipped* (no matmul, no vector work).  The class follows from the
tile's offset ``qi - ki`` and the static (causal, window), so a kernel
holds one straight-line branch per distinct class map (``_TilePlan``:
the diagonal tile, the interior, the window's edge) and picks it by the
offset; its grid runs over the live tiles alone, whose indices are
scalar-prefetched, so a dead tile is neither stepped through nor
fetched.  ``tile_census`` says how often each class engages for a
shape, and the compiled mode logs it once per shape.

Forward emits the per-row softmax stats (l, m) alongside the output as
[1, T] rows, and the backward is ONE Pallas call (``_bwd_kernel``,
``flash_bwd`` in a trace): grid (batch*head, live tiles) with a key
block's tiles in a row, K/V and dk's and dv's accumulators resident,
q/dO and their row constants streamed, each score tile rebuilt once IN
VMEM, transposed, as ``exp(s - lse)`` from the one saved row constant
``lse = m + log l`` (no divide per score, the scale applied once to the
f32 accumulators, ``delta = sum(dO * O)`` made once outside), and dk, dv
AND dq made from it: five matmuls and one exp pass a live sub-tile,
where a dq pass beside a dk-dv pass made seven and two.  dq's sums
cross key blocks, so a head's whole dq lives in VMEM in float32 ([T, D],
8 MB at T 16,384 and D 128; transposed for a latent head), is zeroed at
the head's first grid step and written out at its last; the call sets
its own VMEM limit (``VMEM_LIMIT``; by shape a head's dq and its output
block's buffers take 2 MB at T 2,048, 8 at 8,192 x 64, 16 at 16,384, 28
for the latent widths, 64 at 65,536), and a sequence whose dq does not
fit keeps the two passes (``_backward_plan``, from shapes alone; the
once-per-shape line says ``backward=fused dq_acc_mb=..`` or
``backward=pair why=..``).  There is no XLA backward: the [T, block]
p/ds tiles never make an HBM round-trip between einsums.  Peak memory
stays O(T·block), never the full T x T.  Operands go into the MXU in
their storage dtype (bf16), scores, stats and accumulators are f32, exp
and the final division exact.  A call on a v5e, the pair's two /
the fused one, ms (``tools/flash_kernels_on_chip.py``; my chip run, PR
41): 3.09 / 2.18 at bh 128, T 2,048, D 128; 50.85 / 37.34 at bh 128,
T 8,192, D 64; 42.84 / 30.34 at bh 32, T 16,384, D 128, 11.23 / 7.93
under a window of 2,048; 17.38 / 12.19 at bh 28 under 4,096; 70.85 /
51.09 at the latent widths.

``flash_attention_partial`` exposes the same kernel without the final
normalization, returning (acc, l, m) for one KV block — the building
block ring attention folds across ``ppermute`` hops
(parallel/ring_attention.py).  The ring's *forward* thereby skips the
dense per-shard score matrix; its backward is the hand-written
closed-form pullback ``_partial_stats_bwd`` (scans K blocks,
recomputing each [T, block_k] score tile), so each ring step's bwd is
O(T/sp x block_k) live, never the dense per-shard square.

``latent_attention`` is the same kernels for a head whose scores
run over two parts, ``D_nope + D_rope``, and whose values are ``D_v``
wide (multi-head latent attention): a second score matmul over the RoPE
parts is accumulated into the one S (``_scores``), the values, ``dO``
and the accumulators keep their own width, and the RoPE key, ONE
[seq, D_rope] plane for all the heads of a sequence, is read through a
BlockSpec whose index leaves the head out (``_tile_specs(shared=)``), so
it is never repeated to the heads in HBM; each head's part of its
gradient leaves the backward kernel in float32 and XLA sums them.  Its
calls carry the widths behind the name (``flash_fwd_qk192_v128``).  At
equal widths nothing of this is traced
(tests/flash_equal_width_program.json).  One sequence of 16,384 at 32
heads, 128 | 64 | 128, takes 20.5 ms forward and 51.1 backward on a v5e
(my chip run, PR 41; 21.3 and 71.9 with the two backward passes, PR
37).

Layout: [batch, heads, seq, head_dim]; k and v may hold fewer heads
than q, [batch, kv_heads, seq, head_dim] with ``kv_heads`` dividing
``heads`` (grouped-query attention).  Nothing is repeated for the
kernels: program ``i`` of the grid's first axis reads K/V plane ``i //
group`` (``_tile_specs(shared=group)``, the index latent attention's
one RoPE key has), and the fused backward sums dk and dv over a group's
consecutive heads in two float32 [T, D] planes in VMEM beside dq's, so
they leave at ``kv_heads`` from one rounding (``_group_sum``;
``_backward_plan`` counts the planes, 32 MB at T 16,384 and D 128, and
the once-per-shape line says ``kv_heads=.. group=..`` and
``dkv_acc_mb=..``).  ``group`` comes from the shapes alone; equal head
counts are group 1, whose index is the identity and whose program is
the one it was.  The kernels choose their own
tiling (``_major_tile``, ``_TilePlan``); a caller gives none.  A shape
they cannot take (seq not a multiple of the 128 lanes, an odd head_dim)
falls back to ``_attention_ref``, the one reference attention of the
tree, and in the compiled mode says so once per shape
(``announce_fallback``): on the chip a silent reference path is a slow
path nobody asked for.  Which of kernel, interpreter and reference runs
is ``ops/mode.py``'s answer, asked here and by no caller (an explicit
``interpret=`` is for tests and ``chip_check.py``, which force the
kernel).  Forward and Pallas backward compile and match the reference at
B8·H16·T2048, D64 and D128, full causal and windowed, on a v5e
(``chip_check.py`` at the repo root; my chip run, PR 41), where one
call at D128 takes 1.21 (forward) and 2.18 ms (backward; 1.41 + 1.69
as dq and dk-dv, PR 25): 58 and 80% of the MXU's time for the causal
half (``tools/flash_kernels_on_chip.py``; the figures of 2026-07-29 in
BENCHMARKS.md are superseded by these and by PERF_LEDGER.jsonl).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.ops.batch_shard import per_batch_shard
from elasticdl_tpu.ops.mode import kernel_mode, resolve
from elasticdl_tpu.utils.logging import get_logger

logger = get_logger(__name__)

NEG_INF = -1e30

# ``checkpoint_name``s of the forward's two results that the backward
# reads (``_flash_fwd``): what a remat policy saves to skip the second
# forward (models/remat_keep.py).
KEEP_OUT, KEEP_LSE = "flash_out", "flash_lse"

# chip_smoke.py fails an LM leg on this prefix.
FALLBACK_PREFIX = "attention fallback:"


@functools.lru_cache(maxsize=None)
def _announce_once(what, shape, why):
    logger.warning("%s %s for shape %s took the jnp reference path: %s",
                   FALLBACK_PREFIX, what, shape, why)


def announce_fallback(what, shape, why, mode=None):
    """Once per (call site, shape, reason), where the compiled kernel was
    asked for and a jnp path ran instead.  ``mode``: the op's resolved
    one; a caller outside ``ops/`` leaves it to the tracing context."""
    if (mode or kernel_mode()) == "tpu":
        _announce_once(what, shape, why)


def _attention_ref(q, k, v, causal, scale, window=0):
    """jnp reference in the same layout: q [B, H, T, D], k and v
    [B, G, T, D] with G dividing H (query head i reads K/V head
    ``i // (H // G)``: the reference repeats them to the query heads,
    the kernels index them).  ``window`` > 0 limits causal attention to
    the last ``window`` positions."""
    group = q.shape[1] // k.shape[1]
    if group > 1:
        k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
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


STATS_LANES = 128  # Mosaic wants >=(8,128) tiles; the running stats ride
                   # 128 lanes broadcast inside the kernels.
SUB = 128          # edge of a score sub-tile: the MXU's width

_NT = (((1,), (1,)), ((), ()))   # a @ b^T
_NN = (((1,), (0,)), ((), ()))   # a @ b

# What a [SUB, SUB] score sub-tile needs, from where it lies in the band.
_DEAD, _EDGE, _FULL = "skipped", "edge", "interior"


def _lanes_bcast(x, head_dim):
    """[bq, 128] all-equal-lane stats -> [bq, head_dim]."""
    if head_dim == STATS_LANES:
        return x
    if head_dim < STATS_LANES:
        return x[:, :head_dim]
    return pltpu.repeat(x, head_dim // STATS_LANES, axis=1)


def _major_tile(t, row_bytes):
    """Shared fwd/bwd major-tile policy: the widest of 128 ... 1024 that
    divides t.  A grid step costs ~0.3 us and every matmul reloads the
    MXU's weights once per SUB of the streamed side, so the resident
    side wants to be long: forward / dq / dk-dv take 3.19 / 3.27 / 3.23
    ms a call at 256, 1.44 / 1.70 / 2.00 at 512, 1.21 / 1.41 / 1.69 at
    1024 (bh=128, t=2048, d=128, bf16 — my chip run, PR 25).  Rows over
    512 bytes (d=256 in f32) stop at 512: at 1024 the dk-dv kernel
    wants 19.7 MB of the 16 MB of scoped VMEM."""
    cap = 1024 if row_bytes <= 512 else 512
    return max(bs for bs in (128, 256, 512, 1024)
               if bs <= min(t, cap) and t % bs == 0)


def _sub_class(delta, causal, window):
    """(class, offset) of the sub-tile whose first query lies ``delta``
    sub-tiles below its first key.  q_pos - k_pos is offset + (row -
    lane) inside it, so it spans offset -+ (SUB - 1); an edge keeps its
    offset for the mask, the others need none."""
    if not causal:
        return _FULL, None
    lo, hi = SUB * delta - (SUB - 1), SUB * delta + (SUB - 1)
    if hi < 0 or (window and lo >= window):
        return _DEAD, None
    if lo >= 0 and (not window or hi < window):
        return _FULL, None
    return _EDGE, SUB * delta


class _TilePlan:
    """How the kernels walk the [t, t] score square: static, from
    (t, tile, causal, window) alone.

    ``branches``: [(class map, first offset, last offset)].  A major
    tile's class map ``cmap[a][b]`` (query sub-block a, key sub-block b)
    depends only on the tile's offset ``qi - ki``; a run of offsets with
    one map (the interior of the band) shares one compiled branch.
    ``q_major`` / ``k_major``: the live tiles (qi, ki) in the order the
    forward's (resp. the backward's) grid visits them; a tile with no
    live sub-tile is no grid step.
    """

    def __init__(self, t, tile, causal, window):
        self.tile, self.window = tile, window
        self.n = n = tile // SUB
        self.num = num = t // tile
        self.branches = []
        for dt in range(-(num - 1), num):
            cmap = tuple(
                tuple(_sub_class(dt * n + a - b, causal, window)
                      for b in range(n))
                for a in range(n))
            if all(cls == _DEAD for row in cmap for cls, _ in row):
                continue
            if self.branches and self.branches[-1][0] == cmap:
                self.branches[-1][2] = dt
            else:
                self.branches.append([cmap, dt, dt])
        self.dt_min, self.dt_max = self.branches[0][1], self.branches[-1][2]
        self.q_major = [(qi, ki) for qi in range(num) for ki in range(num)
                        if self.dt_min <= qi - ki <= self.dt_max]
        self.k_major = sorted(self.q_major, key=lambda qk: (qk[1], qk[0]))
        subs = t // SUB
        self.census = {cls: 0 for cls in (_FULL, _EDGE, _DEAD)}
        for delta in range(-(subs - 1), subs):
            self.census[_sub_class(delta, causal, window)[0]] += (
                subs - abs(delta))

    def tables(self, order):
        """(qi, ki) of each grid step as two int32 arrays, for scalar
        prefetch."""
        return (jnp.asarray([qk[0] for qk in order], jnp.int32),
                jnp.asarray([qk[1] for qk in order], jnp.int32))

    def for_tile(self, qi, ki, body, keys_resident=False):
        """Run ``body(cmap)`` for the class map of tile (qi, ki): one
        compiled branch per distinct map, chosen by the tile's offset.
        ``cmap[r][c]`` is indexed (resident sub-block, streamed chunk):
        (query, key) unless ``keys_resident``."""
        for cmap, first, last in self.branches:
            if keys_resident:
                cmap = tuple(zip(*cmap))
            if len(self.branches) == 1:
                body(cmap)
            else:
                pl.when((qi - ki >= first) & (qi - ki <= last))(
                    functools.partial(body, cmap))


_tile_plan = functools.lru_cache(maxsize=None)(_TilePlan)


def tile_census(bh, t, d, tile, causal, window):
    """The line that says how often the tile classes engage for a shape:
    grid steps run of the full grid, and sub-tiles by class."""
    plan = _tile_plan(t, tile, causal, window)
    return (
        "flash tiles: bh=%d t=%d d=%d %s window=%d tile=%d subtile=%d "
        "steps=%d/%d sub=%d interior + %d edge + %d skipped" % (
            bh, t, d, "causal" if causal else "full", window, tile, SUB,
            len(plan.q_major), plan.num ** 2, plan.census[_FULL],
            plan.census[_EDGE], plan.census[_DEAD]))


@functools.lru_cache(maxsize=None)
def announce_tiles(*shape, kv_heads=None, backward=None):
    """Once per compiled shape, beside ``announce_fallback``: the tile
    census, the K/V planes the call reads (``kv_heads``, batch x heads
    as ``bh``, and the query heads to each: ``group``) and, where the
    caller says, which backward the shape gets (``_backward_plan``'s
    two words)."""
    kv_heads = kv_heads or shape[0]
    logger.info(tile_census(*shape)
                + " kv_heads=%d group=%d" % (kv_heads, shape[0] // kv_heads)
                + (" backward=%s %s" % backward if backward else ""))


def _live_span(cmap, c):
    """[r0, r1): the resident sub-blocks with work against streamed
    chunk ``c`` (contiguous: the band is), or None."""
    live = [r for r, row in enumerate(cmap) if row[c][0] != _DEAD]
    return (live[0], live[-1] + 1) if live else None


def _mask_edges(s, cmap, c, window, keys_resident=False):
    """NEG_INF outside the band, on the edge sub-blocks alone of ``s``,
    the [live span, SUB] scores against streamed chunk ``c``.  Rows are
    queries and lanes keys, or the reverse when ``keys_resident``."""
    r0, r1 = _live_span(cmap, c)
    if all(cmap[r][c][0] != _EDGE for r in range(r0, r1)):
        return s
    row = jax.lax.broadcasted_iota(jnp.int32, (SUB, SUB), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (SUB, SUB), 1)
    diff = lane - row if keys_resident else row - lane
    blocks = []
    for r in range(r0, r1):
        blk = _sub_blocks(s, r - r0, r - r0 + 1)
        cls, off = cmap[r][c]
        if cls == _EDGE:
            keep = None                 # q_pos - k_pos = off + diff
            if off - (SUB - 1) < 0:
                keep = diff >= -off
            if window and off + (SUB - 1) >= window:
                below = diff < window - off
                keep = below if keep is None else keep & below
            blk = jnp.where(keep, blk, NEG_INF)
        blocks.append(blk)
    return _stack(blocks)


def _sub_blocks(x, r0, r1):
    """Rows [r0, r1) of ``x`` in sub-blocks of SUB."""
    if (r0, r1 * SUB) == (0, x.shape[0]):
        return x
    return lax.slice(x, (r0 * SUB, 0), (r1 * SUB, x.shape[1]))


def _stack(blocks):
    return blocks[0] if len(blocks) == 1 else lax.concatenate(blocks, 0)


def _accumulate(acc_scr, cmap, parts, w_ref):
    """acc[live rows] += parts[c] @ w[chunk c], for each streamed chunk
    c.  (One matmul over a run of chunks with the same live rows takes
    exactly as long on the v5e: 1.2083 / 1.2077 / 1.2073 ms for the
    forward at 1, 2, 8 chunks a matmul — my chip run, PR 25.)"""
    for c, x in parts.items():
        r0, r1 = _live_span(cmap, c)
        rows = slice(r0 * SUB, r1 * SUB)
        acc_scr[rows, :] = lax.add(acc_scr[rows, :], lax.dot_general(
            x, w_ref[0, c * SUB:(c + 1) * SUB, :], _NN,
            preferred_element_type=jnp.float32))


# The kernel bodies below are Python-unrolled per sub-tile and traced on
# every launch (the compile cache saves the compile, not the trace), the
# forward twice under grad (as the custom_vjp's function and as its
# forward rule).  Where both operands already have one shape
# they use ``lax`` primitives, not operators: a jnp wrapper costs ~5x the
# tracing of the primitive it binds, ~1,500 times a launch.

# Key chunks of SUB the forward folds into its running stats at once:
# 1.93 / 1.82 / 1.44 ms a call at 1 / 2 / 4 (tile 512), 1.25 / 1.21 / 1.33
# at 2 / 4 / 8 (tile 1024) — my chip run, PR 25.
_FWD_STATS_CHUNKS = 4
# ... and the query sub-blocks with the same live chunks that fold as one
# array: 1.208 ms a call at 1, 2 and 4, 1.230 at 8; at 4 the kernel traces
# to 700 equations for 1,309 (start-up pays the trace on every launch).
_FWD_ROW_RUN = 4


def _scores(a_ref, b_ref, rows, cols, rope, scale):
    """[rows, cols] scaled scores of two resident blocks, f32.  ``rope``:
    None, or the two blocks' RoPE parts, whose product over their own
    width is added before the scale (a latent-attention head: scores
    over ``D_nope + D_rope``, two matmuls into one S)."""
    s = lax.dot_general(
        a_ref[0, rows, :], b_ref[0, cols, :], _NT,
        preferred_element_type=jnp.float32,
    )
    if rope is not None:
        s = lax.add(s, lax.dot_general(
            rope[0][0, rows, :], rope[1][0, cols, :], _NT,
            preferred_element_type=jnp.float32))
    return s * scale


def _flash_kernel(qi_tab, ki_tab, q_ref, k_ref, v_ref, *rest, plan, scale,
                  normalize, rope=False):
    # grid: (bh, live tiles), the tiles of one query block in a row, K
    # ascending.  Each grid step sees ONE [1, tile, D] K/V tile —
    # Pallas's automatic pipelining streams tiles HBM->VMEM overlapped
    # with compute, so VMEM never holds the full sequence.  The running
    # (acc, l, m) lives in VMEM scratch, persistent across a query
    # block's steps.  Stats stay 2D [tile, STATS_LANES] (every lane
    # equal) so all vector ops live on full (8, 128) tiles, and leave
    # as one [1, tile] row each.  With ``rope`` two more inputs follow
    # v: the queries' and the keys' RoPE parts (``_scores``).
    qk_rope = tuple(rest[:2]) if rope else None
    o_ref, l_ref, m_ref, acc_scr, l_scr, m_scr = rest[2 * rope:]
    head_dim = v_ref.shape[2]
    step = pl.program_id(1)
    qi, ki = qi_tab[step], ki_tab[step]

    @pl.when(ki == jnp.maximum(0, qi - plan.dt_max))
    def _init():
        acc_scr[...] = jnp.zeros(acc_scr.shape, acc_scr.dtype)
        l_scr[...] = jnp.zeros(l_scr.shape, l_scr.dtype)
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, m_scr.dtype)

    def tile_body(cmap):
        # Operands stay in their storage dtype (bf16 in the mixed-
        # precision path) and accumulate in f32 via
        # preferred_element_type; the scale folds into the f32 scores.
        # Scores are taken a key chunk at a time over the query rows
        # that are live against it (the MXU streams the long side), the
        # stats a run of query sub-blocks at a time over a group of
        # chunks.
        n = plan.n
        for g0 in range(0, n, _FWD_STATS_CHUNKS):
            cols = range(g0, min(n, g0 + _FWD_STATS_CHUNKS))
            s = {}              # b -> (first live row block, scores)
            for b in cols:
                span = _live_span(cmap, b)
                if span is None:
                    continue
                sb = _scores(
                    q_ref, k_ref, slice(span[0] * SUB, span[1] * SUB),
                    slice(b * SUB, (b + 1) * SUB), qk_rope, scale)
                s[b] = span[0], _mask_edges(sb, cmap, b, plan.window)
            # Query sub-blocks with the same live chunks fold together.
            runs = []           # [a0, a1, live chunks]
            for a in range(n):
                live = [b for b in s if cmap[a][b][0] != _DEAD]
                if (runs and runs[-1][1:] == [a, live]
                        and a - runs[-1][0] < _FWD_ROW_RUN):
                    runs[-1][1] = a + 1
                elif live:
                    runs.append([a, a + 1, live])
            p = {b: [] for b in s}          # b -> its live blocks, a up
            for a0, a1, live in runs:
                rows = slice(a0 * SUB, a1 * SUB)
                blocks = [_sub_blocks(s[b][1], a0 - s[b][0], a1 - s[b][0])
                          for b in live]
                m_prev = m_scr[rows, :]
                m_new = jnp.maximum(
                    m_prev, functools.reduce(lax.max, blocks).max(
                        axis=-1, keepdims=True))
                alpha = lax.exp(lax.sub(m_prev, m_new))    # [rows, LANES]
                ps = [lax.exp(lax.sub(blk, m_new)) for blk in blocks]
                l_scr[rows, :] = lax.mul(l_scr[rows, :], alpha) + (
                    functools.reduce(lax.add, ps).sum(
                        axis=-1, keepdims=True))
                m_scr[rows, :] = m_new
                acc_scr[rows, :] = lax.mul(
                    acc_scr[rows, :], _lanes_bcast(alpha, head_dim))
                for b, pb in zip(live, ps):
                    p[b].append(
                        lax.convert_element_type(pb, v_ref.dtype))
            _accumulate(acc_scr, cmap,
                        {b: _stack(blocks) for b, blocks in p.items()},
                        v_ref)

    plan.for_tile(qi, ki, tile_body)

    @pl.when(ki == jnp.minimum(plan.num - 1, qi - plan.dt_min))
    def _finish():
        acc = acc_scr[...]
        l = l_scr[...]
        if normalize:
            o_ref[0] = (
                acc / _lanes_bcast(jnp.maximum(l, 1e-30), head_dim)
            ).astype(o_ref.dtype)
        else:
            o_ref[0] = acc.astype(o_ref.dtype)
        l_ref[0] = l.T[0:1, :]
        m_ref[0] = m_scr[...].T[0:1, :]


def _plane_of(n):
    """Program i of the grid's first axis -> the plane it reads of an
    array that holds one for every ``n`` consecutive programs (n <= 1:
    its own, and no division is traced)."""
    return (lambda i: i // n) if n > 1 else (lambda i: i)


def _tile_specs(tile, d, order_axis, shared=0):
    """(resident, streamed, resident-row stats, streamed-row stats)
    BlockSpecs over a (bh, live tiles) grid whose step s works on tile
    (qi_tab[s], ki_tab[s]); ``order_axis`` 0 keeps the query block
    resident (forward), 1 the key block (backward).  ``shared`` = n > 1:
    the array holds one plane for every n consecutive values of the
    grid's first axis, [bh / n, T, d] (latent attention's one RoPE key
    for the H heads of a batch row; K and V at their own head count,
    one for a group of query heads), and the block's index is the
    plane's: (b * H + h) // n."""
    lead = _plane_of(shared)

    def resident(i, s, qi_tab, ki_tab):
        return (lead(i), (qi_tab, ki_tab)[order_axis][s], 0)

    def streamed(i, s, qi_tab, ki_tab):
        return (lead(i), (qi_tab, ki_tab)[1 - order_axis][s], 0)

    def row_of(index):
        def row(*args):
            i, j, _ = index(*args)
            return (i, 0, j)
        return row

    return (
        pl.BlockSpec((1, tile, d), resident),
        pl.BlockSpec((1, tile, d), streamed),
        pl.BlockSpec((1, 1, tile), row_of(resident)),
        pl.BlockSpec((1, 1, tile), row_of(streamed)),
    )


def _operand_specs(tile, d, dv, order_axis, group):
    """BlockSpecs (q, k, v, out or dO, the queries' row stats, dk, dv)
    of a call over q [bh, T, d] and K, V [bh / group, T, d | dv], one
    plane for ``group`` consecutive query heads (``_tile_specs``:
    ``order_axis`` 0 keeps the query block resident, 1 the key block);
    dk's and dv's are a query head's own [bh, T, .] blocks."""
    specs = lambda width, shared=0: _tile_specs(tile, width, order_axis,
                                                shared)
    qs, ks = specs(d), specs(d, group)
    os, vs = (qs, ks) if dv == d else (specs(dv), specs(dv, group))
    query, key = order_axis, 1 - order_axis
    return (qs[query], ks[key], vs[key], os[query], qs[2 + query],
            qs[key], os[key])


def _call_name(kernel, window, widths=None):
    """The name a kernel's call carries into the compiled program and a
    device trace (``%flash_fwd_w4096.3 = ... custom-call(...)``): the
    kernel, its window where it has one, so that a trace tells a
    windowed layer's calls from a full layer's, and ``widths`` (scores',
    values') where they differ: ``flash_bwd_qk192_v128``."""
    return (kernel + ("_w%d" % window if window else "")
            + ("_qk%d_v%d" % widths if widths else ""))


def _tile_for(t, q, rope):
    """``_major_tile`` by the bytes of a score's row: q's, and its RoPE
    part's where it has one."""
    width = q.shape[3] + (rope[0].shape[3] if rope is not None else 0)
    return _major_tile(t, width * q.dtype.itemsize)


def _rope_parts(rope, order_axis, tile):
    """What a latent-attention call adds to an equal-width one: (the
    RoPE parts as the kernels take them: the queries' [bh, T, D_rope],
    the keys' [b, T, D_rope], one plane a batch row; their BlockSpecs,
    resident side first)."""
    q_rope, k_rope = rope
    b, h, t, dr = q_rope.shape
    q_specs = _tile_specs(tile, dr, order_axis)
    k_specs = _tile_specs(tile, dr, order_axis, shared=h)
    # order_axis 0: queries resident, keys streamed; 1: the reverse
    specs = ((q_specs[0], k_specs[1]) if order_axis == 0
             else (q_specs[1], k_specs[0]))
    return q_rope.reshape(b * h, t, dr), k_rope, specs


def _flash_forward(q, k, v, causal, scale, interpret, normalize=True,
                   window=0, rope=None):
    """Returns (out, l, m); out is normalized iff ``normalize``.
    k and v may hold fewer heads than q, [b, g, t, .] with g dividing
    h: the program of query head i streams the tiles of K/V head
    ``i // (h // g)``.  ``rope``: None, or (q_rope [b, h, t, dr],
    k_rope [b, t, dr]), the RoPE parts of a latent-attention head,
    whose scores run over q's width + dr and whose values are v's
    width."""
    b, h, t, d = q.shape
    dv = v.shape[3]
    bh, kv_heads = b * h, b * k.shape[1]
    group = bh // kv_heads
    qr = q.reshape(bh, t, d)
    kr = k.reshape(kv_heads, t, d)
    vr = v.reshape(kv_heads, t, dv)
    # Work per grid step must amortize the per-step pipeline overhead:
    # a wide q block and a major K/V tile of the same edge, both capped
    # by what divides t.  The grid holds the live tiles only (their
    # indices are scalar-prefetched), so a dead tile costs neither a
    # step nor a DMA.
    tile = _tile_for(t, q, rope)
    plan = _tile_plan(t, tile, causal, window)
    if not interpret:
        announce_tiles(
            bh, t, d, tile, causal, window, kv_heads=kv_heads,
            backward=_backward_plan(
                t, d, rope[0].shape[3] if rope is not None else 0,
                q.dtype.itemsize, group) if normalize
            else ("scan", "why=ring_partial"))
    q_spec, kv_spec, v_spec, o_spec, stat_spec, _, _ = _operand_specs(
        tile, d, dv, 0, group)
    operands, in_specs, widths = [qr, kr, vr], [q_spec, kv_spec, v_spec], None
    if rope is not None:
        q_rope, k_rope, specs = _rope_parts(rope, 0, tile)
        operands += [q_rope, k_rope]
        in_specs += specs
        widths = (d + q_rope.shape[2], dv)
    out_dtype = q.dtype if normalize else jnp.float32
    out, l, m = pl.pallas_call(
        functools.partial(_flash_kernel, plan=plan, scale=scale,
                          normalize=normalize, rope=rope is not None),
        out_shape=(
            jax.ShapeDtypeStruct((bh, t, dv), out_dtype),
            jax.ShapeDtypeStruct((bh, 1, t), jnp.float32),
            jax.ShapeDtypeStruct((bh, 1, t), jnp.float32),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, len(plan.q_major)),
            in_specs=in_specs,
            out_specs=(o_spec, stat_spec, stat_spec),
            scratch_shapes=[
                pltpu.VMEM((tile, dv), jnp.float32),
                pltpu.VMEM((tile, STATS_LANES), jnp.float32),
                pltpu.VMEM((tile, STATS_LANES), jnp.float32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=_call_name("flash_fwd", window, widths),
    )(*plan.tables(plan.q_major), *operands)
    return (
        out.reshape(b, h, t, dv),
        l.reshape(b, h, t),
        m.reshape(b, h, t),
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
    with mask None when not causal — what the ring's banded partial
    and the partial's stats backward both recompute from."""
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


def _bwd_dq_kernel(qi_tab, ki_tab, q_ref, do_ref, k_ref, v_ref, lse_ref,
                   delta_ref, *rest, plan, scale, rope=False):
    """The pair's dq pass, for a sequence whose dq does not fit VMEM
    (``_backward_plan``): dq = scale * sum_j ds_ij k_j, ds = p (dp -
    delta), p = exp(s - lse).  Grid (bh, live tiles), a query block's
    tiles in a row: the q/dO tiles and the row constants stay resident
    while K/V tiles stream through VMEM.  lse and delta arrive as [1,
    tile] rows and are spread over the lanes once a query block.  With
    ``rope`` (``_scores``) the RoPE parts follow the inputs, and their
    dq the outputs and the scratch."""
    if rope:
        (qr_ref, kr_ref, dq_ref, dqr_ref, dq_scr, lse_scr, delta_scr,
         dqr_scr) = rest
        qk_rope = qr_ref, kr_ref
    else:
        dq_ref, dq_scr, lse_scr, delta_scr = rest
        qk_rope = None
    step = pl.program_id(1)
    qi, ki = qi_tab[step], ki_tab[step]
    tile = plan.tile

    @pl.when(ki == jnp.maximum(0, qi - plan.dt_max))
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, dq_scr.dtype)
        if rope:
            dqr_scr[...] = jnp.zeros(dqr_scr.shape, dqr_scr.dtype)
        lse_scr[...] = jnp.broadcast_to(
            lse_ref[0], (STATS_LANES, tile)).T
        delta_scr[...] = jnp.broadcast_to(
            delta_ref[0], (STATS_LANES, tile)).T

    def tile_body(cmap):
        ds = {}
        for b in range(plan.n):
            span = _live_span(cmap, b)
            if span is None:
                continue
            rows = slice(span[0] * SUB, span[1] * SUB)
            keys = slice(b * SUB, (b + 1) * SUB)
            s = _scores(q_ref, k_ref, rows, keys, qk_rope,
                        scale)                         # [rows, SUB]
            s = _mask_edges(s, cmap, b, plan.window)
            p = lax.exp(lax.sub(s, lse_scr[rows, :]))
            dp = lax.dot_general(
                do_ref[0, rows, :], v_ref[0, keys, :], _NT,
                preferred_element_type=jnp.float32,
            )
            ds[b] = lax.convert_element_type(
                lax.mul(p, lax.sub(dp, delta_scr[rows, :])), k_ref.dtype)
        _accumulate(dq_scr, cmap, ds, k_ref)
        if rope:
            _accumulate(dqr_scr, cmap, ds, kr_ref)

    plan.for_tile(qi, ki, tile_body)

    @pl.when(ki == jnp.minimum(plan.num - 1, qi - plan.dt_min))
    def _finish():
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)
        if rope:
            dqr_ref[0] = (dqr_scr[...] * scale).astype(dqr_ref.dtype)


def _dq_rows(dq_scr, first_row, ds_t, cmap, k_ref):
    """dq[the streamed tile's queries] += ds k, a key chunk at a time:
    the [SUB, SUB] blocks of ``ds_t`` ([keys, queries], by query
    sub-block) that are live against chunk c are transposed on the XLU
    and stacked, so that one matmul streams every live query row past
    the chunk's [SUB, D] keys, as the dq pass of the pair did.  (The
    same product with the transposed operand left to the matmul, a
    query sub-block at a time, streams SUB rows a weight tile and takes
    three times as long: 42.7 for 30.3 ms a call, bh=32, t=16384,
    d=128 — my chip run, PR 41.)"""
    for c in range(len(cmap)):
        live = [a for a in ds_t if cmap[c][a][0] != _DEAD]
        if not live:
            continue
        blocks = []
        for a in live:
            r = c - _live_span(cmap, a)[0]
            blocks.append(_sub_blocks(ds_t[a], r, r + 1).T)
        rows = pl.ds(pl.multiple_of(first_row + live[0] * SUB, SUB),
                     len(live) * SUB)
        dq_scr[rows, :] = lax.add(dq_scr[rows, :], lax.dot_general(
            _stack(blocks), k_ref[0, c * SUB:(c + 1) * SUB, :], _NN,
            preferred_element_type=jnp.float32))


def _group_sum(out_ref, plane, acc_scr, rows, member, group, scale):
    """One query head's [tile, D] ``acc_scr`` (a key block's dk or dv)
    into ``rows`` of its K/V head's float32 ``plane``: head ``member``
    of the ``group`` that reads the K/V head starts the rows, adds to
    them, or adds, scales, casts and writes them to the K/V head's
    output block."""
    @pl.when(member == 0)
    def _first():
        plane[rows, :] = acc_scr[...]

    if group > 2:
        @pl.when((member > 0) & (member < group - 1))
        def _add():
            plane[rows, :] = lax.add(plane[rows, :], acc_scr[...])

    @pl.when(member == group - 1)
    def _last():
        out_ref[0, rows, :] = (
            lax.add(plane[rows, :], acc_scr[...]) * scale
        ).astype(out_ref.dtype)


def _bwd_kernel(qi_tab, ki_tab, k_ref, v_ref, q_ref, do_ref, lse_ref,
                delta_ref, *rest, plan, scale, rope=False, group=1):
    """The whole backward from one rebuild of each score tile: dk_j =
    scale * sum_i ds_ij q_i, dv_j = sum_i p_ij dO_i and dq_i = scale *
    sum_j ds_ij k_j, with p = exp(s - lse), ds = p (dp - delta).  Grid
    (bh, live tiles), a key block's tiles in a row: the K/V tiles and
    their accumulators stay resident while q/dO tiles and their row
    constants stream through.  The score tiles are built transposed,
    [keys, queries], so that dk's and dv's matmuls contract a minor
    dimension with a major one (no transpose of p or ds) and lse, delta
    are [1, SUB] rows spread over sublanes.

    dq's sums cross key blocks, which are not consecutive grid steps,
    so a head's whole dq is accumulated in VMEM in float32, zeroed at
    the head's first step and scaled, cast and written out at its last
    (the output block is the head's: it stays in VMEM until the head
    changes).  At equal widths it is [T, D] and takes ``_dq_rows``.
    With ``rope`` (``_scores``: the RoPE parts follow the inputs, their
    gradients the outputs) it is kept transposed, [T / tile, D + D_rope,
    tile]: dq^T = [k | k_rope]^T ds_t needs no transpose of ds, the
    keys' is made once a key block, and 192 rows stream past each
    weight tile (at 128 the weights' load is not hidden: 42.1 for 30.3
    ms at equal widths; the latent call takes 51.1 this way, 54.2 by
    ``_dq_rows`` — my chip run, PR 41).  This head's part of the RoPE
    key's gradient, scale * sum_i ds_ij q_rope_i, leaves in float32:
    the key is one plane for the heads, its gradient their sum, which
    the caller takes.

    ``group`` > 1: K and V hold one head for ``group`` consecutive
    values of the grid's first axis (their blocks' index is ``head //
    group``), and dk, dv leave at that head count, summed over the
    group: a K/V head's whole [T, D] dk and dv are float32 planes in
    VMEM beside dq's, and their output blocks the K/V head's.  A key
    block's accumulators, when its last query tile is done, start the
    plane's rows (the group's first head), are added to them, or (the
    last head) are added, scaled, cast and written out: one rounding of
    the float32 sum, where XLA summed ``group`` rounded planes.

    Results, and scratch in the same order: dk, dv (, dk_rope), then dq
    (, dq_rope) and dq's accumulator (, the keys transposed), then the
    group's two planes.  A call without dq (``_backward_plan``: a
    sequence whose accumulators do not fit) is the pair's dk-dv pass."""
    planes = rest[len(rest) - 2:] if group > 1 else ()
    rest = rest[:len(rest) - len(planes)]
    kq_rope = None
    if rope:
        kr_ref, qr_ref, *rest = rest
        kq_rope = kr_ref, qr_ref
    n = 2 + rope
    outs, scrs = rest[:len(rest) // 2], rest[len(rest) // 2:]
    (dk_ref, dv_ref), (dk_scr, dv_scr) = outs[:2], scrs[:2]
    dq_refs = outs[n:]          # none: the pair's dk-dv pass
    dq_scr = scrs[n] if dq_refs else None
    step = pl.program_id(1)
    qi, ki = qi_tab[step], ki_tab[step]
    tile, d = plan.tile, k_ref.shape[2]
    if planes:                  # which of its K/V head's query heads
        member = pl.program_id(0) % group

    if dq_refs:
        @pl.when(step == 0)
        def _init_head():
            dq_scr[...] = jnp.zeros(dq_scr.shape, dq_scr.dtype)

    @pl.when(qi == jnp.maximum(0, ki + plan.dt_min))
    def _init():
        for scr in scrs[:n]:
            scr[...] = jnp.zeros(scr.shape, scr.dtype)
        if dq_refs and rope:
            kt_scr = scrs[n + 1]
            kt_scr[:d, :] = k_ref[0].T
            kt_scr[d:, :] = kr_ref[0].T

    def tile_body(cmap):
        p_t, ds_t = {}, {}
        for a in range(plan.n):
            span = _live_span(cmap, a)
            if span is None:
                continue
            rows = slice(span[0] * SUB, span[1] * SUB)     # keys
            qs = slice(a * SUB, (a + 1) * SUB)
            s = _scores(k_ref, q_ref, rows, qs, kq_rope,
                        scale)                         # [keys, SUB]
            s = _mask_edges(s, cmap, a, plan.window, keys_resident=True)
            p = lax.exp(s - lse_ref[0, :, qs])
            dp = lax.dot_general(
                v_ref[0, rows, :], do_ref[0, qs, :], _NT,
                preferred_element_type=jnp.float32,
            )
            p_t[a] = lax.convert_element_type(p, do_ref.dtype)
            ds_t[a] = lax.convert_element_type(
                lax.mul(p, dp - delta_ref[0, :, qs]), q_ref.dtype)
            if dq_refs and rope:
                dq_scr[qi, :, qs] = lax.add(
                    dq_scr[qi, :, qs], lax.dot_general(
                        scrs[n + 1][:, rows], ds_t[a], _NN,
                        preferred_element_type=jnp.float32))
        _accumulate(dv_scr, cmap, p_t, do_ref)
        _accumulate(dk_scr, cmap, ds_t, q_ref)
        if rope:
            _accumulate(scrs[2], cmap, ds_t, qr_ref)
        elif dq_refs:
            _dq_rows(dq_scr, qi * tile, ds_t, cmap, k_ref)

    plan.for_tile(qi, ki, tile_body, keys_resident=True)

    @pl.when(qi == jnp.minimum(plan.num - 1, ki + plan.dt_max))
    def _finish():
        if planes:
            rows = pl.ds(pl.multiple_of(ki * tile, tile), tile)
            _group_sum(dk_ref, planes[0], dk_scr, rows, member, group, scale)
            _group_sum(dv_ref, planes[1], dv_scr, rows, member, group, 1.0)
            return
        dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)
        if rope:
            outs[2][0] = (scrs[2][...] * scale).astype(outs[2].dtype)

    if dq_refs:
        @pl.when(step == pl.num_programs(1) - 1)
        def _finish_head():
            if not rope:
                dq_refs[0][0] = (dq_scr[...] * scale).astype(
                    dq_refs[0].dtype)
                return
            dq_ref, dqr_ref = dq_refs
            for j in range(plan.num):
                at = slice(j * tile, (j + 1) * tile)
                acc = dq_scr[j] * scale                  # [d + dr, tile]
                dq_ref[0, at, :] = acc[:d].T.astype(dq_ref.dtype)
                dqr_ref[0, at, :] = acc[d:].T.astype(dqr_ref.dtype)


# What the fused backward call may hold of VMEM, and what of that a
# head's dq may take (its float32 accumulator and the two buffers of
# its output block) with, where a group of query heads reads one K/V
# head, that head's dk and dv planes and their blocks' buffers.  A v5e
# core has 128 MiB; the rest of the call (the tiles, a key block's dk
# and dv accumulators) fits the 16 MiB a call has by default, as the
# dk-dv pass did.
VMEM_LIMIT = 96 * 2 ** 20
_DQ_VMEM = VMEM_LIMIT - 16 * 2 ** 20


def _backward_plan(t, d, d_rope, itemsize, group=1):
    """("fused", the MB of VMEM a head's dq takes there) or ("pair",
    why): which backward ``_pallas_bwd`` runs, from the shapes alone.
    The fused call wants a head's whole dq in VMEM, and with ``group``
    > 1 query heads to a K/V head that head's whole dk and dv beside it
    (``dkv_acc_mb``: a float32 plane and an output block's two buffers
    each, 32 MB at T 16,384 and d=128 in bfloat16); a sequence too long
    for that (over 65,536 at d=128 in bfloat16, 32,768 already with a
    group) keeps the two passes, each of which holds a tile's, and a
    group's dk and dv are then summed outside."""
    lanes = lambda width: -(-width // STATS_LANES) * STATS_LANES
    out = 2 * itemsize * (lanes(d) + lanes(d_rope))   # two buffers each
    # float32: [d + d_rope, T] for a latent head, else [T, d]
    nbytes = t * (out + 4 * (d + d_rope if d_rope else lanes(d)))
    mb = -(-nbytes // 2 ** 20)
    said = "dq_acc_mb=%d" % mb
    if group > 1:
        planes = 2 * t * lanes(d) * (4 + 2 * itemsize)
        nbytes += planes
        said += " dkv_acc_mb=%d" % -(-planes // 2 ** 20)
    if nbytes > _DQ_VMEM:
        return "pair", "why=%s_over_%d" % (
            said.replace("=", "_").replace(" ", "_"), _DQ_VMEM // 2 ** 20)
    return "fused", said


def _pallas_bwd(q, k, v, out, lse, g, causal, scale, interpret,
                window=0, rope=None):
    """Pallas backward: one call, keys resident, that makes dk, dv and
    dq from one rebuild of each score tile (``_bwd_kernel``); for a
    sequence whose dq does not fit VMEM (``_backward_plan``), dk/dv in
    one pass (Q streamed) and dq in another (K streamed).  The
    probability/ds tiles live only in VMEM.  What is constant along a
    row is made once, out here: lse came with the residuals, delta_i =
    sum_d dO_i O_i is one pass over dO and O.  Returns (dq, dk, dv), dk
    and dv at k's and v's own head count (``_flash_forward``: the sum
    over the query heads of a group, made in the fused call's VMEM, or
    outside from the pair's per-head results) and,
    with ``rope`` (``_flash_forward``), also (dq_rope [b, h, t, dr],
    dk_rope [b, h, t, dr] float32: each head's part of the RoPE key's
    gradient, the caller's to sum: the key is one plane)."""
    b, h, t, d = q.shape
    dv = v.shape[3]
    bh, kv_heads = b * h, b * k.shape[1]
    group = bh // kv_heads
    tile = _tile_for(t, q, rope)
    plan = _tile_plan(t, tile, causal, window)
    qr = q.reshape(bh, t, d)
    kr = k.reshape(kv_heads, t, d)
    vr = v.reshape(kv_heads, t, dv)
    gr = g.astype(q.dtype).reshape(bh, t, dv)
    lse = lse.astype(jnp.float32).reshape(bh, 1, t)
    delta = (
        gr.astype(jnp.float32) * out.reshape(bh, t, dv).astype(jnp.float32)
    ).sum(axis=-1).reshape(bh, 1, t)
    latent = rope is not None
    dr = rope[0].shape[3] if latent else 0
    fused = _backward_plan(t, d, dr, q.dtype.itemsize, group)[0] == "fused"
    scratch = lambda width: pltpu.VMEM((tile, width), jnp.float32)
    shaped = lambda width, dtype: jax.ShapeDtypeStruct((bh, t, width), dtype)
    # a plane's whole [t, width] block, for every ``heads`` grid rows
    whole = lambda width, heads=1: pl.BlockSpec(
        (1, t, width),
        lambda i, s, qi_tab, ki_tab: (_plane_of(heads)(i), 0, 0))

    def call(kernel, name, order, operands, in_specs, out_shape, out_specs,
             scratch_shapes, heads_apart=True, **params):
        # ``heads_apart``: no value outlives a step of the grid's first
        # axis; the fused call of a group sums dk, dv across them
        return pl.pallas_call(
            functools.partial(kernel, plan=plan, scale=scale, rope=latent,
                              **({} if heads_apart else {"group": group})),
            out_shape=tuple(out_shape),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(bh, len(order)),
                in_specs=in_specs,
                out_specs=tuple(out_specs),
                scratch_shapes=scratch_shapes,
            ),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=(
                    "parallel" if heads_apart else "arbitrary",
                    "arbitrary"), **params),
            interpret=interpret,
            name=_call_name(name, window, (d + dr, dv) if latent else None),
        )(*plan.tables(order), *operands)

    # Keys resident: dk, dv (, dk_rope) ...
    (q_spec, kv_spec, v_spec, do_spec, qstat_spec, dkv_spec,
     ddv_spec) = _operand_specs(tile, d, dv, 1, group)
    operands = [kr, vr, qr, gr, lse, delta]
    in_specs = [kv_spec, v_spec, q_spec, do_spec, qstat_spec, qstat_spec]
    # a query head's dk, dv, which a group's fused call does not write
    out_shape = [shaped(d, k.dtype), shaped(dv, v.dtype)]
    out_specs = [dkv_spec, ddv_spec]
    scratch_shapes = [scratch(d), scratch(dv)]
    if latent:
        q_rope, k_rope, specs = _rope_parts(rope, 1, tile)
        operands += [k_rope, q_rope]
        in_specs += specs[::-1]
        # a head's own part of the one plane's gradient
        out_shape.append(shaped(dr, jnp.float32))
        out_specs.append(_tile_specs(tile, dr, 1)[0])
        scratch_shapes.append(scratch(dr))
    if fused:
        # ... and dq (, dq_rope), a head's whole block
        out_shape.append(shaped(d, q.dtype))
        out_specs.append(whole(d))
        if latent:
            out_shape.append(shaped(dr, q_rope.dtype))
            out_specs.append(whole(dr))
            scratch_shapes += [
                pltpu.VMEM((plan.num, d + dr, tile), jnp.float32),
                pltpu.VMEM((d + dr, tile), k.dtype)]
        else:
            scratch_shapes.append(pltpu.VMEM((t, d), jnp.float32))
        if group > 1:
            # dk, dv at the K/V heads: a head's whole block, written
            # from the group's two float32 planes
            out_shape[:2] = [
                jax.ShapeDtypeStruct((kv_heads, t, d), k.dtype),
                jax.ShapeDtypeStruct((kv_heads, t, dv), v.dtype)]
            out_specs[:2] = [whole(d, group), whole(dv, group)]
            scratch_shapes += [pltpu.VMEM((t, d), jnp.float32),
                               pltpu.VMEM((t, dv), jnp.float32)]
        dkv = call(_bwd_kernel, "flash_bwd", plan.k_major, operands,
                   in_specs, out_shape, out_specs, scratch_shapes,
                   heads_apart=group == 1, vmem_limit_bytes=VMEM_LIMIT)
        dkv, dq = dkv[:2 + latent], dkv[2 + latent:]
    else:
        dkv = call(_bwd_kernel, "flash_dkv", plan.k_major, operands,
                   in_specs, out_shape, out_specs, scratch_shapes)
        if group > 1:
            dkv = [x.reshape(kv_heads, group, t, x.shape[2]).sum(
                axis=1, dtype=jnp.float32).astype(x.dtype) for x in dkv]
        # Queries resident: dq (, dq_rope).
        q_spec, kv_spec, v_spec, do_spec, qstat_spec, _, _ = _operand_specs(
            tile, d, dv, 0, group)
        operands = [qr, gr, kr, vr, lse, delta]
        in_specs = [q_spec, do_spec, kv_spec, v_spec, qstat_spec,
                    qstat_spec]
        out_shape, out_specs = [shaped(d, q.dtype)], [q_spec]
        scratch_shapes = [scratch(d), scratch(STATS_LANES),
                          scratch(STATS_LANES)]
        if latent:
            q_rope, k_rope, specs = _rope_parts(rope, 0, tile)
            operands += [q_rope, k_rope]
            in_specs += specs
            out_shape.append(shaped(dr, q_rope.dtype))
            out_specs.append(specs[0])
            scratch_shapes.append(scratch(dr))
        dq = call(_bwd_dq_kernel, "flash_dq", plan.q_major, operands,
                  in_specs, out_shape, out_specs, scratch_shapes)
    grads = (dq[0], dkv[0], dkv[1]) + ((dq[1], dkv[2]) if latent else ())
    return tuple(x.reshape(b, -1, t, x.shape[2]) for x in grads)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, scale, interpret, window=0):
    out, _, _ = _flash_forward(q, k, v, causal, scale, interpret,
                               window=window)
    return out


def _flash_fwd(q, k, v, causal, scale, interpret, window=0):
    out, l, m = _flash_forward(q, k, v, causal, scale, interpret,
                               window=window)
    # One row constant for the backward: p = exp(s - lse), no divide.
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    # Named where they are made: a ``jax.checkpoint`` around the caller
    # whose policy saves these two keeps the backward's residuals and
    # does not run this forward a second time (q, k, v are the op's
    # inputs, the caller's to name).  A name outside the op saves a
    # copy of ``out`` and the kernel still runs again.
    out = checkpoint_name(out, KEEP_OUT)
    lse = checkpoint_name(lse, KEEP_LSE)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, interpret, window, res, g):
    q, k, v, out, lse = res
    return _pallas_bwd(q, k, v, out, lse, g, causal, scale, interpret,
                       window=window)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _check_window(window, causal):
    if window and not causal:
        raise ValueError("sliding window requires causal attention")
    if window < 0:
        raise ValueError("window must be >= 0, got %d" % window)


def _unfriendly(t, d):
    """Why the kernel cannot take this shape, or "" when it can."""
    if t % STATS_LANES:
        return "seq %d is not a multiple of the %d lanes" % (
            t, STATS_LANES)
    if d % 128 and d != 64:
        return "head_dim %d is neither 64 nor a multiple of 128" % d
    return ""


def flash_mode(t, d, interpret=None):
    """(mode: "tpu" | "interpret" | "off" as ``flash_attention`` runs a
    sequence of ``t`` at heads of ``d`` here, why not the kernel or
    "")."""
    mode = resolve(interpret)
    why = _unfriendly(t, d) if mode != "off" else ""
    return ("off" if why else mode), why


def flash_attention(q, k, v, causal=True, scale=None, interpret=None,
                    window=0):
    """q: [batch, heads, seq, head_dim]; k, v: [batch, kv_heads, seq,
    head_dim], ``kv_heads`` dividing ``heads`` (grouped-query
    attention: query head i reads K/V head ``i // (heads // kv_heads)``
    and dk, dv come back at ``kv_heads``; nothing is repeated for the
    kernels, which index the K/V planes, and equal counts are the
    multi-head case).  ``window`` > 0 limits
    causal attention to the last ``window`` positions (O(T·W) compute:
    blocks outside the band skip both matmuls and DMA).  The kernel
    where ``ops/mode.py`` allows one and the shape is friendly, per
    shard of the declared batch axis; else ``_attention_ref``."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    _check_window(window, causal)
    if q.shape[1] % k.shape[1] or k.shape[1] != v.shape[1]:
        raise ValueError(
            "flash_attention takes K and V at one head count that divides "
            "the queries'; got q %s, k %s, v %s"
            % (q.shape, k.shape, v.shape))
    mode, why = flash_mode(q.shape[2], q.shape[3], interpret)
    if mode != "off":
        return per_batch_shard(
            lambda q, k, v: _flash(q, k, v, causal, scale,
                                   mode == "interpret", window),
            (q, k, v),
        )
    if why:
        announce_fallback("flash_attention", q.shape, why,
                          resolve(interpret))
    return _attention_ref(q, k, v, causal, scale, window=window)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _latent(q_nope, q_rope, k_nope, k_rope, v, causal, scale, interpret,
            window=0):
    out, _, _ = _flash_forward(q_nope, k_nope, v, causal, scale, interpret,
                               window=window, rope=(q_rope, k_rope))
    return out


def _latent_fwd(q_nope, q_rope, k_nope, k_rope, v, causal, scale,
                interpret, window=0):
    out, l, m = _flash_forward(q_nope, k_nope, v, causal, scale, interpret,
                               window=window, rope=(q_rope, k_rope))
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    out = checkpoint_name(out, KEEP_OUT)          # as ``_flash_fwd``
    lse = checkpoint_name(lse, KEEP_LSE)
    return out, (q_nope, q_rope, k_nope, k_rope, v, out, lse)


def _latent_bwd(causal, scale, interpret, window, res, g):
    q_nope, q_rope, k_nope, k_rope, v, out, lse = res
    dq, dk, dv, dq_rope, dk_rope = _pallas_bwd(
        q_nope, k_nope, v, out, lse, g, causal, scale, interpret,
        window=window, rope=(q_rope, k_rope))
    # one plane for the heads: its gradient is the sum of their parts
    return (dq, dq_rope, dk, dk_rope.sum(axis=1).astype(k_rope.dtype), dv)


_latent.defvjp(_latent_fwd, _latent_bwd)


def _latent_ref(q_nope, q_rope, k_nope, k_rope, v, causal, scale,
                window=0):
    """The latent op by ``_attention_ref``: a head's q and k put together
    the long way, the one RoPE key spread to every head."""
    k_rope = jnp.broadcast_to(k_rope[:, None], q_rope.shape)
    return _attention_ref(jnp.concatenate([q_nope, q_rope], axis=-1),
                          jnp.concatenate([k_nope, k_rope], axis=-1), v,
                          causal, scale, window=window)


def latent_mode(t, d_nope, d_rope, d_v, itemsize=2, interpret=None):
    """(mode: "tpu" | "interpret" | "off" as ``latent_attention`` runs
    shapes of these sizes here, the kernels' major tile or 0, why not
    the kernel or "")."""
    mode = resolve(interpret)
    if mode == "off":
        return mode, 0, ""
    why = next((w for w in (_unfriendly(t, d) for d in (d_nope, d_rope,
                                                        d_v)) if w), "")
    if why:
        return "off", 0, why
    return mode, _major_tile(t, (d_nope + d_rope) * itemsize), ""


def latent_attention(q_nope, q_rope, k_nope, k_rope, v, causal=True,
                     scale=None, interpret=None, window=0):
    """Attention whose scores run over ``D_nope + D_rope`` and whose
    values are ``D_v`` wide (multi-head latent attention): q_nope and
    k_nope [batch, heads, seq, D_nope], q_rope [batch, heads, seq,
    D_rope], v [batch, heads, seq, D_v] -> [batch, heads, seq, D_v].
    ``k_rope`` is [batch, seq, D_rope], ONE RoPE key for all the heads
    of a sequence, which the kernels read as one plane (its block's
    index leaves the head out; its gradient is the heads' sum).  A
    head's score is ``(q_nope . k_nope + q_rope . k_rope) * scale``, two
    matmuls into one S under the tile plan of ``flash_attention``;
    ``scale`` defaults to ``(D_nope + D_rope) ** -0.5``.  Kernel,
    interpreter or ``_latent_ref`` as ``flash_attention`` chooses."""
    d_nope, d_rope, d_v = q_nope.shape[3], q_rope.shape[3], v.shape[3]
    if k_rope.ndim != 3:
        raise ValueError(
            "latent_attention takes the RoPE key as one [batch, seq, "
            "D_rope] plane for all the heads; got %s" % (k_rope.shape,))
    scale = scale if scale is not None else (d_nope + d_rope) ** -0.5
    _check_window(window, causal)
    mode, _, why = latent_mode(q_nope.shape[2], d_nope, d_rope, d_v,
                               q_nope.dtype.itemsize, interpret)
    if mode != "off":
        return per_batch_shard(
            lambda *a: _latent(*a, causal, scale, mode == "interpret",
                               window),
            (q_nope, q_rope, k_nope, k_rope, v),
        )
    if why:
        announce_fallback("latent_attention", q_nope.shape + (d_rope, d_v),
                          why, resolve(interpret))
    return _latent_ref(q_nope, q_rope, k_nope, k_rope, v, causal, scale,
                       window=window)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_partial(q, k, v, causal, scale, interpret, k_offset, window):
    # causal here means the diagonal (k_offset == 0) block, where the
    # kernel's absolute-position mask equals the local mask.
    out, l, m = _flash_forward(
        q, k, v, causal=causal, scale=scale, interpret=interpret,
        normalize=False, window=window,
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


def _partial_banded(q, k, v, scale, k_offset, window, block_k=SUB):
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


def _flash_partial_fwd(q, k, v, causal, scale, interpret, k_offset,
                       window):
    out = _flash_partial(q, k, v, causal, scale, interpret, k_offset,
                         window)
    acc, l, _ = out
    return out, (q, k, v, acc, l)


def _flash_partial_bwd(causal, scale, interpret, k_offset, window, res,
                       g):
    q, k, v, acc, l = res
    ga, gl, gm = g
    tk = k.shape[2]
    if tk // SUB > 1:       # tk is a multiple of SUB: the kernel ran
        return _partial_stats_bwd(
            q, k, v, acc, l, ga, gl, gm, causal, scale, k_offset, SUB,
            window=window,
        )
    _, vjp = jax.vjp(
        lambda q, k, v: _partial_ref(q, k, v, causal, scale, k_offset,
                                     window=window),
        q, k, v,
    )
    return vjp((ga, gl, gm))


_flash_partial.defvjp(_flash_partial_fwd, _flash_partial_bwd)


def flash_attention_partial(q, k, v, causal=True, scale=None, k_offset=0,
                            interpret=None, window=0):
    """Unnormalized online-softmax block attention: returns
    (acc [B,H,T,D] f32, l [B,H,T] f32, m [B,H,T] f32) for this KV block,
    ready to fold into a running (o, l, m) state — the per-shard step of
    ring attention.  Causal masking compares local q rows against k rows
    shifted by ``k_offset``.

    The Pallas kernel serves k_offset == 0 (the ring's diagonal block,
    where absolute and local positions coincide) and every non-causal
    block; a non-zero offset (not needed by the ring's dispatch, which
    routes lower blocks as non-causal and skips upper ones) uses the jnp
    reference ``_partial_ref``, as does the mode ``off``."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    _check_window(window, causal)
    mode = resolve(interpret)
    if mode != "off":
        why = _unfriendly(q.shape[2], q.shape[3])
        if causal and k_offset != 0:
            why = "causal block with k_offset %d" % k_offset
        if not why:
            return _flash_partial(q, k, v, causal, scale,
                                  mode == "interpret", k_offset, window)
        announce_fallback("flash_attention_partial", q.shape, why, mode)
    return _partial_ref(q, k, v, causal, scale, k_offset, window=window)
