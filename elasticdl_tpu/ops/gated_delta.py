"""Gated delta rule (Pallas TPU): the sequence mixer of a Gated DeltaNet
layer, a recurrence over T run a chunk of tokens at a time.

``gated_delta(q, k [B, H, T, d_k], v [B, H, T, d_v], g, beta [B, H, T])
-> o [B, H, T, d_v]``.  A head carries a float32 state ``S`` [d_v, d_k],
zero before a sequence's first token, and at every token

    S_t = alpha_t S_(t-1) (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    o_t = S_t q_t

with ``alpha_t = exp(g_t)`` in (0, 1] (``g`` the log decay, <= 0) and a
write strength ``beta_t`` that may pass 1 (in (0, 2) the transition
keeps an eigenvalue in (-1, 1)).  q and k come normalised and scaled by
the caller; nothing here knows how.

The chunk form (Yang, Kautz, Hatamizadeh, "Gated Delta Networks",
arXiv:2412.06464, section 3.3).  Inside a chunk of C tokens with start
state ``h`` (= S^T, [d_k, d_v]), ``b`` the cumulative sum of ``g`` from
the chunk's first token and ``gamma = exp(b)``:

    A   = strictly_lower(diag(beta) (K K^T * exp(b_r - b_i)))
    U   = (I + A)^-1 diag(beta) (V - diag(gamma) K h)       [C, d_v]
    O   = diag(gamma) Q h + lower(Q K^T * exp(b_r - b_i)) U
    h'  = gamma_C h + (diag(exp(b_C - b)) K)^T U

``(I + A)^-1`` is taken by matmuls alone, no row-by-row substitution
(``_inverse``: pairs of tokens, then blocks joined in pairs).  Every exponent
is a difference ``b_r - b_i`` with i <= r, never positive: nothing
overflows however fast a head forgets.

``A`` reads k, ``b`` and beta and never the state: a chunk's inverse is
made ONCE a forward call, beside the chain of matmuls that carries the
state and not in it, and for P chunks a run of the joins (``PACKS``: two
of 64 on the diagonal blocks of one [128, 128] operand, the MXU's width;
``_inverses``).  What reads the state is ``_chunk``, what both kernels
need of a chunk before it ``_decays``.

Two kernels, one grid step a (block of heads, pack of P chunks), the
pack axis sequential:

 - ``gdn_fwd``: the state of each head of the block resident in VMEM in
   float32 across the pack axis; q, k, v stream through a pack at a
   time; a step builds its heads' inverses, then walks its chunks.
   Writes ``o``, each chunk's START state (float32, [B * H, T / C, d_k,
   d_v]: 283 MB a layer at 15 heads x 16,384 x 96 | 192) and, third,
   the inverses in the compute dtype, the one both kernels multiply by
   ([B * H, T / (P C), C, P C], a pack's side by side: 31.5 MB a layer);
 - ``gdn_bwd``: the same grid walked from the last chunk to the first,
   the state's cotangent resident; takes the start states and the
   inverses as operands, rebuilds the chunk's U and scores from them and
   q, k, v, and writes dq, dk, dv and the cotangents of ``b`` and
   ``beta``.  No product of it runs at the highest precision.

Float32 whatever the compute dtype: the cumulative sums of ``g``, the
decays, ``A`` and the joins of its inverse (matmuls at the highest
precision: Mosaic's default contracts float32 operands in bfloat16), the
state and its cotangent.  The other matmuls take their operands in the
compute dtype (q's) and accumulate in float32, the product with the
inverse among them; what leaves a kernel [T, .]-sized is in the compute
dtype.

The forward's three results carry names for a remat policy
(models/remat_keep.py): with all three kept the backward of a
rematerialized layer does not run ``gdn_fwd`` a second time.

A decay that is a VECTOR a head (Kimi Delta Attention, arXiv:2510.26692:
``g`` [B, H, T, d_k], a log decay a channel of the key) turns the
recurrence into ``S_t = S_(t-1) Diag(alpha_t) (I - beta_t k_t k_t^T) +
beta_t v_t k_t^T`` and the chunk's two score matrices into

    M_xk[r, i] = sum_c x_r[c] k_i[c] exp(b_r[c] - b_i[c])      (i <= r)

(x = k for ``A``, x = q for the output's scores; ``b`` [C, d_k] now),
which no longer factor as ``(X K^T) * decay``: the product ``(X *
exp(b)) (K * exp(-b))^T`` overflows where a channel forgets fast.
``_pair_terms`` splits a chunk into sub-blocks of ``SUB`` tokens and
writes every exponent against a REFERENCE ROW between the two tokens,
``exp(b_r - b_m) exp(b_m - b_i)`` with i <= m <= r, both factors <= 1:
a sub-block's rows against the earlier sub-blocks of their chunk with m
the sub-block's first row (one matmul a sub-block), and inside a
sub-block against its j-th row, m = i (one matmul a j, every sub-block's
column j at once: the direct ``[c, c, d_k]`` form a column at a time,
reduced by the MXU).  ``gamma``, the carry and ``exp(b_C - b)`` become
[C, d_k], [d_k, 1] and [C, d_k]; the rest of the chunk, ``_inverse``,
the grid, the packs, the resident state and the kept names are the
scalar decay's.  Kernels ``kda_fwd`` / ``kda_bwd``: ``b`` rides as a
float32 plane beside k, the backward writes its cotangent a channel.

A floor the caller promises (``gated_delta(.., floor=F)``: a bounded
gate's published floor, no entry of ``g`` under it; 0.0: none) bounds
what a sub-block can forget: ``(SUB - 1) * -F``, 75 at a floor of -5,
and exp(75) is inside float32 and bfloat16 alike.  Where ``SUB * -F <=
FACTORS_TO`` (``pairs_of``: "block") a sub-block's own scores stand
against its FIRST row too, exp(b_r - b_m) <= 1 on its rows times exp(b_m
- b_i) <= exp(75) on every row of the chunk up to its last (the
exponent masked before ``exp``: past the sub-block it is inf), and one
product of the sub-block's rows covers its diagonal block and every
earlier sub-block of its chunk (``_block_terms``): 4 score products a
pack of two chunks where 19 stood, 8 cotangent products where 38.
Everywhere else the nineteen terms stand, unchanged to the operation.

Reference: ``gated_delta_ref``, the same chunk form in plain
``jax.numpy`` (float32 inside), differentiated by JAX: what
``gated_delta`` returns wherever ``ops/mode.py`` answers ``off`` or the
kernel does not tile the shape (it says so: ``announce_fallback``).  The
token-by-token recurrence both are held to is the tests' and the
benchmark reference's, not this file's.
"""

import functools
import itertools
import types

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.ops import flash_attention
from elasticdl_tpu.ops.batch_shard import per_batch_shard
from elasticdl_tpu.ops.mode import resolve

# ``checkpoint_name``s of the forward's results that the backward reads
# or the layer goes on with: the output, the chunk-start states and the
# chunks' inverses.
KEEP_OUT, KEEP_STATES, KEEP_INVERSE = "gdn_out", "gdn_states", "gdn_inverse"

CHUNK = 64
# Heads a grid step runs, the largest that divides the heads: their
# chains of dependent matmuls are independent of each other, so the
# scheduler has several to interleave.
HEAD_BLOCKS = (5, 4, 3, 2, 1)
# Chunks a grid step walks behind one build of their inverses, the
# largest that divides the chunks and stays inside the MXU's width: two
# of 64 fill its 128.
PACKS = (2, 1)
MXU = 128
# Tokens a sub-block of a vector decay's chunk (``_pair_terms``).
SUB = 16
# The largest ``sub * -floor`` under which a sub-block's scores factor
# against its first row (``pairs_of``): float32 ends at exp(88.7), and a
# sum over d_k channels of such terms needs the room between.
FACTORS_TO = 80.0
VMEM_LIMIT = 64 * 1024 * 1024

_F32 = jnp.float32
_NN = (((1,), (0,)), ((), ()))   # a @ b
_NT = (((1,), (1,)), ((), ()))   # a @ b^T
_TN = (((0,), (0,)), ((), ()))   # a^T @ b


def _dot(a, b, dims=_NN):
    return lax.dot_general(a, b, dims, preferred_element_type=_F32)


def pack_of(seq, chunk=CHUNK):
    """Chunks of a sequence of ``seq`` a grid step of the kernels walks."""
    return next(n for n in PACKS if n == 1 or (
        seq // chunk % n == 0 and n * chunk <= MXU))


def inverse_bytes(rows, heads, size, pack=PACKS[0], chunk=CHUNK):
    """HBM bytes of the inverses ``gdn_fwd`` writes for ``rows`` tokens
    of ``heads`` heads in a dtype of ``size`` bytes, ``pack`` chunks'
    side by side (a row of them whole 128-lane tiles)."""
    edge = pack * chunk
    return rows // edge * heads * chunk * -(-edge // 128) * 128 * size


def _inverse(a, chunk=None):
    """(I + a)^-1 of strictly lower triangular ``a`` [.., C, C] float32,
    by matmuls alone, at the highest precision: block forward
    substitution with masks in place of slices.  A pair of tokens is
    exact, ``[[1, 0], [-a, 1]]``; then blocks are joined in pairs until
    one is left, ``[[T1, 0], [-T2 a21 T1, T2]]``, two [C, C] matmuls a
    join (ten at C = 64).  The finite Neumann series of the nilpotent
    ``-a`` over the whole chunk, (I + n)(I + n^2)(I + n^4).., is as many
    matmuls and is not stable: with keys that share a direction (a
    SiLU's outputs do) its terms grow as binomial(C, n) |a|^n before
    they cancel, past float32 at C = 64 (a NaN in the test's draw; 1e-2
    over blocks of 16, 1e-5 over 8).

    With ``chunk`` below ``a``'s edge, ``a`` holds several chunks'
    matrices on its diagonal blocks of that edge and zeros elsewhere:
    the joins stop at the chunk's edge, each of them the same products
    with zeros added, and the result holds the chunks' inverses where
    ``a`` held their matrices."""
    edge = a.shape[-1]
    chunk = chunk or edge
    mm = functools.partial(jnp.matmul, precision=lax.Precision.HIGHEST,
                           preferred_element_type=_F32)
    row = lax.broadcasted_iota(jnp.int32, (edge, edge), 0)
    col = lax.broadcasted_iota(jnp.int32, (edge, edge), 1)
    # whether an entry lies in a diagonal block of edge 2 ** bits
    within = lambda bits: (row >> bits) == (col >> bits)
    inv = (row == col).astype(_F32) - jnp.where(within(1), a, 0.0)
    bits = 1
    while 2 ** bits < chunk:
        below = jnp.where(within(bits + 1) & ~within(bits), a, 0.0)
        inv = inv - mm(mm(inv, below), inv)
        bits += 1
    return inv


# -- the plain twin ----------------------------------------------------------


def gated_delta_ref(q, k, v, g, beta, chunk=CHUNK, floor=0.0):
    """The chunk form in plain ``jax.numpy``, float32 inside, v's dtype
    out; any T (the last chunk padded with tokens that neither decay nor
    write).  ``g`` [B, H, T] a scalar a head, or [B, H, T, d_k] a vector
    (the decayed scores then by ``_pair_scores``, a chunk at a time, its
    terms chosen from ``floor`` as the kernels': ``pairs_of``)."""
    batch, heads, seq, _ = q.shape
    vector = g.ndim == q.ndim
    dtype = v.dtype
    pad = -seq % chunk
    if pad:
        widths = lambda x: [(0, 0), (0, 0), (0, pad)] + [(0, 0)] * (
            x.ndim - 3)
        q, k, v, g, beta = (jnp.pad(x, widths(x))
                            for x in (q, k, v, g, beta))
    n = (seq + pad) // chunk
    split = lambda x: x.astype(_F32).reshape(
        batch, heads, n, chunk, *x.shape[3:])
    q, k, v, g, beta = map(split, (q, k, v, g, beta))
    _, lower, strict = _masks(chunk)
    mm = functools.partial(jnp.matmul, precision=lax.Precision.HIGHEST)
    if vector:
        b = jnp.cumsum(g, axis=-2)                    # [.., C, d_k]
        with jax.default_matmul_precision("highest"):
            pairs = jnp.vectorize(
                functools.partial(_pair_scores, chunk=chunk, floor=floor),
                signature="(m,d),(c,d),(c,d)->(m,c)")(
                    jnp.concatenate([k, q], axis=-2), k, b)
        kk, qk = pairs[..., :chunk, :], pairs[..., chunk:, :]
        gamma, last = jnp.exp(b), b[..., -1:, :]
        to_end = jnp.exp(last - b)
        carry = jnp.swapaxes(jnp.exp(last), -1, -2)   # [.., d_k, 1]
    else:
        b = jnp.cumsum(g, axis=-1)
        diff = b[..., :, None] - b[..., None, :]
        decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
        kk = mm(k, jnp.swapaxes(k, -1, -2)) * decay
        qk = mm(q, jnp.swapaxes(k, -1, -2)) * decay
        gamma, last = jnp.exp(b)[..., None], b[..., -1:]
        to_end = jnp.exp(last - b)[..., None]
        carry = jnp.exp(last)[..., None]
    a = jnp.where(strict, kk, 0.0) * beta[..., None]
    inv = _inverse(a)
    w = mm(inv, k * gamma * beta[..., None])
    u0 = mm(inv, v * beta[..., None])
    scores = jnp.where(lower, qk, 0.0)
    kd = k * to_end

    def step(h, xs):
        w, u0, scores, qg, kd, carry = xs
        u = u0 - mm(w, h)
        o = mm(qg, h) + mm(scores, u)
        return carry * h + mm(jnp.swapaxes(kd, -1, -2), u), o

    chunks_first = lambda x: jnp.moveaxis(x, 2, 0)
    h0 = jnp.zeros((batch, heads, q.shape[-1], v.shape[-1]), _F32)
    _, o = lax.scan(step, h0, tuple(map(chunks_first, (
        w, u0, scores, q * gamma, kd, carry))))
    o = jnp.moveaxis(o, 0, 2).reshape(batch, heads, seq + pad, -1)
    return o[:, :, :seq].astype(dtype)


# -- the kernels -------------------------------------------------------------


def _masks(chunk):
    """(diagonal, lower with the diagonal, strictly lower) [C, C]."""
    row = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    return row == col, row >= col, row > col


def _col(row, eye):
    """A [1, C] row as a [C, 1] column (a masked lane sum: no
    transpose of a one-row array)."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _row(col, eye):
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _decays(k, b, beta):
    """What a chunk is before its start state enters, in both kernels:
    k [C, d_k] in the compute dtype, b and beta [1, C] float32."""
    dtype = k.dtype
    eye, lower, strict = _masks(k.shape[0])
    b_col, beta_col = _col(b, eye), _col(beta, eye)
    decay = jnp.where(
        lower, jnp.exp(jnp.where(lower, b_col - b, 0.0)), 0.0)
    gamma = jnp.exp(b_col)
    # b at the chunk's last token, [1, 1]: a masked lane sum (a slice of
    # the last lane is a layout Mosaic does not broadcast from)
    lane = lax.broadcasted_iota(jnp.int32, b.shape, 1)
    last = jnp.sum(jnp.where(lane == b.shape[1] - 1, b, 0.0), axis=1,
                   keepdims=True)
    kf = k.astype(_F32)
    to_end = jnp.exp(last - b_col)
    return types.SimpleNamespace(
        eye=eye, lower=lower, strict=strict, b_col=b_col,
        beta_col=beta_col, decay=decay, gamma=gamma, carry=jnp.exp(last),
        kf=kf, kb=(kf * (beta_col * gamma)).astype(dtype), to_end=to_end,
        kd=(kf * to_end).astype(dtype))


def _inverses(k, decays, chunk):
    """``(I + A)^-1`` of the P chunks of k [P * C, d_k] (``decays``:
    each one's ``_decays``) by ONE run of ``_inverse``'s joins, over a
    [P * C, P * C] operand with the chunks' ``A`` on its diagonal
    blocks: at P * C = 128 a product fills the MXU where a chunk's own
    fills a quarter.  Returns them side by side, [C, P * C] in the
    compute dtype (chunk p's in columns p C ..): the diagonal blocks'
    rows added, every other block an exact zero.  Nothing here reads the
    state: the joins, the one long chain of a grid step, run beside the
    chain that carries it and not in it."""
    edge = k.shape[0]
    stack = lambda name: jnp.concatenate(
        [getattr(d, name) for d in decays], axis=0)
    b_col, beta_col = stack("b_col"), stack("beta_col")
    row = lax.broadcasted_iota(jnp.int32, (edge, edge), 0)
    col = lax.broadcasted_iota(jnp.int32, (edge, edge), 1)
    # strictly lower, inside a chunk's own block
    inside = (row > col) & (row // chunk == col // chunk)
    decay = jnp.where(inside, jnp.exp(jnp.where(
        inside, b_col - _row(b_col, row == col), 0.0)), 0.0)
    inv = _inverse(_dot(k, k, _NT) * decay * beta_col, chunk)
    blocks = [inv[p * chunk:(p + 1) * chunk] for p in range(edge // chunk)]
    return sum(blocks[1:], blocks[0]).astype(k.dtype)


def _chunk(q, k, v, d, inv, at, h):
    """What both kernels build of a chunk once its start state h [d_k,
    d_v] float32 is there: q, k [C, d_k] and v [C, d_v] in the compute
    dtype, ``d`` the chunk's ``_decays``, ``inv`` its pack's inverses
    [C, P * C] with this chunk's the ``at``-th."""
    dtype = q.dtype
    hc = h.astype(dtype)
    r = (v.astype(_F32) * d.beta_col - _dot(d.kb, hc)).astype(dtype)
    # inv_at r: rows of zeros meet the other chunks' inverses
    u = _dot(inv, jnp.concatenate(
        [r if p == at else jnp.zeros_like(r)
         for p in range(inv.shape[1] // inv.shape[0])], axis=0)).astype(dtype)
    qk = _dot(q, k, _NT)
    return types.SimpleNamespace(
        hc=hc, u=u, qk=qk,
        scores=jnp.where(d.lower, qk * d.decay, 0.0).astype(dtype),
        qg=(q.astype(_F32) * d.gamma).astype(dtype))


def _fwd_kernel(q_ref, k_ref, v_ref, gates_ref, o_ref, states_ref, inv_ref,
                h_scr, *, heads, chunk):
    @pl.when(pl.program_id(1) == 0)
    def _():
        h_scr[...] = jnp.zeros_like(h_scr)

    pack = q_ref.shape[1] // chunk
    rows = [slice(p * chunk, (p + 1) * chunk) for p in range(pack)]
    for i in range(heads):
        decays = [_decays(k_ref[i, rows[p]], gates_ref[i, p, 0:1, :],
                          gates_ref[i, p, 1:2, :]) for p in range(pack)]
        inv = _inverses(k_ref[i], decays, chunk)
        inv_ref[i] = inv
        h = h_scr[i]
        for p, d in enumerate(decays):
            states_ref[i, p] = h
            c = _chunk(q_ref[i, rows[p]], k_ref[i, rows[p]],
                       v_ref[i, rows[p]], d, inv, p, h)
            o = _dot(c.qg, c.hc) + _dot(c.scores, c.u)
            o_ref[i, rows[p]] = o.astype(o_ref.dtype)
            h = d.carry * h + _dot(d.kd, c.u, _TN)
        h_scr[i] = h


def _bwd_kernel(q_ref, k_ref, v_ref, gates_ref, states_ref, inv_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dgates_ref, dh_scr, *, heads, chunk):
    @pl.when(pl.program_id(1) == 0)
    def _():
        dh_scr[...] = jnp.zeros_like(dh_scr)

    pack = q_ref.shape[1] // chunk
    for i, p in itertools.product(range(heads), reversed(range(pack))):
        rows = slice(p * chunk, (p + 1) * chunk)
        q, k, v, do = (ref[i, rows] for ref in (q_ref, k_ref, v_ref, do_ref))
        dtype = q.dtype
        h, dh, inv = states_ref[i, p], dh_scr[i], inv_ref[i]
        d = _decays(k, gates_ref[i, p, 0:1, :], gates_ref[i, p, 1:2, :])
        c = _chunk(q, k, v, d, inv, p, h)
        eye, lower, strict = d.eye, d.lower, d.strict
        decay, beta_col, gamma = d.decay, d.beta_col, d.gamma
        u, hc, kf = c.u, c.hc, d.kf
        cast = lambda x: x.astype(dtype)
        rowsum = lambda x: jnp.sum(x, axis=1, keepdims=True)
        dhc, dof = cast(dh), do.astype(_F32)

        # O = gamma Q h + scores U;  h' = gamma_C h + kd^T U
        du = _dot(c.scores, do, _TN) + _dot(d.kd, dhc)
        dscores = jnp.where(lower, _dot(do, u, _NT), 0.0)
        dqk = cast(dscores * decay)
        dq = gamma * _dot(do, hc, _NT) + _dot(dqk, k)
        dk = _dot(dqk, q, _TN)
        dkd = _dot(u, dhc, _NT)
        dgamma = rowsum(_dot(q, hc) * dof)
        # U = inv R, R = beta V - kb h;  d(inv) = -inv^T . inv^T (the
        # pack's inverses side by side: this chunk's rows of the product)
        dr = _dot(inv, cast(du), _TN)[rows]
        drc = cast(dr)
        da = jnp.where(strict, -_dot(drc, u, _NT), 0.0)
        dkb = -_dot(drc, hc, _NT)
        dh_scr[i] = (d.carry * dh + _dot(c.qg, do, _TN)
                     - _dot(d.kb, drc, _TN))
        dv_ref[i, rows] = (beta_col * dr).astype(dv_ref.dtype)
        dbeta = rowsum(dr * v.astype(_F32))
        both = rowsum(dkb * kf)               # d(beta gamma)
        dk += beta_col * gamma * dkb
        dbeta += gamma * both
        dgamma += beta_col * both
        # A = beta (K K^T * decay), strictly lower
        kk = _dot(k, k, _NT)
        dbeta += rowsum(da * kk * decay)
        dkk = cast(beta_col * da * decay)
        dk += _dot(dkk, k) + _dot(dkk, k, _TN)
        # kd = exp(b_C - b) K
        dk += d.to_end * dkd
        to_end = rowsum(dkd * kf) * d.to_end     # d(b_C - b_i)
        dq_ref[i, rows] = dq.astype(dq_ref.dtype)
        dk_ref[i, rows] = dk.astype(dk_ref.dtype)
        # decay = exp(b_r - b_i): a row's cotangents add, a column's
        # subtract (the diagonal's cancel: its exponent is 0)
        ddiff = (dscores * c.qk + beta_col * da * kk) * decay
        db = (_row(rowsum(ddiff) + dgamma * gamma - to_end, eye)
              - jnp.sum(ddiff, axis=0, keepdims=True))
        at_last = lax.broadcasted_iota(jnp.int32, db.shape, 1) == (
            db.shape[1] - 1)
        db += jnp.where(
            at_last, jnp.sum(to_end) + jnp.sum(dh * h) * d.carry,
            0.0)
        dgates_ref[i, p, 0:1, :] = db
        dgates_ref[i, p, 1:2, :] = _row(dbeta, eye)


# -- a decay that is a vector a head ------------------------------------------


def _held(x, at, size):
    """x [rows, width] with every row replaced by row ``at`` of its own
    block of ``size`` rows (a masked sum down the block: no gather)."""
    rows, width = x.shape
    blocks = x.reshape(rows // size, size, width)
    here = lax.broadcasted_iota(jnp.int32, blocks.shape, 1) == at
    row = jnp.sum(jnp.where(here, blocks, 0.0), axis=1, keepdims=True)
    return jnp.broadcast_to(row, blocks.shape).reshape(rows, width)


def pairs_of(floor, sub=SUB):
    """How a sub-block of ``sub`` tokens' own scores are taken under a
    log decay whose caller promises ``floor`` <= g (0.0: no promise):
    "block", one product against the sub-block's first row
    (``_block_terms``), where the cumulative decay across it cannot pass
    ``FACTORS_TO``; else "columns", one product a row of the sub-block
    (``_pair_terms``)."""
    return "block" if 0 < sub * -floor <= FACTORS_TO else "columns"


def _pair_terms(xs, k, b, chunk, sub):
    """The products whose masked sum is ``M[r, i] = sum_c x_r[c] k_i[c]
    exp(b_r[c] - b_i[c])`` for i <= r inside a chunk, and 0 elsewhere:
    k [E, d_k] (E a whole number of chunks) in the compute dtype, b [E,
    d_k] float32 (each chunk's own cumulative sum of a log decay <= 0),
    xs [n E, d_k] n planes of rows at the same positions (k itself, q).
    Yields (where the term counts [n E, E], the left operand [n E, d_k]
    in the compute dtype, its scale [n E, d_k], the right operand [E,
    d_k], its scale): ``x * left_scale`` times ``(k * right_scale)^T``.
    Every scale is the exponential of a difference against a row m
    between the pair, i <= m <= r, so none is over 1:

     - inside a sub-block of ``sub`` tokens, m = i: one term a j, the
       j-th row of every sub-block at once as the right operand;
     - a sub-block's rows against the earlier sub-blocks of its chunk,
       m the sub-block's first row: one term a sub-block."""
    dtype = k.dtype
    edge = k.shape[0]
    n = xs.shape[0] // edge
    xf, kf = xs.astype(_F32), k.astype(_F32)
    planes = lambda a: jnp.concatenate([a] * n, axis=0) if n > 1 else a
    pos = lax.broadcasted_iota(jnp.int32, (edge, 1), 0)
    r = lax.broadcasted_iota(jnp.int32, (n * edge, edge), 0) % edge
    i = lax.broadcasted_iota(jnp.int32, (n * edge, edge), 1)
    inside = r // sub == i // sub
    before = (r // chunk == i // chunk) & ~inside
    scale = lambda rows, exponent: jnp.where(
        rows, jnp.exp(jnp.where(rows, exponent, 0.0)), 0.0)

    def term(counts, left, right):
        left = planes(left)
        return (counts, (xf * left).astype(dtype), left,
                (kf * right).astype(dtype), right)

    for j in range(sub):
        yield term(inside, scale(pos % sub >= j, b - _held(b, j, sub)),
                   jnp.where(pos % sub == j, 1.0, 0.0))
    for first in range(sub, chunk, sub):
        ref = _held(b, first, chunk)
        yield term(before,
                   scale(pos % chunk // sub == first // sub, b - ref),
                   scale(pos % chunk < first, ref - b))


def _sub_rows(a, first, chunk, sub):
    """The ``sub`` rows from row ``first`` of every block of ``chunk``
    rows of ``a``, block after block."""
    return jnp.concatenate([a[at + first:at + first + sub]
                            for at in range(0, a.shape[0], chunk)], axis=0)


def _from_sub_rows(parts, chunk, sub):
    """``_sub_rows`` undone: ``parts[s]`` the rows from ``s * sub`` of
    every block."""
    return jnp.concatenate(
        [part[at:at + sub] for at in range(0, parts[0].shape[0], sub)
         for part in parts], axis=0)


def _block_terms(xs, k, b, chunk, sub):
    """``_pair_terms`` where a floor under the log decay bounds what a
    sub-block can forget (``pairs_of``: "block"): the reference row m is
    the sub-block's first row for its own columns too, so ONE term a
    sub-block covers every column of its chunk up to the sub-block's
    last row.  Yields, a sub-block of the chunks in turn, (its first
    row in a chunk; the left operand: ITS rows of every chunk of every
    plane alone, ``_sub_rows``, scaled by exp(b_r - b_m) <= 1; that
    scale; the right operand [E, d_k]; its scale, exp(b_m - b): at most
    1 before m, at most exp((sub - 1) * -floor) behind it, inside
    float32 and bfloat16 alike, and 0 past the sub-block, the exponent
    masked BEFORE ``exp`` is taken: a row further down may lie hundreds
    under m, and inf x 0 is a NaN).  What a product puts at i > r the
    caller's mask takes off."""
    dtype = k.dtype
    edge = k.shape[0]
    kf = k.astype(_F32)
    pos = lax.broadcasted_iota(jnp.int32, (edge, 1), 0) % chunk
    own = jnp.concatenate(
        [jnp.exp(b - _held(b, 0, sub))] * (xs.shape[0] // edge), axis=0)
    left = (xs.astype(_F32) * own).astype(dtype)
    for first in range(0, chunk, sub):
        rows = pos < first + sub
        right = jnp.where(rows, jnp.exp(jnp.where(
            rows, _held(b, first, chunk) - b, 0.0)), 0.0)
        yield (first, _sub_rows(left, first, chunk, sub),
               _sub_rows(own, first, chunk, sub),
               (kf * right).astype(dtype), right)


def _lower(shape, chunk):
    """Where i <= r inside a chunk, ``shape`` [n E, E]: n planes of
    rows."""
    r = lax.broadcasted_iota(jnp.int32, shape, 0) % shape[1]
    i = lax.broadcasted_iota(jnp.int32, shape, 1)
    return (r // chunk == i // chunk) & (i <= r)


def _pair_scores(xs, k, b, chunk, sub=SUB, floor=0.0):
    """``M`` of ``_pair_terms`` [n E, E] float32, by the terms that
    ``floor`` allows (``pairs_of``)."""
    if pairs_of(floor, sub) == "block":
        m = _from_sub_rows([_dot(left, right, _NT) for _, left, _, right, _
                            in _block_terms(xs, k, b, chunk, sub)], chunk, sub)
        return jnp.where(_lower(m.shape, chunk), m, 0.0)
    return sum(jnp.where(counts, _dot(left, right, _NT), 0.0)
               for counts, left, _, right, _ in _pair_terms(
                   xs, k, b, chunk, sub))


def _pair_scores_bwd(dm, xs, k, b, chunk, sub=SUB, floor=0.0):
    """(the cotangent of xs [n E, d_k], of k [E, d_k]) through
    ``_pair_scores`` for ``dm`` [n E, E], float32 each; b's is the
    caller's: x * dx summed over the planes, less k * dk (the reference
    rows cancel, whichever they are)."""
    if pairs_of(floor, sub) == "block":
        d = jnp.where(_lower(dm.shape, chunk), dm, 0.0).astype(k.dtype)
        dxs, dk = [], 0.0
        for first, left, left_scale, right, right_scale in _block_terms(
                xs, k, b, chunk, sub):
            rows = _sub_rows(d, first, chunk, sub)
            dxs.append(_dot(rows, right) * left_scale)
            dk += _dot(rows, left, _TN) * right_scale
        return _from_sub_rows(dxs, chunk, sub), dk
    dxs = dk = 0.0
    masked = {}     # dm where a term counts: two masks for all the terms
    for counts, left, left_scale, right, right_scale in _pair_terms(
            xs, k, b, chunk, sub):
        if id(counts) not in masked:
            masked[id(counts)] = jnp.where(counts, dm, 0.0).astype(k.dtype)
        d = masked[id(counts)]
        dxs += _dot(d, right) * left_scale
        dk += _dot(d, left, _TN) * right_scale
    return dxs, dk


def _kda_decays(k, b, beta_col):
    """``_decays`` of a chunk under a vector decay: k [C, d_k], b [C,
    d_k] float32, beta [C, 1]."""
    dtype = k.dtype
    eye = _masks(k.shape[1])[0]
    ends = lax.broadcasted_iota(jnp.int32, (k.shape[0], 1), 0) == (
        k.shape[0] - 1)
    last = jnp.sum(jnp.where(ends, b, 0.0), axis=0, keepdims=True)
    gamma, to_end, carry_row = jnp.exp(b), jnp.exp(last - b), jnp.exp(last)
    kf = k.astype(_F32)
    return types.SimpleNamespace(
        eye=eye, ends=ends, beta_col=beta_col, gamma=gamma, to_end=to_end,
        carry_row=carry_row, carry=_col(carry_row, eye), kf=kf,
        kb=(kf * gamma * beta_col).astype(dtype),
        kd=(kf * to_end).astype(dtype))


def _kda_pack(q, k, b, betas, floor):
    """(kk, qk [E, E] float32: ``_pair_scores`` of a pack's k and q, a
    chunk's scores in its own columns; beta [E, 1]) of a pack of P
    chunks; ``betas`` the chunks' [1, C] rows."""
    chunk = betas[0].shape[1]
    eye = _masks(chunk)[0]
    m = _pair_scores(jnp.concatenate([k, q], axis=0), k, b, chunk,
                     floor=floor)
    edge = k.shape[0]
    return m[:edge], m[edge:], jnp.concatenate(
        [_col(row, eye) for row in betas], axis=0)


def _kda_chunk(q, v, d, scores, inv, at, h):
    """``_chunk`` under a vector decay: ``scores`` [C, P C] the chunk's
    rows of the pack's qk, which meet ``u`` between rows of zeros as the
    inverses do."""
    dtype = q.dtype
    pack = inv.shape[1] // inv.shape[0]
    among = lambda x: jnp.concatenate(
        [x if p == at else jnp.zeros_like(x) for p in range(pack)], axis=0)
    hc = h.astype(dtype)
    r = (v.astype(_F32) * d.beta_col - _dot(d.kb, hc)).astype(dtype)
    u = _dot(inv, among(r)).astype(dtype)
    return types.SimpleNamespace(
        hc=hc, u=u, among=among(u), scores=scores.astype(dtype),
        qg=(q.astype(_F32) * d.gamma).astype(dtype))


def _kda_fwd_kernel(q_ref, k_ref, v_ref, b_ref, gates_ref, o_ref,
                    states_ref, inv_ref, h_scr, *, heads, chunk, floor):
    @pl.when(pl.program_id(1) == 0)
    def _():
        h_scr[...] = jnp.zeros_like(h_scr)

    edge = q_ref.shape[1]
    pack = edge // chunk
    row = lax.broadcasted_iota(jnp.int32, (edge, edge), 0)
    col = lax.broadcasted_iota(jnp.int32, (edge, edge), 1)
    for i in range(heads):
        kk, qk, beta_col = _kda_pack(
            q_ref[i], k_ref[i], b_ref[i],
            [gates_ref[i, p, 0:1, :] for p in range(pack)], floor)
        # kk is zero outside a chunk's own block: ``_inverses``' operand
        full = _inverse(jnp.where(row > col, kk, 0.0) * beta_col, chunk)
        blocks = [full[p * chunk:(p + 1) * chunk] for p in range(pack)]
        inv = sum(blocks[1:], blocks[0]).astype(q_ref.dtype)
        inv_ref[i] = inv
        h = h_scr[i]
        for p in range(pack):
            rows = slice(p * chunk, (p + 1) * chunk)
            states_ref[i, p] = h
            d = _kda_decays(k_ref[i, rows], b_ref[i, rows], beta_col[rows])
            c = _kda_chunk(q_ref[i, rows], v_ref[i, rows], d, qk[rows],
                           inv, p, h)
            o = _dot(c.qg, c.hc) + _dot(c.scores, c.among)
            o_ref[i, rows] = o.astype(o_ref.dtype)
            h = d.carry * h + _dot(d.kd, c.u, _TN)
        h_scr[i] = h


def _kda_bwd_kernel(q_ref, k_ref, v_ref, b_ref, gates_ref, states_ref,
                    inv_ref, do_ref, dq_ref, dk_ref, dv_ref, db_ref,
                    dgates_ref, dh_scr, *, heads, chunk, floor):
    @pl.when(pl.program_id(1) == 0)
    def _():
        dh_scr[...] = jnp.zeros_like(dh_scr)

    edge = q_ref.shape[1]
    pack = edge // chunk
    eye = _masks(chunk)[0]
    row = lax.broadcasted_iota(jnp.int32, (chunk, edge), 0)
    col = lax.broadcasted_iota(jnp.int32, (chunk, edge), 1)
    rowsum = lambda x: jnp.sum(x, axis=1, keepdims=True)
    for i in range(heads):
        q, k, b, inv = q_ref[i], k_ref[i], b_ref[i], inv_ref[i]
        dtype = q.dtype
        cast = lambda x: x.astype(dtype)
        kk, qk, beta_all = _kda_pack(
            q, k, b, [gates_ref[i, p, 0:1, :] for p in range(pack)], floor)
        dqs, dks, dbs, dkks, dqks = ([None] * pack for _ in range(5))
        for p in reversed(range(pack)):
            rows = slice(p * chunk, (p + 1) * chunk)
            v, do = v_ref[i, rows], do_ref[i, rows]
            h, dh = states_ref[i, p], dh_scr[i]
            beta_col = beta_all[rows]
            d = _kda_decays(k[rows], b[rows], beta_col)
            c = _kda_chunk(q[rows], v, d, qk[rows], inv, p, h)
            gamma, kf, qf = d.gamma, d.kf, q[rows].astype(_F32)
            dhc = cast(dh)
            # O = (Q gamma) h + scores U;  h' = carry h + kd^T U
            du = _dot(c.scores, do, _TN)[rows] + _dot(d.kd, dhc)
            dqg = _dot(do, c.hc, _NT)
            dkd = _dot(c.u, dhc, _NT)
            # U = inv R, R = beta V - kb h;  d(inv) = -inv^T . inv^T
            dr = _dot(inv, cast(du), _TN)[rows]
            drc = cast(dr)
            da = jnp.where(row + p * chunk > col,
                           -_dot(drc, c.among, _NT), 0.0)
            dkb = -_dot(drc, c.hc, _NT)
            dh_scr[i] = (d.carry * dh + _dot(c.qg, do, _TN)
                         - _dot(d.kb, drc, _TN))
            dv_ref[i, rows] = (beta_col * dr).astype(dv_ref.dtype)
            both = dkb * kf * gamma                    # d(beta gamma)
            dbeta = (rowsum(dr * v.astype(_F32)) + rowsum(both)
                     + rowsum(da * kk[rows]))
            dgates_ref[i, p, 0:1, :] = _row(dbeta, eye)
            # kd = exp(b_C - b) K;  carry = exp(b_C)
            to_end = dkd * kf * d.to_end               # d(b_C - b)
            last = (jnp.sum(to_end, axis=0, keepdims=True)
                    + _row(rowsum(dh * h), d.eye) * d.carry_row)
            dqs[p] = dqg * gamma
            dks[p] = beta_col * gamma * dkb + d.to_end * dkd
            dbs[p] = (dqg * qf * gamma + beta_col * both - to_end
                      + jnp.where(d.ends, last, 0.0))
            dkks[p] = beta_col * da
            dqks[p] = _dot(do, c.among, _NT)
        stack = lambda parts: jnp.concatenate(parts, axis=0)
        xs = stack([k, q])
        dxs, dk2 = _pair_scores_bwd(stack(dkks + dqks), xs, k, b, chunk,
                                    floor=floor)
        dxk, dxq = dxs[:edge], dxs[edge:]
        kf, qf = k.astype(_F32), q.astype(_F32)
        dq_ref[i] = (stack(dqs) + dxq).astype(dq_ref.dtype)
        dk_ref[i] = (stack(dks) + dxk + dk2).astype(dk_ref.dtype)
        db_ref[i] = stack(dbs) + kf * (dxk - dk2) + qf * dxq


def _specs(heads, chunk, pack, d_k, d_v, steps, reverse, gates=2):
    """BlockSpecs of a [B * H, T, d_k] plane, a [B * H, T, d_v] plane,
    the gates [B * H, T / C, ``gates``, C], the states [B * H, T / C,
    d_k, d_v] and the inverses [B * H, T / (P C), C, P C] for a grid
    (head block, pack of P chunks), the packs walked backwards with
    ``reverse``."""
    at = (lambda j: steps - 1 - j) if reverse else (lambda j: j)
    return (pl.BlockSpec((heads, pack * chunk, d_k),
                         lambda i, j: (i, at(j), 0)),
            pl.BlockSpec((heads, pack * chunk, d_v),
                         lambda i, j: (i, at(j), 0)),
            pl.BlockSpec((heads, pack, gates, chunk),
                         lambda i, j: (i, at(j), 0, 0)),
            pl.BlockSpec((heads, pack, d_k, d_v),
                         lambda i, j: (i, at(j), 0, 0)),
            pl.BlockSpec((heads, None, chunk, pack * chunk),
                         lambda i, j: (i, at(j), 0, 0)))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)


# Both calls are jitted, so that the layers of a stack and a layer's
# second forward under remat, which trace them at the same shapes, trace
# and lower a kernel once (a worker lowers its step at every start).
@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _fwd_call(q, k, v, gates, chunk, heads, pack, interpret):
    """(o [B * H, T, d_v], chunk-start states [B * H, T / C, d_k, d_v]
    float32, the chunks' inverses [B * H, T / (P C), C, P C] in q's
    dtype, P chunks' side by side)."""
    bh, seq, d_k = q.shape
    d_v = v.shape[-1]
    chunks = seq // chunk
    qk, vo, gate, state, inverse = _specs(
        heads, chunk, pack, d_k, d_v, chunks // pack, False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads, chunk=chunk),
        out_shape=(jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((bh, chunks, d_k, d_v), _F32),
                   jax.ShapeDtypeStruct(
                       (bh, chunks // pack, chunk, pack * chunk), q.dtype)),
        grid=(bh // heads, chunks // pack),
        in_specs=[qk, qk, vo, gate],
        out_specs=(vo, state, inverse),
        scratch_shapes=[pltpu.VMEM((heads, d_k, d_v), _F32)],
        compiler_params=_params(),
        interpret=interpret,
        name="gdn_fwd",
    )(q, k, v, gates)


@functools.partial(jax.jit, static_argnums=(7, 8, 9, 10))
def _bwd_call(q, k, v, gates, states, inv, do, chunk, heads, pack,
              interpret):
    """(dq, dk, dv, dgates)."""
    bh, seq, d_k = q.shape
    d_v = v.shape[-1]
    steps = inv.shape[1]
    qk, vo, gate, state, inverse = _specs(
        heads, chunk, pack, d_k, d_v, steps, True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads, chunk=chunk),
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(gates.shape, _F32)),
        grid=(bh // heads, steps),
        in_specs=[qk, qk, vo, gate, state, inverse, vo],
        out_specs=(qk, qk, vo, gate),
        scratch_shapes=[pltpu.VMEM((heads, d_k, d_v), _F32)],
        compiler_params=_params(),
        interpret=interpret,
        name="gdn_bwd",
    )(q, k, v, gates, states, inv, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _gdn(q, k, v, gates, chunk, heads, pack, interpret):
    return _fwd_call(q, k, v, gates, chunk, heads, pack, interpret)[0]


def _gdn_fwd(q, k, v, gates, chunk, heads, pack, interpret):
    o, states, inv = _fwd_call(q, k, v, gates, chunk, heads, pack,
                               interpret)
    # named where they are made, as the flash forward's two: a policy
    # that saves all three does not run this forward a second time
    o = checkpoint_name(o, KEEP_OUT)
    states = checkpoint_name(states, KEEP_STATES)
    inv = checkpoint_name(inv, KEEP_INVERSE)
    return o, (q, k, v, gates, states, inv)


def _gdn_bwd(chunk, heads, pack, interpret, res, do):
    return _bwd_call(*res, do, chunk, heads, pack, interpret)


_gdn.defvjp(_gdn_fwd, _gdn_bwd)


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8, 9))
def _kda_fwd_call(q, k, v, b, gates, chunk, heads, pack, interpret, floor):
    """``_fwd_call`` under a vector decay: ``b`` [B * H, T, d_k] float32
    each chunk's cumulative log decay, ``gates`` [B * H, T / C, 1, C]
    the write strengths, ``floor`` what the caller promises the log
    decay stays over (``pairs_of``)."""
    bh, seq, d_k = q.shape
    d_v = v.shape[-1]
    chunks = seq // chunk
    qk, vo, gate, state, inverse = _specs(
        heads, chunk, pack, d_k, d_v, chunks // pack, False, 1)
    return pl.pallas_call(
        functools.partial(_kda_fwd_kernel, heads=heads, chunk=chunk,
                          floor=floor),
        out_shape=(jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((bh, chunks, d_k, d_v), _F32),
                   jax.ShapeDtypeStruct(
                       (bh, chunks // pack, chunk, pack * chunk), q.dtype)),
        grid=(bh // heads, chunks // pack),
        in_specs=[qk, qk, vo, qk, gate],
        out_specs=(vo, state, inverse),
        scratch_shapes=[pltpu.VMEM((heads, d_k, d_v), _F32)],
        compiler_params=_params(),
        interpret=interpret,
        name="kda_fwd",
    )(q, k, v, b, gates)


@functools.partial(jax.jit, static_argnums=(8, 9, 10, 11, 12))
def _kda_bwd_call(q, k, v, b, gates, states, inv, do, chunk, heads, pack,
                  interpret, floor):
    """(dq, dk, dv, db, dgates)."""
    bh, seq, d_k = q.shape
    d_v = v.shape[-1]
    steps = inv.shape[1]
    qk, vo, gate, state, inverse = _specs(
        heads, chunk, pack, d_k, d_v, steps, True, 1)
    return pl.pallas_call(
        functools.partial(_kda_bwd_kernel, heads=heads, chunk=chunk,
                          floor=floor),
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(b.shape, _F32),
                   jax.ShapeDtypeStruct(gates.shape, _F32)),
        grid=(bh // heads, steps),
        in_specs=[qk, qk, vo, qk, gate, state, inverse, vo],
        out_specs=(qk, qk, vo, qk, gate),
        scratch_shapes=[pltpu.VMEM((heads, d_k, d_v), _F32)],
        compiler_params=_params(),
        interpret=interpret,
        name="kda_bwd",
    )(q, k, v, b, gates, states, inv, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _kda(q, k, v, b, gates, chunk, heads, pack, interpret, floor):
    return _kda_fwd_call(q, k, v, b, gates, chunk, heads, pack, interpret,
                         floor)[0]


def _kda_fwd(q, k, v, b, gates, chunk, heads, pack, interpret, floor):
    o, states, inv = _kda_fwd_call(q, k, v, b, gates, chunk, heads, pack,
                                   interpret, floor)
    o = checkpoint_name(o, KEEP_OUT)
    states = checkpoint_name(states, KEEP_STATES)
    inv = checkpoint_name(inv, KEEP_INVERSE)
    return o, (q, k, v, b, gates, states, inv)


def _kda_bwd(chunk, heads, pack, interpret, floor, res, do):
    return _kda_bwd_call(*res, do, chunk, heads, pack, interpret, floor)


_kda.defvjp(_kda_fwd, _kda_bwd)


def _gates(g, beta, chunk):
    """g, beta [B, H, T] as the kernels take them, [B * H, T / C, 2, C]
    float32: ``b``, the cumulative sum of g from a chunk's first token,
    above beta."""
    rows = lambda x: x.astype(_F32).reshape(
        -1, x.shape[-1] // chunk, 1, chunk)
    return jnp.concatenate(
        [jnp.cumsum(rows(g), axis=-1), rows(beta)], axis=2)


def _unfriendly(seq, d_k, d_v, chunk, vector=False):
    """Why the kernels cannot take this shape, or "" when they can."""
    if seq % chunk:
        return "seq %d is not a multiple of the chunk %d" % (seq, chunk)
    if d_k % 8 or d_v % 8:
        return "head sizes %d | %d are not multiples of 8" % (d_k, d_v)
    if vector and chunk % SUB:
        return "the chunk %d is not a multiple of the sub-block %d" % (
            chunk, SUB)
    return ""


def delta_mode(seq, d_k, d_v, chunk=CHUNK, interpret=None, vector=False):
    """(mode: "tpu" | "interpret" | "off" as ``gated_delta`` runs a
    sequence of ``seq`` here, under a ``vector`` decay or a scalar one;
    why not the kernel or "")."""
    mode = resolve(interpret)
    why = _unfriendly(seq, d_k, d_v, chunk, vector) if mode != "off" else ""
    return ("off" if why else mode), why


def gated_delta(q, k, v, g, beta, chunk=CHUNK, interpret=None, floor=0.0):
    """q, k [B, H, T, d_k], v [B, H, T, d_v] in the compute dtype, g
    (the log decay, <= 0: [B, H, T] a scalar a head, or [B, H, T, d_k]
    a vector, a channel of the key each) and beta [B, H, T] -> o [B, H,
    T, d_v] in v's dtype; every sequence and head starts from a zero
    state.  ``floor`` < 0 is the caller's PROMISE that no entry of a
    vector ``g`` lies under it (0.0: none made; a bounded gate's
    published floor), from which the vector decay's score products are
    chosen (``pairs_of``); nothing checks it here, and a ``g`` under it
    may overflow them.  Differentiable in all five.  The kernels where
    ``ops/mode.py`` allows them and the shape tiles, per shard of the
    declared batch axis; else ``gated_delta_ref``."""
    batch, heads, seq, d_k = q.shape
    d_v = v.shape[-1]
    vector = g.ndim == q.ndim
    mode, why = delta_mode(seq, d_k, d_v, chunk, interpret, vector)
    if mode == "off":
        if why:
            flash_attention.announce_fallback(
                "gated_delta", q.shape, why, resolve(interpret))
        return checkpoint_name(
            gated_delta_ref(q, k, v, g, beta, chunk, floor), KEEP_OUT)
    block = next(n for n in HEAD_BLOCKS if heads % n == 0)

    def op(q, k, v, g, beta):
        planes = lambda x: x.reshape(-1, *x.shape[2:])
        scan = (chunk, block, pack_of(seq, chunk), mode == "interpret")
        if vector:
            chunks = g.astype(_F32).reshape(-1, seq // chunk, chunk, d_k)
            o = _kda(planes(q), planes(k), planes(v),
                     jnp.cumsum(chunks, axis=2).reshape(-1, seq, d_k),
                     beta.astype(_F32).reshape(-1, seq // chunk, 1, chunk),
                     *scan, floor)
        else:
            o = _gdn(planes(q), planes(k), planes(v),
                     _gates(g, beta, chunk), *scan)
        return o.reshape(-1, heads, seq, d_v)

    return per_batch_shard(op, (q, k, v, g, beta))
