"""The latent-attention kernels (``ops/flash_attention.latent_attention``)
in a trace: which ``tpu_custom_call`` is which, and what a call must do,
whatever implements it.

The calls carry their names into the trace as the HLO instruction's own,
the equal-width kernels' with the widths behind them
(``kernels/flash_attention.name_of``): ``flash_fwd_qk192_v128`` (S = Q
K^T over 192, O = P V over 128) and the fused ``flash_bwd_qk192_v128``
(S, dQ, dK over 192, dP, dV over 128: five products where the forward
has two; its results dk, dv, each head's float32 part of the RoPE key's
gradient, dq and dq's RoPE part); on the path that still splits
(``kernels/flash_attention.py`` says which) ``flash_dq_qk192_v128`` (S,
dP over 128, dQ over 192) and ``flash_dkv_qk192_v128`` (S, dP, dV over
128, dK over 192); under remat ``%checkpoint_flash_fwd_qk192_v128__.2``.
A call is counted at the
query-key pairs a causal head scores, T (T + 1) / 2, and at the widths
of the algorithm: a score over ``D_nope + D_rope``, a value of ``D_v``,
not at what a tile pads them to.  Bytes: every operand and result once,
the RoPE key ONCE A SEQUENCE (it is one plane for all the heads), its
gradient once a head (the kernel writes each head's part).  A program
that names no such call (a parent, or a run that fell back) has none of
this kernel's.
"""

from benchmark.lib import manifest

_flash = manifest.load_named("kernels", "flash_attention")

PATTERN = _flash.PATTERN   # the trace events that may be this kernel

# matmuls of a call by the width they contract or produce: (over the
# scores' width, over the values' width)
MATMULS = {"fwd": (1, 1), "bwd": (3, 2), "dq": (2, 1), "dkv": (2, 2)}


pairs = _flash.pairs   # query-key pairs a causal head scores


def call(batch, heads, seq_len, d_qk, d_v, kind, d_rope, bytes_per_el=2):
    """(operations, HBM bytes) one call over ``batch`` sequences of
    ``heads`` heads must do.  Forward: 2 * pairs * (d_qk + d_v) a head;
    the fused backward: S, dQ, dK, dP and dV, 2 * pairs * (3 d_qk + 2
    d_v); dq: S, dP and dQ, 2 * pairs * (2 d_qk + d_v); dkv: S, dP, dV
    and dK, 2 * pairs * (2 d_qk + 2 d_v).  Bytes: q and k at d_qk a head
    but for the RoPE key's ``d_rope``, read once a sequence; v, o and
    their cotangents at d_v; the row statistics in float32."""
    over_qk, over_v = MATMULS[kind]
    flops = 2 * batch * heads * pairs(seq_len) * (
        over_qk * d_qk + over_v * d_v)
    rows = batch * seq_len
    q = rows * heads * d_qk
    k = rows * (heads * (d_qk - d_rope) + d_rope)    # one RoPE key
    v = rows * heads * d_v
    stats = 4 * rows * heads
    if kind == "fwd":        # q, k, v -> o, l, m
        nbytes = (q + k + 2 * v) * bytes_per_el + 2 * stats
    elif kind == "bwd":      # q, k, v, o, do, lse, delta -> dq, dk, dv
        nbytes = ((2 * q + 2 * k + 4 * v) * bytes_per_el + 2 * stats
                  + 4 * rows * heads * d_rope)       # float32 partials
    elif kind == "dq":       # q, do, k, v, lse, delta -> dq
        nbytes = (2 * q + k + 2 * v) * bytes_per_el + 2 * stats
    else:                    # k, v, q, do, lse, delta -> dk, dv, dk_rope
        nbytes = ((q + 2 * k + 3 * v) * bytes_per_el + 2 * stats
                  + 4 * rows * heads * d_rope)       # float32 partials
    return flops, nbytes


def classify(results, operands, hlo="", heads=None, d_rope=0):
    """(kind, (operations, bytes)) of a custom call, or None if it is
    not one of this kernel's: told by the name in the instruction's
    text.  The first result is [batch * heads, T, width]; ``heads``
    (the configuration's) splits its leading size, 1 sequence without;
    ``d_rope`` (the configuration's ``qk_rope_head_dim``: the name
    carries the scores' whole width alone) is the part of a key that is
    one plane a sequence, 0 counts every key a head's own."""
    name = _flash.name_of(hlo)
    if name is None or not name[2] or not results or len(
            results[0][1]) != 3:
        return None
    kind, (d_qk, d_v) = name[0], name[2]
    bh, seq, _ = results[0][1]
    heads = heads or bh
    return kind, call(bh // heads, heads, seq, d_qk, d_v, kind, d_rope)
