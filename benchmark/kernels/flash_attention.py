"""The flash-attention kernels (``ops/flash_attention.py``) in a trace:
which ``tpu_custom_call`` is which, and what a call must do, whatever
implements it.

A call is told by the NAME its instruction carries, never by its count
of results: ``flash_fwd`` (S = QK^T, O = PV) and ``flash_bwd``, the one
call that makes dk, dv and dq from one rebuild of the scores (S, dP, dV,
dQ, dK; PR 41), with ``_w<window>`` behind the name of a layer that
attends over its last ``window`` positions and ``_qk<d>_v<d>`` behind a
latent head's, whatever the lowering puts before (``jvp_``,
``checkpoint_``, ``transpose_jvp_``) or behind (``_b4``, ``__.2``):
``%flash_bwd_w2048.8 = (bf16[4,16384,128], bf16[4,16384,128],
bf16[32,16384,128]) custom-call(..)``.  ``flash_dq`` (S, dP, dQ) and
``flash_dkv`` (S, dP, dV, dK) stay as kinds for as long as the program
can emit them: ``ops/flash_attention._pallas_bwd`` keeps the two passes
for a sequence whose float32 dq does not fit VMEM (``_backward_plan``;
the worker's ``flash tiles:`` line then ends ``backward=pair why=..``
where it says ``backward=fused`` otherwise), at no shape of today's
cells.  This file's ``classify`` takes the plain names alone (a full
causal layer's); ``kernels/banded_attention.py`` the windowed ones
beside them, ``kernels/latent_attention.py`` the latent widths'.

The work is what attention NEEDS, from shapes alone and never from the
kernel's tiles (the diagonal's and a window's edge tiles are computed
whole and masked: that is the kernel's cost, not the algorithm's), so no
kernel can read over 100%: if one does, the count is wrong.  A product
is 2 * pairs * head_dim operations a query head over the query-key pairs
the mask leaves, T (T + 1) / 2 for a full causal layer, W (W + 1) / 2 +
(T - W) W under a window; the forward has two, the backward five (2.5 x
the forward's), and of a split backward dq three and dk-dv four: the
rebuild of the scores is part of the algorithm.  Bytes: every plane
once, q, o, dO and dq at the query heads, K, V and their cotangents at
the K/V heads (a grouped-query call states both: dq's leading size
beside dk's, q's beside k's), the two row statistics in float32.
"""

import re

from benchmark.lib import kernels

# the name of a call: kind, window, a latent head's widths
NAME = re.compile(r"flash_(fwd|bwd|dq|dkv)(?:_w(\d+))?(?:_qk(\d+)_v(\d+))?"
                  r"(?![0-9a-z])")
PATTERN = NAME.pattern   # the trace events that may be this kernel

PRODUCTS = {"fwd": 2, "bwd": 5, "dq": 3, "dkv": 4}
# planes of [T, head_dim] read or written: (at the query heads, at the
# K/V heads).  fwd: q, o | k, v.  bwd: q, o, dO, dq | k, v, dk, dv.
# dq: q, o, dO, dq | k, v.  dkv: q, o, dO | k, v, dk, dv.
PLANES = {"fwd": (2, 2), "bwd": (4, 4), "dq": (4, 2), "dkv": (3, 4)}


def name_of(hlo):
    """(kind, window, (scores' width, values') or None) of the name the
    instruction itself carries, left of its ``=``; None if it is none of
    these kernels' (an operand that is a named call's result names
    nothing)."""
    m = NAME.search(hlo.split(" = ")[0])
    if m is None:
        return None
    widths = (int(m.group(3)), int(m.group(4))) if m.group(3) else None
    return m.group(1), int(m.group(2) or 0), widths


def pairs(seq_len, window=0):
    """Query-key pairs a head scores: every key at or before the query,
    or the last ``window`` of them."""
    if not window or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def heads_of(results, hlo, seq_len):
    """(query heads, K/V heads) of a call, batch in both: the largest
    leading size among its results' [.., T, ..] planes (out's or dq's)
    and the smallest among its results' and operands' (k's or dk's;
    operands cut from the text leave the results')."""
    lead = lambda shapes: [dims[0] for _, dims in shapes
                           if len(dims) == 3 and dims[1] == seq_len]
    mine = lead(results)
    return max(mine), min(mine + lead(kernels.operand_shapes(hlo)))


def call(heads, seq_len, head_dim, kind, window=0, kv_heads=None,
         bytes_per_el=2):
    """(operations, HBM bytes) one call over ``heads`` query heads
    (batch x heads) and ``kv_heads`` K/V heads (as many, unless given)
    must do."""
    kv_heads = kv_heads or heads
    flops = PRODUCTS[kind] * 2 * heads * pairs(seq_len, window) * head_dim
    at_q, at_kv = PLANES[kind]
    plane = seq_len * head_dim * bytes_per_el
    stats = 2 * 4 * heads * seq_len
    return flops, (at_q * heads + at_kv * kv_heads) * plane + stats


def classify_named(results, hlo):
    """(kind, (operations, bytes), window) of a custom call that carries
    one of the equal-width names, or None."""
    name = name_of(hlo)
    if name is None or name[2] or not results or len(results[0][1]) != 3:
        return None
    kind, window, _ = name
    _, seq, dim = results[0][1]
    heads, kv_heads = heads_of(results, hlo, seq)
    return kind, call(heads, seq, dim, kind, window, kv_heads), window


def classify(results, operands, hlo=""):
    """(kind, (operations, bytes)) of a custom call, or None if it is
    not a full causal layer's flash call: told by the name in the
    instruction's text."""
    found = classify_named(results, hlo)
    return found[:2] if found and not found[2] else None
