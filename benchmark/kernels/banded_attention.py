"""The flash-attention kernels (``ops/flash_attention.py``) in a trace
of a stack whose attention layers differ in their window: which
``tpu_custom_call`` is which, and what a call must do.

Since PR 33 the Pallas calls carry their names into the trace as the HLO
instruction's own: ``flash_fwd`` (S = QK^T, O = PV), ``flash_dq`` (S, dP,
dQ) and ``flash_dkv`` (S, dP, dV, dK), with ``_w<window>`` behind the
name where the layer attends over its last ``window`` positions
(``%flash_fwd_w4096.3 = (bf16[28,16384,128], ..) custom-call(..)``;
under remat ``%checkpoint_flash_fwd_w4096__.2``).  A call is counted at
the query-key pairs its band holds, T (T + 1) / 2 for a full causal
layer, W (W + 1) / 2 + (T - W) W under a window, and not at the
sub-tiles the kernel walks (the diagonal's and the window's edge tiles
are computed whole and masked: that is the kernel's cost, not the
algorithm's).  ``kernels/flash_attention.py`` tells calls by their
result counts alone and counts each as full causal at T * T / 2: right
for the cells whose every layer is full, 2.3 x over for a window of
4,096 in 16,384.  A program that names no call (a parent) has none of
this kernel's.
"""

import re

# the trace events that may be this kernel
PATTERN = r"flash_(fwd|dq|dkv)(?:_w(\d+))?(?![0-9a-z])"

MATMULS = {"fwd": 2, "dq": 3, "dkv": 4}
TENSORS = {"fwd": 4, "dq": 6, "dkv": 7}      # read + written, [bh, T, D]


def pairs(seq_len, window=0):
    """Query-key pairs a head scores: every key at or before the query,
    or the last ``window`` of them."""
    if not window or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def call(heads, seq_len, head_dim, kind, window=0, bytes_per_el=2):
    """(operations, HBM bytes) one call over ``heads`` (batch x heads)
    must do: each of its matmuls is 2 * pairs * head_dim a head; the
    score recompute of the backward kernels is part of the algorithm.
    q, k, v, o and their cotangents are read or written once."""
    flops = MATMULS[kind] * 2 * heads * pairs(seq_len, window) * head_dim
    return flops, TENSORS[kind] * heads * seq_len * head_dim * bytes_per_el


def classify(results, operands, hlo=""):
    """(kind, (operations, bytes), window) of a custom call, or None if
    it is not one of this kernel's: told by the name in the
    instruction's text, which ``lib/kernels.roofline_share`` does not
    hand over, so the readers of ``layers/kernel.banded_attention_*``
    pass it."""
    m = re.search(PATTERN, hlo.split(" = ")[0])
    if m is None or not results or len(results[0][1]) != 3:
        return None
    kind, window = m.group(1), int(m.group(2) or 0)
    bh, seq, dim = results[0][1]
    return kind, call(bh, seq, dim, kind, window), window
