"""The delta rule's kernels under a decay that is a vector a head (Kimi
Delta Attention; ``ops/gated_delta.py``'s ``kda_fwd`` / ``kda_bwd``) in
a trace: which ``tpu_custom_call`` is which, and what a call must do,
whatever implements it.

The calls carry their names into the trace as the HLO instruction's own
(``%kda_fwd.3 = (bf16[8,16384,128], f32[8,256,128,128], ..)
custom-call(...)``; under remat ``%checkpoint_kda_fwd__.2``), names the
scalar decay's reader (``kernels/gated_delta.py``: ``gdn_(fwd|bwd)``)
does not match: ``kda_fwd`` returns the output [heads, T, d_v] first,
then the chunk-start states; ``kda_bwd`` dq, dk [heads, T, d_k], dv
[heads, T, d_v], the decay's cotangent [heads, T, d_k] float32 and the
write strength's.

The work is the RECURRENCE's, counted from shapes alone and never from
the kernel's chunk or its sub-blocks: a token of a head decays nothing
(``S Diag(alpha)`` is elementwise, a vector as a scalar), reads ``S k``
(d_k d_v multiply-adds), writes the rank-one correction ``u k^T`` (d_k
d_v) and reads ``S q`` (d_k d_v): 3 d_k d_v forward, and by the same
rule as the scalar decay's 9 backward (six cotangents beside the three
of the forward it runs again to have S).  Bytes: each operand and each
result once: q, k [T, d_k], v, o [T, d_v], the log decay a float32
vector of d_k a token and the write strength a float32 scalar; in the
backward q, k, v, do in and dq, dk, dv out, the decay in and its
cotangent out, the two scalars.  The chunk-start states and the
inverses a kernel may write or read are its own choice and are not
work.  At 8 heads x 16,384 x 128 | 128 the forward's floor is its bytes
(201.9 MB, 0.25 ms a layer: the float32 decay is a third of them), not
its operations (12.9 GFLOP, 0.065 ms); the backward's 403.7 MB (0.49 ms)
against 38.7 GFLOP (0.20 ms).  No kernel can read over 100%.
"""

import re

PATTERN = r"kda_(fwd|bwd)"   # the trace events that may be this kernel

MULTIPLY_ADDS = {"fwd": 3, "bwd": 9}   # times d_k d_v, a token a head


def call(batch, heads, seq_len, d_k, d_v, kind, bytes_per_el=2):
    """(operations, HBM bytes) one call over ``batch`` sequences of
    ``heads`` heads must do."""
    tokens = batch * heads * seq_len
    flops = 2 * MULTIPLY_ADDS[kind] * tokens * d_k * d_v
    planes = {"fwd": 2 * d_k + 2 * d_v,          # q, k, v -> o
              "bwd": 4 * d_k + 4 * d_v}[kind]    # q, k, v, do -> dq, dk, dv
    # float32: g [d_k] and beta (and their cotangents)
    gates = {"fwd": d_k + 1, "bwd": 2 * (d_k + 1)}[kind]
    return flops, tokens * (planes * bytes_per_el + 4 * gates)


def classify(results, operands, hlo=""):
    """(kind, (operations, bytes)) of a custom call, or None if it is
    not one of this kernel's: told by the name in the instruction's
    text, counted from its results' shapes ([heads, T, width]; the
    forward's first is o, beside the states [heads, T / C, d_k, d_v];
    the backward's first three dq, dk, dv)."""
    m = re.search(PATTERN, hlo.split(" = ")[0])
    if m is None or not results or len(results[0][1]) != 3:
        return None
    kind = m.group(1)
    planes = [dims for _, dims in results if len(dims) == 3]
    if kind == "fwd":
        states = [dims for dtype, dims in results
                  if len(dims) == 4 and dtype == "f32"]
        if not states:
            return None
        (bh, seq, d_v), d_k = planes[0], states[0][2]
    else:
        if len(planes) < 3:
            return None
        (bh, seq, d_k), d_v = planes[0], planes[2][2]
    return kind, call(1, bh, seq, d_k, d_v, kind)
