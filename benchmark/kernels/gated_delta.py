"""The gated delta rule's kernels (``ops/gated_delta.py``) in a trace:
which ``tpu_custom_call`` is which, and what a call must do, whatever
implements it.

The Pallas calls carry their names into the trace as the HLO
instruction's own (``%gdn_fwd.12 = (bf16[15,16384,192], f32[15,256,96,
192]) custom-call(...)``; under remat ``%checkpoint_gdn_fwd__.2``):
``gdn_fwd`` returns the output [heads, T, d_v] first, ``gdn_bwd`` dq
[heads, T, d_k], dk, dv [heads, T, d_v] and the gates' cotangents.

The work is the RECURRENCE's, counted from shapes alone and never from
the kernel's chunk: a token of a head decays nothing (a scalar times the
state is elementwise), reads ``S k`` (d_k d_v multiply-adds), writes the
rank-one correction ``u k^T`` (d_k d_v) and reads ``S q`` (d_k d_v): 3
d_k d_v multiply-adds forward.  The backward by the same rule: the
cotangents of q (``S^T do``), of S from the output (``do q^T``), of u
(``dS k``), of k from the write (``dS^T u``), of S from the read (``du
k^T``) and of k from the read (``S^T du``), six more, beside the three
of the forward it has to run again to have S: 9 d_k d_v.  Bytes: each
operand and each result once: q, k [T, d_k], v, o [T, d_v], the decay
and the write strength as float32 scalars a token; in the backward q,
k, v, do in and dq, dk, dv out and the four scalars.  The chunk-start
states a kernel may write or read are its own choice and are not work.
A later kernel with another chunk, or one that fuses the convolutions,
is read against the same count, and none can pass 100%: at 15 heads x
16,384 x 96 | 192 the forward's floor is its bytes (285 MB, 0.35 ms a
layer), not its operations (27.2 GFLOP, 0.14 ms); the backward's 570 MB
(0.70 ms) against 81.5 GFLOP (0.41 ms).
"""

import re

PATTERN = r"gdn_(fwd|bwd)"   # the trace events that may be this kernel

MULTIPLY_ADDS = {"fwd": 3, "bwd": 9}   # times d_k d_v, a token a head


def call(batch, heads, seq_len, d_k, d_v, kind, bytes_per_el=2):
    """(operations, HBM bytes) one call over ``batch`` sequences of
    ``heads`` heads must do."""
    tokens = batch * heads * seq_len
    flops = 2 * MULTIPLY_ADDS[kind] * tokens * d_k * d_v
    planes = {"fwd": 2 * d_k + 2 * d_v,          # q, k, v -> o
              "bwd": 4 * d_k + 4 * d_v}[kind]    # q, k, v, do -> dq, dk, dv
    scalars = {"fwd": 2, "bwd": 4}[kind]         # g, beta (, dg, dbeta)
    return flops, tokens * (planes * bytes_per_el + 4 * scalars)


def classify(results, operands, hlo=""):
    """(kind, (operations, bytes)) of a custom call, or None if it is
    not one of this kernel's: told by the name in the instruction's
    text, counted from its results' shapes ([heads, T, width]; the
    forward's first is o, the backward's first three dq, dk, dv)."""
    m = re.search(PATTERN, hlo.split(" = ")[0])
    if m is None or not results or len(results[0][1]) != 3:
        return None
    kind = m.group(1)
    planes = [dims for _, dims in results if len(dims) == 3]
    if kind == "fwd":
        states = [dims for _, dims in results if len(dims) == 4]
        if not states:
            return None
        (bh, seq, d_v), d_k = planes[0], states[0][2]
    else:
        if len(planes) < 3:
            return None
        (bh, seq, d_k), d_v = planes[0], planes[2][2]
    return kind, call(1, bh, seq, d_k, d_v, kind)
