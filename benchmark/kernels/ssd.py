"""The state-space scan's kernels (``ops/ssd.py``) in a trace: which
``tpu_custom_call`` is which, and what a call must do, whatever
implements it.

The Pallas calls carry their names into the trace as the HLO
instruction's own (``%ssd_fwd.3 = (bf16[1,16384,4096], f32[1,8,128,512,
128]) custom-call(...)``; under remat ``%checkpoint_ssd_fwd__.2``):
``ssd_fwd`` returns the output y [B, T, heads x head_dim] first, then
the chunk-start states [B, groups, T / C, heads / groups x head_dim,
state]; ``ssd_bwd`` dx [B, T, heads x head_dim], dB and dC [B, T, groups
x state] and the gates' cotangents [B, heads, T / C, 2, C].

The work is the RECURRENCE's, counted from shapes alone and never from
the kernel's chunk: a token of a head decays nothing (a scalar times the
state is elementwise), writes ``dt x B^T`` (d_state d_head
multiply-adds) and reads ``S C`` (d_state d_head): 2 d_state d_head
forward.  The backward by ``kernels/gated_delta.py``'s rule: the
cotangents of C (``S^T dy``), of S from the output (``dy C^T``), of the
write's value (``dS B``) and of B (``dS^T (dt x)``), four more, beside
the two of the forward it has to run again to have S: 6 d_state d_head.
Bytes: each operand and each result once: x and y [T, heads x head_dim]
in the compute dtype, B and C [T, groups x state] ONCE a group (the
heads of a group share them), the log decay and the step as float32
scalars a token a head; in the backward x, B, C, dy in and dx, dB, dC
out and the four scalars.  The chunk-start states a kernel may write or
read are its own choice and are not work.  A later kernel with another
chunk, or one that fuses the convolution, is read against the same
count, and none can pass 100%: at 64 heads x 16,384 x 64 | 128 in 8
groups the forward's floor is its bytes (343.9 MB, 0.42 ms a layer), not
its operations (34.4 GFLOP, 0.17 ms); the backward's 553.6 MB (0.68 ms)
against 103.1 GFLOP (0.52 ms).
"""

import re

PATTERN = r"ssd_(fwd|bwd)"   # the trace events that may be this kernel

MULTIPLY_ADDS = {"fwd": 2, "bwd": 6}   # times d_state d_head, a token a head


def call(batch, seq_len, inner, shared, state, heads, kind, bytes_per_el=2):
    """(operations, HBM bytes) one call over ``batch`` sequences must do:
    ``inner`` = heads x head_dim values a token, ``shared`` = groups x
    state values of B (and of C) a token, ``heads`` float32 scalars a
    token of each gate."""
    tokens = batch * seq_len
    flops = 2 * MULTIPLY_ADDS[kind] * tokens * inner * state
    planes = {"fwd": 2 * inner + 2 * shared,        # x, B, C -> y
              "bwd": 3 * inner + 4 * shared}[kind]  # x, B, C, dy -> dx, dB, dC
    scalars = {"fwd": 2, "bwd": 4}[kind]            # g, dt (, dg, ddt)
    return flops, tokens * (planes * bytes_per_el + 4 * scalars * heads)


def classify(results, operands, hlo="", heads=0, state=0):
    """(kind, (operations, bytes)) of a custom call, or None if it is
    not one of this kernel's: told by the name in the instruction's
    text, counted from its results' shapes (the forward's first is y [B,
    T, inner] beside the states [B, groups, T / C, inner / groups,
    state]; the backward's first three dx [B, T, inner], dB, dC [B, T,
    groups x state], its last the gates' [B, heads, ..]).  ``heads`` and
    ``state``: the configuration's, for what a call's results do not
    show (the forward's float32 scalars a head; the backward's
    d_state)."""
    m = re.search(PATTERN, hlo.split(" = ")[0])
    if m is None or not results or len(results[0][1]) != 3:
        return None
    kind = m.group(1)
    planes = [dims for _, dims in results if len(dims) == 3]
    wide = [dims for dtype, dims in results
            if len(dims) == 5 and dtype == "f32"]
    if not wide:
        return None
    batch, seq, inner = planes[0]
    if kind == "fwd":
        _, groups, _, _, state = wide[0]
        shared = groups * state
    elif len(planes) >= 3 and state:
        shared, heads = planes[1][2], wide[0][1]
    else:
        return None
    return kind, call(batch, seq, inner, shared, state, heads, kind)
