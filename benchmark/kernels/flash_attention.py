"""The flash-attention kernels (``ops/flash_attention.py``) in a trace:
which ``tpu_custom_call`` is which, and what a call must do.

The Pallas kernels carry no name of their own into the trace, so a call
is told apart by its results: the forward returns (out, l, m), ``dq``
returns dq, ``dk-dv`` returns (dk, dv).  Shapes are the kernel's own
[batch*heads, seq, head_dim].
"""

PATTERN = "tpu_custom_call"   # the trace events that may be this kernel


def call(batch, heads, seq_len, head_dim, kind, bytes_per_el=2):
    """(operations, HBM bytes) one causal flash-attention kernel call must
    do.  ``kind``: ``fwd`` (S = QK^T, O = PV), ``dq`` (S, dP, dQ) or
    ``dkv`` (S, dP, dV, dK): the score recompute is part of the
    algorithm.  Each matmul is 2*B*H*T*T*D, halved by causality."""
    matmuls = {"fwd": 2, "dq": 3, "dkv": 4}[kind]
    tensor = batch * heads * seq_len * head_dim * bytes_per_el
    tensors = {"fwd": 4, "dq": 6, "dkv": 7}[kind]    # read + written
    flops = matmuls * 2 * batch * heads * seq_len * seq_len * head_dim // 2
    return flops, tensors * tensor


def classify(results, operands):
    """(kind, (operations, bytes)) of a custom call with these results
    ([(dtype, dims)]) and this many operands, or None if it is not one of
    this kernel's calls."""
    kind = {3: "fwd", 1: "dq", 2: "dkv"}.get(len(results))
    if kind is None or len(results[0][1]) != 3:
        return None
    bh, seq, dim = results[0][1]
    return kind, call(1, bh, seq, dim, kind)
