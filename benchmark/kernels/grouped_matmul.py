"""The grouped-matmul kernels (``ops/grouped_matmul.py``) in a trace:
which ``tpu_custom_call`` is which, and what a call must do.

The Pallas calls carry their names into the trace as the HLO
instruction's own (``%gmm_nn.3 = bf16[rows, n] custom-call(...)``):
``gmm_nn`` is rows @ weights, ``gmm_nt`` rows @ weights^T (the model's
forward never asks for that, so it is the input gradient) and ``gmm_tn``
the weight gradient, rows^T @ rows per group, written ``[groups * k,
n]``.  Every result is 2-D, which is also how the flash kernels'
``classify`` (3-D results) leaves them alone.
"""

import re

PATTERN = r"gmm_(nn|nt|tn)"   # the trace events that may be this kernel

KINDS = {"nn": "fwd", "nt": "dlhs", "tn": "drhs"}


def call(rows, k, n, kind, groups=0, bytes_per_el=2):
    """(operations, HBM bytes) one call must do: ``rows`` sorted rows,
    contraction ``k`` wide, result ``n`` wide for ``fwd`` / ``dlhs``;
    for ``drhs`` the ``[k, n]`` gradient of each of ``groups`` weights
    from [rows, k] and [rows, n].  Every operand is read once and every
    result written once; 2 * rows * k * n operations in all three."""
    weights = max(groups, 1) * k * n
    return 2 * rows * k * n, bytes_per_el * (rows * k + rows * n + weights)


def classify(results, operands, hlo="", rows=None, widths=(), groups=1):
    """(kind, (operations, bytes)) of a custom call, or None if it is not
    one of this kernel's.  ``lib/kernels.roofline_share`` hands over the
    results and an operand count alone, and neither holds a weight
    gradient's row count nor a forward's contraction: the reader
    (``layers/kernel.grouped_matmul_roofline.py``) passes the
    instruction's text, the rows a chip sorts in a step, the model's
    two widths (hidden, expert) and its number of experts."""
    m = re.search(PATTERN, hlo)
    if m is None or len(results) != 1 or len(results[0][1]) != 2:
        return None
    kind = KINDS[m.group(1)]
    first, n = results[0][1]
    other = [w for w in widths if w != n]
    if rows is None or len(other) != 1:
        return None
    k = other[0]
    if kind == "drhs":
        return kind, call(rows, k, n, kind, groups=first // k)
    return kind, call(first, k, n, kind, groups=groups)   # first == rows
