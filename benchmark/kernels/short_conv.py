"""The gated short convolution's kernels (``ops/short_conv.py``) in a
trace: which ``tpu_custom_call`` is which, and what a call must do.

The Pallas calls carry their names into the trace as the HLO
instruction's own (``%sconv_fwd.3 = bf16[rows, e] custom-call(...)``):
``sconv_fwd`` returns ``C * conv(B * u)`` [rows, e]; ``sconv_bwd`` returns
(dB | dC | du [rows, 3e], each row tile's part of the taps' gradient
[tiles * 8, e]).  Every result is 2-D, which is how the flash kernels'
``classify`` (3-D results) leaves them alone, and ``gmm_(nn|nt|tn)`` does
not match the names.
"""

import re

PATTERN = r"sconv_(fwd|bwd)"   # the trace events that may be this kernel


def call(rows, channels, kind, taps=3, bytes_per_el=2):
    """(operations, HBM bytes) one call must do on ``rows`` x
    ``channels``.  ``fwd`` reads B, C, u and writes the result, 4 passes;
    the gate, ``taps`` multiplies and ``taps - 1`` adds, the second
    gate.  ``bwd`` reads B, C, u and dout and writes dB, dC, du, 7
    passes; it rebuilds the gate and the convolution, runs the taps over
    ``dout * C``, and forms the three products and the taps' own
    gradient (a multiply and an add a tap).  The taps and their
    gradient's parts are not counted: under a thousandth of a pass."""
    passes = {"fwd": 4, "bwd": 7}[kind]
    conv = 2 * taps - 1
    ops = {"fwd": 2 + conv, "bwd": 2 + conv + 1 + conv + 2 + 2 * taps}[kind]
    return ops * rows * channels, passes * rows * channels * bytes_per_el


def classify(results, operands, hlo="", taps=3):
    """(kind, (operations, bytes)) of a custom call, or None if it is not
    one of this kernel's.  The door ``lib/kernels.roofline_share`` uses
    hands no text over: the reader
    (``layers/kernel.short_conv_roofline.py``) passes the instruction's."""
    m = re.search(PATTERN, hlo)
    if m is None or not results or any(len(r[1]) != 2 for r in results):
        return None
    kind = m.group(1)
    if len(results) != {"fwd": 1, "bwd": 2}[kind]:
        return None
    rows, width = results[0][1]
    channels = width // 3 if kind == "bwd" else width
    return kind, call(rows, channels, kind, taps)
