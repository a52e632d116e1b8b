"""The hyper-connection kernels (``ops/hyper_mix.py``) in a trace: which
``tpu_custom_call`` is which, and the least a call must move, whatever
implements it.

The calls carry their names into the trace as the HLO instruction's own:
``hc_pre_fwd`` (a sublayer's read: the stream -> its streams mixed, and
the 24 logits a token), ``hc_post_fwd`` (its write: the stream and the
sublayer's result -> the next stream), ``hc_post_bwd`` and
``hc_pre_bwd``; under remat ``%checkpoint_hc_pre_fwd__.2``.  They are
bound by memory: a call is counted at the bytes the ALGORITHM must move
(every operand and result once at its own dtype, the maps at their 2 n
+ n^2 float32 values a token and not at the 128-lane tile the kernels
pad them to) and at the logits' matmul alone of its operations.  A
program that names no such call (a parent, a run that took the jnp
path) has none of this kernel's.
"""

import re

# the trace events that may be this kernel
PATTERN = r"hc_(pre|post)_(fwd|bwd)(?![0-9a-z])"

# stream-sized and sublayer-sized arrays a call reads and writes
PASSES = {
    ("pre", "fwd"): (1, 1),     # X -> u
    ("post", "fwd"): (2, 1),    # X, y -> X'
    ("post", "bwd"): (3, 2),    # X, y, dX' -> dX, dy
    ("pre", "bwd"): (3, 1),     # X, du, dX -> dX
}
# float32 map values a token a call reads and writes, in units of 2 n +
# n^2: the logits out; the maps in; the maps in and their cotangent
# out; the logits and their cotangent in
MAPS = {("pre", "fwd"): 1, ("post", "fwd"): 1, ("post", "bwd"): 2,
        ("pre", "bwd"): 2}


def call(rows, width, streams, which, bytes_per_el=2):
    """(operations, HBM bytes) one call over ``rows`` tokens of a stream
    ``streams`` x ``width`` wide must do."""
    wide, narrow = PASSES[which]
    logits = 2 * streams + streams * streams
    nbytes = rows * ((wide * streams + narrow) * width * bytes_per_el
                     + MAPS[which] * logits * 4)
    flops = (2 * rows * streams * width * logits
             if which[0] == "pre" else 0)
    return flops, nbytes


def classify(results, operands, hlo="", streams=None):
    """(kind, (operations, bytes)) of a custom call, or None if it is
    not one of this kernel's: told by the name in the instruction's
    text.  Every result is [rows, columns]; a call's widest is the
    stream (``streams`` x width: the configuration's ``hc_mult``) but
    for ``hc_pre_fwd``, whose first is one stream wide."""
    m = re.search(PATTERN, hlo.split(" = ")[0])
    if m is None or not results or not streams or any(
            len(dims) != 2 for _, dims in results):
        return None
    which = m.group(1), m.group(2)
    dtype, (rows, columns) = results[0]
    width = columns if which == ("pre", "fwd") else columns // streams
    size = {"bf16": 2, "f16": 2, "f32": 4}.get(dtype, 2)
    return "_".join(which), call(rows, width, streams, which, size)
