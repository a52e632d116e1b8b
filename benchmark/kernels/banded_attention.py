"""The flash-attention kernels (``ops/flash_attention.py``) in a trace
of a stack whose attention layers differ in their window: which
``tpu_custom_call`` is which, and what a call must do.

The calls carry their names into the trace as the HLO instruction's own
(since PR 33): ``flash_fwd`` and the fused ``flash_bwd`` (``flash_dq``
and ``flash_dkv`` on the path that still splits), with ``_w<window>``
behind the name where the layer attends over its last ``window``
positions (``%flash_fwd_w4096.3 = (bf16[28,16384,128], ..)
custom-call(..)``; under remat ``%checkpoint_flash_fwd_w4096__.2``).
The names, the query-key pairs of a band and a call's work are
``kernels/flash_attention.py``'s, which reads the plain names alone: a
call is counted at the pairs its band holds, T (T + 1) / 2 for a full
causal layer, W (W + 1) / 2 + (T - W) W under a window, not at the
sub-tiles the kernel walks, and the fused backward at five products
where the forward has two.  The latent widths' calls
(``_qk<d>_v<d>``) are ``kernels/latent_attention.py``'s.  A program
that names no call has none of this kernel's.
"""

from benchmark.lib import manifest

_flash = manifest.load_named("kernels", "flash_attention")

PATTERN = _flash.PATTERN   # the trace events that may be this kernel
pairs, call = _flash.pairs, _flash.call


def classify(results, operands, hlo=""):
    """(kind, (operations, bytes), window) of a custom call, or None if
    it is not one of this kernel's: told by the name in the
    instruction's text."""
    return _flash.classify_named(results, hlo)
