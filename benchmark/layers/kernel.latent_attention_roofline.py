"""The latent-attention kernels against their roofline: the least time
their calls in the traced window could take (a causal head at T (T + 1)
/ 2 query-key pairs, scores over ``qk_nope_head_dim + qk_rope_head_dim``
and values of ``v_head_dim``, the RoPE key read once a sequence; the
fused backward at three products over the scores' width and two over
the values'; operations over peak FLOP/s or bytes over peak bytes/s,
whichever is larger, per call) over the time they took in the trace;
forward and backward (dq and dk-dv on the path that splits) apart on
stderr.  Calls are told by the names they carry
(``kernels/latent_attention.py``).  Nothing in a configuration that does
not list the kernel, or where the program names no such call (a run
that fell back to the reference)."""

from benchmark.lib import kernels

KERNEL = "latent_attention"


def calls(run):
    """[((kind, (operations, bytes)), seconds, calls)] of the kernel's
    calls in the trace."""
    return kernels.calls(
        run, KERNEL, heads=run.config.get("num_attention_heads"),
        d_rope=run.config.get("qk_rope_head_dim", 0))


def read(run):
    return kernels.roofline(run, KERNEL, calls(run))
