"""The flash-attention kernels of a stack whose attention layers are all
full causal against their roofline: the least time their calls in the
traced window could take (operations over peak FLOP/s or bytes over peak
bytes/s, whichever is larger, per call) over the time they took in the
trace; the forward and the fused backward (``flash_fwd``, ``flash_bwd``;
``flash_dq`` and ``flash_dkv`` on the path that splits) apart on stderr.
Calls are told by the names they carry and counted at what attention
needs (``kernels/flash_attention.py``).  Nothing in a configuration that
does not list the kernel."""

from benchmark.lib import kernels


def read(run):
    return kernels.roofline_share(run, "flash_attention")
