"""The flash-attention kernels against their roofline: the least time
their calls in the traced window could take (operations over peak FLOP/s
or bytes over peak bytes/s, whichever is larger, per call) over the time
they took in the trace.  Nothing in a configuration that does not list
the kernel."""

from benchmark.lib import kernels


def read(run):
    return kernels.roofline_share(run, "flash_attention")
