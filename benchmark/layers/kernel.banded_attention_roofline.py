"""The flash-attention kernels of a stack whose layers differ in their
window against their roofline: the least time their calls in the traced
window could take (a full layer's at T (T + 1) / 2 query-key pairs a
head, a windowed layer's at its band's; the fused backward at five
products where the forward has two; operations over peak FLOP/s or
bytes over peak bytes/s, whichever is larger, per call) over the time
they took in the trace; full and windowed calls, and forward and
backward (dq and dk-dv on the path that splits), apart on stderr.  Calls
are told by the names they carry (``kernels/banded_attention.py``).
Nothing in a configuration that does not list the kernel, or where the
program names no such call."""

from benchmark.lib import kernels

KERNEL = "banded_attention"


def calls(run):
    """[((kind, (operations, bytes), window), seconds, calls)] of the
    kernel's calls in the trace."""
    return kernels.calls(run, KERNEL)


def read(run):
    return kernels.roofline(
        run, KERNEL, calls(run), label=lambda call: "%s %s" % (
            "window=%d" % call[2] if call[2] else "full", call[0]))
