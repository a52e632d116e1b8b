"""Share of the device's busy time spent in the state-space scan's
kernels' calls (``ssd_fwd`` and ``ssd_bwd`` together): what of the step
the Mamba-2 layers' scans are, beside their projections, convolutions
and gated norms, which are XLA's matmuls and fusions and the
short-convolution kernel.  ~3% of the step's operations and more of its
time: a chain of dependent chunk steps.  Nothing where the program makes
no such call."""

from benchmark.lib import manifest

roofline = manifest.load_named("layers", "kernel.ssd_roofline")


def read(run):
    t = run.trace
    seconds = sum(call[2] for call in roofline.calls(run))
    if not seconds or not t["busy_s"]:
        return None
    return 100.0 * seconds / t["busy_s"]
