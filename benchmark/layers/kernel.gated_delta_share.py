"""Share of the device's busy time spent in the gated delta rule's
kernels' calls (forward and backward together): what of the step the
delta layers' scans are, beside their projections, convolutions and
MLPs, which are XLA's matmuls and the short-convolution kernel.  Under
1% of the step's operations and not of its time: a chain of dependent
chunk steps.  Nothing where the program makes no such call."""

from benchmark.lib import manifest

roofline = manifest.load_named("layers", "kernel.gated_delta_roofline")


def read(run):
    t = run.trace
    seconds = sum(call[2] for call in roofline.calls(run))
    if not seconds or not t["busy_s"]:
        return None
    return 100.0 * seconds / t["busy_s"]
