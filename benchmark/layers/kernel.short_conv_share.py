"""Share of the device's busy time spent in the short-convolution
kernels' calls (forward and backward together): what of the step the
conv layers' mixing is, beside their two projections, which are XLA's
matmuls.  Nothing where the program makes no such call."""

from benchmark.lib import manifest

roofline = manifest.load_named("layers", "kernel.short_conv_roofline")


def read(run):
    t = run.trace
    seconds = sum(call[2] for call in roofline.calls(run))
    if not seconds or not t["busy_s"]:
        return None
    return 100.0 * seconds / t["busy_s"]
