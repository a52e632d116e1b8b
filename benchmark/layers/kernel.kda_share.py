"""Share of the device's busy time spent in the vector-decay delta
rule's kernels' calls (``kda_fwd`` and ``kda_bwd`` together): what of
the step the KDA layers' scans are, beside their projections,
convolutions and expert layers, which are XLA's matmuls, the
short-convolution kernel and the dispatch.  Under 1% of the step's
operations and not of its time: a chain of dependent chunk steps.
Nothing where the program makes no such call."""

from benchmark.lib import manifest

roofline = manifest.load_named("layers", "kernel.kda_roofline")


def read(run):
    t = run.trace
    seconds = sum(call[2] for call in roofline.calls(run))
    if not seconds or not t["busy_s"]:
        return None
    return 100.0 * seconds / t["busy_s"]
