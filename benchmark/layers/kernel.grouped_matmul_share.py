"""Share of the device's busy time spent in the grouped-matmul kernels'
calls (forward, input gradient and weight gradient together): what of
the step the expert block's multiplies are, beside the sort, the
gathers and the expert stack's optimizer update, which are XLA's.
Nothing where the program makes no such call."""

from benchmark.lib import manifest

roofline = manifest.load_named("layers", "kernel.grouped_matmul_roofline")


def read(run):
    t = run.trace
    seconds = sum(call[2] for call in roofline.calls(run))
    if not seconds or not t["busy_s"]:
        return None
    return 100.0 * seconds / t["busy_s"]
