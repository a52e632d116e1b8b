"""Share of the device's busy time spent in the latent-attention
kernels' calls (forward, dq and dk-dv together): what of the step
attention's scores are, beside the projections, the experts and the
head, which are XLA's and the grouped matmul's.  Nothing where the
program names no such call."""

from benchmark.lib import manifest

roofline = manifest.load_named("layers", "kernel.latent_attention_roofline")


def read(run):
    t = run.trace
    seconds = sum(call[2] for call in roofline.calls(run))
    if not seconds or not t["busy_s"]:
        return None
    return 100.0 * seconds / t["busy_s"]
