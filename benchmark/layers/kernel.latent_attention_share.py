"""Share of the device's busy time spent in the latent-attention
kernels' calls (forward and backward together): what of the step
attention's scores are, beside the projections, the experts and the
head, which are XLA's and the grouped matmul's.  Nothing where the
program names no such call."""

from benchmark.lib import kernels, manifest

roofline = manifest.load_named("layers", "kernel.latent_attention_roofline")


def read(run):
    return kernels.busy_share(run, roofline.calls(run))
