"""Share of the device's busy time spent in the hyper-connection
kernels' calls (``hc_pre_fwd``, ``hc_post_fwd``, ``hc_pre_bwd`` and
``hc_post_bwd`` together): what of the step reading and writing a
residual stream four wide is, beside the sublayers between, which are
one wide.  The calls alone: the maps' Sinkhorn rounds and the matmul
that makes ``phi``'s gradient are XLA's and carry no name.  Nothing
where the program makes no such call."""

from benchmark.lib import manifest

roofline = manifest.load_named("layers", "kernel.hyper_mix_roofline")


def read(run):
    t = run.trace
    seconds = sum(call[2] for call in roofline.calls(run))
    if not seconds or not t["busy_s"]:
        return None
    return 100.0 * seconds / t["busy_s"]
