"""The grouped-matmul kernels against their roofline: the least time
their calls in the traced window could take (operations over peak FLOP/s
or bytes over peak bytes/s, whichever is larger, per call) over the time
they took in the trace; forward, input-gradient and weight-gradient
calls apart on stderr.  A call's text holds its result's shape alone, so
the rows a chip sorts in a step (its share of batch_size x seq_len x
experts a token), the two widths and the number of experts come from the
configuration and the traffic.  Nothing in a configuration that does not
list the kernel, or where the program makes no such call (a parent)."""

import re
import sys

from benchmark.lib import kernels, manifest, peaks

KERNEL = "grouped_matmul"


def calls(run):
    """[(kind, (operations, bytes), seconds, calls)] of the kernel's
    calls in the trace."""
    t = run.trace
    if not t or KERNEL not in run.config.get("kernels", ()):
        return []
    module = manifest.load_named("kernels", KERNEL)
    config, flags = run.config, run.traffic["flags"]
    rows = (flags["batch_size"] * config["seq_len"]
            * config["num_experts_per_tok"]) // run.cell["chips"]
    widths = (config["hidden_size"], config["intermediate_size"])
    out = []
    for hlo, (seconds, count) in t["custom_calls"].items():
        parsed = kernels.parse_call(hlo) if re.search(
            module.PATTERN, hlo) else None
        call = module.classify(
            *parsed, hlo=hlo, rows=rows, widths=widths,
            groups=config["num_experts"]) if parsed else None
        if call is not None:
            out.append((call[0], call[1], seconds, count))
    return out


def read(run):
    least = taken = 0.0
    bounds = {}
    for kind, (flops, nbytes), seconds, count in calls(run):
        floor, bound = peaks.roofline_seconds(flops, nbytes,
                                              run.device["kind"])
        least += count * floor
        taken += seconds
        seen = bounds.setdefault((kind, bound), [0.0, 0.0, 0.0])
        seen[0] += count * floor
        seen[1] += seconds
        seen[2] += count
    if not taken:
        return None
    for (kind, bound), (floor, seconds, count) in sorted(bounds.items()):
        print("[benchmark] %s %s: %s-bound, least %.6f s of %.6f s taken "
              "(%.1f%%) in %.1f calls" % (KERNEL, kind, bound, floor,
                                          seconds, 100 * floor / seconds,
                                          count),
              file=sys.stderr, flush=True)
    return 100.0 * least / taken
