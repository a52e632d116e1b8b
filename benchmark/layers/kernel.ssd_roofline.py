"""The state-space scan's kernels against their roofline: the least time
their calls in the traced window could take for the work the RECURRENCE
needs (``kernels/ssd.py``: operations and bytes from shapes alone, never
from the kernel's chunk, B and C once a group, so no kernel can read
over 100%) over the time they took in the trace; forward and backward
calls apart on stderr.  The calls are told by the names they carry
(``ssd_fwd``, ``ssd_bwd``) and counted from their results' shapes and
the configuration's ``mamba_num_heads`` and ``ssm_state_size``.  Nothing
in a configuration that does not list the kernel, or where the program
makes no such call (a parent; a program that took the reference
path)."""

import re
import sys

from benchmark.lib import kernels, manifest, peaks

KERNEL = "ssd"


def calls(run):
    """[(kind, (operations, bytes), seconds, calls)] of the kernel's
    calls in the trace."""
    t = run.trace
    if not t or KERNEL not in run.config.get("kernels", ()):
        return []
    module = manifest.load_named("kernels", KERNEL)
    heads = run.config.get("mamba_num_heads", 0)
    state = run.config.get("ssm_state_size", 0)
    out = []
    for hlo, (seconds, count) in t["custom_calls"].items():
        parsed = kernels.parse_call(hlo) if re.search(
            module.PATTERN, hlo) else None
        call = module.classify(*parsed, hlo=hlo, heads=heads,
                               state=state) if parsed else None
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
              "(%.2f%%) in %.1f calls" % (KERNEL, kind, bound, floor,
                                          seconds, 100 * floor / seconds,
                                          count),
              file=sys.stderr, flush=True)
    return 100.0 * least / taken
