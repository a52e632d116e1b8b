"""The flash-attention kernels of a stack whose layers differ in their
window against their roofline: the least time their calls in the traced
window could take (a full layer's at T (T + 1) / 2 query-key pairs a
head, a windowed layer's at its band's; operations over peak FLOP/s or
bytes over peak bytes/s, whichever is larger, per call) over the time
they took in the trace; full and windowed calls, and forward, dq and
dk-dv, apart on stderr.  Calls are told by the names they carry
(``kernels/banded_attention.py``).  Nothing in a configuration that does
not list the kernel, or where the program names no such call (a
parent)."""

import sys

from benchmark.lib import kernels, manifest, peaks

KERNEL = "banded_attention"


def calls(run):
    """[(kind, window, (operations, bytes), seconds, calls)] of the
    kernel's calls in the trace."""
    t = run.trace
    if not t or KERNEL not in run.config.get("kernels", ()):
        return []
    module = manifest.load_named("kernels", KERNEL)
    out = []
    for hlo, (seconds, count) in t["custom_calls"].items():
        parsed = kernels.parse_call(hlo)
        call = module.classify(*parsed, hlo=hlo) if parsed else None
        if call is not None:
            kind, work, window = call
            out.append((kind, window, work, seconds, count))
    return out


def read(run):
    least = taken = 0.0
    groups = {}
    for kind, window, (flops, nbytes), seconds, count in calls(run):
        floor, bound = peaks.roofline_seconds(flops, nbytes,
                                              run.device["kind"])
        least += count * floor
        taken += seconds
        seen = groups.setdefault((window, kind, bound), [0.0, 0.0, 0.0])
        seen[0] += count * floor
        seen[1] += seconds
        seen[2] += count
    if not taken:
        return None
    for (window, kind, bound), (floor, seconds, count) in sorted(
            groups.items()):
        print("[benchmark] %s %s %s: %s-bound, least %.6f s of %.6f s "
              "taken (%.1f%%) in %.1f calls" % (
                  KERNEL, "window=%d" % window if window else "full", kind,
                  bound, floor, seconds, 100 * floor / seconds, count),
              file=sys.stderr, flush=True)
    return 100.0 * least / taken
