"""A named kernel against its roofline, from the custom calls of a trace.

An event's name is its HLO instruction, ``%x = <results> custom-call(
<operands>), custom_call_target="tpu_custom_call"``.  What a call is, and
the operations and bytes it must do, says the kernel's own file,
``kernels/<name>.py``: ``classify(results, operands)``.  A configuration
lists the kernels its model calls under ``kernels``.
"""

import re
import sys

from benchmark.lib import manifest, peaks

_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")


def parse_call(hlo):
    """([(dtype, dims)] of the results, number of operands) of a
    custom-call instruction's text, or None."""
    m = re.match(r"%?\S+ = (.*?) custom-call\((.*)", hlo, re.S)
    if not m:
        return None
    results = [(d, tuple(int(x) for x in dims.split(",") if x))
               for d, dims in _SHAPE.findall(m.group(1))]
    operands = m.group(2).split("custom_call_target")[0]
    return results, operands.count("%")


def roofline_share(run, kernel):
    """Sum over the kernel's calls of the least time a call could take,
    over the time the calls took in the trace, in percent."""
    t = run.trace
    if not t or kernel not in run.config.get("kernels", ()):
        return None
    module = manifest.load_named("kernels", kernel)
    pattern = re.compile(module.PATTERN)
    least = taken = 0.0
    bounds = {}
    for hlo, (seconds, calls) in t["custom_calls"].items():
        parsed = parse_call(hlo) if pattern.search(hlo) else None
        call = module.classify(*parsed) if parsed else None
        if call is None:
            continue
        kind, (flops, nbytes) = call
        floor, bound = peaks.roofline_seconds(flops, nbytes,
                                              run.device["kind"])
        least += calls * floor
        taken += seconds
        seen = bounds.setdefault((kind, bound), [0.0, 0.0])
        seen[0] += calls * floor
        seen[1] += seconds
    if not taken:
        return None
    for (kind, bound), (floor, seconds) in sorted(bounds.items()):
        print("[benchmark] %s %s: %s-bound, least %.6f s of %.6f s taken "
              "(%.1f%%)" % (kernel, kind, bound, floor, seconds,
                            100 * floor / seconds),
              file=sys.stderr, flush=True)
    return 100.0 * least / taken
