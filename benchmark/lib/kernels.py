"""A named kernel against its roofline, from the custom calls of a trace.

An event's name is its HLO instruction, ``%x = <results> custom-call(
<operands>), custom_call_target="tpu_custom_call"``.  What a call is, and
the operations and bytes it must do, says the kernel's own file,
``kernels/<name>.py``: ``classify(results, operands, hlo=<the text>)``,
which tells a call by the name its instruction carries.  A configuration
lists the kernels its model calls under ``kernels``.
"""

import inspect
import re
import sys

from benchmark.lib import manifest, peaks

_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")


def shapes(text):
    """[(dtype, dims)] of the arrays a piece of HLO text names."""
    return [(d, tuple(int(x) for x in dims.split(",") if x))
            for d, dims in _SHAPE.findall(text)]


def _split_call(hlo):
    """(the results' text, the operands') of a custom-call instruction's
    text, or None."""
    m = re.match(r"%?\S+ = (.*?) custom-call\((.*)", hlo, re.S)
    return m and (m.group(1), m.group(2).split("custom_call_target")[0])


def parse_call(hlo):
    """([(dtype, dims)] of the results, number of operands) of a
    custom-call instruction's text, or None."""
    parts = _split_call(hlo)
    return parts and (shapes(parts[0]), parts[1].count("%"))


def operand_shapes(hlo):
    """[(dtype, dims)] of a custom call's operands, as far as its text
    states them (a trace's does; a test's may cut them)."""
    parts = _split_call(hlo)
    return shapes(parts[1]) if parts else []


def calls(run, kernel, **of_config):
    """[(what the kernel's ``classify`` said, seconds, calls)] of the
    kernel's calls in the trace; ``of_config`` is what its ``classify``
    asks of the configuration (a ``classify`` that takes no ``hlo`` goes
    by the results and the count of operands alone).  Nothing untraced,
    or in a configuration that does not list the kernel."""
    t = run.trace
    if not t or kernel not in run.config.get("kernels", ()):
        return []
    module = manifest.load_named("kernels", kernel)
    pattern = re.compile(module.PATTERN)
    takes_text = "hlo" in inspect.signature(module.classify).parameters
    out = []
    for hlo, (seconds, count) in t["custom_calls"].items():
        parsed = parse_call(hlo) if pattern.search(hlo) else None
        call = module.classify(*parsed, **(
            dict(of_config, hlo=hlo) if takes_text
            else of_config)) if parsed else None
        if call is not None:
            out.append((call, seconds, count))
    return out


def roofline(run, kernel, found, label=lambda call: call[0]):
    """Sum over the calls ``found`` (``calls``) of the least time a call
    could take, over the time they took in the trace, in percent; a
    call's work is the second of what ``classify`` said.  The calls of
    one ``label`` (their kind, unless given) together on stderr."""
    least = taken = 0.0
    groups = {}
    for call, seconds, count in found:
        flops, nbytes = call[1]
        floor, bound = peaks.roofline_seconds(flops, nbytes,
                                              run.device["kind"])
        least += count * floor
        taken += seconds
        seen = groups.setdefault((label(call), bound), [0.0, 0.0, 0.0])
        seen[0] += count * floor
        seen[1] += seconds
        seen[2] += count
    if not taken:
        return None
    for (name, bound), (floor, seconds, count) in sorted(groups.items()):
        print("[benchmark] %s %s: %s-bound, least %.6f s of %.6f s taken "
              "(%.1f%%) in %.1f calls" % (kernel, name, bound, floor,
                                          seconds, 100 * floor / seconds,
                                          count),
              file=sys.stderr, flush=True)
    return 100.0 * least / taken


def roofline_share(run, kernel):
    return roofline(run, kernel, calls(run, kernel))


def busy_share(run, found):
    """Share of the device's busy time the calls ``found`` took, in
    percent."""
    seconds = sum(seconds for _, seconds, _ in found)
    if not seconds or not run.trace["busy_s"]:
        return None
    return 100.0 * seconds / run.trace["busy_s"]
