"""Share of the device's busy time spent in the hyper-connection
kernels' fused calls: ``hc_post_pre_fwd`` (a sublayer's write and the
next sublayer's read of what it wrote) and ``hc_pre_post_bwd`` (their
way back), which ``kernel.hyper_mix_share`` does not match.  The two
shares together are what the stream's reading and writing costs a step.
On stderr each name's calls, how many of them one execution of the step
program makes, and the time a call.  Nothing where the program makes no
such call (a parent; a run that took the jnp path)."""

import bisect
import collections
import json
import os
import re
import sys

# the trace events that are the fused pair's, by the HLO instruction's
# own name (under remat ``%checkpoint_hc_post_pre_fwd__.2``)
PATTERN = r"hc_(post_pre_fwd|pre_post_bwd)(?![0-9a-z])"


def kind_of(hlo):
    m = re.search(PATTERN, hlo.split(" = ")[0])
    return m and m.group(1)


def per_execution(run):
    """{kind: calls inside one execution of the step program}: the
    commonest count over the executions in the trace (one cut by the
    trace's edge holds fewer, and ``trace["steps"]`` counts it whole),
    from the events ``xplane.load_in_child`` left beside the trace; {}
    without them."""
    path = os.path.join(run.trace_dir or "", "reduced.json")
    if not os.path.exists(path):
        return {}
    with open(path) as fh:
        raw = json.load(fh)
    chip = min(raw["devices"])
    spent = collections.Counter()
    for name, _, ns in raw.get("modules", {}).get(chip, ()):
        spent[name] += ns
    if not spent:
        return {}
    step = max(spent, key=spent.get)
    runs = sorted((start, start + ns) for name, start, ns
                  in raw["modules"][chip] if name == step)
    starts = [start for start, _ in runs]
    seen = collections.defaultdict(collections.Counter)
    for name, start, _ in raw["devices"][chip]:
        kind = kind_of(name)
        at = bisect.bisect_right(starts, start) - 1
        if kind and at >= 0 and start < runs[at][1]:
            seen[kind][at] += 1
    return {kind: collections.Counter(counts.values()).most_common(1)[0][0]
            for kind, counts in seen.items()}


def read(run):
    t = run.trace
    if not t or not t["busy_s"]:
        return None
    seen = {}
    for hlo, (seconds, count) in t["custom_calls"].items():
        kind = kind_of(hlo)
        if kind:
            both = seen.setdefault(kind, [0.0, 0.0])
            both[0] += seconds
            both[1] += count
    if not seen:
        return None
    each = per_execution(run)
    for kind, (seconds, count) in sorted(seen.items()):
        print("[benchmark] hyper_fused %s: %.1f calls, %s a step program, "
              "%.6f s taken, %.4f ms a call" % (
                  kind, count, each.get(kind, "-"), seconds,
                  1e3 * seconds / count), file=sys.stderr, flush=True)
    return 100.0 * sum(seconds for seconds, _ in seen.values()) / t["busy_s"]
