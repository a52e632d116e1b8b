"""From a profiler trace to numbers.

Two halves.  ``load`` runs in a child process (it needs jaxlib's xplane
reader; the benchmark's parent never imports JAX) and boils an
``.xplane.pb`` down to plain lists.  Everything else is arithmetic on
those lists and is tested on ``fixtures/tiny_trace.json``:

    {"devices": {"/device:TPU:0": [[name, start_ns, dur_ns], ...]},
     "modules": {"/device:TPU:0": [[name, start_ns, dur_ns], ...]},
     "host": [[name, start_ns, dur_ns, thread], ...]}

``devices`` holds each chip's "XLA Ops" line and ``modules`` its "XLA
Modules" line (one event per executed program: the training step is the
one that takes most time); ``host`` holds the host planes' events of
``HOST_MIN_NS`` and longer: the runtime's own (executions, transfers,
waits) and whatever the program puts on the profiler's clock; today the
program annotates nothing (PERF.md, for the ``tracing`` issue).  An op's
name is its HLO instruction as the profiler writes it.
"""

import glob
import json
import os
import re
import subprocess
import sys

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_MIN_NS = 100_000      # shorter host events explain no idle gap
HOST_MAX_EVENTS = 20_000   # the longest are kept
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute")
MOSAIC = re.compile(r"custom-call|tpu_custom_call")

# -- the child: xplane.pb -> plain lists -------------------------------------

def load(trace_dir):
    """Runs where jaxlib is importable.  Returns the dict above, plus
    ``lines``: every plane and line seen with its event count."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return None
    data = ProfileData.from_file(paths[-1])
    out = {"devices": {}, "modules": {}, "host": [], "lines": {},
           "examples": []}
    for plane in data.planes:
        is_device = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            events = list(line.events)
            out["lines"]["%s | %s" % (plane.name, line.name)] = len(events)
            if is_device and line.name == OPS_LINE:
                out["devices"][plane.name] = [
                    [e.name, int(e.start_ns), int(e.duration_ns)]
                    for e in events]
            elif is_device and line.name == MODULES_LINE:
                out["modules"][plane.name] = [
                    [e.name, int(e.start_ns), int(e.duration_ns)]
                    for e in events]
            elif not is_device:
                out["host"].extend(
                    [e.name, int(e.start_ns), int(e.duration_ns), line.name]
                    for e in events if e.duration_ns >= HOST_MIN_NS)
    out["host"].sort(key=lambda e: -e[2])
    del out["host"][HOST_MAX_EVENTS:]
    return out


def load_in_child(trace_dir, root):
    """``load`` in a process of its own, on the CPU backend."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=root + os.pathsep + os.environ.get("PYTHONPATH", ""))
    out_path = os.path.join(trace_dir, "reduced.json")
    code = ("import json,sys; from benchmark.lib import xplane; "
            "json.dump(xplane.load(sys.argv[1]), open(sys.argv[2], 'w'))")
    done = subprocess.run([sys.executable, "-c", code, trace_dir, out_path],
                          env=env, cwd=root, capture_output=True, text=True,
                          timeout=240)
    if done.returncode != 0:
        raise RuntimeError("reading the trace failed: %s"
                           % done.stderr[-2000:])
    with open(out_path) as fh:
        return json.load(fh)


# -- arithmetic on plain lists ------------------------------------------------

def union(intervals):
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def total(intervals):
    return sum(end - start for start, end in intervals)


def overlap(merged, start, end):
    """Length of [start, end) covered by a merged interval list."""
    return sum(max(0, min(e, end) - max(s, start)) for s, e in merged
               if s < end and e > start)


def self_times(events):
    """[(name, self_ns)]: an event's duration less what its children
    cover.  A ``while`` around a scanned layer stack holds every op of the
    stack; only self time may be summed by name."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []   # stack of [name, end, child_ns]

    def close(until):
        while stack and stack[-1][1] <= until:
            name, end, child, dur = stack.pop()
            out.append((name, max(0, dur - child)))

    for name, start, dur in order:
        close(start)
        if stack:
            stack[-1][2] += min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, 0, dur])
    close(float("inf"))
    return out


def short_name(hlo):
    """``%fusion.4 = bf16[8,2048]{...} fusion(...)`` -> ``fusion.4
    bf16[8,2048]``: enough to find the op, short enough for a ledger."""
    m = re.match(r"%?(\S+) = \(?(\w+\[[\d,]*\])?", hlo)
    if not m:
        return hlo[:80]
    kind = " custom-call" if "custom-call(" in hlo else ""
    return "%s %s%s" % (m.group(1), m.group(2) or "", kind)


def step_range(trace, chip):
    """(start, end, n) of the training step's executions on a chip: the
    module that takes most of the time, first start to last end.  Without
    a modules line: the whole window and no count."""
    events = trace.get("modules", {}).get(chip)
    if not events:
        t0, t1 = window_of(trace)
        return t0, t1, 0
    spent = {}
    for name, _, dur in events:
        spent[name] = spent.get(name, 0) + dur
    step = max(spent, key=spent.get)
    mine = [(s, s + d) for name, s, d in events if name == step]
    return min(s for s, _ in mine), max(e for _, e in mine), len(mine)


def window_of(trace):
    """[start, end] of what the devices recorded, in ns."""
    events = [e for chip in trace["devices"].values() for e in chip]
    return min(e[1] for e in events), max(e[1] + e[2] for e in events)


def host_window_of(trace):
    """[start, end] of the host's spans, in ns: the host tracer stops at
    once, the device tracer seconds later, so the two windows differ."""
    if not trace["host"]:
        return None
    return (min(e[1] for e in trace["host"]),
            max(e[1] + e[2] for e in trace["host"]))


def gaps(merged, start, end):
    """The idle intervals of [start, end] given the merged busy ones."""
    out, at = [], start
    for s, e in merged:
        if s > at:
            out.append((at, min(s, end)))
        at = max(at, e)
    if at < end:
        out.append((at, end))
    return [(s, e) for s, e in out if e > s]


def name_gap(trace, start, end):
    """What the host was doing in an idle gap: the innermost (shortest)
    host event that covers at least half of it; failing that the event
    that overlaps it most; ``none`` if the host recorded nothing there.
    The name is the event's own, cut at its arguments."""
    half, best = (end - start) / 2.0, None
    for name, s, d, _ in trace["host"]:
        covered = min(s + d, end) - max(s, start)
        if covered <= 0:
            continue
        rank = (0, d) if covered >= half else (1, -covered)
        if best is None or rank < best[0]:
            best = (rank, name)
    if best is None:
        return "none"
    return re.split(r"[(\s]", best[1], maxsplit=1)[0][:48] or "unnamed"


def reduce(trace):
    """Every number the per-layer readers take from a trace, in seconds."""
    if not trace or not trace["devices"]:
        return None
    t0, t1 = window_of(trace)
    window_ns = t1 - t0
    chips = sorted(trace["devices"])
    busy_ns = mosaic_ns = coll_ns = 0
    by_name, custom_calls = {}, {}
    for chip in chips:
        events = trace["devices"][chip]
        busy_ns += total(union((s, s + d) for _, s, d in events))
        for name, ns in self_times(events):
            by_name[name] = by_name.get(name, 0) + ns
            if MOSAIC.search(name):
                mosaic_ns += ns
                seen = custom_calls.setdefault(name, [0.0, 0])
                seen[0] += ns / 1e9 / len(chips)
                seen[1] += 1.0 / len(chips)
            if COLLECTIVE.search(name):
                coll_ns += ns
    n = len(chips)
    # Per step: inside the steps' own range, so that a step cut by the
    # window's edge neither counts nor contributes.
    steps = step_busy_ns = step_range_ns = 0
    for chip in chips:
        s0, s1, count = step_range(trace, chip)
        steps += count
        step_range_ns += s1 - s0
        step_busy_ns += overlap(union(
            (s, s + d) for _, s, d in trace["devices"][chip]), s0, s1)
    # Gaps are named by the host's spans, so only where the host recorded.
    first = trace["devices"][chips[0]]
    h0, h1 = host_window_of(trace) or (t0, t0)
    idle = gaps(union((s, s + d) for _, s, d in first), max(t0, h0),
                min(t1, h1)) if h1 > h0 else []
    idle.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "chips": n,
        "steps": steps / n,
        "step_busy_s": step_busy_ns / n / 1e9,
        "step_range_s": step_range_ns / n / 1e9,
        "mosaic_s": mosaic_ns / n / 1e9,
        "custom_calls": custom_calls,
        "collective_exposed_s": coll_ns / n / 1e9,
        "host_window_s": (h1 - h0) / 1e9,
        "device_ops": [[short_name(name), ns / n / 1e9] for name, ns in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[name_gap(trace, s, e), (e - s) / 1e9]
                      for s, e in idle[:10]],
    }
