#!/usr/bin/env python3
"""The step anatomy of one traced run, and the clock check, as JSON.

    python3 benchmark/tools/anatomy.py <reduced.json> [steps-per-task]

``<reduced.json>`` is what a ``--trace 1`` run leaves in
``.bench_work/<cell>/trace/``.  Prints, over the stretch of whole
``edl.step`` spans (benchmark/lib/spans.py):

 - ``spans``: for each ``edl.*`` name its count, total and self time (the
   span less its children on its thread) in ms, and both per step;
 - ``gaps``: the device's idle time inside the stretch by the innermost
   ``edl.*`` span the training thread was in at each instant
   (``xplane.name_gap`` names a whole gap by one host event of any kind:
   ``np.asarray`` inside ``edl.loss_sync``; this asks which of the
   program's phases the idle time fell in);
 - ``clock``: the program's steps on the host plane beside the step
   program's executions on the device plane (``xplane.step_range``'s
   module): counts and mean start-to-start intervals, with the
   executions that the trace's edges cut told apart from the whole ones.
   If the two planes share a clock, the intervals agree.

Not part of a measurement: a builder's tool, like ``sets.py``.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import spans as spanlib, xplane  # noqa: E402


def span_table(s):
    t0, t1 = s.stretch
    # self time: a span less what its children on its thread cover
    self_ns = [span.end - span.start for span in s.spans]
    for span in s.spans:
        if span.parent is not None:
            self_ns[span.parent] -= min(
                span.end, s.spans[span.parent].end) - span.start
    table = {}
    for span, own in zip(s.spans, self_ns):
        if span.start < t0 or span.end > t1:
            continue
        row = table.setdefault(span.name, {"count": 0, "total_ms": 0.0,
                                           "self_ms": 0.0})
        row["count"] += 1
        row["total_ms"] += (span.end - span.start) / 1e6
        row["self_ms"] += max(0, own) / 1e6
    for row in table.values():
        row["total_ms_per_step"] = row["total_ms"] / s.steps
        row["self_ms_per_step"] = row["self_ms"] / s.steps
    return table


def gaps_by_span(trace, s):
    """{span name: ms}: the first chip's idle time inside the stretch,
    each instant of it given to the innermost span the training thread
    was in (``none`` where it was in none)."""
    chip = sorted(trace["devices"])[0]
    busy = xplane.union((b, b + d) for _, b, d in trace["devices"][chip])
    idle = xplane.gaps(busy, *s.stretch)
    out = {"none": sum(g1 - g0 for g0, g1 in idle) / 1e6}
    for span in s.spans:
        if span.thread != s.thread:
            continue
        covered = xplane.overlap(idle, span.start, span.end) / 1e6
        if not covered:
            continue
        # what a span covers is its own and no longer its parent's
        out[span.name] = out.get(span.name, 0.0) + covered
        up = "none" if span.parent is None else s.spans[span.parent].name
        out[up] -= covered
    return {name: ms for name, ms in out.items() if ms > 1e-6}


def clock(trace, s, steps_per_task):
    """The first chip's executions of the step program beside the
    program's own steps."""
    chip = sorted(trace["modules"])[0]
    events = trace["modules"][chip]
    spent = {}
    for name, _, dur in events:
        spent[name] = spent.get(name, 0) + dur
    module = max(spent, key=spent.get)
    runs = sorted((b, b + d) for name, b, d in events if name == module)
    ops = trace["devices"][chip]
    d0, d1 = min(b for _, b, _ in ops), max(b + d for _, b, d in ops)
    h0, h1 = xplane.host_window_of(trace)
    # An execution under way when this chip's trace began or ended is in
    # the trace as a stump from or to the edge of what the chip recorded:
    # its start or its end is the window's, not its own.
    whole = [(b, e) for b, e in runs if b > d0 + 1000 and e < d1 - 1000]
    starts = [b for b, _ in runs if b > d0 + 1000]
    intervals = s.step_intervals_ms(steps_per_task)
    return {
        "host": {"steps": s.steps,
                 "stretch_ms": s.stretch_ns / 1e6,
                 "intervals": len(intervals),
                 "mean_interval_ms": sum(intervals) / len(intervals)
                 if intervals else None,
                 "window_ms": (h1 - h0) / 1e6},
        "device": {"module": module[:60], "executions": len(runs),
                   "cut_by_the_windows_edges": len(runs) - len(whole),
                   "whole": len(whole),
                   "whole_mean_duration_ms": sum(
                       e - b for b, e in whole) / 1e6 / len(whole)
                   if whole else None,
                   "mean_start_to_start_ms":
                   (starts[-1] - starts[0]) / 1e6 / (len(starts) - 1)
                   if len(starts) > 1 else None,
                   "executions_as_durations": sum(
                       e - b for b, e in runs) / (sum(
                           e - b for b, e in whole) / len(whole))
                   if whole else None,
                   "window_ms": (d1 - d0) / 1e6,
                   "window_starts_after_host_ms": (d0 - h0) / 1e6,
                   "window_ends_after_host_ms": (d1 - h1) / 1e6},
    }


def main(argv):
    with open(argv[0]) as fh:
        trace = json.load(fh)
    steps_per_task = int(argv[1]) if len(argv) > 1 else 0
    s = spanlib.of_trace(trace)
    if s is None or not s.steps:
        print(json.dumps({"spans": None, "why": "no edl.step in the trace"}))
        return 1
    out = {"spans": span_table(s)}
    if trace.get("devices"):
        out["gaps"] = gaps_by_span(trace, s)
    if trace.get("modules") and trace.get("devices"):
        out["clock"] = clock(trace, s, steps_per_task)
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
