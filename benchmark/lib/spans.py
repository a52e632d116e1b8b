"""The program's own spans in a traced run.

The program puts every ``Timing`` phase on the profiler's clock as an
annotation named ``edl.<phase>`` (elasticdl_tpu/utils/timing.py;
docs/observability.md has the vocabulary).  ``xplane.load`` already writes
the host planes' events to ``<trace_dir>/reduced.json`` as ``[name,
start_ns, dur_ns, thread]``; this module keeps those named ``edl.*``,
nests them per thread and answers what the per-layer readers ask.  A
program that annotates nothing (every commit before PR 24) gives ``None``,
and so does every reader built on it.

What the source can and cannot show:

 - ``reduced.json`` holds no host event under ``xplane.HOST_MIN_NS`` (100
   us) and no arguments.  A total over short spans (a ``data_wait`` that
   found its batch ready takes ~20 us) is a total of those of 100 us and
   longer; a step's number is not there, so the steps of a fused window
   pass are taken from the traffic's ``fused_steps``.
 - The profiler keeps an annotation only if it began *and* ended inside
   the trace.  The per-step loop fences once a task, so one ``loss_sync``
   lasts most of a task (2.2 s of 2.3 s); a share over the whole host
   window would lose up to one such span at each edge, a third of a 6 s
   window.  Shares and per-step numbers are therefore taken over the
   **stretch** from the first whole ``edl.step``'s start to the last one's
   end, clipped to ``xplane.host_window_of``: inside it the training
   thread's time is all in whole spans.
 - Threads are the profiler's host lines, named after the OS thread, and
   every Python thread of a worker is called ``python3``.  The span names
   tell the two threads apart instead: ``edl.reader_*`` are the prefetch
   producer's (``READER``), every other one the training thread's, and
   the producer's never nest in the training thread's.
"""

import collections
import json
import os

from benchmark.lib import xplane

PREFIX = "edl."
STEP = "edl.step"
READER = "edl.reader_"

Span = collections.namedtuple("Span", "name start end thread parent")


def nest(events):
    """``[[name, start_ns, dur_ns, line], ...]`` -> [Span], by thread and
    start: each with the index of the innermost span of its own thread
    that holds it (None at the top).  A span's thread is its host line
    and, of the two Python threads that share a line's name, the one its
    own name says."""
    order = sorted(
        ((name, start, start + dur, (line, name.startswith(READER)))
         for name, start, dur, line in events),
        key=lambda e: (e[3], e[1], -e[2]))
    spans, stack, thread = [], [], None
    for name, start, end, of in order:
        if of != thread:
            stack, thread = [], of
        while stack and spans[stack[-1]].end <= start:
            stack.pop()
        spans.append(Span(name, start, end, of, stack[-1] if stack else None))
        stack.append(len(spans) - 1)
    return spans


class Spans:
    """The ``edl.*`` spans of one traced window."""

    def __init__(self, spans, host_window, steps_per_span=1):
        self.spans = spans
        self.steps_per_span = max(1, int(steps_per_span))
        steps = sorted((s for s in spans if s.name == STEP),
                       key=lambda s: s.start)
        self.step_spans = steps
        self.thread = steps[0].thread if steps else None
        h0, h1 = host_window
        if steps:
            h0, h1 = max(h0, steps[0].start), min(h1, steps[-1].end)
        self.stretch = (h0, h1)

    @property
    def stretch_ns(self):
        return self.stretch[1] - self.stretch[0]

    @property
    def steps(self):
        """Optimizer steps the stretch holds."""
        return len(self.step_spans) * self.steps_per_span

    def named(self, name):
        return [s for s in self.spans if s.name == name]

    def total_ns(self, name):
        """Time in spans of that name, as far as it lies in the stretch."""
        t0, t1 = self.stretch
        return sum(max(0, min(s.end, t1) - max(s.start, t0))
                   for s in self.named(name))

    def inside_ns(self, name, parent_name):
        """Time in spans of that name directly or further inside a span
        named ``parent_name``, on the parent's thread."""
        out = 0
        for s in self.spans:
            if s.name != name:
                continue
            up = s.parent
            while up is not None and self.spans[up].name != parent_name:
                up = self.spans[up].parent
            if up is not None:
                out += s.end - s.start
        return out

    def step_intervals_ms(self, steps_per_task=0):
        """Milliseconds from one ``edl.step``'s start to the next one's,
        per optimizer step.  With ``steps_per_task``, over the longest run
        of steps that holds whole tasks: the per-step loop fences once a
        task, so a task's steps start in a burst and a run cut elsewhere
        would weigh the burst or the fence too much."""
        starts = [s.start for s in self.step_spans]
        per_task = self.spans_per_task(steps_per_task)
        if per_task > 1 and len(starts) > per_task:
            whole = (len(starts) - 1) // per_task * per_task
            starts = starts[:whole + 1]
        return [(b - a) / 1e6 / self.steps_per_span
                for a, b in zip(starts, starts[1:])]

    def spans_per_task(self, steps_per_task):
        """``edl.step`` spans a task of that many steps makes."""
        return max(1, steps_per_task // self.steps_per_span)


def of_trace(trace, steps_per_span=1):
    """The Spans of a raw trace (the dict ``xplane.load`` returns), or
    None where the program annotated nothing."""
    events = [e for e in (trace or {}).get("host", [])
              if e[0].startswith(PREFIX)]
    if not events:
        return None
    return Spans(nest(events), xplane.host_window_of(trace), steps_per_span)


def of_run(run):
    """The Spans of a traced run, or None (no trace, or no ``edl.*`` in
    it).  Read once a run."""
    if not hasattr(run, "spans"):
        path = os.path.join(run.trace_dir, "reduced.json")
        trace = None
        if run.traced and os.path.isfile(path):
            with open(path) as fh:
                trace = json.load(fh)
        run.spans = of_trace(trace, run.traffic["flags"].get(
            "fused_steps", 1))
    return run.spans


def with_steps(run):
    """``of_run`` where the stretch holds at least one step, else None:
    what the per-step and share readers need."""
    spans = of_run(run)
    return spans if spans and spans.steps and spans.stretch_ns > 0 else None


def share(run, name):
    """Per cent of the stretch spent in spans of that name, or None."""
    spans = with_steps(run)
    if spans is None:
        return None
    return 100.0 * spans.total_ns(name) / spans.stretch_ns


def step_intervals_ms(run):
    """``Spans.step_intervals_ms`` over the run's whole tasks; [] where
    there is nothing to read."""
    spans = with_steps(run)
    if spans is None:
        return []
    return spans.step_intervals_ms(
        run.traffic["flags"]["num_minibatches_per_task"])


def task_step_ms(run):
    """The mean of those intervals over each whole task's worth of them
    in turn, one number a task; [] where there is not one whole task.
    Any such run of intervals holds one fence pass, wherever it begins,
    so the numbers are steady where the single intervals are not."""
    spans = with_steps(run)
    if spans is None:
        return []
    per_task = spans.spans_per_task(
        run.traffic["flags"]["num_minibatches_per_task"])
    intervals = step_intervals_ms(run)
    return [sum(intervals[i:i + per_task]) / per_task
            for i in range(0, len(intervals) - per_task + 1, per_task)]
