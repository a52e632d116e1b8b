"""Per-phase timing accumulators.

Built-in observability from day one (SURVEY.md §5.1): the reference only has
a DEBUG-level Timing helper (elasticdl/python/common/timing_utils.py:17-48);
here timing is always on, cheap, and reportable, and integrates with the JAX
profiler for device traces.

The profiler's clock: every interval ``start``/``end``/``timeit`` time is
also a ``jax.profiler.TraceAnnotation`` named ``edl.<phase>``, so that a
device trace (``--profile_dir``, or any ``jax.profiler`` trace of the
process) shows the step anatomy on the same clock as the device's
operations (docs/observability.md).  Outside a running trace an
annotation costs ~0.5 us and records nothing: that is the off state,
there is no switch.  This module never imports JAX; it annotates only in
a process that already has (the worker), so the master and the PS shards
start no slower.

Set-up is timed by the same phases: ``SETUP``, the process's one
``SetupTimeline``, turns an entry point's marks into contiguous
``setup_<phase>`` phases from the OS's start of the process to its first
piece of work, and says them in one ``<role> setup:`` log line
(docs/observability.md, "Set-up timeline").

Thread model: phases and counters are written by training/executor
threads while /statz, /metrics, and Timing.report() readers snapshot
concurrently.  Every mutation AND every snapshot runs under one plain
lock — the critical sections are a handful of dict operations (never
IO, never another lock), so the hot-path cost is one uncontended
acquire (~100 ns) and a reader can never observe a torn
(total bumped, count not) pair or a mid-resize dict.  The historical
``dict(list(...))`` snapshot idiom protected ``counters()``/
``summary()`` but left ``report()``/``sync_fraction`` reading live
dicts; the hammer test in tests/test_observability.py drives writers
against every snapshot path.
"""

import contextlib
import os
import sys
import threading
import time
from collections import defaultdict

from elasticdl_tpu.utils import hist as hist_mod

# Where /proc cannot say when the OS started the process, set-up counts
# from here: this module's import, the first of the package's.
_IMPORTED_AT = time.time()


def _annotate(name, ids):
    """An entered profiler annotation ``edl.<name>`` carrying ``ids`` as
    its arguments; None in a process that has not imported JAX."""
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    if profiler is None:
        return None
    annotation = profiler.TraceAnnotation("edl." + name, **ids)
    annotation.__enter__()
    return annotation


class Timing:
    """Accumulates wall-clock per named phase across calls.

    Behind every phase's (total, count) mean sits a streaming
    log-bucketed histogram (utils/hist.py) fed by the same
    ``observe``/``end`` calls, so any phase has a derivable p50/p99
    and a windowed recent view — globally switchable via
    ``hist.set_enabled`` / ``ELASTICDL_HIST=off`` (bench overhead
    legs)."""

    def __init__(self, enabled=True, logger=None):
        self._enabled = enabled
        self._logger = logger
        self._lock = threading.Lock()
        self.reset()

    def reset(self):
        with self._lock:
            self._totals = defaultdict(float)
            self._counts = defaultdict(int)
            self._starts = {}
            self._events = defaultdict(int)
            self._hists = {}


    def bump(self, name, n=1):
        """Count a discrete event (no duration) — e.g. how often an
        async gradient push actually overlapped compute vs. blocked, or
        embedding-prefetch hits vs. misses."""
        if self._enabled:
            with self._lock:
                self._events[name] += n

    def counters(self):
        with self._lock:
            return dict(self._events)

    def observe(self, name, seconds, n=1):
        """Record ``n`` already-measured durations of ``seconds`` each
        — for phases whose start and end happen on different threads
        (e.g. a serving request's queue wait: enqueued on the request
        thread, measured when the batcher executor picks it up).  The
        bulk form (n > 1) is for per-step stats derived once per fused
        window."""
        if self._enabled:
            h = None
            with self._lock:
                self._totals[name] += seconds * n
                self._counts[name] += n
                if hist_mod.hist_enabled():
                    # Get-or-create under the Timing lock (dict
                    # mutation); the observe itself runs on the
                    # histogram's own leaf lock OUTSIDE this one.
                    h = self._hists.get(name)
                    if h is None:
                        h = self._hists[name] = hist_mod.Histogram()
            if h is not None:
                h.observe(seconds, n=n)

    def start(self, name, **ids):
        """Open phase ``name`` on the calling thread; ``ids`` (``step=``,
        ``task=``) go to the profiler annotation only.  Open phases are
        keyed by thread, so the prefetch producer and the training thread
        may time at once, the same name included."""
        if self._enabled:
            key = (threading.get_ident(), name)
            annotation = _annotate(name, ids)
            now = time.perf_counter()
            with self._lock:
                self._starts[key] = (now, annotation)

    def end(self, name):
        if self._enabled:
            now = time.perf_counter()
            h = seconds = annotation = None
            with self._lock:
                opened = self._starts.pop(
                    (threading.get_ident(), name), None)
                if opened is not None:
                    seconds = now - opened[0]
                    annotation = opened[1]
                    self._totals[name] += seconds
                    self._counts[name] += 1
                    if hist_mod.hist_enabled():
                        h = self._hists.get(name)
                        if h is None:
                            h = self._hists[name] = (
                                hist_mod.Histogram())
            if annotation is not None:
                annotation.__exit__(None, None, None)
            if h is not None:
                h.observe(seconds)

    @contextlib.contextmanager
    def timeit(self, name, **ids):
        self.start(name, **ids)
        try:
            yield
        finally:
            self.end(name)

    # -- histogram readers (the percentile plane) ---------------------------

    def histograms(self, names=None):
        """{phase: snapshot dict} for every phase with a histogram
        (or only ``names``) — the shape utils/prom.py renders as
        native Prometheus histograms and /statz ships raw."""
        with self._lock:
            hists = {
                name: h for name, h in self._hists.items()
                if names is None or name in names
            }
        return {name: h.snapshot() for name, h in hists.items()}

    def hist_snapshot(self, name):
        with self._lock:
            h = self._hists.get(name)
        return h.snapshot() if h is not None else None

    def percentile(self, name, q):
        """qth quantile estimate for a phase (seconds), or None."""
        snap = self.hist_snapshot(name)
        return hist_mod.quantile(snap, q) if snap else None

    def recent(self, name, window_secs=5.0, now=None):
        """Delta snapshot over roughly the last ``window_secs`` for a
        phase (see hist.Histogram.recent), or None — the direct
        windowed-load signal /statz surfaces so consumers stop
        re-deriving it by probe-differencing."""
        with self._lock:
            h = self._hists.get(name)
        return h.recent(window_secs, now=now) if h is not None else None

    def sync_fraction(self, dispatch_name, sync_name):
        """Blocked-on-device share of an async hot loop: with the fused
        driver the step enqueue is timed under ``dispatch_name``
        ("window_dispatch") and the cadence loss fetch under
        ``sync_name`` ("loss_sync"), so this is ~0 when overlap works
        and ->1 when every step stalls on the device.  None until both
        phases have samples' worth of time."""
        with self._lock:
            dispatch = self._totals.get(dispatch_name, 0.0)
            sync = self._totals.get(sync_name, 0.0)
        if dispatch + sync <= 0.0:
            return None
        return sync / (dispatch + sync)

    def summary(self):
        with self._lock:
            totals = dict(self._totals)
            counts = dict(self._counts)
            events = dict(self._events)
        out = {
            name: {
                "total_s": totals[name],
                "count": counts.get(name, 0),
                "mean_s": totals[name] / max(1, counts.get(name, 0)),
            }
            for name in totals
        }
        # ZeRO-1 section: the sharded-update byte counters
        # (reduce-scatter/all-gather payloads per step, elastic reshard
        # traffic) grouped so bench/statz consumers see them as one
        # block.  Present only when a zero1 trainer bumped them, so
        # phase-only consumers (which iterate {total_s,...} entries)
        # are unaffected elsewhere.
        zero1 = {
            name: count for name, count in events.items()
            if name.startswith("zero1_")
        }
        if zero1:
            out["zero1"] = zero1
        # Serving embedding hot-row cache counters (hits/misses/
        # evictions, serving/embedding_service.py), grouped the same
        # way for /statz and bench consumers.
        emb_cache = {
            name: count for name, count in events.items()
            if name.startswith("emb_cache.")
        }
        if emb_cache:
            out["emb_cache"] = emb_cache
        return out

    def report(self):
        if self._logger is None:
            return
        # One coherent snapshot for BOTH sections: the counter loop
        # used to iterate the live events dict and could hit a
        # concurrent writer's resize mid-report.
        summary = self.summary()
        counters = self.counters()
        for name, s in sorted(summary.items()):
            if "total_s" not in s:
                continue  # counter section (zero1), logged below
            self._logger.info(
                "timing[%s]: total=%.3fs count=%d mean=%.4fs",
                name,
                s["total_s"],
                s["count"],
                s["mean_s"],
            )
        for name, n in sorted(counters.items()):
            self._logger.info("counter[%s]: %d", name, n)


def trace_options():
    """The profiler's options that keep a trace of a training job usable
    (chip runs, PR 23): with the Python tracer on and the HLO protos in,
    6 s of ResNet-50 made a 234 MB trace that took minutes to write; host
    level 2 adds millions of futex waits of the gRPC threads.  Level 1
    holds the runtime's events and the program's annotations.  Every
    trace the program starts takes these (``device_trace``,
    ``tracing.profilez_capture``)."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    options.enable_hlo_proto = False
    return options


@contextlib.contextmanager
def device_trace(log_dir):
    """Capture an XLA/JAX profiler trace around a block (xplane format):
    the device's operations and, on the same clock, the ``edl.*`` spans."""
    import jax

    jax.profiler.start_trace(log_dir, profiler_options=trace_options())
    try:
        yield
    finally:
        jax.profiler.stop_trace()


# -- the set-up timeline ------------------------------------------------------

# A process's set-up, in the order its marks come (docs/observability.md,
# "Set-up timeline").  ``import`` runs from the OS's start of the process
# to ``main()``'s first line; every other phase from its mark to the next.
WORKER_SETUP = ("import", "backend_init", "build", "param_init",
                "first_task_fetch", "first_batch", "first_dispatch",
                "first_run", "first_report")
MASTER_SETUP = ("import", "build", "launch")

# What the compile listener (worker/main.xla_compiles_logged) adds up
# while the timeline is open, as the line names it.
_OF_FIRST_DISPATCH = ("trace_s", "lower_s", "compile_or_load_s")
_OF_EVERY_PHASE = ("programs", "cache_hits", "cache_misses")


def process_age():
    """Seconds since the OS started this process: the start time of
    ``/proc/self/stat`` (clock ticks since boot) against the boot clock.
    ``/proc/stat``'s ``btime`` would give the same instant in whole
    seconds only.  Without ``/proc``, since this module's import."""
    try:
        with open("/proc/self/stat") as fh:
            stat = fh.read()
        ticks = int(stat[stat.rindex(")") + 2:].split()[19])
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - (
            ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        age = -1.0
    return age if age >= 0 else time.time() - _IMPORTED_AT


class SetupTimeline:
    """From the process's start to the end of its first piece of work, as
    contiguous phases: a mark ends the open phase and opens the next, so
    the phases partition the time by construction.  Each phase is a
    ``Timing`` phase ``setup_<name>`` (an ``edl.setup_<name>`` annotation
    in a whole-run ``--profile_dir`` trace); when the timeline closes it
    logs one ``<role> setup:`` line of ``key=value`` fields and records
    one ``<role>.setup`` flight-recorder event.

    A process has one (``SETUP``), begun by its entry point alone: in a
    process whose ``main()`` never ran (a library user, a test) every
    mark finds ``open`` false and returns, as it does after the close."""

    def __init__(self):
        self.open = False       # the one attribute a mark tests
        self._begun = False
        self._lock = threading.Lock()

    def begin(self, role, phases, logger):
        """``main()``'s first line: ``phases[0]`` (the interpreter's
        start and the import chain) ends here, ``phases[1]`` opens.  A
        second ``main()`` in one process begins nothing."""
        with self._lock:
            if self._begun:
                return
            self._begun = True
            age, now = process_age(), time.perf_counter()
            self.role, self._logger = role, logger
            self._phases = tuple(phases)
            self.t0 = time.time() - age
            self.timing = Timing()
            self.timing.observe("setup_" + phases[0], age)
            self._seconds = {phases[0]: age}
            self._counts = defaultdict(lambda: defaultdict(float))
            self._index, self._at = 1, now
            self.timing.start("setup_" + phases[1])
            self.open = True

    def mark(self, phase):
        """``phase`` opens and the open one ends, at one instant.  A mark
        of the open phase or of an earlier one (the second step's mark
        of ``first_dispatch``) does nothing: phases only advance; one
        that is never marked stays at 0."""
        # elint: disable=EL001 -- the hot path's one test: a flag published by single assignment, tested again under the lock
        if not self.open:
            return
        with self._lock:
            # Another role's phase (a trainer built in a master's
            # process) is not this timeline's.
            index = (self._phases.index(phase)
                     if phase in self._phases else -1)
            if self.open and index > self._index:
                self._advance_locked(index)

    def _advance_locked(self, index):
        now = time.perf_counter()
        ended = self._phases[self._index]
        self._seconds[ended] = now - self._at
        self.timing.end("setup_" + ended)
        self._index, self._at = index, now
        if index < len(self._phases):
            self.timing.start("setup_" + self._phases[index])

    def add(self, **counts):
        """Counts of the open phase (the compile listener's)."""
        # elint: disable=EL001 -- as in mark(): tested again under the lock
        if not self.open:
            return
        with self._lock:
            if self.open:
                bucket = self._counts[self._phases[self._index]]
                for key, value in counts.items():
                    bucket[key] += value

    def close(self, into=None):
        """End the open phase and say the whole: the line, the event and,
        into ``into`` (the process's own ``Timing``), each phase's
        seconds for its end-of-run report.  Returns the fields, or None
        where the timeline is not open: a second close says nothing."""
        with self._lock:
            if not self.open:
                return None
            self._advance_locked(len(self._phases))
            self.open = False
            # Rounded first, so that the line's phases sum to its total.
            seconds = {phase: round(self._seconds.get(phase, 0.0), 6)
                       for phase in self._phases}
            counts = {phase: dict(c) for phase, c in self._counts.items()}
            role, logger, t0 = self.role, self._logger, self.t0
        fields = {"t0": int(round(t0 * 1000))}
        fields.update((phase + "_s", s) for phase, s in seconds.items())
        fields["total_s"] = round(sum(seconds.values()), 6)
        if counts:
            first = counts.get("first_dispatch", {})
            for key in _OF_FIRST_DISPATCH:
                fields[key] = round(first.get(key, 0.0), 6)
            for key in _OF_EVERY_PHASE:
                fields[key] = int(sum(
                    c.get(key, 0) for c in counts.values()))
            fields["init_compile_or_load_s"] = round(counts.get(
                "param_init", {}).get("compile_or_load_s", 0.0), 6)
        logger.info("%s setup: %s", role, " ".join(
            "%s=%s" % (key, "%.6f" % value if isinstance(value, float)
                       else value) for key, value in fields.items()))
        from elasticdl_tpu.utils import tracing

        tracing.event(role + ".setup", **fields)
        if into is not None:
            for phase, s in seconds.items():
                into.observe("setup_" + phase, s)
        return fields


SETUP = SetupTimeline()
