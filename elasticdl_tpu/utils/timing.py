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

The steady state is measured at the fence: ``FenceWatch`` observes
``step_time`` from one device fence's return to the next one's, keeps
what the host did in between, and says a stalled interval in one
``worker stall:`` line (docs/observability.md, "Stalls").

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
import gc
import os
import resource
import statistics
import sys
import threading
import time
from collections import defaultdict, deque

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

    def totals(self, phases, counter=None):
        """The seconds so far of each of ``phases`` and the count of
        ``counter``, at one instant: what ``FenceWatch`` differences
        from fence to fence."""
        with self._lock:
            return ([self._totals.get(name, 0.0) for name in phases],
                    self._events.get(counter, 0))

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


# -- the fence ----------------------------------------------------------------

# A fence is a stall where its interval a step lies over the quiet fences'
# median by more than STALL_RATIO and the whole interval over ``steps``
# medians by more than STALL_EXCESS_S.  Quiet runs' tasks repeat to
# 0.01-0.7%, and the slightest stall the builders saw was a task at
# 1.21 x (PERF.md section 6, PR 52): constants, not options.
STALL_RATIO = 1.05
STALL_EXCESS_S = 0.050
QUIET_FENCES = 32       # the ring whose median a fence is judged against
QUIET_FENCES_MIN = 3    # fences in the ring before one is judged
JUDGED_KEPT = 4096      # newest judged fences behind ``fence_p50_ms``
# The counter the compile listener bumps (worker/main.xla_compiles_logged).
XLA_PROGRAMS = "xla_programs"
PRESSURE_DIR = "/proc/pressure"
# The training thread's phases an interval is told apart by, under the
# stall line's names; the rest of the interval is ``host_other``.
_FENCE_PHASES = {"fence_wait": "loss_sync", "data_wait": "data_wait",
                 "rpc": "progress_rpc", "task_fetch": "task_fetch"}
_STALL_MS = ("fence_wait", "data_wait", "rpc", "task_fetch", "host_other",
             "host_excess")
_STALL_COUNTS = ("nivcsw", "majflt")


def _ms(seconds):
    return round(1e3 * seconds, 3)


def _pressure_us(kind):
    """``some total=`` of ``/proc/pressure/<kind>``: the microseconds so
    far in which some task of the machine (of the container, where it has
    a cgroup of its own) waited for the resource.  None where the kernel
    keeps no such file."""
    try:
        with open(os.path.join(PRESSURE_DIR, kind)) as fh:
            return int(fh.readline().rsplit("total=", 1)[1])
    except (OSError, IndexError, ValueError):
        return None


class FenceWatch:
    """The training loop measured at its fences: the one place where the
    host knows the device has caught up (docs/observability.md, "Worker
    step-time anatomy" and "Stalls").

    Both loops call ``fence(step)`` right after a ``timeit("loss_sync")``
    fence returns, with the number of the last optimizer step the fence
    proves done.  Each call takes one ``perf_counter()`` and

    - observes ``step_time``: the interval from the previous fence's
      return to this one's over the steps run in it, once a step
      (``n=steps``).  It is the only ``step_time`` observation, so the
      master's percentiles and its straggler sweep read the device-paced
      step and not the dispatch burst between fences.  A fence with no
      step since the previous one (the task-final fence right behind a
      log-cadence fence) is no interval: its time goes into the next.
      Steps dispatched after a run's last fence (a task preempted in
      the per-step loop) are never observed;
    - keeps what the host did in the interval, as differences of totals:
      the owner's ``Timing`` phases, the collector's pauses
      (``gc.callbacks``, process-wide), the process's CPU time, its
      involuntary context switches and major faults (``getrusage``), the
      machine's pressure-stall microseconds and the compile listener's
      programs;
    - once the set-up timeline has closed and QUIET_FENCES_MIN quiet
      fences exist, judges the interval against their median a step
      (STALL_RATIO, STALL_EXCESS_S).  A stall is one ``worker stall:``
      line and one ``worker.stall`` flight-recorder event, and stays out
      of the ring.

    ``start()`` and ``report()`` bracket the run (``Worker.run``): the
    collector's callback is installed between them, and ``report()`` logs
    the run's one ``worker fences:`` line.  Written and read by the
    training thread alone; the collector's callback runs on whichever
    thread allocates, one collection at a time."""

    def __init__(self, timing, logger=None):
        self._timing = timing
        self._logger = logger
        self.task = 0           # the task in hand, for the stall line
        self._step = 0          # the last step a fence has proven done
        self._gc = [0, 0.0, 0.0]    # pauses, their seconds, one's start
        self._pressure = [kind for kind in ("cpu", "io", "memory")
                          if _pressure_us(kind) is not None]
        # (interval, interval less the fence's wait) a step, in seconds
        self._quiet = deque(maxlen=QUIET_FENCES)
        self._judged = deque(maxlen=JUDGED_KEPT)    # interval a step
        self._fences = self._steps = self._stalls = 0
        self._max = self._excess = self._host_excess = 0.0
        self._was = {}          # what ``_read`` falls back on
        self._at, self._was = time.perf_counter(), self._read()

    def _on_gc(self, phase, _info):
        if phase == "start":
            self._gc[2] = time.perf_counter()
        else:
            self._gc[0] += 1
            self._gc[1] += time.perf_counter() - self._gc[2]

    def _read(self):
        """Every total an interval is a difference of: seconds, counts,
        and the pressure files' microseconds."""
        seconds, programs = self._timing.totals(
            _FENCE_PHASES.values(), XLA_PROGRAMS)
        usage = resource.getrusage(resource.RUSAGE_SELF)
        out = dict(zip(_FENCE_PHASES, seconds))
        out.update(gc_n=self._gc[0], gc=self._gc[1],
                   cpu=usage.ru_utime + usage.ru_stime,
                   nivcsw=usage.ru_nivcsw, majflt=usage.ru_majflt,
                   compiles=programs)
        for kind in self._pressure:
            # A file that went away reads as no pressure since.
            out["psi_" + kind] = (_pressure_us(kind)
                                  or self._was.get("psi_" + kind, 0))
        return out

    def start(self):
        """The run begins: the first interval counts from here."""
        if self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)
        self._at, self._was = time.perf_counter(), self._read()

    def fence(self, step, now=None):
        """A fence has returned and proves every step up to ``step``
        done.  ``now`` is its ``perf_counter()`` (a test's clock)."""
        now = time.perf_counter() if now is None else now
        steps = step - self._step
        if steps <= 0:
            return
        reading = self._read()
        interval = now - self._at
        spent = {key: reading[key] - self._was[key] for key in reading}
        self._at, self._step, self._was = now, step, reading
        a_step = interval / steps
        self._timing.observe("step_time", a_step, n=steps)
        if SETUP.open:
            return      # the compile never counts
        away = interval - spent["fence_wait"]
        self._fences += 1
        self._steps += steps
        self._judged.append(a_step)
        self._max = max(self._max, a_step)
        median = excess = 0.0
        if len(self._quiet) >= QUIET_FENCES_MIN:
            median = statistics.median(q[0] for q in self._quiet)
            excess = interval - steps * median
        if not (a_step > STALL_RATIO * median and excess > STALL_EXCESS_S):
            self._quiet.append((a_step, away / steps))
            return
        spent["host_other"] = away - sum(
            spent[key] for key in _FENCE_PHASES if key != "fence_wait")
        spent["host_excess"] = max(0.0, away - steps * statistics.median(
            q[1] for q in self._quiet))
        self._stalls += 1
        self._excess += excess
        self._host_excess += spent["host_excess"]
        fields = {"step": step, "task": self.task, "steps": steps,
                  "interval_ms": _ms(interval), "median_ms": _ms(median),
                  "excess_ms": _ms(excess)}
        fields.update((key + "_ms", _ms(spent[key])) for key in _STALL_MS)
        fields.update(gc_n=spent["gc_n"], gc_ms=_ms(spent["gc"]),
                      cpu_ms=_ms(spent["cpu"]))
        fields.update((key, spent[key]) for key in _STALL_COUNTS)
        fields.update(("psi_%s_ms" % kind[:3], round(
            spent["psi_" + kind] / 1e3, 3)) for kind in self._pressure)
        fields["compiles"] = spent["compiles"]
        if self._logger is not None:
            self._logger.warning("worker stall: %s", " ".join(
                "%s=%s" % item for item in fields.items()))
        from elasticdl_tpu.utils import tracing

        tracing.event("worker.stall", **fields)

    def report(self):
        """The run is over: one ``worker fences:`` line over the fences
        judged (the steady state by the program's own word; the median
        over the newest JUDGED_KEPT of them)."""
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        if self._logger is None:
            return
        self._logger.info(
            "worker fences: fences=%d steps=%d fence_p50_ms=%.3f "
            "fence_max_ms=%.3f stalls=%d stall_excess_ms=%.3f "
            "stall_host_ms=%.3f", self._fences, self._steps,
            1e3 * statistics.median(self._judged or [0.0]),
            1e3 * self._max, self._stalls, 1e3 * self._excess,
            1e3 * self._host_excess)
