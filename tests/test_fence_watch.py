"""The loop measured at its fences (utils/timing.FenceWatch,
docs/observability.md "Worker step-time anatomy" and "Stalls"): the one
``step_time`` observation, a stalled fence's ``worker stall:`` line, the
run's ``worker fences:`` line, and what the master reads of them.  Clocks
are injected (``fence(step, now=)``, ``Timing.observe``) wherever a real
one would make a number unsteady."""

import gc
import logging
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from elasticdl_tpu.master.servicer import MasterServicer
from elasticdl_tpu.master.task_manager import TaskManager
from elasticdl_tpu.models import mnist
from elasticdl_tpu.proto import elastic_pb2 as pb
from elasticdl_tpu.utils import hist, timing as timing_mod, tracing
from elasticdl_tpu.utils.timing import (
    QUIET_FENCES,
    FenceWatch,
    SetupTimeline,
    Timing,
)
from elasticdl_tpu.worker import worker as worker_mod
from tests.test_fused_driver import FakeMasterClient, run_worker

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Lines:
    """A logger that keeps its messages."""

    def __init__(self):
        self.lines = []

    def info(self, message, *args):
        self.lines.append(message % args)

    warning = info

    def of(self, mark):
        return [dict(item.split("=", 1) for item in line.split()[2:])
                for line in self.lines if line.startswith(mark)]


class Loop:
    """A watch driven by hand: ``fence(seconds, steps)`` is one interval
    of ``seconds`` on the injected clock, ending in a fence that waited
    ``wait`` of them."""

    def __init__(self, **phases):
        self.log, self.timing = Lines(), Timing()
        self.watch = FenceWatch(self.timing, logger=self.log)
        self.at, self.step = self.watch._at, 0

    def fence(self, seconds, steps=4, wait=None, **phases):
        self.at += seconds
        self.step += steps
        phases["loss_sync"] = 0.9 * seconds if wait is None else wait
        for name, spent in phases.items():
            self.timing.observe(name, spent)
        self.watch.fence(self.step, now=self.at)

    def quiet(self, n, seconds=1.0, steps=4):
        for _ in range(n):
            self.fence(seconds, steps)
        return self

    def summary(self):
        self.watch.report()
        line, = self.log.of("worker fences:")
        return {key: float(value) for key, value in line.items()}

    def stalls(self):
        return [{key: float(value) for key, value in line.items()}
                for line in self.log.of("worker stall:")]


# -- the watch alone, on an injected clock ------------------------------------


def test_a_quiet_run_logs_no_stall_and_its_median_is_the_interval():
    loop = Loop().quiet(12, seconds=0.8, steps=4)
    assert loop.stalls() == []
    assert loop.summary() == {
        "fences": 12, "steps": 48, "fence_p50_ms": 200.0,
        "fence_max_ms": 200.0, "stalls": 0, "stall_excess_ms": 0.0,
        "stall_host_ms": 0.0}
    # one step_time sample a step, each the fence-paced step
    assert loop.timing.summary()["step_time"]["count"] == 48
    assert loop.timing.summary()["step_time"]["mean_s"] == pytest.approx(0.2)


def test_a_run_without_a_fence_still_says_its_line():
    assert Loop().summary()["fences"] == 0


def test_a_tripled_interval_is_one_stall_and_stays_out_of_the_ring():
    loop = Loop().quiet(5)
    loop.watch.task = 7
    loop.fence(3.0)
    loop.quiet(5)
    stall, = loop.stalls()
    assert stall["step"] == 24 and stall["task"] == 7 and stall["steps"] == 4
    assert stall["interval_ms"] == 3000.0
    assert stall["median_ms"] == 250.0           # a step
    assert stall["excess_ms"] == 2000.0          # 3000 - 4 x 250
    # the ring holds the ten quiet fences and not the stall: the median
    # the next one is judged against has not moved
    assert [round(q[0], 9) for q in loop.watch._quiet] == [0.25] * 10
    summary = loop.summary()
    assert summary["fences"] == 11 and summary["stalls"] == 1
    assert summary["stall_excess_ms"] == 2000.0
    assert summary["fence_p50_ms"] == 250.0
    assert summary["fence_max_ms"] == 750.0


def test_the_stall_is_one_flight_recorder_event_with_the_lines_fields():
    loop = Loop().quiet(3)
    loop.fence(2.0)
    event, = [e for e in tracing.default_tracer().recorder.snapshot()
              if e and e.get("name") == "worker.stall"
              and e["attrs"]["interval_ms"] == 2000.0]
    line, = loop.log.of("worker stall:")
    assert {key: str(value) for key, value in event["attrs"].items()} == line


@pytest.mark.parametrize("cause, host_excess", [
    ({"data_wait": 2.0, "wait": 0.9}, 2000.0),      # a starved reader
    ({"progress_rpc": 2.0, "wait": 0.9}, 2000.0),   # a slow report
    ({"task_fetch": 1.5, "wait": 1.4}, 1500.0),     # a late task, in part
    ({"wait": 2.9}, 0.0),                           # the device, queued
])
def test_host_excess_is_the_part_the_threads_own_phases_explain(
        cause, host_excess):
    """Quiet fences: 1 s of which 0.9 s wait on the device.  A stall of
    2 s over that is the host's where a phase of the training thread
    took it, and not where the fence itself waited longer."""
    loop = Loop().quiet(4)
    loop.fence(3.0, **cause)
    stall, = loop.stalls()
    assert stall["excess_ms"] == 2000.0
    assert stall["host_excess_ms"] == pytest.approx(host_excess, abs=1e-6)
    assert stall["fence_wait_ms"] == 1e3 * cause["wait"]
    named = {"data_wait": "data_wait_ms", "progress_rpc": "rpc_ms",
             "task_fetch": "task_fetch_ms"}
    spent = {"data_wait_ms": 0.0, "rpc_ms": 0.0, "task_fetch_ms": 0.0}
    for phase, seconds in cause.items():
        if phase in named:
            spent[named[phase]] = 1e3 * seconds
    assert {key: stall[key] for key in spent} == spent
    # the four phases and the rest add up to the interval
    assert sum(stall[key] for key in (
        "fence_wait_ms", "data_wait_ms", "rpc_ms", "task_fetch_ms",
        "host_other_ms")) == pytest.approx(3000.0)
    assert loop.summary()["stall_host_ms"] == pytest.approx(host_excess,
                                                            abs=1e-6)


@pytest.mark.parametrize("ratio, seconds, stalled", [
    (1.04, 10.0, False),     # 400 ms over, but within 5% a step
    (1.30, 0.10, False),     # 30% over, but 30 ms in all
    (1.06, 1.0, True),       # 6% and 60 ms
    (1.21, 3.0, True),       # the slightest the builders saw
])
def test_both_thresholds_must_be_passed(ratio, seconds, stalled):
    loop = Loop().quiet(4, seconds=seconds)
    loop.fence(ratio * seconds)
    assert len(loop.stalls()) == int(stalled)


def test_nothing_is_judged_before_three_quiet_fences():
    loop = Loop().quiet(2)
    loop.fence(5.0)              # the third fence: no median yet
    assert loop.stalls() == []
    loop.fence(1.0)              # judged against median(1, 1, 5) / 4
    loop.fence(5.0)
    assert len(loop.stalls()) == 1


def test_nothing_is_judged_while_set_up_is_open(monkeypatch):
    setup = SetupTimeline()
    monkeypatch.setattr(timing_mod, "SETUP", setup)
    setup.begin("worker", timing_mod.WORKER_SETUP, logging.getLogger("x"))
    loop = Loop()
    loop.fence(40.0)             # the compile
    loop.quiet(3)
    assert loop.watch._fences == 0 and not loop.watch._quiet
    # observed all the same: one sample a step
    assert loop.timing.summary()["step_time"]["count"] == 16
    setup.close()
    loop.quiet(3)
    loop.fence(3.0)
    stall, = loop.stalls()
    assert stall["median_ms"] == 250.0     # the compile never counted
    assert loop.summary()["fences"] == 4


def test_fences_of_different_step_counts_compare_a_step():
    loop = Loop()
    for steps in (4, 8, 2, 4, 8):
        loop.fence(0.25 * steps, steps)
    assert loop.stalls() == []
    loop.fence(2.0, 2)           # 1 s a step where 0.25 s is quiet
    stall, = loop.stalls()
    assert stall["steps"] == 2 and stall["median_ms"] == 250.0
    assert stall["excess_ms"] == 1500.0
    assert loop.summary()["steps"] == 28


def test_a_fence_with_no_step_is_no_interval():
    """The task-final fence right behind a log-cadence fence: its time
    goes into the next interval."""
    loop = Loop().quiet(4)
    loop.at += 0.2
    loop.watch.fence(loop.step, now=loop.at)      # no step since
    loop.fence(0.8)                               # 0.2 + 0.8: quiet
    assert loop.stalls() == []
    assert loop.summary()["fences"] == 5
    assert loop.timing.summary()["step_time"]["count"] == 20


def test_the_ring_keeps_the_newest_quiet_fences():
    loop = Loop().quiet(QUIET_FENCES, seconds=1.0)
    loop.quiet(QUIET_FENCES, seconds=1.04)    # a drift is no stall
    assert loop.stalls() == []
    loop.fence(1.2)
    stall, = loop.stalls()
    assert stall["median_ms"] == 260.0


def test_pressure_fields_only_where_the_kernel_has_the_files(
        monkeypatch, tmp_path):
    monkeypatch.setattr(timing_mod, "PRESSURE_DIR", str(tmp_path / "none"))
    loop = Loop().quiet(3)
    loop.fence(3.0)
    line, = loop.log.of("worker stall:")
    assert not [key for key in line if key.startswith("psi_")]
    assert list(line)[-6:] == ["gc_n", "gc_ms", "cpu_ms", "nivcsw",
                               "majflt", "compiles"]


def test_pressure_is_the_files_some_total_in_ms(monkeypatch, tmp_path):
    def write(kind, total):
        (tmp_path / kind).write_text(
            "some avg10=0.00 avg60=0.00 avg300=0.00 total=%d\n"
            "full avg10=0.00 avg60=0.00 avg300=0.00 total=7\n" % total)

    monkeypatch.setattr(timing_mod, "PRESSURE_DIR", str(tmp_path))
    for kind in ("cpu", "io", "memory"):
        write(kind, 1000)
    loop = Loop().quiet(3)
    write("cpu", 1000 + 1_234_567)
    write("memory", 1000 + 2000)
    os.remove(str(tmp_path / "io"))      # gone: no pressure since
    loop.fence(3.0)
    line, = loop.log.of("worker stall:")
    assert (line["psi_cpu_ms"], line["psi_io_ms"], line["psi_mem_ms"]) == (
        "1234.567", "0.0", "2.0")
    assert list(line) == (
        "step task steps interval_ms median_ms excess_ms fence_wait_ms "
        "data_wait_ms rpc_ms task_fetch_ms host_other_ms host_excess_ms "
        "gc_n gc_ms cpu_ms nivcsw majflt psi_cpu_ms psi_io_ms psi_mem_ms "
        "compiles").split()


def test_collections_and_compiles_of_the_interval_are_counted():
    loop = Loop()
    loop.watch.start()
    loop.at = loop.watch._at
    try:
        loop.quiet(3)
        for _ in range(3):
            gc.collect()
        loop.timing.bump(timing_mod.XLA_PROGRAMS, 2)
        burn = time.process_time()
        while time.process_time() - burn < 0.02:
            pass
        loop.fence(3.0)
    finally:
        loop.watch.report()
    stall, = loop.stalls()
    assert stall["gc_n"] >= 3 and stall["gc_ms"] > 0
    assert stall["compiles"] == 2
    assert stall["cpu_ms"] >= 20.0
    assert loop.watch._on_gc not in gc.callbacks   # went with the run


def test_the_compile_listener_counts_on_the_workers_timing():
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.worker import main as worker_main

    timing = Timing()
    x = jnp.ones((7, 3))
    with worker_main.xla_compiles_logged(lambda: 0, lambda: timing):
        jax.jit(lambda v: v * 5.0 - 2.0)(x).block_until_ready()
    assert timing.counters()[timing_mod.XLA_PROGRAMS] == 1


def test_timing_with_the_watch_imports_no_jax():
    code = ("import sys\n"
            "import elasticdl_tpu.utils.timing as t\n"
            "w = t.FenceWatch(t.Timing())\n"
            "w.start(); w.fence(4); w.report()\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n")
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]


# -- both loops, through a real worker ----------------------------------------


@pytest.fixture(scope="module")
def spec():
    return mnist.model_spec(learning_rate=1e-3)


@pytest.fixture(scope="module")
def dataset():
    return mnist.synthetic_data(n=192, seed=1)


class SlowLoss:
    """A lazy loss whose fetch blocks: the device still running when the
    host comes to the fence."""

    def __init__(self, loss, wait):
        self.loss, self.wait = loss, wait

    def __float__(self):
        self.wait()
        return float(np.asarray(self.loss).reshape(-1)[-1])

    def __array__(self, dtype=None, copy=None):
        self.wait()
        return np.asarray(self.loss, dtype=dtype)


class DeviceClock:
    """``utils.timing``'s clock for a test whose numbers a loaded machine
    would move: it stands still but where the device is waited for."""

    def __init__(self):
        self.now = 0.0

    def perf_counter(self):
        return self.now

    def __getattr__(self, name):
        return getattr(time, name)


def slow_fences(seconds, clock=None):
    """A ``run_worker`` hook: every loss the trainer hands back takes
    ``seconds`` to fetch, in either loop: slept, or on ``clock`` alone."""
    def wait():
        if clock is None:
            time.sleep(seconds)
        else:
            clock.now += seconds

    def hook(_worker, trainer):
        step, window = trainer.train_minibatch, trainer.train_window

        def train_minibatch(features, labels):
            loss, version = step(features, labels)
            return SlowLoss(loss, wait), version

        def train_window(staged):
            losses, version = window(staged)
            return SlowLoss(losses, wait), version

        trainer.train_minibatch = train_minibatch
        trainer.train_window = train_window
    return hook


def worker_lines(run):
    """``run()`` with the worker module's log lines kept."""
    lines = []
    handler = logging.Handler()
    handler.emit = lambda record: lines.append(record.getMessage())
    worker_mod.logger.addHandler(handler)
    try:
        return run(), lines
    finally:
        worker_mod.logger.removeHandler(handler)


@pytest.mark.parametrize("fused_steps", [1, 2])
def test_the_task_final_fence_is_inside_the_step_time_samples(
        dataset, spec, fused_steps):
    """3 tasks of 2 steps, each task's final fence held 60 ms: one
    ``step_time`` sample a step, and the waits are in them.  The
    per-minibatch and per-window observations this replaced saw the
    dispatch alone: their six samples summed to the run less its fences."""
    (_mc, _trainer, worker), lines = worker_lines(lambda: run_worker(
        dataset, spec, fused_steps=fused_steps,
        worker_hook=slow_fences(0.06)))
    summary = worker.timing.summary()
    assert summary["step_time"]["count"] == worker.steps_done == 6
    assert summary["loss_sync"]["count"] == 3
    assert summary["loss_sync"]["total_s"] >= 0.18
    assert summary["step_time"]["total_s"] >= summary["loss_sync"]["total_s"]
    # ... and every sample is a fence-paced step: none is the dispatch
    assert worker.timing.percentile("step_time", 0.0) >= 0.03 / 2.16
    said = [line for line in lines if line.startswith("worker fences:")]
    assert len(said) == 1 and " fences=3 steps=6 " in said[0]
    assert lines.index(said[0]) > max(
        i for i, line in enumerate(lines) if line.startswith("timing["))


def test_neither_loop_observes_step_time_itself():
    for name in ("worker.py", "fused_driver.py"):
        with open(os.path.join(ROOT, "elasticdl_tpu", "worker", name)) as fh:
            assert '"step_time"' not in fh.read().replace(
                'hist_snapshot("step_time")', "").replace(
                    '{"step_time": d}', ""), name


# -- what the master reads ----------------------------------------------------


class ReportingClient(FakeMasterClient):
    """Hands each progress report's piggyback to a real servicer, as
    ``MasterClient.report_batch_done`` does."""

    def __init__(self, sizes, worker_id, servicer):
        super().__init__(sizes, worker_id=worker_id)
        self._servicer = servicer

    def report_batch_done(self, count, telemetry=None):
        super().report_batch_done(count, telemetry)
        telemetry = telemetry or {}
        self._servicer.report_batch_done(pb.ReportBatchDoneRequest(
            worker_id=self.worker_id, record_count=count,
            steps_done=telemetry.get("steps_done", 0),
            steps_per_sec=telemetry.get("steps_per_sec", 0.0),
            hist_delta=telemetry.get("hist_delta", "")))


def test_the_master_sees_the_worker_whose_fences_are_slow(
        dataset, spec, monkeypatch):
    """Two workers dispatch alike (one program, one host); the second's
    device takes three times as long, which only its fences show.  The
    sweep flags it, and the job's ``step_time_p50_ms`` lies in a bucket
    of the fence-paced steps: the dispatch burst's is decades below.
    ``utils.timing``'s clock moves with the staged device alone, so the
    buckets are the staged ones on any machine, and on it a pass's
    dispatch takes both workers no time at all."""
    clock = DeviceClock()
    monkeypatch.setattr(timing_mod, "time", clock)
    servicer = MasterServicer(TaskManager(
        training_shards=[("f", 0, 64)], records_per_task=32))
    fast, slow = 0.068, 0.204      # a step: 2 steps a fence
    assert hist.bucket_index(slow) == hist.bucket_index(fast) + 1
    passes = {}
    for _ in range(servicer.STRAGGLER_SUSTAIN_SWEEPS):
        for worker_id, a_step in ((1, fast), (2, slow)):
            _mc, trainer, worker = run_worker(
                dataset, spec, fused_steps=1,
                mc=ReportingClient([64] * 3, worker_id, servicer),
                worker_hook=slow_fences(2 * a_step, clock))
            passes[worker_id] = trainer.timing.percentile(
                "step_dispatch", 0.5)
        servicer.straggler_sweep()
    assert servicer.stragglers() == [2]
    # the case the dispatch cannot show: it is the same on both, and
    # neither's is anywhere near its step
    for dispatch in passes.values():
        assert dispatch < fast / 2.16
    telemetry = servicer.telemetry()
    workers = telemetry["workers"]
    assert workers[2]["straggler"] and not workers[1]["straggler"]
    assert workers[2]["step_p50_ms"] > 2 * workers[1]["step_p50_ms"]
    p50 = telemetry["job"]["step_time_p50_ms"] / 1e3
    assert hist.bucket_index(p50) in (hist.bucket_index(fast),
                                      hist.bucket_index(slow))
    assert telemetry["job"]["step_hist"]["count"] == 24
