"""The set-up timeline (docs/observability.md, "Set-up timeline"): marks
into contiguous phases, the one line a process, the compile listener's
whole account of a program's build, the master's ``worker ready:`` line,
and one managed CPU job that prints each of them once."""

import json
import logging
import os
import subprocess
import sys
import time
from types import SimpleNamespace

import pytest

from benchmark.lib import job as joblib
from elasticdl_tpu.master.servicer import MasterServicer
from elasticdl_tpu.master.task_manager import TaskManager
from elasticdl_tpu.models import mnist
from elasticdl_tpu.proto import elastic_pb2 as pb
from elasticdl_tpu.utils import timing as timing_mod
from elasticdl_tpu.utils import tracing
from elasticdl_tpu.utils.timing import (
    MASTER_SETUP,
    WORKER_SETUP,
    SetupTimeline,
    Timing,
)
from tests.test_fused_driver import run_worker

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER_FIELDS = (
    ["t0"] + [phase + "_s" for phase in WORKER_SETUP] + ["total_s"] +
    ["trace_s", "lower_s", "compile_or_load_s", "programs", "cache_hits",
     "cache_misses", "init_compile_or_load_s"])


class _Lines(logging.Handler):
    def __init__(self):
        super().__init__()
        self.lines = []

    def emit(self, record):
        self.lines.append(record.getMessage())

    def saying(self, mark):
        return [line for line in self.lines if line.startswith(mark)]


@pytest.fixture
def heard():
    """(logger, its lines): a logger of the test's own."""
    logger = logging.getLogger("test_setup_timeline.%d" % time.time_ns())
    logger.setLevel(logging.INFO)
    logger.propagate = False
    lines = _Lines()
    logger.addHandler(lines)
    return logger, lines


def _begun(heard, role="worker", phases=WORKER_SETUP):
    timeline = SetupTimeline()
    timeline.begin(role, phases, heard[0])
    return timeline


def _fields(line, mark):
    assert line.startswith(mark), line
    return joblib.fields(line[len(mark):])


# -- marks into phases --------------------------------------------------------


def test_the_phases_are_the_tables():
    assert WORKER_SETUP == (
        "import", "backend_init", "build", "param_init", "first_task_fetch",
        "first_batch", "first_dispatch", "first_run", "first_report")
    assert MASTER_SETUP == ("import", "build", "launch")


def test_marks_give_contiguous_phases_that_sum_to_the_total(heard):
    timeline = _begun(heard)
    began = time.perf_counter()
    for phase in WORKER_SETUP[2:]:
        time.sleep(0.002)
        timeline.mark(phase)
    time.sleep(0.002)
    got = timeline.close()
    wall = time.perf_counter() - began
    line, = heard[1].saying("worker setup: ")
    fields = _fields(line, "worker setup: ")
    # no compile was heard: the phases alone, in the table's order
    assert list(fields) == WORKER_FIELDS[:len(WORKER_SETUP) + 2]
    phases = [float(fields[p + "_s"]) for p in WORKER_SETUP]
    assert abs(sum(phases) - float(fields["total_s"])) < 1e-6
    assert all(s >= 0.002 for s in phases[1:])
    # nothing between the phases is dark: what follows ``import`` is the
    # wall time from the begin to the close
    assert abs(sum(phases[1:]) - wall) < 2e-3
    # ``import`` runs from the OS's start of the process, and ``t0`` is
    # that start on the epoch's clock, in ms
    assert phases[0] == pytest.approx(
        timing_mod.process_age() - (time.perf_counter() - began), abs=0.05)
    assert int(fields["t0"]) == pytest.approx(
        (time.time() - timing_mod.process_age()) * 1000, abs=50)
    assert got["total_s"] == float(fields["total_s"])
    # each phase is a Timing phase of its own name
    assert set(timeline.timing.summary()) == {
        "setup_" + phase for phase in WORKER_SETUP}


def test_a_mark_after_the_close_records_nothing_and_a_second_close_is_silent(
        heard):
    timeline = _begun(heard)
    timeline.mark("first_dispatch")
    into = Timing()
    assert timeline.close(into=into) is not None
    before = dict(timeline.timing.summary())
    assert not timeline.open
    timeline.mark("first_run")              # a job switch's trainer
    timeline.add(programs=1)
    assert timeline.close(into=into) is None
    assert len(heard[1].saying("worker setup: ")) == 1
    assert timeline.timing.summary() == before
    # the process's own Timing got each phase once, for its report
    assert {name: s["count"] for name, s in into.summary().items()} == {
        "setup_" + phase: 1 for phase in WORKER_SETUP}
    timeline.begin("worker", WORKER_SETUP, heard[0])   # a second main()
    assert not timeline.open


def test_marks_only_advance_and_a_skipped_phase_reads_zero(heard):
    timeline = _begun(heard)
    timeline.mark("first_dispatch")
    time.sleep(0.002)
    timeline.mark("first_run")
    time.sleep(0.002)
    timeline.mark("first_dispatch")   # the second step's: not a new phase
    timeline.mark("first_run")
    timeline.mark("launch")           # another role's: not this timeline's
    fields = timeline.close()
    assert fields["first_run_s"] >= 0.002 and fields["first_dispatch_s"] < 0.1
    assert fields["param_init_s"] == fields["first_batch_s"] == 0.0
    assert timeline.timing.summary()["setup_first_dispatch"]["count"] == 1


def test_a_timeline_no_entry_point_began_ignores_every_mark():
    timeline = SetupTimeline()
    timeline.mark("build")
    timeline.add(programs=1)
    assert timeline.close() is None and not timeline.open


def test_the_master_line_and_the_flight_recorder_event(heard):
    tracer = tracing._TRACER
    timeline = _begun(heard, "master", MASTER_SETUP)
    timeline.mark("launch")
    timeline.close()
    line, = heard[1].saying("master setup: ")
    fields = _fields(line, "master setup: ")
    assert list(fields) == ["t0", "import_s", "build_s", "launch_s",
                            "total_s"]
    assert float(fields["total_s"]) == pytest.approx(sum(
        float(fields[p + "_s"]) for p in MASTER_SETUP), abs=1e-6)
    events = [e for e in tracer.recorder.snapshot()
              if e and e.get("name") == "master.setup"]
    assert events and events[-1]["attrs"]["t0"] == int(fields["t0"])
    assert events[-1]["attrs"]["total_s"] == float(fields["total_s"])


def test_process_age_without_proc_counts_from_the_modules_import(
        monkeypatch):
    def no_proc(*_a, **_k):
        raise OSError("no /proc here")

    monkeypatch.setattr("builtins.open", no_proc)
    age = timing_mod.process_age()
    assert 0 <= age <= time.time() - timing_mod._IMPORTED_AT + 0.01


# -- the compile listener -----------------------------------------------------


@pytest.fixture
def timeline(heard, monkeypatch):
    """A begun timeline in the place of the process's, in every module
    that marks it."""
    from elasticdl_tpu.worker import collective_trainer, fused_driver
    from elasticdl_tpu.worker import main as worker_main
    from elasticdl_tpu.worker import worker as worker_mod

    timeline = _begun(heard)
    for module in (timing_mod, worker_main, collective_trainer,
                   fused_driver, worker_mod):
        monkeypatch.setattr(module, "SETUP", timeline)
    return timeline


def test_the_listener_says_the_whole_of_a_programs_build(heard, timeline):
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.worker import main as worker_main

    lines = _Lines()
    worker_main.logger.addHandler(lines)
    inner = jax.jit(lambda x: jnp.tanh(x) * 2.0)

    @jax.jit
    def step(x):
        for _ in range(20):
            x = inner(x) + jnp.sin(x)
        return x.sum()

    x = jnp.ones((7, 11))
    try:
        with worker_main.xla_compiles_logged(lambda: 3):
            timeline.mark("param_init")
            (x * 2.0).block_until_ready()
            timeline.mark("first_dispatch")
            began = time.perf_counter()
            step(x).block_until_ready()
            wall = time.perf_counter() - began
            timeline.mark("first_run")
            jax.jit(lambda v: v - 1.0)(x).block_until_ready()
    finally:
        worker_main.logger.removeHandler(lines)
    compiles = lines.saying("xla compile: ")
    assert len(compiles) >= 3
    for line in compiles:
        # the line there was, then the new fields, in that order
        assert list(_fields(line, "xla compile: ")) == [
            "secs", "step", "fun", "trace_s", "lower_s", "cache"]
        assert line.startswith("xla compile: secs=") and " step=3 fun=" in line
    of_step, = [line for line in compiles if "fun=jit(step)" in line]
    fields = timeline.close()
    assert list(fields) == WORKER_FIELDS
    assert fields["trace_s"] > 0 and fields["lower_s"] > 0
    assert fields["compile_or_load_s"] > 0 and fields["programs"] >= 3
    assert fields["init_compile_or_load_s"] > 0
    # ``inner`` was traced inside ``step``'s trace: counted once, so the
    # parts stay within the call's wall time
    assert (fields["trace_s"] + fields["lower_s"]
            + fields["compile_or_load_s"]) <= wall
    assert float(_fields(of_step, "xla compile: ")["trace_s"]) == (
        pytest.approx(fields["trace_s"], abs=1e-3))
    # first_dispatch's alone: not param_init's program, not first_run's
    assert fields["compile_or_load_s"] == pytest.approx(
        float(_fields(of_step, "xla compile: ")["secs"]), abs=1e-3)
    line, = heard[1].saying("worker setup: ")
    assert list(_fields(line, "worker setup: ")) == WORKER_FIELDS


CACHE_PROBE = """
import logging, sys
import jax, jax.numpy as jnp
from elasticdl_tpu.utils.timing import SETUP, WORKER_SETUP
from elasticdl_tpu.worker import main as worker_main
logging.basicConfig(stream=sys.stdout, level=logging.INFO,
                    format="%(message)s")
SETUP.begin("worker", WORKER_SETUP, logging.getLogger("probe"))
with worker_main.xla_compiles_logged(lambda: 0):
    SETUP.mark("first_dispatch")
    jax.jit(lambda x: (jnp.tanh(x) @ x.T).sum())(
        jnp.ones((16, 16))).block_until_ready()
SETUP.close()
"""


def test_a_cache_directory_gives_a_miss_first_and_a_hit_next(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1",
               PYTHONPATH=ROOT)
    said = []
    for _ in range(2):
        done = subprocess.run([sys.executable, "-c", CACHE_PROBE], cwd=ROOT,
                              env=env, capture_output=True, text=True,
                              timeout=240)
        assert done.returncode == 0, done.stderr[-2000:]
        lines = (done.stdout + done.stderr).splitlines()
        after = lambda mark, holding: next(
            l[l.index(mark):] for l in lines if mark in l and holding in l)
        said.append((
            _fields(after("xla compile: ", "fun=jit(<lambda>)"),
                    "xla compile: ")["cache"],
            _fields(after("worker setup: ", ""), "worker setup: ")))
    (first, cold), (second, warm) = said
    assert first == "miss" and second == "hit"
    assert int(cold["cache_misses"]) >= 1 and int(cold["cache_hits"]) == 0
    assert int(warm["cache_misses"]) == 0 and int(warm["cache_hits"]) >= 1


# -- the worker's marks, both loops -------------------------------------------


@pytest.mark.parametrize("fused_steps", [1, 2])
def test_a_workers_first_task_is_its_setup_and_no_later_one(
        heard, timeline, fused_steps):
    spec = mnist.model_spec(learning_rate=1e-3)
    dataset = mnist.synthetic_data(n=128, seed=1)
    timeline.mark("build")
    _mc, trainer, worker = run_worker(dataset, spec, fused_steps=fused_steps)
    assert not timeline.open
    line, = heard[1].saying("worker setup: ")
    fields = {k: float(v) for k, v in _fields(
        line, "worker setup: ").items()}
    for phase in ("param_init", "first_task_fetch", "first_batch",
                  "first_dispatch", "first_run", "first_report"):
        assert fields[phase + "_s"] > 0, phase
    assert abs(sum(fields[p + "_s"] for p in WORKER_SETUP)
               - fields["total_s"]) < 1e-6
    # the first step's marks went with the first step
    assert "_run_step" not in vars(trainer)
    assert worker.timing.summary()["setup_first_dispatch"]["count"] == 1
    # a second job in the process (a job switch, a rebuild): nothing more
    run_worker(dataset, spec, fused_steps=fused_steps)
    assert len(heard[1].saying("worker setup: ")) == 1


# -- the master's view --------------------------------------------------------


def test_worker_ready_is_logged_once_an_incarnation_the_master_launched():
    from elasticdl_tpu.master import servicer as servicer_mod

    launched = time.time() - 5.0
    manager = SimpleNamespace(
        launched_at=lambda wid: launched if wid == 0 else None)
    tm = TaskManager(training_shards=[("f", 0, 96)], records_per_task=32,
                     num_epochs=1)
    servicer = MasterServicer(tm, worker_manager=manager)
    lines = _Lines()
    servicer_mod.logger.addHandler(lines)
    try:
        for wid in (0, 0, 7):     # 7: a worker this master did not launch
            task = servicer.get_task(pb.GetTaskRequest(worker_id=wid)).task
            time.sleep(0.01)
            servicer.report_task_result(
                pb.ReportTaskResultRequest(task_id=task.id))
    finally:
        servicer_mod.logger.removeHandler(lines)
    line, = lines.saying("worker ready: ")
    fields = {k: float(v) for k, v in _fields(
        line, "worker ready: ").items()}
    assert list(fields) == ["id", "launch_to_register_s",
                            "register_to_first_task_s", "first_task_s"]
    assert fields["id"] == 0 and fields["launch_to_register_s"] >= 5.0
    assert 0 <= fields["register_to_first_task_s"] < 1.0
    assert 0.01 <= fields["first_task_s"] < 1.0


# -- one definition of the profiler's options ---------------------------------


def test_profilez_takes_the_options_of_device_trace(tmp_path, monkeypatch):
    import jax

    started = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda path, **kw: started.append((path, kw)))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    body = tracing.profilez_capture(0, trace_dir=str(tmp_path))
    assert body["ok"], body
    with timing_mod.device_trace(str(tmp_path / "whole")):
        pass
    (_, on_request), (_, whole_run) = started
    for kw in (on_request, whole_run):
        options = kw["profiler_options"]
        assert (options.python_tracer_level, options.host_tracer_level,
                options.enable_hlo_proto) == (0, 1, False)


# -- no JAX where there was none ----------------------------------------------


def test_the_timeline_and_the_master_import_no_jax():
    code = ("import logging, sys\n"
            "import elasticdl_tpu.utils.timing as t\n"
            "import elasticdl_tpu.master.main\n"
            "import elasticdl_tpu.master.worker_manager\n"
            "t.SETUP.begin('master', t.MASTER_SETUP, logging.getLogger())\n"
            "t.SETUP.mark('launch')\n"
            "assert t.SETUP.close()['total_s'] > 0\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n")
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]


# -- one managed job ----------------------------------------------------------


def test_a_managed_job_prints_each_line_once(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    began = time.time()
    done = subprocess.run(
        [sys.executable, "-m", "elasticdl_tpu.master.main",
         "--data_origin", "synthetic_mnist:128", "--model_zoo", "mnist",
         "--batch_size", "32", "--num_epochs", "1", "--num_workers", "1",
         "--num_minibatches_per_task", "2"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    text = done.stdout + done.stderr
    assert done.returncode == 0, text[-3000:]
    assert "job finished" in text
    lines = text.splitlines()
    said = lambda mark: [l[l.index(mark) + len(mark):] for l in lines
                         if mark in l]
    master, = said("master setup: ")
    worker, = said("worker setup: ")
    ready, = said("worker ready: ")
    master, worker, ready = (
        {k: float(v) for k, v in joblib.fields(report).items()}
        for report in (master, worker, ready))
    assert list(worker) == WORKER_FIELDS
    assert all(worker[p + "_s"] > 0 for p in WORKER_SETUP)
    assert abs(sum(worker[p + "_s"] for p in WORKER_SETUP)
               - worker["total_s"]) < 1e-6
    assert worker["programs"] >= 1 and worker["compile_or_load_s"] > 0
    assert worker["trace_s"] > 0 and worker["lower_s"] > 0
    # on one clock: the master's set-up ends where its first worker's
    # process starts, and both lie inside this test's own stamps
    master_end = master["t0"] / 1e3 + master["total_s"]
    assert began - 0.05 <= master["t0"] / 1e3 <= worker["t0"] / 1e3
    assert abs(master_end - worker["t0"] / 1e3) < 0.5
    assert worker["t0"] / 1e3 + worker["total_s"] <= time.time()
    # the master bounds the worker's account from outside
    outside = (ready["launch_to_register_s"]
               + ready["register_to_first_task_s"] + ready["first_task_s"])
    assert abs(outside - worker["total_s"]) < 0.5
    compiles = said("xla compile: ")
    assert compiles and all(
        list(joblib.fields(c))[:3] == ["secs", "step", "fun"]
        and {"trace_s", "lower_s", "cache"} <= set(joblib.fields(c))
        for c in compiles)
    # the phases are in the worker's end-of-run report too
    assert sum("timing[setup_" in l for l in lines) == len(WORKER_SETUP)
    json.dumps(worker)
