"""The step anatomy on the profiler's clock (docs/observability.md):
``Timing`` phases as ``edl.*`` annotations in a real CPU profiler trace,
the span vocabulary of both loops, the prefetch producer's phases, the
compile listener, and the benchmark's span readers on a hand-built
fixture (benchmark/fixtures/tiny_trace_spans.json)."""

import glob
import json
import logging
import os
import subprocess
import sys
import threading
import time
from types import SimpleNamespace

import pytest

from benchmark.lib import manifest, spans as spanlib
from elasticdl_tpu.data.parallel_reader import prefetch_batches
from elasticdl_tpu.models import mnist
from elasticdl_tpu.utils.timing import Timing
from tests.test_fused_driver import run_worker

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(ROOT, "benchmark", "fixtures",
                       "tiny_trace_spans.json")
MS = 1_000_000


# -- (a) a Timing phase is an annotation in the profiler's trace -------------


def _trace_options():
    """The options of benchmark/lib/bench_zoo.py and of
    utils/timing.device_trace."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    options.enable_hlo_proto = False
    return options


def _host_events(trace_dir):
    """{line: [(event name, start, end, stats)]} of the host planes."""
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        for nth, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("edl."):
                    lines.setdefault((plane.name, nth, line.name), []).append(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)))
    return lines


def test_timing_phases_land_in_a_profiler_trace_nested_and_by_thread(
        tmp_path):
    import jax

    timing = Timing()

    def other():
        with timing.timeit("reader_batch"):
            time.sleep(0.002)

    jax.profiler.start_trace(str(tmp_path), profiler_options=_trace_options())
    try:
        thread = threading.Thread(target=other)
        with timing.timeit("step", step=3, task=7):
            thread.start()
            with timing.timeit("x", step=3):
                time.sleep(0.002)
            thread.join()
    finally:
        jax.profiler.stop_trace()
    lines = _host_events(str(tmp_path))
    mine = [line for line, events in lines.items()
            if any(e[0] == "edl.step" for e in events)]
    assert len(mine) == 1
    by_name = {e[0]: e for e in lines[mine[0]]}
    step, inner = by_name["edl.step"], by_name["edl.x"]
    assert step[1] <= inner[1] and inner[2] <= step[2]       # nested
    assert step[3] == {"step": 3, "task": 7} and inner[3] == {"step": 3}
    # the other thread's phase is on a line of its own (under the same
    # name: the profiler names a line after the OS thread, and Python
    # names only its own thread object)
    assert "edl.reader_batch" not in by_name
    assert sorted(e[0] for line, events in lines.items()
                  if line != mine[0] for e in events) == ["edl.reader_batch"]
    # the phase names and totals are what they were: no prefix in Timing
    assert set(timing.summary()) == {"step", "x", "reader_batch"}


def test_device_trace_uses_the_workable_options(tmp_path):
    """``--profile_dir``: Python tracer off, no HLO protos, and the
    program's spans in the trace.  Traced in a process of its own: a
    process that has compiled for a described chip
    (``tests/tpu_compile.py``) writes those programs' protos into every
    later trace, megabytes of them, whatever the options say."""
    subprocess.run([sys.executable, "-c", (
        "import sys, time\n"
        "from elasticdl_tpu.utils.timing import Timing, device_trace\n"
        "with device_trace(sys.argv[1]):\n"
        "    with Timing().timeit('step'):\n"
        "        time.sleep(0.001)\n"), str(tmp_path)], check=True, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    lines = _host_events(str(tmp_path))
    assert [e[0] for events in lines.values() for e in events] == [
        "edl.step"]
    path, = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    assert os.path.getsize(path) < 100_000   # no Python frames, no protos


# -- (b) the master's and the PS's modules stay off JAX -----------------------


def test_timing_and_tracing_import_no_jax():
    code = ("import sys\n"
            "import elasticdl_tpu.utils.timing as t\n"
            "import elasticdl_tpu.utils.tracing\n"
            "with t.Timing().timeit('x', step=1): pass\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n")
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]


# -- (c) one vocabulary in both loops -----------------------------------------


@pytest.fixture(scope="module")
def spec():
    return mnist.model_spec(learning_rate=1e-3)


@pytest.fixture(scope="module")
def dataset():
    return mnist.synthetic_data(n=192, seed=1)


@pytest.mark.parametrize("fused_steps", [1, 2])
def test_both_loops_time_the_same_phases(dataset, spec, fused_steps):
    """192 records, tasks of 64, batches of 32: 3 tasks of 2 minibatches.
    The per-step loop counts a step, a progress report per minibatch and a
    pull per minibatch plus the one that sees the stream's end; the fused
    loop the same per window pass (here one pass of 2 steps a task)."""
    _mc, _trainer, worker = run_worker(dataset, spec,
                                       fused_steps=fused_steps)
    got = {name: s["count"] for name, s in worker.timing.summary().items()
           if "count" in s}
    passes = 6 // fused_steps
    assert got["step"] == passes
    assert got["progress_rpc"] == passes
    assert got["data_wait"] == passes + 3
    assert got["task_process"] == 3 and "task" not in got
    assert got["task_fetch"] == 4          # 3 tasks + "job finished"
    assert got["loss_sync"] >= 3           # each task's final fence
    assert got["reader_batch"] == 6 + 3
    if fused_steps == 1:   # the trainer's own Timing holds its phases
        assert _trainer.timing.summary()["step_dispatch"]["count"] == 6
    else:
        assert got["window_dispatch"] == passes
    # step_time keeps its meaning: one observation per optimizer step
    assert got["step_time"] == 6


# -- (d) the producer's phases, and two threads timing at once ----------------


def test_prefetch_producer_times_batches_and_not_the_full_queue():
    timing = Timing()

    def slow_source():
        for item in range(5):
            time.sleep(0.004)
            yield item

    batches = prefetch_batches(slow_source(), depth=1, timing=timing,
                               prepare=lambda item: item * 10)
    got = []
    for item in batches:
        time.sleep(0.02)           # a slow consumer: the queue stays full
        got.append(item)
    assert got == [0, 10, 20, 30, 40]
    summary = timing.summary()
    assert set(summary) == {"reader_batch"}
    assert summary["reader_batch"]["count"] == 5 + 1   # + the stream's end
    # the reads are in it, the waits on the full queue are not
    assert 0.02 <= summary["reader_batch"]["total_s"] < 0.06


def test_prefetch_without_timing_records_nothing():
    assert list(prefetch_batches(iter(range(3)))) == [0, 1, 2]


def test_two_threads_timing_the_same_phase_do_not_collide():
    timing = Timing()
    opened = threading.Barrier(2)

    def hold(seconds):
        timing.start("p")
        opened.wait()              # both are open at once
        time.sleep(seconds)
        timing.end("p")

    threads = [threading.Thread(target=hold, args=(s,))
               for s in (0.01, 0.05)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    p = timing.summary()["p"]
    assert p["count"] == 2
    assert 0.055 <= p["total_s"] < 0.2     # 0.01 + 0.05, neither lost
    timing.end("p")                        # nothing open: no effect
    assert timing.summary()["p"]["count"] == 2


# -- (e) a recompile is visible from inside -----------------------------------


def test_compile_listener_logs_one_line_per_fresh_jit():
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.worker import main as worker_main

    lines = []
    handler = logging.Handler()
    handler.emit = lambda record: lines.append(record.getMessage())
    steps = {"done": 0}
    small, large = jnp.ones((3, 5)), jnp.ones((4, 5))   # before listening
    fn = jax.jit(lambda x: x * 3.0 + 1.0)
    worker_main.logger.addHandler(handler)
    try:
        with worker_main.xla_compiles_logged(lambda: steps["done"]):
            fn(small).block_until_ready()
            first = list(lines)
            steps["done"] = 12
            fn(small).block_until_ready()      # compiled already: no line
            fn(large).block_until_ready()      # a new shape: one more
        heard = list(lines)
        # the listener went with the block: a second main() in one
        # process does not log every compile twice
        fn(jnp.ones((5, 5))).block_until_ready()
    finally:
        worker_main.logger.removeHandler(handler)
    assert len(first) == 1 and " step=0 " in first[0]
    assert len(heard) == 2 and " step=12 " in heard[1]
    assert heard[1].startswith("xla compile: secs=")
    assert lines == heard


def test_device_report_states_reserved_bytes_per_chip():
    from elasticdl_tpu.utils.device import (
        device_report,
        format_device_report,
    )

    report = device_report()
    assert len(report["peak_bytes_reserved"]) == report["local_devices"]
    line = format_device_report(report)
    fields = dict(item.split("=", 1) for item in line.split())
    assert "peak_bytes_reserved" in fields and "peak_bytes_in_use" in fields


# -- (f) the benchmark's readers on the hand-built fixture --------------------


@pytest.fixture(scope="module")
def trace():
    with open(FIXTURE) as fh:
        return json.load(fh)


def _run(trace, tmp_path, fused_steps=None, text=""):
    """What a per-layer reader sees of a traced run."""
    os.makedirs(os.path.join(str(tmp_path), "trace"), exist_ok=True)
    if trace is not None:
        with open(os.path.join(str(tmp_path), "trace", "reduced.json"),
                  "w") as fh:
            json.dump(trace, fh)
    flags = {"num_minibatches_per_task": 2, "batch_size": 8}
    if fused_steps:
        flags["fused_steps"] = fused_steps
    return SimpleNamespace(
        trace_dir=os.path.join(str(tmp_path), "trace"), traced=True,
        traffic={"flags": flags}, job=SimpleNamespace(text=text),
        times={"open": 100.0, "close": 120.0})


def _read(metric, run):
    return manifest.load_named("layers", metric).read(run)


def test_spans_nest_by_thread_and_the_threads_are_told_by_name(trace):
    s = spanlib.of_trace(trace)
    parent = lambda x: (None if x.parent is None
                        else s.spans[x.parent].name)
    by = {(x.name, x.start // MS): x for x in s.spans}
    assert parent(by["edl.task_process", 103]) is None
    assert parent(by["edl.data_wait", 104]) == "edl.task_process"
    assert parent(by["edl.step", 110]) == "edl.task_process"
    assert parent(by["edl.loss_sync", 127]) == "edl.step"
    assert parent(by["edl.progress_rpc", 194]) == "edl.step"
    # the producer's batches are on a line of the same name as the
    # training thread's: 103..108 lies inside task_process and 109..114
    # overlaps the step 110..120, and neither nests
    assert {parent(x) for x in s.named("edl.reader_batch")} == {None}
    assert by["edl.reader_batch", 109].thread != s.thread
    assert by["edl.step", 110].thread == s.thread
    # a child whose parent the trace's edge cut stands alone
    assert parent(by["edl.progress_rpc", 17]) is None


def test_the_stretch_is_the_whole_steps(trace):
    s = spanlib.of_trace(trace)
    # whole steps: task 0's second, both of tasks 1-5, task 6's first
    assert s.steps == 12
    assert s.stretch == (20 * MS, 620 * MS)
    assert len(s.named("edl.task_process")) == 5 and len(
        s.named("edl.task_fetch")) == 6


EXPECTED = {
    # first pulls of tasks 1-6 (5 each) + 1 in each of 12 steps, of 600
    "data.wait_share": 100.0 * 42 / 600,
    # tasks 1-6: 5 + 5 + 1 each
    "reader.busy_share": 100.0 * 66 / 600,
    # 6 first steps x 2 + 6 fence steps x 3, over 12 steps
    "task.rpc_ms_per_step": 30.0 / 12,
    "task.fetch_ms_per_task": 2.0,
    # first steps 10 - 1, fence steps 78 - 1 - 67
    "loop.host_ms_per_step": (6 * 9 + 6 * 10) / 12.0,
    "loop.loss_sync_share": 100.0 * 6 * 67 / 600,
    # 11 starts from 20 to 520 hold 5 whole tasks: 90, 10, 90, ...
    "loop.step_interval_ms": 50.0,
    # every task's worth of intervals is 90 + 10
    "loop.slowest_task_step_ms": 50.0,
}


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_span_readers_on_the_fixture(trace, tmp_path, metric):
    assert _read(metric, _run(trace, tmp_path)) == pytest.approx(
        EXPECTED[metric])


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_span_readers_read_nothing_where_the_program_annotates_nothing(
        trace, tmp_path, metric):
    bare = dict(trace, host=[e for e in trace["host"]
                             if not e[0].startswith("edl.")])
    assert _read(metric, _run(bare, tmp_path)) is None
    # and where the traced run left no reduced trace at all
    assert _read(metric, _run(None, tmp_path / "none")) is None


def test_fused_window_passes_count_their_steps(trace, tmp_path):
    run = _run(trace, tmp_path, fused_steps=2)
    # each edl.step is a pass of 2 steps, and a task of 2 steps one pass
    assert _read("task.rpc_ms_per_step", run) == pytest.approx(30.0 / 24)
    assert _read("loop.step_interval_ms", run) == pytest.approx(
        (610 - 20) / 11 / 2.0)


def test_the_slowest_task_stands_out_and_a_fence_pass_does_not(
        trace, tmp_path):
    # task 3 stalls: everything from 330 ms on (the second step's fence
    # began at 327) comes 40 ms later
    late = dict(trace, host=[
        [name, start + (40 * MS if start >= 330 * MS else 0), dur, line]
        for name, start, dur, line in trace["host"]])
    run = _run(late, tmp_path)
    # steps start at 20, 110, 120, 210, 220, 310, 320, 450, 460, 550, 560
    # (650 is past the last whole task): 90+10, 90+10, 90+10, 130+10, ...
    assert _read("loop.slowest_task_step_ms", run) == pytest.approx(70.0)
    assert _read("loop.step_interval_ms", run) == pytest.approx(54.0)
    # under one whole task (steps at 20 and 110 only): nothing
    short = dict(trace, host=[e for e in trace["host"] if e[1] < 120 * MS])
    run = _run(short, tmp_path / "short")
    assert _read("loop.slowest_task_step_ms", run) is None
    assert _read("loop.step_interval_ms", run) == pytest.approx(90.0)


def test_absent_spans_read_as_nothing_or_as_no_time(trace, tmp_path):
    without = lambda name: dict(trace, host=[
        e for e in trace["host"] if e[0] != name])
    run = _run(without("edl.task_fetch"), tmp_path / "a")
    assert _read("task.fetch_ms_per_task", run) is None
    assert _read("data.wait_share", run) == pytest.approx(7.0)
    # every batch was ready in under 100 us: no span, no time
    run = _run(without("edl.data_wait"), tmp_path / "b")
    assert _read("data.wait_share", run) == 0.0
    # no whole step: no stretch, nothing per step
    run = _run(without("edl.step"), tmp_path / "c")
    assert _read("loop.loss_sync_share", run) is None
    assert _read("task.fetch_ms_per_task", run) == 2.0


def _stamped(second, message):
    return ("[2026-09-27 02:00:%02d,500] [INFO] [worker-0] "
            "[elasticdl_tpu.worker.main:1:on_duration] %s" % (second, message))


def test_backend_compiles_are_counted_inside_the_window(tmp_path):
    from benchmark.lib import job

    t = lambda second: job.stamp_seconds(_stamped(second, ""))
    text = "\n".join([
        _stamped(1, "xla compile: secs=0.412 step=0 fun=init"),
        _stamped(5, "xla compile: secs=31.0 step=0 fun=train_step"),
        _stamped(20, "xla compile: secs=0.050 step=31 fun=_pad"),
        _stamped(21, "step 40 loss 1.0 (version 40)"),
        _stamped(40, "xla compile: secs=0.020 step=70 fun=late"),
    ])
    run = _run(None, tmp_path, text=text)
    run.times = {"open": t(10), "close": t(30)}
    assert _read("cache.backend_compiles_in_window", run) == 1.0
    run.times = {"open": t(6), "close": t(19)}
    assert _read("cache.backend_compiles_in_window", run) == 0.0
    # a program that logs no such line states nothing
    run.job.text = _stamped(21, "step 40 loss 1.0 (version 40)")
    assert _read("cache.backend_compiles_in_window", run) is None


def test_anatomy_tool_checks_the_clocks_on_the_fixture(trace):
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "tools",
                                      "anatomy.py"), FIXTURE, "2"],
        capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    out = json.loads(done.stdout)
    assert out["spans"]["edl.step"]["self_ms_per_step"] == pytest.approx(2.0)
    assert out["spans"]["edl.loss_sync"]["count"] == 6
    host, device = out["clock"]["host"], out["clock"]["device"]
    assert host["steps"] == 12 and host["mean_interval_ms"] == 50.0
    # 13 executions, the last one a stump that ends with the trace
    assert device["executions"] == 13 and device["whole"] == 12
    assert device["cut_by_the_windows_edges"] == 1
    assert device["whole_mean_duration_ms"] == 45.0
    assert device["mean_start_to_start_ms"] == 50.0
    # the device idles 5 of every 50 ms, 12 times in the stretch, each
    # time in a task's second step: at base+20..25 while the host pulls
    # (1), prepares (2) and dispatches (the first 2 of 3), and at
    # base+70..75 under the fence
    assert out["gaps"] == {
        "edl.loss_sync": pytest.approx(30.0),
        "edl.data_wait": pytest.approx(6.0),
        "edl.batch_prep": pytest.approx(12.0),
        "edl.step_dispatch": pytest.approx(12.0)}


def test_every_new_metric_is_in_the_manifest_with_a_reader():
    book = manifest.Manifest(ROOT)
    names = {m["name"]: m for m in book.doc["per_layer"]}
    for metric in list(EXPECTED) + ["cache.backend_compiles_in_window"]:
        assert names[metric]["moves"] == "records_per_s"
        assert callable(book.reader(metric))
    assert names["loop.step_interval_ms"]["source"] == "program_span"
    assert names["cache.backend_compiles_in_window"]["source"] == \
        "program_counter"
