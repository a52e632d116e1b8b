"""The four fence metrics of the worker loop: each reader against a real
job log (``benchmark/fixtures/fence_job_log.txt``: `lfm2-24b-a2b.seq8192`,
untraced, seed 2147484110 of the builder's chip call ``c2`` of PR 52, the
one run of its twelve that held a stall; the two argument dumps cut),
nothing where the program prints no ``worker fences:`` line, and the
manifest's entries."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmark.lib import job, manifest

ROOT = os.path.dirname(manifest.BENCH_DIR)
FIXTURE = os.path.join(manifest.BENCH_DIR, "fixtures", "fence_job_log.txt")

# The fixture's own two lines, field by field; the run's window as its
# detail.json had it (seconds of the epoch, the chip machine's clock UTC).
FENCES = {"fences": 15, "steps": 60, "fence_p50_ms": 413.189,
          "fence_max_ms": 445.518, "stalls": 1, "stall_excess_ms": 132.810,
          "stall_host_ms": 117.053}
STALL = {"step": 52, "task": 13, "steps": 4, "interval_ms": 1782.074,
         "median_ms": 412.316, "excess_ms": 132.81,
         "fence_wait_ms": 1645.4, "data_wait_ms": 117.542, "rpc_ms": 6.586,
         "task_fetch_ms": 1.083, "host_other_ms": 11.463,
         "host_excess_ms": 117.053, "gc_n": 0, "gc_ms": 0.0, "cpu_ms": 60.0,
         "nivcsw": 0, "majflt": 0, "compiles": 0}
NAMES = ["loop.fence_ms_per_step", "loop.stalls_in_window",
         "loop.stall_excess_ms", "loop.stall_host_ms"]
EXPECTED = dict(zip(NAMES, (413.189, 1.0, 132.81, 117.053)))


@pytest.fixture(scope="module")
def text():
    with open(FIXTURE) as fh:
        return fh.read()


def _stall_at(text):
    line, = [l for l in text.splitlines() if "worker stall: " in l]
    return job.stamp_seconds(line)


def _run(text, open_=None, close=None):
    """A run whose window holds the fixture's stall, unless told."""
    at = _stall_at(text) if "worker stall: " in text else 100.0
    return SimpleNamespace(
        job=SimpleNamespace(text=text),
        times={"open": at - 18.275 if open_ is None else open_,
               "close": at + 3.369 if close is None else close})


def _read(name, run):
    return manifest.load_named("layers", name).read(run)


def _without(text, mark):
    return "\n".join(l for l in text.splitlines() if mark not in l)


def test_the_fixture_holds_the_two_lines_and_they_parse(text):
    assert text.count("worker fences: ") == 1
    assert text.count("worker stall: ") == 1
    lines = text.splitlines()
    said = next(l for l in lines if "worker fences: " in l)
    assert {k: float(v) for k, v in job.fields(
        said.split("worker fences: ")[1]).items()} == FENCES
    stall = next(l for l in lines if "worker stall: " in l)
    assert {k: float(v) for k, v in job.fields(
        stall.split("worker stall: ")[1]).items()} == STALL
    # the line is out before the one the harness waits on, once a worker
    order = [i for i, l in enumerate(lines) if "worker fences: " in l
             or "worker end-of-run: " in l]
    assert len(order) == 2 and "worker fences: " in lines[order[0]]
    assert "worker end-of-run:" not in said
    # the interval by the thread's phases sums to it, and the summary
    # line is the stall line's
    assert sum(STALL[k] for k in (
        "fence_wait_ms", "data_wait_ms", "rpc_ms", "task_fetch_ms",
        "host_other_ms")) == pytest.approx(STALL["interval_ms"], abs=0.01)
    assert STALL["excess_ms"] == pytest.approx(
        STALL["interval_ms"] - STALL["steps"] * STALL["median_ms"], abs=0.01)
    assert FENCES["stall_excess_ms"] == STALL["excess_ms"]
    assert FENCES["stall_host_ms"] == STALL["host_excess_ms"]
    # the chip's machine has no /proc/pressure: the three fields are left out
    assert not [k for k in STALL if k.startswith("psi_")]


@pytest.mark.parametrize("name", NAMES)
def test_each_reader_reads_its_line(text, name):
    assert _read(name, _run(text)) == pytest.approx(EXPECTED[name], abs=1e-9)


@pytest.mark.parametrize("name", NAMES[1:])
def test_a_stall_outside_the_window_is_not_the_windows(text, name):
    at = _stall_at(text)
    assert _read(name, _run(text, at, at + 20.0)) == 0.0      # (open, ..
    assert _read(name, _run(text, at - 20.0, at)) == EXPECTED[name]  # close]
    assert _read(name, _run(text, at + 0.001, at + 20.0)) == 0.0
    assert _read(name, _run(text, at - 20.0, at - 0.001)) == 0.0


@pytest.mark.parametrize("name", NAMES[1:])
def test_a_quiet_run_reads_zero(text, name):
    quiet = _without(text, "worker stall: ")
    assert _read(name, _run(quiet)) == 0.0


@pytest.mark.parametrize("name", NAMES)
def test_a_log_without_the_line_reads_nothing(text, name):
    """Every commit before PR 52: the driver's traced runs of the parent
    lay these readers over a program that prints neither line."""
    bare = _without(_without(text, "worker fences: "), "worker stall: ")
    assert _read(name, _run(bare)) is None
    assert _read(name, _run("")) is None
    # a stall line alone (a worker killed before its exit) counts nothing
    assert _read(name, _run(_without(text, "worker fences: "))) is None


def test_a_worker_that_judged_no_fence_has_no_step(text):
    none = text.replace("fences=15 steps=60 fence_p50_ms=413.189",
                        "fences=0 steps=0 fence_p50_ms=0.000")
    assert _read("loop.fence_ms_per_step", _run(none)) is None
    assert _read("loop.stalls_in_window", _run(none)) == 1.0


def test_several_workers_lines_are_averaged(text):
    second = ("\n[2026-10-02 01:34:41,070] [INFO] [worker-1] "
              "[elasticdl_tpu.worker.worker:639:report] worker fences: "
              "fences=15 steps=60 fence_p50_ms=415.189 fence_max_ms=420.000 "
              "stalls=0 stall_excess_ms=0.000 stall_host_ms=0.000\n")
    assert _read("loop.fence_ms_per_step", _run(text + second)) == \
        pytest.approx(414.189)


def test_the_manifest_lists_the_four():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        doc = json.load(fh)
    e2e = {m["name"] for m in doc["end_to_end"]}
    names = [m["name"] for m in doc["per_layer"]]
    first = names.index("loop.fence_ms_per_step")
    # appended after every metric the benchmark had, in the issue's order
    assert first == 52 and names[first:first + 4] == NAMES
    for m in doc["per_layer"][first:first + 4]:
        assert m["moves"] == "records_per_s" and m["moves"] in e2e
        assert m["source"] == "program_counter" and m["better"] == "lower"
        assert m["unit"] == ("count" if m["name"] == "loop.stalls_in_window"
                             else "ms")
        assert m["layer"] == "worker loop"
        assert "workloads" not in m     # every cell reports records_per_s
        assert os.path.isfile(os.path.join(
            manifest.BENCH_DIR, "layers", m["name"] + ".py"))
        assert callable(manifest.Manifest(ROOT).reader(m["name"]))
    # a layer named before is named letter for letter
    assert "worker loop" in {m["layer"] for m in doc["per_layer"][:first]}
