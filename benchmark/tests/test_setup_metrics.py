"""The ``setup.*`` metrics: each reader against the head of a real job log
(``benchmark/fixtures/setup_job_log_head.txt``: `lfm2-24b-a2b.seq8192`,
traced and warm, seed 2147483817 of the builder's chip call ``c2`` of
PR 35, up to the worker's first logged loss; the two argument dumps
cut), nothing where the program prints no such line, and the manifest's
entries."""

import json
import os
import re
from types import SimpleNamespace

import pytest

from benchmark.lib import job, manifest, setup_line

ROOT = os.path.dirname(manifest.BENCH_DIR)
FIXTURE = os.path.join(manifest.BENCH_DIR, "fixtures",
                       "setup_job_log_head.txt")

# The fixture's own two lines, field by field.
MASTER = {"t0": 1790643574998, "import_s": 0.964342, "build_s": 4.373921,
          "launch_s": 0.002248, "total_s": 5.340511}
WORKER = {"t0": 1790643580328, "import_s": 3.885431,
          "backend_init_s": 8.304697, "build_s": 1.34537,
          "param_init_s": 8.438913, "first_task_fetch_s": 0.002432,
          "first_batch_s": 0.020058, "first_dispatch_s": 19.54962,
          "first_run_s": 1.867981, "first_report_s": 0.004442,
          "total_s": 43.418944, "trace_s": 12.301809, "lower_s": 2.967408,
          "compile_or_load_s": 3.89609, "programs": 53, "cache_hits": 9,
          "cache_misses": 0, "init_compile_or_load_s": 6.888875}

EXPECTED = {
    "setup.master_s": MASTER["total_s"],
    "setup.worker_import_s": WORKER["import_s"],
    "setup.backend_init_s": WORKER["backend_init_s"],
    "setup.build_s": WORKER["build_s"],
    "setup.param_init_s": WORKER["param_init_s"],
    "setup.first_data_s": (WORKER["first_task_fetch_s"]
                           + WORKER["first_batch_s"]),
    "setup.trace_lower_s": WORKER["trace_s"] + WORKER["lower_s"],
    "setup.compile_or_load_s": WORKER["compile_or_load_s"],
    "setup.cache_misses": 0.0,
    "setup.first_run_s": WORKER["first_run_s"],
}
NAMES = sorted(EXPECTED) + ["setup.unaccounted_s"]
LAYERS = {
    "setup.master_s": "entry + launch",
    "setup.worker_import_s": "entry + launch",
    "setup.backend_init_s": "entry + launch",
    "setup.build_s": "entry + launch",
    "setup.param_init_s": "trainer",
    "setup.first_data_s": "task plane",
    "setup.trace_lower_s": "model",
    "setup.compile_or_load_s": "compile cache",
    "setup.cache_misses": "compile cache",
    "setup.first_run_s": "trainer",
    "setup.unaccounted_s": "benchmark harness",
}


@pytest.fixture(scope="module")
def text():
    with open(FIXTURE) as fh:
        return fh.read()


def _run(text, master_start=None, first_completion=None):
    completions = [] if first_completion is None else [
        first_completion, first_completion + 2.0]
    return SimpleNamespace(
        job=SimpleNamespace(text=text, completions=completions),
        times={"master_start": master_start})


def _read(name, run):
    return manifest.load_named("layers", name).read(run)


def _without(text, mark):
    return "\n".join(l for l in text.splitlines() if mark not in l)


def test_the_fixture_holds_one_line_of_each_and_they_parse(text):
    assert text.count("master setup: ") == text.count("worker setup: ") == 1
    assert text.count("worker ready: ") == 1
    assert setup_line.master(_run(text)) == MASTER
    assert setup_line.worker(_run(text)) == WORKER
    # the lines are the form ``worker device:`` has: job.fields reads them
    line = next(l for l in text.splitlines() if "worker setup: " in l)
    assert {k: float(v) for k, v in job.fields(
        line.split("worker setup: ")[1]).items()} == WORKER
    # the phases sum to the total, as the program promises
    phases = [k for k in WORKER if k.endswith("_s")][:9]
    assert phases[0] == "import_s" and phases[-1] == "first_report_s"
    assert sum(WORKER[k] for k in phases) == pytest.approx(
        WORKER["total_s"], abs=1e-6)
    # every compile line says the whole of its program's build
    compiles = [l for l in text.splitlines() if "xla compile: " in l]
    assert compiles and all(re.search(
        r"xla compile: secs=\S+ step=\d+ fun=\S+ trace_s=\S+ lower_s=\S+ "
        r"cache=(hit|miss|off)$", l) for l in compiles)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_each_reader_reads_its_field(text, name):
    assert _read(name, _run(text)) == pytest.approx(EXPECTED[name],
                                                    abs=1e-9)


def test_unaccounted_closes_the_sum_by_construction(text):
    master_start = MASTER["t0"] / 1e3 - 0.031      # the harness's Popen
    first_completion = (WORKER["t0"] / 1e3 + WORKER["total_s"]) + 0.012
    run = _run(text, master_start, first_completion)
    got = _read("setup.unaccounted_s", run)
    assert (_read("setup.master_s", run) + WORKER["total_s"] + got
            == pytest.approx(first_completion - master_start, abs=1e-9))
    # the master's spawn, the worker's, and the harness's poll
    spawn = WORKER["t0"] / 1e3 - (MASTER["t0"] / 1e3 + MASTER["total_s"])
    assert got == pytest.approx(0.031 + spawn + 0.012, abs=1e-6)
    # no task completed yet: nothing to subtract from
    assert _read("setup.unaccounted_s", _run(text, master_start)) is None


@pytest.mark.parametrize("name", NAMES)
def test_a_log_without_the_lines_reads_nothing(text, name):
    """Every commit before PR 35: the driver's traced runs of the parent
    lay these readers over a program that prints neither line."""
    bare = _without(_without(text, "worker setup: "), "master setup: ")
    assert _read(name, _run(bare, 100.0, 160.0)) is None
    assert _read(name, _run("", 100.0, 160.0)) is None
    only_worker = _without(text, "master setup: ")
    expected_none = name in ("setup.master_s", "setup.unaccounted_s")
    assert (_read(name, _run(only_worker, 100.0, 160.0)) is None) \
        == expected_none


def test_a_line_that_lacks_a_field_reads_nothing(text):
    """A worker that heard no compile logs the phases alone."""
    cut = re.sub(r" trace_s=.*$", "", text, flags=re.M)
    assert "worker setup: " in cut and " trace_s=" not in cut
    run = _run(cut)
    assert _read("setup.trace_lower_s", run) is None
    assert _read("setup.cache_misses", run) is None
    assert _read("setup.param_init_s", run) == WORKER["param_init_s"]


def test_the_first_workers_line_is_the_one_read(text):
    relaunched = text + ("\n[2026-09-29 01:00:00,000] [INFO] [worker-1] "
                         "[__main__:418:close] worker setup: t0=1 "
                         "import_s=99.0 total_s=99.0\n")
    assert _read("setup.worker_import_s",
                 _run(relaunched)) == WORKER["import_s"]


def test_the_manifest_lists_the_eleven():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        doc = json.load(fh)
    e2e = {m["name"] for m in doc["end_to_end"]}
    names = [m["name"] for m in doc["per_layer"]]
    first = names.index("setup.master_s")
    # appended after every metric the benchmark had, in the issue's order
    assert first == 34 and names[first:first + 11] == [
        "setup.master_s", "setup.worker_import_s", "setup.backend_init_s",
        "setup.build_s", "setup.param_init_s", "setup.first_data_s",
        "setup.trace_lower_s", "setup.compile_or_load_s",
        "setup.cache_misses", "setup.first_run_s", "setup.unaccounted_s"]
    mine = doc["per_layer"][first:first + 11]
    assert {m["name"] for m in mine} == set(NAMES)
    for m in mine:
        assert m["moves"] == "setup_s" and m["moves"] in e2e
        assert m["source"] == "program_counter" and m["better"] == "lower"
        assert m["unit"] == ("count" if m["name"] == "setup.cache_misses"
                             else "s")
        assert m["layer"] == LAYERS[m["name"]]
        assert "workloads" not in m          # every cell reports setup_s
        assert os.path.isfile(os.path.join(
            manifest.BENCH_DIR, "layers", m["name"] + ".py"))
        assert callable(manifest.Manifest(ROOT).reader(m["name"]))
    # a layer named before is named letter for letter
    assert {m["layer"] for m in mine} <= {
        m["layer"] for m in doc["per_layer"][:first]}
