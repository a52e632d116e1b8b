"""``remat.estimate_over_gb``: the reader against the two real job logs
under ``benchmark/fixtures`` that hold a ``remat keep:`` line (the newer
with ``need=`` and ``grads_standing=``, the older without), a log
without one, a job that rebuilt its step, and the manifest's entry."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmark.lib import manifest

ROOT = os.path.dirname(manifest.BENCH_DIR)
NAME = "remat.estimate_over_gb"
# (the fixture, its line's predicted_peak, the peak its job's end-of-run
# line states: in use + reserved; the older log is a head and ends
# before that line, so the test gives it one)
LOGS = [("fence_job_log.txt", 15807565064, 5740147200 + 9449914368),
        ("setup_job_log_head.txt", 15873150728, 15_500_000_000)]


def _text(fixture):
    with open(os.path.join(manifest.BENCH_DIR, "fixtures", fixture)) as fh:
        return fh.read()


def _read(text, peak):
    run = SimpleNamespace(job=SimpleNamespace(text=text),
                          memory_peak_bytes=lambda: peak)
    return manifest.load_named("layers", NAME).read(run)


@pytest.mark.parametrize("fixture, predicted, peak", LOGS)
def test_the_estimate_less_the_chips_peak(fixture, predicted, peak):
    text = _text(fixture)
    assert text.count("remat keep: ") == 1
    assert ("grads_standing=" in text) == (fixture == "fence_job_log.txt")
    assert _read(text, peak) == pytest.approx((predicted - peak) / 1e9)
    # the estimate stood over that chip, by what the band allowed then
    assert 0 < _read(text, peak) < 0.9


def test_nothing_without_the_line_or_without_a_peak():
    text = _text(LOGS[0][0])
    without = "\n".join(l for l in text.splitlines()
                        if "remat keep: " not in l)
    assert _read(without, LOGS[0][2]) is None
    assert _read(text, 0) is None


def test_a_rebuilt_steps_line_is_the_one_read():
    """A step whose compile ran out of memory is rebuilt with nothing
    kept and logs a second line: the program that ran is the last."""
    text = _text(LOGS[0][0])
    said = next(l for l in text.splitlines() if "remat keep: " in l)
    again = said.replace("predicted_peak=15807565064",
                         "predicted_peak=14000000000").replace(
                             "fallback=0", "fallback=1")
    assert _read(text + "\n" + again + "\n", 15_000_000_000) == -1.0


def test_the_manifest_lists_it_for_the_model_layer():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        doc = json.load(fh)
    entry, = [m for m in doc["per_layer"] if m["name"] == NAME]
    # it stood last in PR 58; later PRs' metrics follow it
    assert entry in doc["per_layer"]
    assert entry == {
        "name": NAME, "unit": "GB", "better": "lower",
        "source": "program_counter", "layer": "model",
        "moves": "records_per_s",
        "workloads": [w["name"] for w in doc["workloads"]]}
