"""Model FLOP/s utilization: the forward + backward operations a record
requires (``opcounts/<name>.py`` on the configuration's shapes, recompute
not counted) times this run's records per second, over chips times the
published peak."""

from benchmark.lib import manifest, peaks


def read(run):
    if not run.window or "opcounts" not in run.config:
        return None
    flops = manifest.load_named(
        "opcounts", run.config["opcounts"]).train_flops(run.config)
    peak = peaks.peaks_of(run.device["kind"])["bf16_flops"]
    return 100.0 * flops * run.window["records_per_s"] / (
        run.cell["chips"] * peak)
