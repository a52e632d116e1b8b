"""Milliseconds a step from one fence's return to the next one's: the
``fence_p50_ms`` of the worker's ``worker fences:`` line (the median over
the fences it judged, after its set-up; elasticdl_tpu/utils/timing.py,
``FenceWatch``), the mean over the workers.  The step by the program's own
clock, in any run's log, traced or not: beside ``loop.step_interval_ms``
(the spans of the traced seconds) it must agree to 0.5%.  Nothing where the
program prints no such line, as every commit before PR 52, or judged no
fence."""

import re

from benchmark.lib import job

_FENCES = re.compile(r"worker fences: (.*)$", re.M)


def read(run):
    said = [job.fields(report) for report in _FENCES.findall(run.job.text)]
    medians = [float(f["fence_p50_ms"]) for f in said
               if int(f.get("fences", 0)) > 0 and "fence_p50_ms" in f]
    if not medians:
        return None
    return sum(medians) / len(medians)
