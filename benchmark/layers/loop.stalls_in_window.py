"""Stalled fences inside the measured window: the workers' ``worker
stall:`` log lines (one per fence whose interval lay over the quiet
fences' median a step by more than 5% and 50 ms: elasticdl_tpu/utils/
timing.py, ``FenceWatch``) stamped in (``open``, ``close``].  0.0 in a quiet
run.  Nothing where the program prints no ``worker fences:`` line at its
end, as every commit before PR 52: such a program logs no stall either."""

from benchmark.lib import job

MARK = "worker stall: "


def stalls(run):
    """The fields of each stall line stamped inside the window, as
    numbers; None where the program does not watch its fences."""
    if "worker fences: " not in run.job.text:
        return None
    found = []
    for line in run.job.text.splitlines():
        if MARK not in line:
            continue
        at = job.stamp_seconds(line)
        if at is not None and run.times["open"] < at <= run.times["close"]:
            found.append({key: float(value) for key, value in job.fields(
                line.split(MARK, 1)[1]).items()})
    return found


def read(run):
    found = stalls(run)
    return None if found is None else float(len(found))
