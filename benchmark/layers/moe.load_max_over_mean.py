"""The fullest expert's rows over the mean expert's, from the worker's
``moe load: step= layers= rows= max= mean= padded_rows=`` lines stamped
inside the measured window (one per logged loss): 1 is a balanced
router, the number of experts over the experts a token one that has
collapsed.  The mean over the window's lines.  Nothing where the program
logs no such line (a dense model, a parent)."""

from benchmark.lib import job

MARK = "moe load:"


def lines(run):
    """The fields of each ``moe load:`` line inside the window."""
    out = []
    for line in run.job.text.splitlines():
        if MARK not in line:
            continue
        at = job.stamp_seconds(line)
        if at is not None and run.times["open"] <= at <= run.times["close"]:
            out.append({k: float(v) for k, v in job.fields(
                line.split(MARK, 1)[1]).items()})
    return out


def read(run):
    seen = [f["max"] / f["mean"] for f in lines(run) if f.get("mean")]
    return sum(seen) / len(seen) if seen else None
