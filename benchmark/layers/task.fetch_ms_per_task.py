"""Milliseconds of one ``edl.task_fetch``: the ``get_task`` call and any
wait for a task to exist, the bubble between two tasks.  The mean over the
fetches the trace holds; each task is fetched once, and a 6 s trace holds
more whole fetches than whole tasks (an ``edl.task_process`` cut by the
trace's edge is not in it).  Fetches under 100 us are not in the source
(benchmark/lib/spans.py)."""

from benchmark.lib import spans


def read(run):
    s = spans.of_run(run)
    fetches = s.named("edl.task_fetch") if s else []
    if not fetches:
        return None
    return sum(f.end - f.start for f in fetches) / 1e6 / len(fetches)
