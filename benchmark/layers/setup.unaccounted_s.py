"""Seconds of set-up that neither process accounts for: the harness's
stamp of the first task completion less ``master_start``, less the
master's ``total_s``, less the first worker's ``total_s``.  The two
processes' spawns, the harness's poll, and whatever no phase covers."""

from benchmark.lib import setup_line


def read(run):
    master, worker = setup_line.master(run), setup_line.worker(run)
    if master is None or worker is None or not run.job.completions:
        return None
    return (run.job.completions[0] - run.times["master_start"]
            - master["total_s"] - worker["total_s"])
