"""How far ``models/remat_keep.py``'s estimate of the step's peak lies
over what the fullest chip held, in GB (1e9): the ``predicted_peak=`` of
the job's last ``remat keep:`` line (the list the step that ran was
built with: a step rebuilt with nothing kept logs a later line) less
``run.memory_peak_bytes()``.  Over is room the list left unused; under
is a list chosen against room that was not there.  Nothing where the
job logs no such line (``remat=false``) or the workers stated no
peak."""

import re

_PEAK = re.compile(r"remat keep: .*\bpredicted_peak=(\d+)")


def read(run):
    said = _PEAK.findall(run.job.text)
    peak = run.memory_peak_bytes()
    if not said or not peak:
        return None
    return (int(said[-1]) - peak) / 1e9
