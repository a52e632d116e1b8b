"""Seconds from the first worker's ``worker device:`` line to its first
step: model init, compile or cache load, first step.  Warm in every run
of a checkout but the first."""


def read(run):
    if "device" not in run.times:
        return None
    return run.times["first_step"] - run.times["device"]
