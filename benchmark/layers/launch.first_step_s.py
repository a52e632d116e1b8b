"""Seconds from the master's launch to the first training step the master
shows (entry + launch, compile or cache load, first step)."""


def read(run):
    return run.times["first_step"] - run.times["master_start"]
