"""Seconds the benchmark itself spent generating (or finding) the data."""


def read(run):
    return run.times["datagen_s"]
