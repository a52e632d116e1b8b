"""BENCHMARK.json, and the files its names resolve to.

A cell, a configuration, a traffic mix and a per-layer metric are each a
file of their own plus one entry in BENCHMARK.json; so is whatever a
configuration or a traffic file names: its plain reference
(``reference/<name>.py``), its operation count (``opcounts/<name>.py``),
its kernel (``kernels/<name>.py``) and its data generator
(``generators/<name>.py``).  Nothing here knows a name.  An unknown name
is an error that lists the names found.
"""

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)


class ManifestError(Exception):
    pass


def _pick(entries, name, what):
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise ManifestError("unknown %s %r; known: %s" % (
        what, name, ", ".join(sorted(e["name"] for e in entries))))


def _load_json(path, what):
    if not os.path.isfile(path):
        raise ManifestError("%s: no file %s" % (what, path))
    with open(path) as fh:
        return json.load(fh)


def load_named(kind, name, bench_dir=BENCH_DIR):
    """The module ``<bench_dir>/<kind>/<name>.py``, found by its name."""
    folder = os.path.join(bench_dir, kind)
    path = os.path.join(folder, "%s.py" % name)
    if not os.path.isfile(path):
        known = sorted(f[:-3] for f in os.listdir(folder)
                       if f.endswith(".py")) if os.path.isdir(folder) else []
        raise ManifestError("no %s named %r (%s); known: %s"
                            % (kind, name, path, ", ".join(known)))
    spec = importlib.util.spec_from_file_location(
        "benchmark_%s_%s" % (kind, "".join(
            c if c.isalnum() else "_" for c in str(name))), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reports(metric, cell_name):
    """Whether ``metric`` (an entry of end_to_end or per_layer) is reported
    by the cell: every cell, unless the entry lists ``workloads``."""
    return "workloads" not in metric or cell_name in metric["workloads"]


class Manifest:
    def __init__(self, root, bench_dir=BENCH_DIR, files_root=None):
        """``root`` holds BENCHMARK.json; a configuration's ``file`` is
        relative to ``files_root`` (the same directory unless a test reads
        another manifest against this checkout's files)."""
        self.root = root
        self.bench_dir = bench_dir
        self.files_root = files_root or root
        self.doc = _load_json(os.path.join(root, "BENCHMARK.json"),
                              "manifest")

    def cell(self, name):
        """Everything one run of the cell needs, resolved to data."""
        workload = _pick(self.doc["workloads"], name, "workload")
        config_entry = _pick(self.doc["configs"], workload["config"],
                             "config")
        config_file = os.path.join(self.files_root, config_entry["file"])
        config = _load_json(config_file, "config %s" % workload["config"])
        traffic = _load_json(
            os.path.join(self.bench_dir, "traffic",
                         workload["traffic"] + ".json"),
            "traffic %s" % workload["traffic"])
        return {
            "name": name,
            "chips": workload["chips"],
            "config_file": config_file,
            "config": config,
            "traffic": traffic,
            "end_to_end": [m for m in self.doc["end_to_end"]
                           if reports(m, name)],
            "per_layer": [m for m in self.doc["per_layer"]
                          if reports(m, name)],
        }

    def reader(self, metric_name):
        """The ``read(run)`` function of a per-layer metric's own file."""
        return load_named("layers", metric_name, self.bench_dir).read
