#!/usr/bin/env python3
"""Several runs of the benchmark in one call, for the builder's own sets.

    python3 benchmark/tools/sets.py <out-name> <cell>:<seconds>:<trace>:<seed>[,...] ...

Runs each item in order through ``benchmark/run.py`` (each a process tree
of its own), copies the run's detail, trace summary and the tail of its
log to ``chiprun_out/bench/<out-name>/`` (or to ``<out-name>`` itself if
it is an absolute path: a run from an unpacked archive writes where the
chip tool collects) and prints one line per run, with the state of the
host before it (processes, free memory, entries of the temporary
directory), to see what a creeping set-up follows.
Not part of a measurement: the driver calls ``benchmark/run.py`` itself.
"""

import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def host_state():
    with open("/proc/meminfo") as fh:
        mem = dict(line.split(":", 1) for line in fh)
    return {"processes": sum(name.isdigit() for name in os.listdir("/proc")),
            "mem_available_mb": int(mem["MemAvailable"].split()[0]) // 1024,
            "cached_mb": int(mem["Cached"].split()[0]) // 1024,
            "tmp_entries": len(os.listdir(tempfile.gettempdir()))}


def main(argv):
    out_dir = os.path.join(ROOT, "chiprun_out", "bench", argv[0])
    os.makedirs(out_dir, exist_ok=True)
    rows = []
    for index, item in enumerate(argv[1:]):
        cell, seconds, trace, seed = item.split(":")
        started, before = time.time(), host_state()
        done = subprocess.run(
            [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
             "--workload", cell, "--seconds", seconds, "--trace", trace,
             "--seed", seed], cwd=ROOT, capture_output=True, text=True)
        last = (done.stdout.strip().splitlines() or [""])[-1]
        row = {"item": item, "rc": done.returncode,
               "wall_s": round(time.time() - started, 1), "host": before}
        try:
            row["result"] = json.loads(last)
        except ValueError:
            row["stderr"] = done.stderr[-3000:]
        work = os.path.join(ROOT, ".bench_work", cell)
        tag = "%02d-%s-t%s-%s" % (index, cell, trace, seed)
        for name in ("detail.json", "trace_lines.json"):
            if os.path.exists(os.path.join(work, name)):
                shutil.copy(os.path.join(work, name),
                            os.path.join(out_dir, tag + "-" + name))
        log = os.path.join(work, "job.log")
        if os.path.exists(log):
            with open(log, errors="replace") as fh:
                lines = [l[:400] for l in fh.read().splitlines()]
            odd = [l for l in lines if "Traceback" in l or "Error" in l
                   or "bench-trace" in l or l.startswith(" ")][:60]
            with open(os.path.join(out_dir, tag + "-job.log"), "w") as fh:
                fh.write("\n".join(lines[:5] + ["... odd lines:"] + odd
                                   + ["..."] + lines[-120:]))
        trace_dir = os.path.join(work, "trace")
        if os.path.isdir(trace_dir):
            row["trace_dir"] = {
                os.path.relpath(os.path.join(d, f), trace_dir):
                os.path.getsize(os.path.join(d, f))
                for d, _, files in os.walk(trace_dir) for f in files}
            for name in ("done", "error"):
                if os.path.exists(os.path.join(trace_dir, name)):
                    shutil.copy(os.path.join(trace_dir, name),
                                os.path.join(out_dir, tag + "-" + name))
        rows.append(row)
        print("SETS " + json.dumps(row), flush=True)
        with open(os.path.join(out_dir, "rows.json"), "w") as fh:
            json.dump(rows, fh, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
