#!/usr/bin/env python3
"""The reference comparison (``lib/compare.py``) of each configuration
on an EMPTY compile cache, timed as the runner spawns it: what
``runner.COMPARE_CAP_S`` is set from (PERF.md, section 7 (29)).

    python3 benchmark/tools/cold_compare.py [--warm] [--seed N]
                                            [--out DIR] [config ...]

Each configuration's comparison runs in a process of its own
(``runner.spawn_compare``) with ``JAX_COMPILATION_CACHE_DIR`` at a
directory inside the checkout that is emptied first, the driver's
condition for a checkout's first traced run; ``--warm`` runs it once
more on what the first left there.  One line a run on stdout, and
``<out>/cold_compare.json``.  This process never imports JAX.
"""

import argparse
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import manifest, runner  # noqa: E402


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("configs", nargs="*")
    parser.add_argument("--seed", type=int, default=3411220957)
    parser.add_argument("--warm", action="store_true")
    parser.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "cold_compare"))
    args = parser.parse_args()
    book = manifest.Manifest(ROOT)
    files = {c["name"]: c["file"] for c in book.doc["configs"]}
    cache = os.path.join(ROOT, ".bench_work", "cold_compare_cache")
    os.makedirs(args.out, exist_ok=True)
    rows = []
    for name in args.configs or sorted(files):
        shutil.rmtree(cache, ignore_errors=True)
        for state in ("cold", "warm")[:1 + args.warm]:
            row = {"config": name, "cache": state, "seed": args.seed}
            try:
                line, row["seconds"] = runner.spawn_compare(
                    ROOT, os.path.join(ROOT, files[name]), args.seed, cache)
                row.update(phases=line["seconds"], ok=line["ok"],
                           rel_diff=line["rel_diff"])
            except runner.RunFailed as e:
                row["failed"] = str(e)
            rows.append(row)
            print(json.dumps(row), flush=True)
            with open(os.path.join(args.out, "cold_compare.json"), "w") as fh:
                json.dump(rows, fh, indent=1)
    shutil.rmtree(cache, ignore_errors=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
