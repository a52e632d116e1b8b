"""GB a training step moves beyond what the K/V heads hold because the
program repeats K and V to the query heads before its attention kernels
(grouped-query attention through kernels that take as many K/V heads as
query heads): the worker's ``attention block: ... layers= kv_repeat_bytes=
kv_repeat_again_bytes=`` line, a layer's K, V and their two gradients at
``heads - kv_heads`` heads each, plus K and V once more where a
rematerialized layer's backward makes the repeat again, times the
attention layers.  A count from shapes, stated by the program, not a
measurement: it is what a kernel that reads K/V head ``head // group``
takes to 0.  Nothing where the program logs no such line (a parent)."""

from benchmark.lib import job

MARK = "attention block:"


def read(run):
    said = [job.fields(line.split(MARK, 1)[1])
            for line in run.job.text.splitlines() if MARK in line]
    said = [f for f in said if "kv_repeat_bytes" in f and "layers" in f]
    if not said:
        return None
    # one line a compiled shape: the training step's is the largest
    return max(int(f["layers"]) * (int(f["kv_repeat_bytes"]) + int(
        f.get("kv_repeat_again_bytes", 0))) for f in said) / 1e9
