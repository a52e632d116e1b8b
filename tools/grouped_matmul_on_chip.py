#!/usr/bin/env python3
"""Time the grouped-matmul kernels alone, and the dispatch around them,
on the chip.

    python3 tools/grouped_matmul_on_chip.py [--rows 131072] [--hidden 2048]
        [--width 1024] [--groups 64] [--top_k 8] [--sizes balanced zipf]
        [--row_tile 512 ...] [--reference]

Prints one JSON line per (group sizes, row tile): ms a call of each
kernel (device time of its ``custom-call`` events in a profiler trace,
told apart by name as ``benchmark/kernels/grouped_matmul.py`` does) at
the up-projection's shape ([rows, hidden] x [groups, hidden, width]) and
the down-projection's, the least the MXU allows, the rows computed beyond
the real ones, and the device time of everything else in one forward +
backward of the model's dispatch (sort, gathers, the combine).
``--reference`` adds ``lax.ragged_dot`` in the kernels' place.  Exits 3
without a TPU: a CPU timing is no device number.
"""

import argparse
import collections
import json
import os
import re
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def group_sizes(kind, rows, groups, seed=0):
    """``balanced``: equal; ``zipf``: shares ~ rank ** -1.1, shuffled."""
    import numpy as np

    if kind == "balanced":
        share = np.full(groups, 1.0 / groups)
    else:
        share = np.arange(1, groups + 1, dtype=np.float64) ** -1.1
        share = np.random.default_rng(seed).permutation(share / share.sum())
    sizes = np.floor(share * rows).astype(np.int64)
    sizes[np.argmax(sizes)] += rows - sizes.sum()
    return sizes.astype(np.int32)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=131072)
    ap.add_argument("--hidden", type=int, default=2048)
    ap.add_argument("--width", type=int, default=1024)
    ap.add_argument("--groups", type=int, default=64)
    ap.add_argument("--top_k", type=int, default=8)
    ap.add_argument("--sizes", nargs="+", default=["balanced", "zipf"])
    ap.add_argument("--row_tile", nargs="+", type=int, default=[0])
    ap.add_argument("--reference", action="store_true")
    ap.add_argument("--iters", type=int, default=3)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import peaks, xplane
    from elasticdl_tpu.ops import grouped_matmul as gm
    from elasticdl_tpu.ops.mode import kernels_off
    from elasticdl_tpu.ops.moe_dispatch import moe_experts

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("grouped_matmul_on_chip: platform is %r, not tpu"
              % dev.platform, file=sys.stderr)
        return 3
    peak = peaks.peaks_of(dev.device_kind)["bf16_flops"]
    rng = np.random.default_rng(0)
    m, e, f, x, k = (args.rows, args.hidden, args.width, args.groups,
                     args.top_k)
    bf16 = lambda *shape: jnp.asarray(
        rng.standard_normal(shape, np.float32) * 0.05, jnp.bfloat16)
    w_gate, w_up, w_down = bf16(x, e, f), bf16(x, e, f), bf16(x, f, e)
    h = bf16(1, m // k, e)
    cot = bf16(1, m // k, e)
    gates = jnp.full((1, m // k, k), 1.0 / k, jnp.float32)

    for kind in args.sizes:
        sizes = group_sizes(kind, m, x)
        # experts [1, n, k]: the assignments that give these group sizes
        experts = jnp.asarray(np.random.default_rng(1).permutation(
            np.repeat(np.arange(x), sizes)).reshape(1, m // k, k), jnp.int32)
        for tile in args.row_tile:
            gm.ROW_TILE = tile or gm.ROW_TILE
            for kernel in ["tpu"] + ["ref"] * args.reference:
                def loss(h, wg, wu, wd):
                    with kernels_off(kernel != "tpu"):
                        out, _ = moe_experts(h, gates, experts, wg, wu, wd)
                    return (out.astype(jnp.float32)
                            * cot.astype(jnp.float32)).sum()

                step = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))
                jax.block_until_ready(step(h, w_gate, w_up, w_down))
                with tempfile.TemporaryDirectory(prefix="gmm_") as trace:
                    with jax.profiler.trace(trace):
                        for _ in range(args.iters):
                            out = step(h, w_gate, w_up, w_down)
                        jax.block_until_ready(out)
                    reduced = xplane.load(trace)
                calls = collections.defaultdict(list)
                other = collections.defaultdict(float)
                events = next(iter(reduced["devices"].values()))
                for name, _, dur in events:
                    head = name.split(" = ")[0].lstrip("%")
                    shape = name.split(" = ")[-1].split(" ")[0]
                    kernel_name = re.search(r"gmm_(nn|nt|tn)", head)
                    if kernel_name:
                        calls["%s %s" % (kernel_name.group(0), shape)
                              ].append(dur / 1e6)
                    elif not head.startswith(("jit_", "while", "cond")):
                        other[head[:48]] += dur / 1e6 / args.iters
                row = {
                    "device": dev.device_kind, "sizes": kind,
                    "kernel": kernel, "row_tile": gm.row_tile(m),
                    "rows": m, "max_group": int(sizes.max()),
                    "padded_rows": int(gm.padded_rows(
                        jnp.asarray(sizes), m)),
                    "mxu_least_ms": 1e3 * 2 * m * e * f / peak,
                    "kernel_ms": {n: round(float(np.median(ms)), 4)
                                  for n, ms in sorted(calls.items())},
                    "kernel_calls": {n: len(ms) // args.iters
                                     for n, ms in sorted(calls.items())},
                    "other_ms_total": round(sum(other.values()), 3),
                    "other_ms_top": {n: round(ms, 3) for n, ms in sorted(
                        other.items(), key=lambda kv: -kv[1])[:10]},
                }
                print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
