#!/usr/bin/env python3
"""Time the gated delta rule's scan alone, beside its jnp twin, on the chip.

    python3 tools/gated_delta_on_chip.py [--heads 15] [--t 16384]
        [--key_dim 96] [--value_dim 192] [--chunk 64 ...]
        [--head_block 5 ...] [--pack 2 ...] [--iters 3] [--no_reference]
        [--decay scalar|vector] [--floor 0 -5 ...]

Prints one JSON line a (chunk, head block, pack: the chunks a grid step
walks behind one build of their inverses): the device time of the
forward kernel's and the backward kernel's calls (``gdn_fwd``,
``gdn_bwd``, told by name in a profiler trace) in ms a call, the least
time the chip could take for the work the recurrence needs
(``benchmark/kernels/gated_delta.py``: counted from shapes, not from the
chunk) and the share of it, and what else the jitted forward and
backward ran around the calls (the cumulative sums, the packing);
beside it, once, the device time of the jnp twin
(``ops/gated_delta.gated_delta_ref``) forward and forward + backward,
and the largest relative distance of the kernels' output and gradients
from the twin's.  The default shape is the ``olmo-hybrid-7b.seq16384``
cell's, one layer; ``--decay vector --heads 8 --key_dim 128 --value_dim
128`` is the ``solar-open2-250b.seq16384`` cell's (a log decay a channel
of the key, calls ``kda_fwd`` / ``kda_bwd``, counted by
``benchmark/kernels/kda.py``; channel 0 of every head forgets at once
and channel 1 never, beside the others' 0.3 to 0.9999 a token).
``--floor F ..`` (vector decay) promises the op that no log decay lies
under F, one line a floor on the same draw, whose channel 0 then sits AT
the lowest floor given: ``--floor 0 -5`` is the ``ling-3.0-flash.seq16384``
cell's call with today's nineteen score products a pack and with the four
its floor allows (``pairs`` in the line: ``ops/gated_delta.pairs_of``).
Exits 3 without a TPU: a CPU timing is no device
number (``--t 256 --heads 2`` there is a rehearsal: every call runs in
the interpreter, none is timed).
"""

import argparse
import itertools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--heads", type=int, default=15)
    ap.add_argument("--t", type=int, default=16384)
    ap.add_argument("--key_dim", type=int, default=96)
    ap.add_argument("--value_dim", type=int, default=192)
    ap.add_argument("--chunk", type=int, nargs="+", default=[64])
    ap.add_argument("--head_block", type=int, nargs="+", default=[0],
                    help="heads a grid step runs; 0: the op's own choice")
    ap.add_argument("--pack", type=int, nargs="+", default=[0],
                    help="chunks a grid step walks; 0: the op's own choice")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--no_reference", action="store_true")
    ap.add_argument("--decay", choices=("scalar", "vector"),
                    default="scalar")
    ap.add_argument("--floor", type=float, nargs="+", default=[0.0],
                    help="the log decay's promised floor; 0: none")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import manifest, peaks
    from elasticdl_tpu.ops import gated_delta as gd
    from tools.row_moves_on_chip import device_ms

    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    if not on_chip and args.t > 512:
        print("gated_delta_on_chip: platform is %r, not tpu" % dev.platform,
              file=sys.stderr)
        return 3
    vector = args.decay == "vector"
    name = "kda_" if vector else "gdn_"
    work = manifest.load_named(
        "kernels", "kda" if vector else "gated_delta").call
    H, T, dk, dv = args.heads, args.t, args.key_dim, args.value_dim
    rng = np.random.default_rng(0)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    bf16 = lambda x: jnp.asarray(x, jnp.bfloat16)
    q = bf16(unit(rng.standard_normal((1, H, T, dk))) * dk ** -0.5)
    k = bf16(unit(rng.standard_normal((1, H, T, dk))))
    v = bf16(rng.standard_normal((1, H, T, dv)))
    # decays from 0.3 to 0.9999 a token, write strengths in (0, 2)
    g = -np.exp(rng.uniform(np.log(1e-4), np.log(1.2),
                            (1, H, T) + (dk,) * vector))
    if vector:
        lowest = min(args.floor)
        g[..., 0], g[..., 1] = lowest or -30.0, 0.0
        g = np.maximum(g, lowest or -np.inf)
    g = jnp.asarray(g, jnp.float32)
    beta = jnp.asarray(rng.uniform(0.05, 1.95, (1, H, T)), jnp.float32)
    do = bf16(rng.standard_normal((1, H, T, dv)))
    operands = (q, k, v, g, beta)

    def both(fn):
        forward = jax.jit(fn)
        backward = jax.jit(lambda *a: jax.vjp(fn, *a)[1](do))
        return forward, backward

    far = lambda a, b: float(
        jnp.linalg.norm((a - b).astype(jnp.float32).ravel())
        / jnp.linalg.norm(b.astype(jnp.float32).ravel()))
    twin = None
    if not args.no_reference:
        forward, backward = both(gd.gated_delta_ref)
        twin = dict(out=forward(*operands), grads=backward(*operands))
        twin["fwd_ms"] = device_ms(forward, operands, 1)[1]
        twin["fwd_bwd_ms"] = device_ms(backward, operands, 1)[1]
    packs = gd.PACKS
    for chunk, block, pack, floor in itertools.product(
            args.chunk, args.head_block, args.pack, args.floor):
        if block:
            gd.HEAD_BLOCKS = (block,)
        gd.PACKS = (pack, 1) if pack else packs
        forward, backward = both(
            lambda *a: gd.gated_delta(*a, chunk=chunk, floor=floor,
                                      interpret=not on_chip))
        ops_f, all_f = device_ms(forward, operands, args.iters)
        ops_b, all_b = device_ms(backward, operands, args.iters)
        row = {"device": dev.device_kind, "heads": H, "t": T,
               "key_dim": dk, "value_dim": dv, "decay": args.decay,
               "chunk": chunk,
               "head_block": block or next(
                   n for n in gd.HEAD_BLOCKS if H % n == 0),
               "pack": gd.pack_of(T, chunk)}
        if vector:
            row.update(floor=floor, pairs=gd.pairs_of(floor))
        for kind, ops in (("fwd", ops_f), ("bwd", ops_b)):
            # under ``jax.vjp`` XLA wraps the name: transpose_jvp_..
            ms = sum(v for op, v in ops.items() if name + kind in op)
            if not ms:      # the rehearsal: the calls ran, untimed
                if on_chip:
                    print("no %s%s among %s" % (name, kind, sorted(ops)),
                          file=sys.stderr)
                continue
            flops, nbytes = work(1, H, T, dk, dv, kind)
            floor, bound = peaks.roofline_seconds(
                flops, nbytes, dev.device_kind)
            row[kind] = {"ms": round(ms, 4),
                         "least_ms": round(1e3 * floor, 4),
                         "bound": bound,
                         "roofline_pct": round(1e5 * floor / ms, 3)}
        if on_chip:
            row["around_fwd_ms"] = round(all_f - row["fwd"]["ms"], 4)
            # the backward's program runs the forward kernel too
            row["bwd_program_ms"] = round(all_b, 4)
        if twin:
            row["twin_fwd_ms"] = round(twin["fwd_ms"], 3)
            row["twin_fwd_bwd_ms"] = round(twin["fwd_bwd_ms"], 3)
            row["out_from_twin"] = far(forward(*operands), twin["out"])
            row["grads_from_twin"] = [
                far(a, b) for a, b in zip(backward(*operands),
                                          twin["grads"])]
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
