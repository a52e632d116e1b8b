#!/usr/bin/env python3
"""Time the flash-attention kernels alone, on the chip.

    python3 tools/flash_kernels_on_chip.py [--bh 128] [--t 2048] [--d 128]
        [--kv_heads N] [--window 0] [--latent] [--root DIR] [--set NAME=INT]

Prints one JSON line: ms a call of the forward and the backward kernels
(device time of each ``custom-call`` event in a profiler trace, by the
name the call carries: ``fwd`` and ``bwd``, or ``fwd``, ``dq`` and
``dkv`` for a ``--root`` from before the backward was one call) and of
the whole forward and backward on the host's clock (XLA glue around the
kernels included).  ``--kv_heads N`` gives K and V ``N`` heads for the
``--bh`` query heads (grouped-query attention: the kernels read K/V head
``head // (bh / N)`` and the backward sums dk, dv over the group; a
``--root`` from before the kernels took K/V at their own head count is
given them repeated, the call its model made).  ``--latent`` times ``latent_attention``'s calls:
scores over ``--d`` + 64, one RoPE key a sequence, values of ``--d``.
``--root DIR`` imports ``elasticdl_tpu`` from another checkout (the
parent commit unpacked beside this one), so one call measures both.
Exits 3 without a TPU: a CPU timing is no device number.
"""

import argparse
import collections
import inspect
import json
import os
import re
import sys
import tempfile
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--bh", type=int, default=128)
    ap.add_argument("--t", type=int, default=2048)
    ap.add_argument("--d", type=int, default=128)
    ap.add_argument("--kv_heads", type=int, default=0,
                    help="K/V heads for the --bh query heads (0: --bh)")
    ap.add_argument("--window", type=int, default=0)
    ap.add_argument("--latent", action="store_true")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--set", action="append", default=[],
                    metavar="NAME=INT", help="set a module constant of "
                    "ops/flash_attention.py before tracing")
    ap.add_argument("--root", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    args = ap.parse_args()
    sys.path.insert(0, args.root)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from elasticdl_tpu.ops import flash_attention as fa

    for item in args.set:
        name, value = item.split("=")
        assert hasattr(fa, name), name
        setattr(fa, name, int(value))
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("flash_kernels_on_chip: platform is %r, not tpu"
              % dev.platform, file=sys.stderr)
        return 3
    rng = np.random.RandomState(0)
    tensor = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.bfloat16)
    wide = (1, args.bh, args.t, args.d)
    g = tensor(*wide)
    if args.latent:
        rope = 64
        operands = (tensor(*wide), tensor(1, args.bh, args.t, rope),
                    tensor(*wide), tensor(1, args.t, rope), tensor(*wide))
        static = (True, (args.d + rope) ** -0.5, False, args.window)
        fwd_rule, bwd_rule = fa._latent_fwd, fa._latent_bwd
    else:
        grouped = "flash_mode" in vars(fa)    # K/V at their own count
        kv = (1, args.kv_heads or args.bh if grouped else args.bh,
              args.t, args.d)
        operands = (tensor(*wide), tensor(*kv), tensor(*kv))
        static = (True, args.d ** -0.5, False, args.window)
        if "block_q" in inspect.signature(fa._flash_fwd).parameters:
            # --root at a checkout from before PR 28: block_q, block_k
            static = static[:2] + (128, 128) + static[2:]
        fwd_rule, bwd_rule = fa._flash_fwd, fa._flash_bwd
    fwd = jax.jit(lambda *a: fwd_rule(*a, *static))
    bwd = jax.jit(lambda res, g: bwd_rule(*static, res, g))

    def host_ms(fn, *a):
        jax.block_until_ready(fn(*a))
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = fn(*a)
        jax.block_until_ready(out)
        return 1e3 * (time.perf_counter() - t0) / args.iters

    _, res = fwd(*operands)
    row = {"root": args.root, "set": args.set, "device": dev.device_kind,
           "shape": [args.bh, args.t, args.d],
           "kv_heads": operands[2].shape[1], "window": args.window,
           "latent": args.latent,
           "host_ms": {"fwd": host_ms(fwd, *operands),
                       "bwd": host_ms(bwd, res, g)}}

    # Device time of each custom call, told apart by the name it carries.
    from benchmark.lib import xplane
    with tempfile.TemporaryDirectory(prefix="flash_trace_") as trace_dir:
        with jax.profiler.trace(trace_dir):
            for _ in range(5):
                _, res = fwd(*operands)
                out = bwd(res, g)
            jax.block_until_ready(out)
        reduced = xplane.load(trace_dir)
    by_kind = collections.defaultdict(list)
    other = collections.defaultdict(float)
    for name, _, dur in next(iter(reduced["devices"].values())):
        call = (re.search(r"flash_(fwd|bwd|dq|dkv)", name.split(" = ")[0])
                if "tpu_custom_call" in name else None)
        if call:
            by_kind[call.group(1)].append(dur / 1e6)
        else:
            other[name.split(" = ")[0][:40]] += dur / 1e6 / 5
    row["kernel_ms"] = {kind: round(float(np.median(ms)), 4)
                        for kind, ms in sorted(by_kind.items())}
    row["glue_ms_per_fwd_bwd"] = {
        n: round(ms, 4) for n, ms in sorted(other.items(),
                                            key=lambda kv: -kv[1])[:8]}
    print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
