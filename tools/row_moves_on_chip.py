#!/usr/bin/env python3
"""Time the row kernel alone, beside the jnp row moves, on the chip.

    python3 tools/row_moves_on_chip.py [--shape NAME ...] [--live 0.5]
        [--iters 5]

Prints one JSON line a shape: for each of a share's four row moves
(``gather``: tokens to rows; ``sum``: rows to tokens, weighted, float32
out; ``gather_back``: the sum's pullback, a weighted float32 gather with
the row dots; ``sum_back``: the gather's pullback) the device time of
the row kernel's calls (``rows_pack`` + ``rows_gather`` | ``rows_sum``,
told by name in a profiler trace), in ms a call, in ns a live row and as
the share of 819 GB/s the bytes the move needs (a live row read and
written once) come to; beside it the device time of the jnp reference
(``ops/moe_dispatch.tokens_to_rows`` / ``rows_to_tokens`` and their
pullbacks, every XLA op of the jitted move), and ``held_positions`` (the
sort's inverse over the held experts) beside a second ``argsort``.

Shapes: ``lfm2`` 32,768 tokens x 2,048, 4 a token, a bound of 32,768
rows; ``smallthinker`` 16,384 x 2,560, 6 a token, 49,152; ``olmoe``
16,384 x 2,048, 8 a token, all 131,072 rows live (what the all-experts
dispatch would hand the kernel); ``nemotron3`` (by name alone) 16,384 x
2,688, 6 a token, 12,288: a 16-bit row of 21 lane tiles, moved as 1,408
words; ``solar`` 16,384 x 4,096, 8 a token, 6,656 (8 of 320 experts
held) and ``ling`` 16,384 x 2,560, 8 a token, 4,096 (8 of 512): the two
thinnest shares.  ``sum_terms`` / ``sum_slots`` on a line: the slots
the sums' vector phase walks of the tokens x K it has
(``row_moves.sum_terms``).  Exits 3 without a TPU: a CPU timing is no
device number.
"""

import argparse
import collections
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# name: (tokens, width, choices a token, rows of the buffer, experts,
# held): the two share cells' dispatch and the all-experts one's
SHAPES = {
    "lfm2": (32768, 2048, 4, 32768, 64, 8),
    "smallthinker": (16384, 2560, 6, 49152, 64, 16),
    "olmoe": (16384, 2048, 8, 131072, 64, 64),
    "nemotron3": (16384, 2688, 6, 12288, 128, 8),
    "solar": (16384, 4096, 8, 6656, 320, 8),
    "ling": (16384, 2560, 8, 4096, 512, 8),
    # a rehearsal's: the one shape a run without a TPU may take
    "tiny": (256, 256, 4, 512, 8, 2),
}


def device_ms(fn, args, iters):
    """{op name: ms a call} of every device op of ``iters`` calls of the
    jitted ``fn``, and the calls' whole device time in ms a call."""
    import jax

    from benchmark.lib import xplane

    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory(prefix="rows_") as trace:
        with jax.profiler.trace(trace):
            for _ in range(iters):
                out = fn(*args)
            jax.block_until_ready(out)
        reduced = xplane.load(trace)
    # a rehearsal without a TPU has no device plane, and no time
    events = next(iter(reduced["devices"].values()), [])
    by = collections.Counter()
    for name, ns in xplane.self_times(events):
        head = name.split(" = ")[0].lstrip("%")
        if not head.startswith(("jit_", "while", "cond")):
            by[head.rstrip("0123456789.")] += ns / 1e6 / iters
    return dict(by), sum(by.values())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", nargs="+", default=list(SHAPES)[:3],
                    choices=list(SHAPES))
    ap.add_argument("--live", type=float, default=0.5,
                    help="share of a share's buffer rows that are held")
    ap.add_argument("--iters", type=int, default=5)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import peaks
    from elasticdl_tpu.ops import moe_dispatch as md
    from elasticdl_tpu.ops import row_moves

    dev = jax.devices()[0]
    if dev.platform != "tpu" and args.shape != ["tiny"]:
        print("row_moves_on_chip: platform is %r, not tpu" % dev.platform,
              file=sys.stderr)
        return 3
    # a rehearsal (``tiny`` without a TPU) runs every call and times none
    bandwidth = dev.platform == "tpu" and peaks.peaks_of(
        dev.device_kind)["hbm_bytes_per_s"]
    for name in args.shape:
        n, w, k, bound, total, held = SHAPES[name]
        rng = np.random.default_rng(0)
        live = bound if held == total else int(args.live * bound)
        # the (token, choice) pairs of the live rows: each token's
        # choices distinct, the rows sorted as the dispatch sorts them
        pairs = np.sort(rng.permutation(n * k)[:live])
        claims = np.full(bound, n * k, np.int64)
        claims[:live] = rng.permutation(pairs)   # expert order is random
        pos = np.full(n * k, -1, np.int64)
        pos[claims[:live]] = np.arange(live)
        tok = jnp.asarray(np.where(np.arange(bound) < live, claims // k, n),
                          jnp.int32)
        pos = jnp.asarray(pos.reshape(n, k), jnp.int32)
        claims = jnp.asarray(claims, jnp.int32)
        bf16 = lambda *shape: jnp.asarray(
            rng.standard_normal(shape, np.float32), jnp.bfloat16)
        x, y, g_rows = bf16(n, w), bf16(bound, w), bf16(bound, w)
        g = jnp.asarray(rng.standard_normal((n, w), np.float32))
        gates = jnp.asarray(rng.random((n, k), np.float32))
        scale = gates.reshape(-1).at[claims].get(mode="fill", fill_value=0)
        count = jnp.int32(live)

        # move: (the kernel's call, the jnp move, their operands, the
        # bytes a live row needs: read once, written once)
        moves = {
            "gather": (
                lambda x, tok: row_moves.row_sum(x, tok[:, None])[0],
                lambda x, tok: md.tokens_to_rows(n, x, tok),
                (x, tok), 2 * 2 * w),
            "sum": (
                lambda y, pos, gates, tok, scale: row_moves.row_sum(
                    y, pos, gates, live=count, out_dtype=jnp.float32)[0],
                lambda y, pos, gates, tok, scale: md.rows_to_tokens(
                    n, y, tok, scale),
                (y, pos, gates, tok, scale), 2 * w),
            "gather_back": (
                lambda g, tok, scale, y: row_moves.row_sum(
                    g, tok[:, None], scale[:, None], other=y,
                    out_dtype=y.dtype),
                lambda g, tok, scale, y: md._rows_to_tokens_bwd(
                    n, (y, tok, scale), g),
                (g, tok, scale, y), (4 + 2 + 2) * w),
            "sum_back": (
                lambda g_rows, pos, tok: row_moves.row_sum(
                    g_rows, pos, live=count)[0],
                lambda g_rows, pos, tok: md._tokens_to_rows_bwd(
                    n, tok, g_rows)[0],
                (g_rows, pos, tok), 2 * w),
        }
        row = {"device": dev.device_kind, "shape": name, "tokens": n,
               "width": w, "top_k": k, "rows": bound, "live_rows": live}
        if hasattr(row_moves, "sum_terms"):    # a tree before PR 69: all
            terms, slots = map(int, row_moves.sum_terms(pos, bound))
            row.update(sum_terms=terms, sum_slots=slots,
                       sum_term_share=round(terms / slots, 4))
        for move, (kernel, reference, operands, row_bytes) in moves.items():
            ops, _ = device_ms(jax.jit(kernel), operands, args.iters)
            mine = {op: ms for op, ms in ops.items()
                    if op.startswith("rows_")}
            ms = sum(mine.values())
            _, ref_ms = device_ms(jax.jit(reference), operands, args.iters)
            if not ms:      # the rehearsal: the calls ran, untimed
                row[move] = None
                continue
            row[move] = {
                "kernel_ms": round(ms, 4),
                "calls_ms": {op: round(v, 4) for op, v in mine.items()},
                "around_ms": round(sum(ops.values()) - ms, 4),
                "ns_a_live_row": round(1e6 * ms / live, 2),
                "hbm_share": round(
                    live * row_bytes / (ms / 1e3) / bandwidth, 4),
                "reference_ms": round(ref_ms, 4),
                "reference_ns_a_row": round(1e6 * ref_ms / bound, 2),
            }
        if held != total:
            flat = jnp.asarray(rng.integers(0, total, n * k), jnp.int32)
            sizes = jnp.bincount(flat, length=total)[:held].astype(
                jnp.int32)
            row["held_positions_ms"] = round(device_ms(
                jax.jit(md.held_positions), (flat, sizes),
                args.iters)[1], 4)
            order = jnp.argsort(flat, stable=True).astype(jnp.int32)
            row["second_argsort_ms"] = round(device_ms(
                jax.jit(jnp.argsort), (order,), args.iters)[1], 4)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
