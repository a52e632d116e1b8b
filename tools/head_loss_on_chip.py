#!/usr/bin/env python3
"""Time the LM head and its loss alone on the chip, as the op
(``ops/head_loss.py``) and as the two functions the trainer ran before it
(``next_token_loss`` over ``_head``'s float32 logits, JAX's derivative),
at the benchmark's two head shapes.

    python3 tools/head_loss_on_chip.py [--shapes 4x4096:untied 8x2048:tied]
        [--dim 2048] [--vocab 50304] [--iters 5]

What is timed is one trainer step of the zoo's LM at depth 0: embedding
lookup, final norm, head, loss, both gradients and AdamW, in bfloat16
compute with one example weighing zero.  The neighbours are there because
the compiler decides with them in view: it fuses the norm into the
matmuls' operands and AdamW into the weight gradient's epilogue where it
may, and the head's matmuls alone time differently (PERF.md section 6,
PR 27).  Prints one JSON line per (shape, formulation): the compiled
program's temporary bytes, ms a call of the whole program and of each
device operation (device time in a profiler trace), and the bytes of
each operation's operands and results as the compiled HLO states them (an
upper bound where a fusion reads a slice).  Exits 3 without a TPU: a CPU
timing is no device number.
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

ITEMSIZE = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
            "u8": 1, "pred": 1, "s64": 8, "f64": 8}
ARRAY = re.compile(r"\b(%s)\[([\d,]*)\]" % "|".join(ITEMSIZE))
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*?) ([\w-]+)\((.*)$")


def entry_results(text):
    """[(instruction, op, operand names, [(dtype, bytes) of each
    result])] over the entry computation of a compiled module's text:
    the arrays that are buffers, where a value inside a fused
    computation lives in registers."""
    entry = re.search(r"^ENTRY [^\n]*\{\n(.*?)^\}", text, re.S | re.M)
    out, by_name = [], {}
    for line in entry.group(1).splitlines():
        m = INSTRUCTION.match(line)
        if not m:
            continue
        name, shape, op, rest = m.groups()
        results = []
        for dtype, dims in ARRAY.findall(shape):
            n = ITEMSIZE[dtype]
            for d in filter(None, dims.split(",")):
                n *= int(d)
            results.append((dtype, n))
        operands = re.findall(r"%([\w.-]+)", rest.split("), ")[0])
        if op == "get-tuple-element":
            index = int(re.search(r"index=(\d+)", rest).group(1))
            results = [by_name[operands[0]][index]]
        by_name[name] = results
        out.append((name, op, operands, results))
    return out


def hlo_bytes(text):
    """{instruction: bytes of its results and its operands}."""
    entries = entry_results(text)
    size = {name: sum(n for _, n in results)
            for name, _, _, results in entries}
    return {name: size[name] + sum(size.get(o, 0) for o in operands)
            for name, _, operands, _ in entries}


def parent_loss(spec):
    """``next_token_loss(_head(...))``, as ``model_spec``'s loss was
    before the op."""
    from elasticdl_tpu.models import transformer as tfm

    def loss_fn(outputs, tokens):
        logits = tfm._head(outputs["params"], outputs["hidden"], spec.config)
        return tfm.next_token_loss(logits, tokens)

    return loss_fn


FORMULATIONS = {"parent": parent_loss, "op": lambda spec: spec.loss_fn}


def measure(formulation, b, t, tied, dim, vocab, iters):
    """One trainer step (``CollectiveTrainer._loss_and_grads`` and the
    optimizer's update, bfloat16 compute) of the zoo's LM at depth 0."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from benchmark.lib import xplane
    from elasticdl_tpu.models import transformer as tfm
    from elasticdl_tpu.worker.collective_trainer import _masked_mean

    spec = tfm.model_spec(vocab_size=vocab, dim=dim, num_heads=dim // 128,
                          num_layers=0, seq_len=t, tied_embeddings=tied)
    loss_fn = FORMULATIONS[formulation](spec)
    tx = spec.optimizer
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, vocab, (b, t)), jnp.int32)
    weights = jnp.asarray([1.0] * (b - 1) + [0.0], jnp.float32)

    def step(params, opt_state):
        def mean_loss(p):
            p = jax.tree_util.tree_map(
                lambda a: a.astype(jnp.bfloat16), p)
            per_example = loss_fn(spec.apply_fn(p, tokens, True), tokens)
            return _masked_mean(per_example.astype(jnp.float32), weights)

        loss, grads = jax.value_and_grad(mean_loss)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    params = jax.jit(spec.init_fn)(jax.random.PRNGKey(0))
    # unit-scale activations, as a trained stack's are
    params["embed"] = params["embed"] * 50.0
    opt_state = tx.init(params)
    step = jax.jit(step, donate_argnums=(0, 1))
    compiled = step.lower(params, opt_state).compile()
    sizes = hlo_bytes(compiled.as_text())
    params, opt_state, loss = step(params, opt_state)
    first = float(loss)
    with tempfile.TemporaryDirectory(prefix="head_") as trace:
        with jax.profiler.trace(trace):
            for _ in range(iters):
                params, opt_state, loss = step(params, opt_state)
            jax.block_until_ready(loss)
        reduced = xplane.load(trace)
    ops = collections.defaultdict(float)
    for name, _, dur in next(iter(reduced["devices"].values())):
        ops[name.split(" = ")[0].lstrip("%")] += dur / 1e6 / iters
    modules = next(iter(reduced["modules"].values()))
    return {
        "loss": first,
        "temp_bytes": compiled.memory_analysis().temp_size_in_bytes,
        "program_ms": round(sum(d for _, _, d in modules) / 1e6 / iters, 3),
        "ops": [{"op": op, "ms": round(ms, 3), "bytes": sizes.get(op)}
                for op, ms in sorted(ops.items(), key=lambda kv: -kv[1])
                if ms >= 0.02],
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="+",
                    default=["4x4096:untied", "8x2048:tied"])
    ap.add_argument("--dim", type=int, default=2048)
    ap.add_argument("--vocab", type=int, default=50304)
    ap.add_argument("--iters", type=int, default=5)
    args = ap.parse_args()

    import jax

    from benchmark.lib import peaks

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("head_loss_on_chip: platform is %r, not tpu" % dev.platform,
              file=sys.stderr)
        return 3
    peak = peaks.peaks_of(dev.device_kind)
    for shape in args.shapes:
        rows, kind = shape.split(":")
        b, t = map(int, rows.split("x"))
        for name in FORMULATIONS:
            row = {
                "device": dev.device_kind, "shape": shape,
                "formulation": name,
                "mxu_least_ms_a_matmul": round(
                    1e3 * 2 * b * t * args.dim * args.vocab
                    / peak["bf16_flops"], 3),
            }
            row.update(measure(name, b, t, kind == "tied", args.dim,
                               args.vocab, args.iters))
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
