#!/usr/bin/env python3
"""Time the LM head and its loss alone on the chip, as the op
(``ops/head_loss.py``) and as the two functions the trainer ran before it
(``next_token_loss`` over ``_head``'s float32 logits, JAX's derivative),
at the benchmark's two head shapes.

    python3 tools/head_loss_on_chip.py [--shapes 4x4096:untied 8x2048:tied]
        [--dim 2048] [--vocab 50304] [--iters 5] [--ids uniform zipf]
        [--formulations parent op]

A shape may name its own widths, ``1x16384:untied:2560:37984`` (rows,
head, dim, vocabulary), so that one call times several cells' shapes.

What is timed is one trainer step of the zoo's LM at depth 0: embedding
lookup, final norm, head, loss, both gradients and AdamW, in bfloat16
compute with one example (of several) weighing zero.  The neighbours are
there because the compiler decides with them in view: it fuses the norm
into the matmuls' operands and AdamW into the weight gradient's epilogue
where it may, and the head's matmuls alone time differently (PERF.md
section 6, PR 27).  Prints one JSON line per (shape, formulation): the compiled
program's temporary bytes, ms a call of the whole program and of each
device operation (device time in a profiler trace), and the bytes of
each operation's operands and results as the compiled HLO states them (an
upper bound where a fusion reads a slice).  ``--ids zipf`` draws the
tokens as the benchmark's cells do (``benchmark/generators/
tokens_zipf_fixed_ids.py``: exponent 1.1, one id 15% of them), ``uniform``
with hardly a duplicate; the operation that adds the stream's cotangent
into the embedding table's gradient (the ``embed_grad`` kernel, or a
scatter whose result is ``[vocab, dim]``) says ``"is": "embed_grad"``
(PERF.md section 6, PR 53: its time by shape).  The model casts its
float32 parameters itself, as in a cell's job.  Exits 3 without a TPU: a
CPU timing is no device number.
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


def embed_grad_ops(text, vocab, dim):
    """The entry instructions of a compiled module's text that add rows
    into a ``[vocab, dim]`` table: the ``embed_grad`` kernel
    (``ops/embed_rows.py``), a scatter, or a fusion whose computation
    (or one it calls) holds one."""
    scatters = re.compile(
        r" = (?:f32|bf16)\[%d,%d\]\S* scatter\(" % (vocab, dim))
    holds, calls, entry = set(), collections.defaultdict(set), []
    body = in_entry = None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            in_entry, body = head.groups()
        elif line.startswith("}"):
            body = None
        elif body:
            called = re.findall(r"calls=%?([\w.\-]+)", line)
            calls[body].update(called)
            if scatters.search(line):
                holds.add(body)
            if in_entry and INSTRUCTION.match(line):
                entry.append((INSTRUCTION.match(line).group(1), called,
                              bool(scatters.search(line))))

    def reaches(name):
        return name in holds or any(map(reaches, calls[name]))

    return {name for name, called, scatter in entry
            if "embed_grad" in name or scatter or any(map(reaches, called))}


def draw_ids(ids, vocab, count):
    """``count`` token ids: ``uniform`` over the vocabulary, or ``zipf``
    as the benchmark's generator draws a cell's."""
    import numpy as np

    rng = np.random.default_rng(0)
    if ids == "uniform":
        return rng.integers(0, vocab, count)
    from benchmark.generators import tokens_zipf_fixed_ids as zipf

    p = np.arange(1, vocab + 1, dtype=np.float64) ** -1.1
    drawn = np.minimum(np.searchsorted(np.cumsum(p / p.sum()),
                                       rng.random(count)), vocab - 1)
    return zipf._rng(zipf.IDS, vocab).permutation(vocab)[drawn]


def parent_loss(spec):
    """``next_token_loss(_head(...))``, as ``model_spec``'s loss was
    before the op."""
    from elasticdl_tpu.models import transformer as tfm

    def loss_fn(outputs, tokens):
        logits = tfm._head(outputs["params"], outputs["hidden"], spec.config)
        return tfm.next_token_loss(logits, tokens)

    return loss_fn


FORMULATIONS = {"parent": parent_loss, "op": lambda spec: spec.loss_fn}


def measure(formulation, b, t, tied, dim, vocab, iters, ids="uniform"):
    """One trainer step (``CollectiveTrainer._loss_and_grads`` and the
    optimizer's update, bfloat16 compute) of the zoo's LM at depth 0."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from benchmark.lib import xplane
    from elasticdl_tpu.models import transformer as tfm
    from elasticdl_tpu.worker.collective_trainer import _masked_mean

    # the zoo's spec for its configuration, loss and optimizer; depth 0
    # is made here (a stack has no kind of layer without a layer): the
    # zoo's lookup straight into its loss
    spec = tfm.model_spec(vocab_size=vocab, dim=dim, num_heads=dim // 128,
                          num_layers=1, seq_len=t, tied_embeddings=tied)
    loss_fn = FORMULATIONS[formulation](spec)

    def apply_fn(p, tokens):
        return {"params": p, "hidden": tfm._embed(p, tokens, spec.config)}

    tx = spec.optimizer
    tokens = jnp.asarray(draw_ids(ids, vocab, b * t).reshape(b, t),
                         jnp.int32)
    weights = jnp.asarray([1.0] * max(b - 1, 1) + [0.0] * (b > 1),
                          jnp.float32)

    def step(params, opt_state):
        def mean_loss(p):
            per_example = loss_fn(apply_fn(p, tokens), tokens)
            return _masked_mean(per_example.astype(jnp.float32), weights)

        loss, grads = jax.value_and_grad(mean_loss)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    params = jax.jit(spec.init_fn)(jax.random.PRNGKey(0))
    del params["layers"]
    # unit-scale activations, as a trained stack's are
    params["embed"] = params["embed"] * 50.0
    opt_state = tx.init(params)
    step = jax.jit(step, donate_argnums=(0, 1))
    compiled = step.lower(params, opt_state).compile()
    sizes = hlo_bytes(compiled.as_text())
    embed_grad = embed_grad_ops(compiled.as_text(), vocab, dim)
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
        "ops": [dict({"op": op, "ms": round(ms, 3), "bytes": sizes.get(op)},
                     **({"is": "embed_grad"} if op in embed_grad else {}))
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
    ap.add_argument("--ids", nargs="+", choices=("uniform", "zipf"),
                    default=["uniform"])
    ap.add_argument("--formulations", nargs="+", choices=list(FORMULATIONS),
                    default=list(FORMULATIONS))
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
        rows, kind, *widths = shape.split(":")
        b, t = map(int, rows.split("x"))
        dim, vocab = map(int, widths) if widths else (args.dim, args.vocab)
        for ids in args.ids:
            for name in args.formulations:
                row = {
                    "device": dev.device_kind, "shape": shape, "dim": dim,
                    "vocab": vocab, "ids": ids, "formulation": name,
                    "mxu_least_ms_a_matmul": round(
                        1e3 * 2 * b * t * dim * vocab / peak["bf16_flops"],
                        3),
                }
                row.update(measure(name, b, t, kind == "tied", dim, vocab,
                                   args.iters, ids))
                print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
