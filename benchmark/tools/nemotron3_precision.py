#!/usr/bin/env python3
"""The readings behind ``reference/nemotron-3-nano-30b-a3b.py``'s limits,
on the chip, at the configuration's widths, for a few seeds:

    python3 benchmark/tools/nemotron3_precision.py [--seeds 1 2 3]

(a) the product's loss against the plain reference (what
``lib/compare.py`` decides ``correct`` by), the program's router against
the reference's on the same inputs and the program's Mamba-2 mixer,
attention, shared expert, held experts (EVERY layer) and the
state-space scan on the probe that remembers against the reference's
(``layer_errors``);
(b) the reference itself with every matmul operand outside the router
rounded to float8 (e4m3), the nearest precision below the bfloat16 the
configuration states: its loss's relative difference and its layers'
distances;
(c) with the recurrent state held in bfloat16 and (d) with the chunks'
cumulative log decays in bfloat16 (every operand float32), where the
configuration states float32: the probe's distance;
each of (b)-(d) has to be past at least one limit on every seed
(``refused_by``);
(e) the quartiles over (token, head) of the decay ``exp(dt A)`` and of
``dt`` in each Mamba-2 layer at the weights as drawn, and the share of a
state that outlives a chunk of 128 tokens: whether the scan being held
is a trivial one.
One JSON line per seed, with the seconds each part took.  ``--rehearse``
runs it on the CPU at the tiny size (what it finds there says nothing of
the limits).
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

NAME = "nemotron-3-nano-30b-a3b"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", nargs="+", type=int, default=[1, 2, 3])
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import manifest
    from benchmark.lib.runner import merge, params_string
    from elasticdl_tpu.models.spec import load_model_spec

    with open(os.path.join(manifest.BENCH_DIR, "configs",
                           NAME + ".json")) as fh:
        config = json.load(fh)
    if args.rehearse:
        config = merge(config, config.get("rehearsal"))
    ref = manifest.load_named("reference", NAME)
    spec = load_model_spec(
        config["cli"]["model_zoo"],
        model_params=params_string(config["cli"]["model_params"]))
    shape = ref.shape_of(config)
    # tokens are arguments, so that every seed runs the programs the
    # first one compiled
    product = jax.jit(lambda p, x: spec.loss_fn(
        spec.apply_fn(p, x, True), x).mean())

    def total(p, x, **how):
        per_record, seen = ref.loss(p, x, **how, **shape)
        return float(per_record.mean()), seen

    limits = ref.ceilings()
    variants = {
        "float8": dict(rounded=jnp.float8_e4m3fn),
        "bf16_state": dict(state=jnp.bfloat16),
        "bf16_decays": dict(decays=jnp.bfloat16),
    }
    layers = ref.layer_errors(config)
    lowered = {name: ref.layer_errors(config, **how)
               for name, how in variants.items()}
    sizes = (shape["heads"], shape["width"], shape["d_state"],
             shape["groups"])

    @jax.jit
    def decays(h, w):
        g, dt = ref.mamba_operands(h, w, *sizes)[4:]
        size = min(ref.CHUNK, g.shape[1])
        keep = jnp.exp(g[:, :g.shape[1] // size * size].reshape(
            g.shape[0], -1, size, g.shape[2]).sum(axis=2))
        return jnp.exp(g), dt, keep

    quartiles = lambda a: [float(x) for x in np.quantile(
        np.asarray(a, np.float64).ravel(), (0.25, 0.5, 0.75))]

    for seed in args.seeds:
        took = {}
        clock = time.time()

        def lap(name):
            nonlocal clock
            took[name], clock = round(time.time() - clock, 1), time.time()

        params, x = ref.inputs(
            config, jax.jit(spec.init_fn)(jax.random.PRNGKey(
                seed % (2 ** 31))), np.random.default_rng(seed))
        got = float(product(params, x))
        lap("product")
        with jax.default_matmul_precision("highest"):
            want, seen = total(params, x)
            lap("reference")
            # the layers' inputs: on the host until a layer reads its own
            seen = jax.device_get(seen)
            rel = {"float8": abs(total(params, x, **variants["float8"])[0]
                                 - want) / abs(want)}
            lap("float8_loss")
        try:
            routing = ref.check_routing(config, params, seen)
        except SystemExit as refusal:
            routing = float(str(refusal).split(": ")[1].split(" ")[0])
        out = {
            "seed": seed, "device": jax.devices()[0].device_kind,
            "product_loss": got, "reference_loss": want,
            "product_rel_diff": abs(got - want) / abs(want),
            "product_routing": routing,
            "product_layers": layers(params, seen, True),
            "tolerance": ref.TOLERANCE, "ceilings": limits,
            "routing_floor": ref.SAME_INPUT_ROUTING_FLOOR}
        lap("product_layers")
        for name in variants:
            # float8 moves every part; the state's and the decays'
            # precisions the probes alone
            found = {"layers": lowered[name](params, seen,
                                             name == "float8")}
            refused = [part for part, error in found["layers"].items()
                       if not error <= limits[part]]
            if name in rel:
                found["rel_diff"] = rel[name]
                refused += ["loss"] * (rel[name] > ref.TOLERANCE)
            found["refused_by"] = refused
            out[name] = found
            lap(name)
        out["scan"] = []
        with jax.default_matmul_precision("highest"):
            for s, w, kind in zip(seen, ref.layers_of(params),
                                  shape["kinds"]):
                if kind == "mamba":
                    alpha, dt, keep = decays(s.h, w)
                    out["scan"].append({
                        "alpha": quartiles(alpha), "dt": quartiles(dt),
                        "chunk_keep": float(keep.mean())})
        out["seconds"] = took
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
