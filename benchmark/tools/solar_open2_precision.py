#!/usr/bin/env python3
"""The readings behind ``reference/solar-open2-250b.py``'s limits, on
the chip, at the configuration's widths, for a few seeds:

    python3 benchmark/tools/solar_open2_precision.py [--seeds 1 2 3]
        [--attribute]

(a) the product's loss against the plain reference (what
``lib/compare.py`` decides ``correct`` by) and the program's KDA mixer,
gated attention, shared expert and held experts against the
reference's on the same inputs (``layer_errors``);
(b) the reference itself with every matmul operand rounded to float8
(e4m3), the nearest precision below the bfloat16 the configuration
states: its loss's relative difference and its layers' distances, one
of which has to be past its limit on every seed;
(c) the reference with the delta rule's state alone held in bfloat16
(every operand float32), where the configuration states float32: the
same two readings;
(d) the reference with a SCALAR decay in the vector's place (a head's
mean log decay on every channel: a dropped decay channel,
``without=("channels",)``): what the program would read had it run the
scalar kernel, which the layer ceiling has to refuse;
(e) the quartiles of ``alpha`` a channel, ``beta`` and the norm of the
state after the last token in each linear layer at the weights as drawn
(``scan_statistics``): whether the scan being held is a trivial one;
(f) with ``--attribute``, where the product's distance comes from: the
reference with its matmul operands rounded to bfloat16 in every part
and in each part alone (``PARTS``).  One JSON line per seed.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

NAME = "solar-open2-250b"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", nargs="+", type=int, default=[1, 2, 3])
    ap.add_argument("--attribute", action="store_true")
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
    exact = jax.jit(lambda p, x: (lambda l, c: (l.mean(), c))(
        *ref.loss(p, x, **shape)))
    lower = lambda **how: jax.jit(lambda p, x: ref.loss(
        p, x, **how, **shape)[0].mean())
    float8 = lower(rounded=jnp.float8_e4m3fn)
    coarse_state = lower(state=jnp.bfloat16)
    one_decay = lower(without=("channels",))
    bfloat16 = {"all": lower(rounded=jnp.bfloat16), **{
        part: lower(rounded=jnp.bfloat16, parts=(part,))
        for part in ref.PARTS}} if args.attribute else {}
    layers = ref.layer_errors(config)
    float8_layers = ref.layer_errors(config, rounded=jnp.float8_e4m3fn)
    state_layers = ref.layer_errors(config, state=jnp.bfloat16)
    scalar_layers = ref.layer_errors(config, without=("channels",))
    for seed in args.seeds:
        params, x = ref.inputs(
            config, jax.jit(spec.init_fn)(jax.random.PRNGKey(
                seed % (2 ** 31))), np.random.default_rng(seed))
        got = float(product(params, x))
        with jax.default_matmul_precision("highest"):
            want, seen = exact(params, x)
            want = float(want)
            # 2.2 GB of layer inputs: on the host until a layer reads
            # its own (the lowered-precision losses need the room)
            seen = jax.device_get(seen)
            rel = lambda fn: abs(float(fn(params, x)) - want) / abs(want)
            in_float8, in_bf16_state = rel(float8), rel(coarse_state)
            scalar = rel(one_decay)
            attributed = {part: rel(fn) for part, fn in bfloat16.items()}
        print(json.dumps({
            "seed": seed, "device": jax.devices()[0].device_kind,
            "product_loss": got, "reference_loss": want,
            "product_rel_diff": abs(got - want) / abs(want),
            "product_layers": layers(params, seen),
            "float8_rel_diff": in_float8,
            "float8_layers": float8_layers(params, seen),
            "bf16_state_rel_diff": in_bf16_state,
            "bf16_state_layers": state_layers(params, seen),
            "scalar_decay_rel_diff": scalar,
            "scalar_decay_layers": scalar_layers(params, seen),
            "tolerance": ref.TOLERANCE,
            "layer_ceiling": ref.SAME_INPUT_LAYER_CEILING,
            "scan": ref.scan_statistics(config, params, seen),
            **({"bfloat16_rel_diff": attributed} if attributed else {})}),
            flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
