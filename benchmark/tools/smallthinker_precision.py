#!/usr/bin/env python3
"""The two readings behind ``reference/smallthinker-21b-a3b.py``'s
limits, on the chip, at the configuration's widths, for a few seeds:

    python3 benchmark/tools/smallthinker_precision.py [--seeds 1 2 3]

(a) the product's loss against the plain reference (what
``lib/compare.py`` decides ``correct`` by), and the routing line;
(b) the reference itself with every matmul operand outside the router
rounded to float8 (e4m3), the nearest precision below the bfloat16 the
configuration states: its relative difference has to be over TOLERANCE;
(c) a bfloat16 router against the float32 one on the reference's own
router inputs: the share of (token, choice) pairs that agree has to be
under SAME_INPUT_ROUTING_FLOOR.  One JSON line per seed.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

NAME = "smallthinker-21b-a3b"


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
    exact = jax.jit(lambda p, x: (lambda l, c: (l.mean(), c))(
        *ref.loss(p, x, **shape)))
    float8 = jax.jit(lambda p, x: ref.loss(
        p, x, rounded=jnp.float8_e4m3fn, **shape)[0].mean())
    coarse = jax.jit(lambda h, w: ref.route(
        h.astype(jnp.bfloat16), w.astype(jnp.bfloat16), shape["top_k"])[1])
    for seed in args.seeds:
        params, x = ref.inputs(
            config, jax.jit(spec.init_fn)(jax.random.PRNGKey(
                seed % (2 ** 31))), np.random.default_rng(seed))
        got = float(product(params, x))
        with jax.default_matmul_precision("highest"):
            want, seen = exact(params, x)
            lower = float(float8(params, x))
            ref.check_routing(config, seen, shape["top_k"])
        want = float(want)
        # a router computed in bfloat16 on the reference's router inputs
        agree = [float((coarse(h, w) & chosen).sum() / chosen.sum())
                 for chosen, h, w in seen]
        print(json.dumps({
            "seed": seed, "device": jax.devices()[0].device_kind,
            "product_loss": got, "reference_loss": want,
            "product_rel_diff": abs(got - want) / abs(want),
            "float8_reference_loss": lower,
            "float8_rel_diff": abs(lower - want) / abs(want),
            "tolerance": ref.TOLERANCE,
            "bf16_router_same_input_agreement": min(agree),
            "routing_floor": ref.SAME_INPUT_ROUTING_FLOOR}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
