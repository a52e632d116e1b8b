#!/usr/bin/env python3
"""The two readings behind ``reference/lfm2-24b-a2b.py``'s limits, on the
chip, at the configuration's widths, for a few seeds:

    python3 benchmark/tools/lfm2_precision.py [--seeds 1 2 3]

(a) the product's loss against the plain reference (what
``lib/compare.py`` decides ``correct`` by), and the routing line;
(b) the reference itself with every matmul operand outside the router
rounded to float8 (e4m3), the nearest precision below the bfloat16 the
configuration states: its relative difference has to be over TOLERANCE;
(c) a bfloat16 router against the float32 one on the reference's own
router inputs and ``expert_bias``: the share of (token, choice) pairs
that agree has to be under SAME_INPUT_ROUTING_FLOOR.  One JSON line per
seed.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

NAME = "lfm2-24b-a2b"


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
    for seed in args.seeds:
        key = jax.random.PRNGKey(seed % (2 ** 31))
        params, x, y, _ = ref.case(
            config, jax.jit(spec.init_fn)(key), np.random.default_rng(seed),
            key)
        got = float(jax.jit(lambda p: spec.loss_fn(
            spec.apply_fn(p, x, True), y).mean())(params))
        with jax.default_matmul_precision("highest"):
            want, seen = jax.jit(lambda p: (lambda l, c: (l.mean(), [
                (chosen, h, w["w_router"], w["expert_bias"])
                for chosen, h, w in c]))(*ref.loss(p, x, **shape)))(params)
            float8 = float(jax.jit(lambda p: ref.loss(
                p, x, rounded=jnp.float8_e4m3fn, **shape)[0].mean())(params))
        want = float(want)
        agree = []
        for chosen, h, w, bias in seen:
            coarse = jax.jit(lambda h, w, bias: ref.route(
                h.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                bias.astype(jnp.bfloat16), shape["top_k"])[1])(h, w, bias)
            agree.append(float((coarse & chosen).sum() / chosen.sum()))
        print(json.dumps({
            "seed": seed, "device": jax.devices()[0].device_kind,
            "product_loss": got, "reference_loss": want,
            "product_rel_diff": abs(got - want) / abs(want),
            "float8_reference_loss": float8,
            "float8_rel_diff": abs(float8 - want) / abs(want),
            "tolerance": ref.TOLERANCE,
            "bf16_router_same_input_agreement": min(agree),
            "routing_floor": ref.SAME_INPUT_ROUTING_FLOOR}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
