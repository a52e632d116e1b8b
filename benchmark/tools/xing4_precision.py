#!/usr/bin/env python3
"""The readings behind ``reference/xing4.0-29b-a4b.py``'s limits, on the
chip, at the configuration's widths and the cell's timed sizes (two
sequences of 4,096), for a few seeds:

    python3 benchmark/tools/xing4_precision.py [--seeds 1 2 3]

(a) the product's loss against the plain reference (what
``lib/compare.py`` decides ``correct`` by), the routing line, and the
program's latent attention, shared expert, held experts,
multi-token-prediction module and one mixing sublayer against the
reference's on the same inputs (``layer_errors``);
(b) the reference itself with every matmul operand outside the routers
and the maps rounded to float8 (e4m3), the nearest precision below the
bfloat16 the configuration states: its loss's relative difference and
its layers' distances, one of which has to be past its limit on every
seed;
(c) the reference with its Sinkhorn rounds in bfloat16 and all else
float32: its H_res's column sums have to be past
SINKHORN_COLUMN_CEILING on every seed (the stream's distance does not
tell it: the reference's file says why);
(d) the reference's main loss alone, the module's left out: its
distance from the whole loss has to be past TOLERANCE on every seed;
(e) a bfloat16 router against the float32 one on the reference's own
router inputs: under SAME_INPUT_ROUTING_FLOOR.  One JSON line per seed,
and a last line ``refused`` that says of each of float8, the bfloat16
Sinkhorn and the dropped module on how many seeds a limit refused it.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

NAME = "xing4.0-29b-a4b"


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
    weight = shape["mtp_weight"]
    whole = lambda main, mtp: (main + weight * mtp).mean()
    # tokens are arguments, so that every seed runs the programs the
    # first one compiled
    product = jax.jit(lambda p, x: spec.loss_fn(
        spec.apply_fn(p, x, True), x).mean())

    @jax.jit
    def exact(p, x):
        main, mtp, seen, probe = ref.loss(p, x, **shape)
        return whole(main, mtp), main.mean(), seen, probe

    float8 = jax.jit(lambda p, x: whole(*ref.loss(
        p, x, rounded=jnp.float8_e4m3fn, **shape)[:2]))
    coarse_sinkhorn = jax.jit(lambda p, x: whole(*ref.loss(
        p, x, sinkhorn_dtype=jnp.bfloat16, **shape)[:2]))
    layers = ref.layer_errors(config)
    float8_layers = ref.layer_errors(config, rounded=jnp.float8_e4m3fn)
    sinkhorn_layers = ref.layer_errors(config, sinkhorn_dtype=jnp.bfloat16)
    coarse = jax.jit(lambda u, w, b: ref.route(
        u.astype(jnp.bfloat16), w.astype(jnp.bfloat16), b,
        shape["top_k"])[1])
    limits = ref.ceilings()
    over = lambda errors: sorted(part for part, error in errors.items()
                                 if error > limits[part])
    refused = {"float8": 0, "bfloat16_sinkhorn": 0, "module_dropped": 0}
    for seed in args.seeds:
        params, x = ref.inputs(
            config, jax.jit(spec.init_fn)(jax.random.PRNGKey(
                seed % (2 ** 31))), np.random.default_rng(seed))
        got = float(product(params, x))
        with jax.default_matmul_precision("highest"):
            want, main, seen, probe = exact(params, x)
            want, main = float(want), float(main)
            rel = lambda value: abs(float(value) - want) / abs(want)
            lower = rel(float8(params, x))
            rounds = rel(coarse_sinkhorn(params, x))
            ref.check_routing(config, params, seen, shape["top_k"])
        agree = [float((coarse(s.u, w["w_router"], w["expert_bias"])
                        & s.chosen).sum() / s.chosen.sum())
                 for s, w in zip(seen, ref.expert_layers(params))]
        row = {
            "seed": seed, "device": jax.devices()[0].device_kind,
            "product_loss": got, "reference_loss": want,
            "product_rel_diff": abs(got - want) / abs(want),
            "product_layers": layers(params, seen, probe, x),
            "float8_rel_diff": lower,
            "float8_layers": float8_layers(params, seen, probe, x),
            "bfloat16_sinkhorn_rel_diff": rounds,
            "bfloat16_sinkhorn_layers": {
                part: error for part, error in sinkhorn_layers(
                    params, seen, probe, x).items()
                if part in ("mixing", "sinkhorn_columns")},
            "module_dropped_rel_diff": rel(main),
            "tolerance": ref.TOLERANCE, "ceilings": limits,
            "bf16_router_same_input_agreement": min(agree),
            "routing_floor": ref.SAME_INPUT_ROUTING_FLOOR}
        row["product_over"] = over(row["product_layers"])
        row["float8_over"] = over(row["float8_layers"])
        refused["float8"] += bool(lower > ref.TOLERANCE
                                  or row["float8_over"])
        refused["bfloat16_sinkhorn"] += bool(
            rounds > ref.TOLERANCE
            or over(row["bfloat16_sinkhorn_layers"]))
        refused["module_dropped"] += bool(
            row["module_dropped_rel_diff"] > ref.TOLERANCE)
        print(json.dumps(row), flush=True)
    print(json.dumps({"refused": refused, "seeds": len(args.seeds)}),
          flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
