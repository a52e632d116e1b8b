#!/usr/bin/env python3
"""The readings behind ``reference/ling-3.0-flash.py``'s limits, on the
chip, at the configuration's widths, for a few seeds:

    python3 benchmark/tools/ling3_precision.py [--seeds 1 2 3]

(a) the product's loss against the plain reference (what
``lib/compare.py`` decides ``correct`` by), the program's router against
the reference's on the same inputs and the program's KDA mixer, gated
latent attention, shared expert, held experts, module and the delta rule
on the probe that remembers against the reference's (``layer_errors``);
(b) the reference itself with every matmul operand rounded to float8
(e4m3), the nearest precision below the bfloat16 the configuration
states: its loss's relative difference and its layers' distances;
(c) with the log decays and the delta rule's state held in bfloat16
(every operand float32), where the configuration states float32;
(d) without the clamp, (e) without the group limit (a plain top-8 of
512: its routing agreement with the program's), (f) without the module;
each of (b)-(f) has to be past at least one limit on every seed
(``refused_by``);
(g) the quartiles of ``alpha`` a channel, ``beta`` and the norm of the
state after the last token in each KDA layer at the weights as drawn
(``scan_statistics``): whether the scan being held is a trivial one.
One JSON line per seed.  ``--rehearse`` runs it on the CPU at the tiny
size (what it finds there says nothing of the limits).
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

NAME = "ling-3.0-flash"


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
    # tokens are arguments, so that every seed runs the programs the
    # first one compiled
    product = jax.jit(lambda p, x: spec.loss_fn(
        spec.apply_fn(p, x, True), x).mean())

    def total(p, x, **how):
        main, mtp, seen, probe = ref.loss(p, x, **how, **shape)
        return float((main + weight * mtp).mean()), seen, probe

    limits = ref.ceilings()
    variants = {
        "float8": dict(rounded=jnp.float8_e4m3fn),
        "bf16_gate_state": dict(state=jnp.bfloat16),
        "no_clamp": dict(without=("clamp",)),
        "no_groups": dict(without=("groups",)),
        "no_module": dict(without=("module",)),
    }
    layers = ref.layer_errors(config)
    lowered = {name: ref.layer_errors(config, **how)
               for name, how in variants.items()
               if name not in ("no_groups", "no_module")}

    def routing(params, seen, without=()):
        try:
            return ref.check_routing(config, params, seen, without)
        except SystemExit as refusal:
            return float(str(refusal).split(": ")[1].split(" ")[0])

    for seed in args.seeds:
        params, x = ref.inputs(
            config, jax.jit(spec.init_fn)(jax.random.PRNGKey(
                seed % (2 ** 31))), np.random.default_rng(seed))
        got = float(product(params, x))
        with jax.default_matmul_precision("highest"):
            want, seen, probe = total(params, x)
            # the layers' inputs: on the host until a layer reads its own
            seen, probe = jax.device_get((seen, probe))
            rel = {name: abs(total(params, x, **how)[0] - want) / abs(want)
                   for name, how in variants.items()}
        out = {
            "seed": seed, "device": jax.devices()[0].device_kind,
            "product_loss": got, "reference_loss": want,
            "product_rel_diff": abs(got - want) / abs(want),
            "product_routing": routing(params, seen),
            "product_layers": layers(params, seen, probe, True),
            "tolerance": ref.TOLERANCE, "ceilings": limits,
            "routing_floor": ref.SAME_INPUT_ROUTING_FLOOR}
        for name, how in variants.items():
            found = {"rel_diff": rel[name]}
            refused = ["loss"] if rel[name] > ref.TOLERANCE else []
            if name in lowered:
                found["layers"] = lowered[name](params, seen, probe, True)
                refused += [part for part, error in found["layers"].items()
                            if not error <= limits[part]]
            if name == "no_groups":
                found["routing"] = routing(params, seen, ("groups",))
                if found["routing"] < ref.SAME_INPUT_ROUTING_FLOOR:
                    refused.append("routing")
            found["refused_by"] = refused
            out[name] = found
        out["scan"] = ref.scan_statistics(config, params, seen)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
