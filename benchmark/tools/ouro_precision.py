#!/usr/bin/env python3
"""The readings behind ``reference/ouro-2.6b.py``'s limits, on the chip,
at the configuration's widths, for a few seeds:

    python3 benchmark/tools/ouro_precision.py [--seeds 1 2 3] [--rehearse]

(a) the product's loss in bfloat16 (what ``lib/compare.py`` decides
``correct`` by) and in float32 (``dtype=float32``: a bug that the
bfloat16 distance would hide shows here) against the plain reference,
with the four turns' losses, the exit distribution and its entropy of
both sides, and each turn's final-normed state against the reference's
(``turn_errors``);
(b) the reference itself with every matmul operand rounded to float8
(e4m3), the nearest precision below the bfloat16 the configuration
states: its loss's relative difference and its turns' distances, one of
which has to be past its limit on every seed;
(c) the reference with each of its PIECES left out (the gate left at
zero, the entropy term, the last turn, the final norm on the carry, the
norms on the sublayers' outputs): its loss's relative difference and
the largest distance of a turn's state, one of which has to be past its
limit (a dropped turn also leaves the exit distribution a turn short).
One JSON line per seed, and all of them in
``chiprun_out/ouro_precision/readings.jsonl``.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

NAME = "ouro-2.6b"


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
    cli = config["cli"]

    def product(**over):
        spec = load_model_spec(cli["model_zoo"], model_params=params_string(
            dict(cli["model_params"], **over)))

        def run(p, x):
            out = spec.apply_fn(p, x, True)
            loss = spec.loss_fn(out, x).mean()
            return loss, spec.step_stats_fn(out), out["turns"]

        return spec, jax.jit(run)

    spec, stated = product()
    _, in_float32 = product(dtype="float32")
    shape = ref.shape_of(config)
    listed = lambda a: [float(v) for v in np.asarray(a).reshape(-1)]
    out_dir = os.path.join(ROOT, "chiprun_out", "ouro_precision")
    os.makedirs(out_dir, exist_ok=True)
    for seed in args.seeds:
        params, x = ref.inputs(
            config, jax.jit(spec.init_fn)(jax.random.PRNGKey(
                seed % (2 ** 31))), np.random.default_rng(seed))
        sides = {}
        for name, run in (("bfloat16", stated), ("float32", in_float32)):
            with jax.default_matmul_precision(
                    "highest" if name == "float32" else "default"):
                loss, stats, turns = run(params, x)
            sides[name] = (float(loss), stats, turns)
        with jax.default_matmul_precision("highest"):
            want, seen = ref.loss(params, x, **shape)
            want = float(want.mean())
            rel = lambda got: abs(float(got) - want) / abs(want)
            low, low_seen = ref.loss(params, x, rounded=jnp.float8_e4m3fn,
                                     **shape)
            without, without_states = {}, {}
            for piece in ref.PIECES:
                other, other_seen = ref.loss(params, x, without=(piece,),
                                             **shape)
                without[piece] = rel(other.mean())
                # a dropped turn has no state to compare: the count differs
                without_states[piece] = max(ref.turn_errors(
                    other_seen.states, seen.states[:len(other_seen.states)]))
        line = {
            "seed": seed, "device": jax.devices()[0].device_kind,
            "reference_loss": want,
            "reference_turn_losses": listed(seen.turn_losses),
            "reference_exit": listed(seen.exit),
            "reference_exit_entropy": float(seen.entropy),
            "float8_rel_diff": rel(low.mean()),
            "float8_turn_state_errors": ref.turn_errors(
                low_seen.states, seen.states),
            "without_rel_diff": without,
            "without_turn_state_error": without_states,
            "tolerance": ref.TOLERANCE,
            "turn_state_ceiling": ref.TURN_STATE_CEILING}
        for name, (loss, stats, turns) in sides.items():
            line.update({
                name + "_loss": loss, name + "_rel_diff": rel(loss),
                name + "_turn_losses": listed(stats["ut_loss"]),
                name + "_exit": listed(stats["ut_exit"]),
                name + "_exit_entropy": float(stats["ut_exit_entropy"]),
                name + "_turn_state_errors": ref.turn_errors(
                    turns, seen.states)})
        print(json.dumps(line), flush=True)
        with open(os.path.join(out_dir, "readings.jsonl"), "a") as fh:
            fh.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
