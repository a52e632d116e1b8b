#!/usr/bin/env python3
"""The product's loss against a configuration's plain reference, on one
seeded microbatch at the configuration's widths, in a process of its own.

Run after the job has exited and released the chip.  The product side is
the ``ModelSpec``'s ``apply_fn`` + ``loss_fn`` as the trainer calls them
(bfloat16 compute where the configuration's flags say so, kernels on
their defaults).  Everything that belongs to one configuration is in its
reference's own file, ``reference/<name>.py``:

``TOLERANCE``   largest relative difference of the mean loss, with its
                reason beside it;
``MICROBATCH``  records in the microbatch;
``case(config, params, rng, key)``  returns ``(params, x, y,
                reference_loss)``: the product's freshly initialised
                ``params`` as both sides shall use them, the seeded inputs
                and labels, and ``reference_loss(params)``, the plain
                float32 loss per record, run here at ``highest`` matmul
                precision.

Prints one JSON line; its ``seconds`` are this process's phases by the
host's clock (import and parameter init, the reference's case, the
product's compile and run, the reference's compile and run): what the
runner's cap on the comparison (``runner.COMPARE_CAP_S``) was set from.
JAX's compile cache is where the environment says
(``JAX_COMPILATION_CACHE_DIR``: the runner hands over the job's).
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--config-file", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args()
    seconds, last = {}, [time.time()]

    def phase(name):
        seconds[name] = time.time() - last[0]
        last[0] = time.time()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import manifest
    from benchmark.lib.runner import merge, params_string
    from elasticdl_tpu.models.spec import load_model_spec

    with open(args.config_file) as fh:
        config = json.load(fh)
    if args.rehearse:
        config = merge(config, config.get("rehearsal"))
    cli = config["cli"]
    spec = load_model_spec(cli["model_zoo"],
                           model_params=params_string(cli["model_params"]))
    ref = manifest.load_named("reference", config["reference"])
    rng = np.random.default_rng(args.seed)
    key = jax.random.PRNGKey(args.seed % (2 ** 31))
    params = jax.block_until_ready(jax.jit(spec.init_fn)(key))
    phase("import_init_s")
    params, x, y, reference_loss = ref.case(config, params, rng, key)
    phase("case_s")

    bf16 = cli.get("flags", {}).get("use_bf16", False)

    def product(params, x, y):
        if bf16:   # as CollectiveTrainer._loss_and_grads casts them
            cast = lambda a: a.astype(jnp.bfloat16) if jnp.issubdtype(
                a.dtype, jnp.floating) else a
            params, x = jax.tree_util.tree_map(cast, (params, x))
        out = spec.apply_fn(params, x, True)
        return spec.loss_fn(out, y).astype(jnp.float32).mean()

    got = float(jax.jit(product)(params, x, y))
    phase("product_s")
    with jax.default_matmul_precision("highest"):
        want = float(jax.jit(lambda p: reference_loss(p).mean())(params))
    phase("reference_s")
    rel = abs(got - want) / abs(want)
    device = jax.devices()[0]
    print(json.dumps({
        "reference": config["reference"], "product_loss": got,
        "reference_loss": want, "rel_diff": rel, "tolerance": ref.TOLERANCE,
        "ok": rel <= ref.TOLERANCE, "microbatch": ref.MICROBATCH,
        "platform": device.platform, "kind": device.device_kind,
        "seconds": seconds}),
        flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
