"""The benchmark configurations' rehearsal steps lower to the text they
lowered to when ``tests/rehearsal_step_hashes.json`` was written: a PR
that adds options to ``TransformerConfig`` (PR 54: ``hyper_streams``,
``q_latent_rank``, ``rope_scaling``, ``mtp_modules``; PR 56:
``moe_groups``, ``delta_gate_floor``, ``ffn_limits``, ``attn_gate=head``,
with every hash written from the parent's tree, ``xing4.0-29b-a4b``'s
among them) holds the models
that leave them at their defaults to the very program they had, loss,
gradients and step statistics, before any chip says so.  A PR that
means to change a listed model's step writes the file anew,

    JAX_PLATFORMS=cpu python tests/test_rehearsal_lowering.py \\
        > tests/rehearsal_step_hashes.json

and says in its CHANGES.md line which and why.  A configuration that is
not in the file (one a later PR adds) is not held to anything here.
"""

import glob
import hashlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
HASHES = os.path.join(HERE, "rehearsal_step_hashes.json")
CONFIGS = sorted(glob.glob(os.path.join(ROOT, "benchmark", "configs",
                                        "*.json")))


def step_hash(path):
    """sha256 of the StableHLO text of one training step (loss, every
    gradient, the step statistics) of the configuration's rehearsal
    model on two sequences, traced on shapes alone."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib.runner import merge, params_string
    from elasticdl_tpu.models.spec import load_model_spec

    with open(path) as fh:
        config = json.load(fh)
    config = merge(config, config.get("rehearsal"))
    cli = config["cli"]
    spec = load_model_spec(cli["model_zoo"],
                           model_params=params_string(cli["model_params"]))
    params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, config["seq_len"]), jnp.int32)

    def step(params, tokens):
        def loss(p):
            out = spec.apply_fn(p, tokens, True)
            return spec.loss_fn(out, tokens).mean(), (
                spec.step_stats_fn(out) if spec.step_stats_fn else ())

        return jax.value_and_grad(loss, has_aux=True)(params)

    text = jax.jit(step).lower(params, tokens).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    json.dump({os.path.basename(path): step_hash(path) for path in CONFIGS},
              sys.stdout, indent=1)
    print()
else:
    import subprocess

    import pytest

    with open(HASHES) as fh:
        WAS = json.load(fh)

    @pytest.fixture(scope="module")
    def lowered():
        """Every configuration's hash by the command above, in a process
        of its own, as the file was written: what a process traced
        before is in the text it lowers to (ROADMAP C19: behind other
        files ``nemotron-3-nano-30b-a3b.json`` read ``3a107421..`` on
        the driver's machines; alone, the stored ``6a59985e..``)."""
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__)], check=True,
            capture_output=True, text=True,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        return json.loads(out.stdout)

    @pytest.mark.parametrize(
        "path", [p for p in CONFIGS if os.path.basename(p) in WAS],
        ids=os.path.basename)
    def test_a_configurations_rehearsal_step_lowers_to_the_text_it_had(
            lowered, path):
        name = os.path.basename(path)
        assert lowered[name] == WAS[name]

    def test_the_eight_configurations_before_pr_54_are_all_held():
        assert len(WAS) >= 8 and set(WAS) <= {
            os.path.basename(p) for p in CONFIGS}
