"""The cut ``nemotron-3-nano-30b-a3b`` model at its rehearsal size (a
Mamba-2 layer, an expert layer, a Mamba-2 layer, an attention layer and
an expert layer, ONE sublayer each: published layers 2-6, ``MEM*E``; 2
of 8 two-matrix experts) against ``benchmark/reference/
nemotron-3-nano-30b-a3b.py``: the loss and every gradient leaf in
float32, the jnp twins and the interpreted kernels, and what the
reference's limits can tell apart.  The mechanisms one by one:
tests/test_nemotron3_layers.py.
"""

import functools

import jax.numpy as jnp
import pytest

from benchmark.lib.runner import params_string
from elasticdl_tpu.models.spec import load_model_spec
from tests import reference_check as rc

NAME = "nemotron-3-nano-30b-a3b"
PUBLISHED, CONFIG = rc.configuration(NAME, None), rc.configuration(NAME)
LOSS_TOLERANCE = 2e-6
GRAD_TOLERANCE = 5e-4


def _spread(w):      # scores wider than the bias, as at 2688
    return {k: _spread(v) if isinstance(v, dict) else
            10.0 * v if k == "w_router" else v for k, v in w.items()}


CASE = functools.partial(rc.rehearsal, NAME, 3, _spread)


def test_the_rehearsal_model_is_the_cells_with_smaller_numbers():
    case = CASE()
    REF, SHAPE, cfg = case.ref, case.shape, case.spec().config
    cell = load_model_spec("transformer", model_params=params_string(
        PUBLISHED["cli"]["model_params"])).config
    assert "".join(k.op for k in cfg.kinds) == "memae"
    assert "".join(k.op for k in cell.kinds) == "mememaeme"
    assert PUBLISHED["hybrid_override_pattern"][:9] == "MEMEM*EME"
    assert all((k.op != "e") + k.ffn == 1 for k in cell.kinds)
    assert [k.dense for k in cell.kinds] == [k.op != "e" for k in cell.kinds]
    for field in ("moe_router", "ffn_activation", "mixer_ffn", "conv_bias",
                  "conv_kernel", "scan_periods", "moe_route_scale",
                  "moe_shared_experts", "rope_kinds", "norm_eps", "remat",
                  "tied_embeddings"):
        assert getattr(cfg, field) == getattr(cell, field), field
    assert (cell.ssm_heads, cell.ssm_head_dim, cell.ssm_state,
            cell.ssm_groups) == (64, 64, 128, 8)
    assert (cell.moe_top_k, cell.moe_experts, cell.moe_experts_held,
            cell.mlp_dim, cell.shared_dim) == (6, 128, 8, 1856, 3712)
    assert (cell.num_heads, cell.kv_heads, cell.head_dim, cell.dim) == (
        32, 2, 128, 2688)
    assert not any(k.rope for k in cell.kinds if k.op == "a")
    assert SHAPE["kinds"] == ("mamba", "experts", "mamba", "attention",
                              "experts")
    assert REF.shape_of(PUBLISHED)["kinds"].count("mamba") == 4


@pytest.mark.parametrize("mode", ["off", "interpret"])
def test_the_stack_of_single_sublayers_matches_the_reference(mode):
    """The loss and every gradient leaf against the plain reference:
    ``off`` the jnp twins under ``jax.checkpoint``, ``interpret`` the
    state-space scan's, the convolution's, the flash and the dispatch's
    kernels in the interpreter.  No gradient reaches ``expert_bias``."""
    far, still, _, _ = rc.check(CASE(), mode, LOSS_TOLERANCE,
                                GRAD_TOLERANCE)
    assert len(still) == 2 and all("expert_bias" in name for name in still)
    # two Mamba-2 mixers of 9 leaves, attention's 5, two expert layers
    # of 6 beside their bias, embed, ln_f, lm_head
    assert len(far) == 2 * 9 + 5 + 2 * 6 + 3


@pytest.mark.parametrize("piece", rc.manifest.load_named(
    "reference", NAME).PIECES)
def test_the_reference_without_one_mechanism_is_another_loss(piece):
    """Each mechanism the row forced (the skip ``D``, the bias on the
    taps, the gate before the norm, the route's scale, the shared
    expert) left out, or a gate product put back, moves the loss by far
    more than the tolerance the product is held to."""
    case = CASE()
    want = float(rc.wanted(case)[0][0])
    other = float(case.reference(without=(piece,))(case.params)[0])
    assert not abs(other - want) <= 20 * LOSS_TOLERANCE * want, (
        piece, other, want)


def test_the_layer_check_passes_in_float32_and_sees_what_it_should():
    """``case`` as ``lib/compare.py`` calls it: the routing floor and
    every layer ceiling hold at float32; the same check refuses the
    reference in float8 part by part, and a bfloat16 state or bfloat16
    cumulative decays move the probe that remembers by orders."""
    case = CASE()
    REF, params = case.ref, case.params
    _, seen = REF.loss(params, case.tokens[:1], **case.shape)
    assert REF.check_routing(CONFIG, params, seen) == 1.0
    limits = REF.ceilings()
    # ``case`` holds the first Mamba-2 layer, the first expert layer and
    # the probe; ``every`` layer and attention is the precision tool's
    assert set(REF.layer_errors(CONFIG)(params, seen)) == set(
        REF.LAYER_PARTS) - {"attention"}
    errors = REF.layer_errors(CONFIG)(params, seen, True)
    assert set(errors) == set(REF.LAYER_PARTS)
    assert all(errors[part] <= 1e-4 for part in errors), errors
    worse = REF.layer_errors(CONFIG, rounded=jnp.float8_e4m3fn)(
        params, seen, True)
    for part in ("mamba", "attention", "shared_expert", "routed_experts"):
        assert worse[part] > limits[part], (part, worse)
    # at 256 tokens a rounding has had no time to drift past the
    # ceiling: it is further off than float32 by orders, which the chip
    # shows at 16,384 (benchmark/tools/nemotron3_precision.py)
    for how in (dict(state=jnp.bfloat16), dict(decays=jnp.bfloat16)):
        coarse = REF.layer_errors(CONFIG, **how)(params, seen)
        assert coarse["ssm_state"] > 100 * errors["ssm_state"], how
