"""The cut ``ling-3.0-flash`` model at its rehearsal size (a leading
dense KDA layer, a KDA expert layer, the head-gated latent expert layer
and the multi-token-prediction module: ``dda`` + a module, 2 of 8 heads,
2 of 16 experts in 4 groups) against ``benchmark/reference/
ling-3.0-flash.py``: both losses and every gradient leaf in float32,
the jnp twins and the interpreted kernels, and what the reference's
limits can tell apart.  The mechanisms one by one:
tests/test_ling3_layers.py.
"""

import functools

import jax
import jax.numpy as jnp
import pytest

from benchmark.lib.runner import params_string
from elasticdl_tpu.models.spec import load_model_spec
from tests import reference_check as rc

NAME = "ling-3.0-flash"
PUBLISHED, CONFIG = rc.configuration(NAME, None), rc.configuration(NAME)
CASE = functools.partial(rc.rehearsal, NAME)
LOSS_TOLERANCE = 2e-6
GRAD_TOLERANCE = 5e-4


def test_the_rehearsal_model_is_the_cells_with_smaller_numbers():
    case = CASE()
    REF, SHAPE, cfg = case.ref, case.shape, case.spec().config
    cell = load_model_spec("transformer", model_params=params_string(
        PUBLISHED["cli"]["model_params"])).config
    assert "".join(k.op for k in cfg.kinds) == "dda"
    assert "".join(k.op for k in cell.kinds) == "dddddda"
    assert [k.dense for k in cell.kinds] == [True] + [False] * 6
    assert (cell.mtp_kind.op, cell.mtp_kind.limit) == ("a", 0.0)
    assert [(k.limit, k.shared_limit) for k in cell.kinds] == [
        (0.0, 0.0)] + [(4.0, 5.0)] * 4 + [(4.0, 7.0)] * 2
    for field in ("moe_router", "attn_gate", "delta_kind", "delta_rank",
                  "delta_gate_floor", "scan_periods", "mtp_modules",
                  "mtp_weight", "moe_route_scale", "head_shares", "remat"):
        assert getattr(cfg, field) == getattr(cell, field), field
    assert (cell.moe_groups, cell.moe_top_groups, cell.moe_top_k,
            cell.moe_experts, cell.moe_experts_held) == (8, 4, 8, 512, 8)
    assert SHAPE["kinds"] == ("linear", "linear", "latent")
    assert REF.shape_of(PUBLISHED)["kinds"] == ("linear",) * 6 + ("latent",)
    assert REF.shape_of(PUBLISHED)["limits"] == (
        (REF.NO_LIMIT,) * 2,) + ((4.0, 5.0),) * 4 + ((4.0, 7.0),) * 2


@pytest.mark.parametrize("mode", ["off", "interpret"])
def test_the_dda_stack_and_its_module_match_the_reference(mode):
    """The total loss and every gradient leaf against the plain
    reference: ``off`` the jnp twins under ``jax.checkpoint``,
    ``interpret`` the vector-decay scan's, the convolution's, the latent
    flash and the dispatch's kernels in the interpreter.  No gradient
    reaches ``expert_bias``."""
    far, still, _, _ = rc.check(CASE(), mode, LOSS_TOLERANCE, GRAD_TOLERANCE)
    assert all("expert_bias" in name or name.split("'")[-2] in (
        "w_gate", "w_up", "w_down", "w_router") for name in still), still
    # two KDA mixers of 9 leaves, the latent one of 6 and the module's,
    # 2 norms a block, the FFNs (a dense one of 3; 7 an expert layer, the
    # bias apart), the module's 3 and embed, ln_f, lm_head; at most two
    # layers' held experts idle
    assert len(far) >= 2 * 9 + 2 * 6 + 4 * 2 + 3 + 3 * 7 + 3 + 3 - 8


@pytest.mark.parametrize("piece", ["floor", "groups", "clamp", "head_gate",
                                   "module"])
def test_the_reference_without_one_mechanism_is_another_loss(piece):
    """Each of the five mechanisms the row forced moves the loss by far
    more than the tolerance the product is held to (``inputs`` draws the
    experts' gate and up projections wide enough for the limits to
    bite)."""
    case = CASE()
    want = float(rc.wanted(case)[0][0])
    less = float(jax.jit(case.reference(without=(piece,)))(case.params)[0])
    assert not abs(less - want) <= 20 * LOSS_TOLERANCE * want, (
        piece, less, want)


def test_the_layer_check_passes_in_float32_and_sees_what_it_should():
    """``case`` as ``lib/compare.py`` calls it: the routing floor and
    every layer ceiling hold at float32, and the same check refuses the
    reference in float8, with a bfloat16 gate and state (the probe that
    remembers), without the clamp and without the gate a head; the
    routing check refuses a router without the group limit."""
    case = CASE()
    REF, params = case.ref, case.params
    main, mtp, seen, probe = REF.loss(params, case.tokens[:1], **case.shape)
    assert REF.check_routing(CONFIG, params, seen) == 1.0
    with pytest.raises(SystemExit, match="router chose other experts"):
        REF.check_routing(CONFIG, params, seen, without=("groups",))
    limits = REF.ceilings()
    # ``case`` holds one KDA expert layer, the latent layer and the
    # probe; ``every`` layer and the module is the precision tool's
    assert set(REF.layer_errors(CONFIG)(params, seen, probe)) == set(
        REF.LAYER_PARTS) - {"mtp"}
    errors = REF.layer_errors(CONFIG)(params, seen, probe, True)
    assert set(errors) == set(REF.LAYER_PARTS)
    assert all(errors[part] <= 1e-4 for part in errors), errors
    for how, parts in (
            (dict(rounded=jnp.float8_e4m3fn),
             ("kda", "attention", "shared_expert", "routed_experts", "mtp")),
            (dict(without=("clamp",)), ("shared_expert", "routed_experts")),
            (dict(without=("head_gate",)), ("attention", "mtp"))):
        worse = REF.layer_errors(CONFIG, **how)(params, seen, probe, True)
        for part in parts:
            assert worse[part] > limits[part], (how, part, worse)
    # at 64 tokens a bfloat16 state has had no time to drift past the
    # ceiling: it is further off than float32 by orders, which the chip
    # shows at 16,384 (benchmark/tools/ling3_precision.py)
    coarse = REF.layer_errors(CONFIG, state=jnp.bfloat16)(params, seen,
                                                          probe)
    assert coarse["kda_state"] > 100 * errors["kda_state"]
