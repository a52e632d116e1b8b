"""The delta-rule cells' whole training steps (``olmo-hybrid-7b``,
``solar-open2-250b``, ``ling-3.0-flash``) through the TPU's own compiler,
for a v5e that is described and not attached: the other half of
``test_step_compile_tpu.py``, in a file of its own so that no file is a
worker's whole run (``--dist loadfile``; ROADMAP C16).  Nothing runs, so
no result or time is checked here.
"""

import re

import jax
import pytest

from elasticdl_tpu.models import remat_keep as rk, transformer as tfm
from elasticdl_tpu.ops import batch_shard
from elasticdl_tpu.ops.mode import SWITCH
from tests.tpu_compile import (  # noqa: F401 (the fixtures)
    V5E_LIMIT, _bare_estimate_is_bounded, _estimate, _fused_computations,
    _inventory_is_held, _model_params, _mosaic_calls, _names, _products,
    _step, _updates_in_matmuls, cell_steps, one_chip)


@pytest.fixture(scope="module")
def delta_cell():
    """The ``olmo-hybrid-7b.seq16384`` cell from shapes, for the two
    tests that each compile its step: (the spec, its abstract
    parameters, the bytes the trainer holds beside the step)."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv(SWITCH, "tpu")       # the ops' own choice on a chip
        spec = tfm.model_spec(**_model_params("olmo-hybrid-7b"))
    params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    state = jax.eval_shape(spec.optimizer.init, params)
    nbytes = lambda tree: sum(
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(tree))
    assert nbytes(params) == 4 * 766241946
    return spec, params, 2 * nbytes(params) + nbytes(state)


def test_the_delta_stacks_step_fits_a_v5e_with_nothing_kept(
        one_chip, monkeypatch, delta_cell):
    """The ``olmo-hybrid-7b.seq16384`` cell's whole training step (one
    sequence of 16,384 through three gated-delta layers and a full NoPE
    layer at 15 of 30 heads, a SwiGLU of 11,008 in each, an untied head
    over 12,544 ids, AdamW; 766.2 M parameters) through the TPU's
    compiler with nothing kept: the configuration's condition for its
    two-way head share (12.77 GB of the 16.91; 13.00 until PR 46), so
    the three-way fallback was not taken; ``remat_keep``'s estimate is
    over the compiler's count by 0.12 GB since PR 50 counts one layer's
    worth of this unrolled dense stack's gradients at the layer place
    (``grads_standing``; +2.15 while it counted them whole, as
    ``lfm2-24b-a2b``'s +2.01 still does; with none counted it would
    read 0.56 UNDER).  The scan runs once forward and once again in
    each delta layer's backward, and the convolution with it.

    Since PR 46 (``models/transformer._updates_apart``) no matmul
    carries an AdamW update as its epilogue (27 did): each of the twelve
    MLP weight gradients is a fusion that writes its float32 matrix
    from the convolution through a convert alone, and the update is a
    pass of its own behind it."""
    monkeypatch.setenv(SWITCH, "tpu")     # the ops' own choice on a chip
    spec, params, held = delta_cell
    compiled = _step(spec, one_chip, 1, 16384).compile()
    stats = compiled.memory_analysis()
    counted = stats.argument_size_in_bytes + stats.temp_size_in_bytes
    assert counted < 0.95 * 16911433728
    # a barrier a leaf keeps no gradient waiting: the parent's 13.00 GB
    assert counted < 12.998e9 + 0.1e9, counted
    estimate = held + rk.step_bytes(spec.config, params, 16384)
    assert -0.1e9 < estimate - counted < 0.5e9, (estimate, counted)
    text = compiled.as_text()
    names = [c.split(" = ")[0].lstrip("%") for c in _mosaic_calls(text)]
    count = lambda name: len([c for c in names if re.search(
        r"(^|_)" + name + r"(__)?\.\d+$", c)])
    assert (count("gdn_fwd"), count("gdn_bwd")) == (6, 3), names
    assert (count("sconv_silu_fwd"), count("sconv_silu_bwd")) == (6, 3)
    assert (count("flash_fwd"), count("flash_bwd")) == (2, 1), names
    assert not _updates_in_matmuls(text)
    # a layer's gate and up products in both of its forwards and the
    # gated product's cotangent, a layer of four (the step with the
    # room stated makes eight fewer: the next test)
    assert _products(text, "bf16[16384,11008]") == 4 * (2 + 2 + 1)
    mlp_grads = [body for body in _fused_computations(text).values()
                 if re.search(r"ROOT \S+ = f32\[1,(3840,11008|11008,3840)\]",
                              body[-1])
                 and any(" convolution(" in l for l in body)]
    assert len(mlp_grads) == 12
    for body in mlp_grads:
        product = next(i for i, l in enumerate(body) if " convolution(" in l)
        assert [re.search(r" (\w+)\(", l).group(1)
                for l in body[product + 1:]] == ["convert", "bitcast"], body


def test_the_delta_stacks_step_keeps_its_mlps_products_in_the_room_it_has(
        one_chip, monkeypatch, delta_cell):
    """Of the 2.68 GB of gradients that the trainer states for the four
    unrolled dense layers none stands where the step's peak is (the
    first layer back-propagated, ``grads_standing``), so ``choose``
    takes the four MLPs' gate and up products and the delta layers'
    projection of q, k, v beside the eight names it took before PR 50:
    4.50 GB kept, a predicted peak of 16.02 GB against the compiler's
    15.72 (arguments + temporaries; the chip measured 15.706: my chip
    run, PR 50), under the limit less the reserve.  The step makes
    eight ``[16384, 3840] x [3840, 11008]`` products fewer than the
    nothing-kept step of the test above (the second
    forward's gate and up, a layer of four) and three of the six
    ``[16384, 3840] x [3840, 5760]``; the scan still runs twice forward
    and once backward a delta layer (its 1.51 GB do not fit), and no
    matmul carries an AdamW update."""
    monkeypatch.setenv(SWITCH, "tpu")     # the ops' own choice on a chip
    spec, params, held = delta_cell
    limit = 16911433728           # a v5e's bytes_limit (chip run, PR 29)
    room = batch_shard.DeviceRoom(limit, limit - held)
    names, kept, budget, peak = rk.choose(spec.config, params, 16384, room)
    assert {rk.KEEP_GATE, rk.KEEP_UP, rk.KEEP_DELTA_IN} <= set(names)
    assert kept <= budget and peak <= (1 - rk.RESERVE) * limit

    compiled = _step(spec, one_chip, 1, 16384, room).compile()
    stats = compiled.memory_analysis()
    counted = stats.argument_size_in_bytes + stats.temp_size_in_bytes
    assert counted < (1 - rk.RESERVE) * limit, counted
    assert -0.1e9 < peak - counted < 0.9e9, (peak, counted)
    text = compiled.as_text()
    assert _products(text, "bf16[16384,11008]") == 4 * (2 + 2 + 1) - 8
    assert _products(text, "bf16[16384,5760]") == 3
    calls = [c.split(" = ")[0].lstrip("%") for c in _mosaic_calls(text)]
    count = lambda name: len([c for c in calls if re.search(
        r"(^|_)" + name + r"(__)?\.\d+$", c)])
    assert (count("gdn_fwd"), count("gdn_bwd")) == (6, 3), calls
    assert (count("flash_fwd"), count("flash_bwd")) == (1, 1), calls
    assert not _updates_in_matmuls(text)


def test_the_kda_expert_stacks_step_fits_a_v5e_with_nothing_kept(
        cell_steps):
    """The ``solar-open2-250b.seq16384`` cell (one sequence of 16,384
    through a gated NoPE GQA layer at 8 query heads on 1 K/V head and
    three KDA layers at 8 of 64 heads, a 320-wide router over 8 held
    experts of 1,280 and a shared expert in each, an untied head over
    24,576 ids, AdamW; 840,875,672 parameters) fits with nothing kept,
    the configuration's condition for its 8-way head share, so the
    16-way fallback is not taken.  The step the cell runs says so, the
    one ``..keeps_its_scans_results`` compiles (a compile with nothing
    kept read 14.98 GB of the 16.91 and the estimate +0.46 over it, in
    126 s of tier-1): what fits with 1.0 GB kept fits without, the
    estimate with nothing kept lies where that compile bounds it, and
    the convolution still runs again in the backward (the scan and the
    flash call read their kept results)."""
    step = cell_steps("solar-open2-250b", 1, 16384, True)
    nbytes = lambda tree: sum(
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(tree))
    assert nbytes(step.params) == 4 * 840875672
    assert step.counted < 0.95 * V5E_LIMIT, step.counted
    _bare_estimate_is_bounded(step, 16384)
    text = step.compiled.as_text()
    names = _names(text)
    assert (names["gdn_fwd"], names["gdn_bwd"]) == (0, 0), names
    assert (names["sconv_silu_fwd"], names["sconv_silu_bwd"]) == (6, 3)
    assert (names["flash_fwd"], names["flash_bwd"]) == (1, 1), names
    assert not _updates_in_matmuls(text)


def test_the_kda_expert_stacks_step_keeps_its_scans_results(cell_steps):
    """The same cell's step with the names ``remat_keep`` chooses since
    PR 60, the scans' outputs, chunk-start states and inverses (0.55 GB
    for the three layers) among them: ``kda_fwd`` runs three times a
    step, once a layer, where it ran six (the second forward reads the
    kept results), and the compiler's count, 15.87 GB, stays under the
    limit less the reserve and under the predicted 15.97.  The one
    whole-step compile PR 60 adds to tier-1 (67 s here, under ROADMAP
    C16's 90 s a case); the chip's own run of the cell is its other
    witness (PERF.md section 6, PR 60)."""
    step = cell_steps("solar-open2-250b", 1, 16384, True)
    names, kept, budget, peak = step.chosen
    from elasticdl_tpu.ops import gated_delta

    assert {gated_delta.KEEP_OUT, gated_delta.KEEP_STATES,
            gated_delta.KEEP_INVERSE} <= set(names), names
    assert kept <= budget and peak <= (1 - rk.RESERVE) * V5E_LIMIT
    assert step.counted < (1 - rk.RESERVE) * V5E_LIMIT, step.counted
    calls = _names(step.compiled.as_text())
    assert (calls["kda_fwd"], calls["kda_bwd"]) == (3, 3), calls


# (configuration, sequences, their length, whether ``choose``'s list is
# kept) of this file's unrolled stacks with expert layers; the hybrid's
# slow as the test is that makes its compile
EXPERT_STEPS = [
    ("solar-open2-250b", 1, 16384, False),
    ("solar-open2-250b", 1, 16384, True),
    pytest.param("ling-3.0-flash", 1, 16384, False,
                 marks=pytest.mark.slow),
]


@pytest.mark.parametrize("config,batch,rows,keep", EXPERT_STEPS)
def test_the_expert_layers_inventory_is_held_to_the_compilers_count(
        cell_steps, config, batch, rows, keep):
    """``tests/test_step_compile_tpu.py``'s test of the same name, for
    the delta-rule cells with expert layers: ``remat_keep``'s predicted
    peak against the TPU compiler's count of the whole step: over it
    by under 0.5 GB with ``choose``'s list kept (the KDA cell +0.15),
    by under 0.9 with nothing kept where that step is compiled (the
    linear / latent hybrid's +0.08, a slow case; the KDA cell's case
    reads what the kept compile bounds: its own read +0.46).  The
    compiles are this file's other tests' (``cell_steps``)."""
    _inventory_is_held(cell_steps, config, batch, rows, keep,
                       bare=("ling-3.0-flash",))


# -- the linear / latent hybrid's step (PR 56) --------------------------------

LING_PARAMETERS = 654478128


def test_the_hybrids_parameters_are_the_configurations_count():
    """``ling-3.0-flash`` as ``init_params`` builds it: a leading dense
    KDA layer (62,953,608), five KDA expert layers (70,163,080 each),
    the latent expert layer (63,498,240), the module (76,610,560), the
    untied 19,648-id vocabulary (100,597,760) and the last norm: the
    count the configuration's ``reduced_why`` states, shapes alone."""
    spec = tfm.model_spec(**_model_params("ling-3.0-flash"))
    params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    count = lambda tree: sum(a.size for a in jax.tree_util.tree_leaves(tree))
    layers = params["layers"]
    assert count(layers["lead"]["0"]) == 62953608
    assert [count(layers["period"][str(i)]) for i in range(6)] == [
        70163080] * 5 + [63498240]
    assert count(params["mtp"]) == 76610560
    assert count(params) == LING_PARAMETERS
    mixer = lambda w, names: sum(w[name].size for name in names)
    assert mixer(layers["period"]["0"], (
        "w_qkv", "delta_conv", "w_a", "w_out_gate", "w_b", "A_log",
        "dt_bias", "o_norm", "wo")) == 15762568
    assert mixer(layers["period"]["5"], (
        "wq", "w_kv_a", "kv_norm", "w_kv_b", "w_attn_gate", "wo")) == 9097728


@pytest.mark.slow
def test_the_linear_latent_hybrids_step_fits_a_v5e_with_nothing_kept(
        cell_steps):
    """The ``ling-3.0-flash.seq16384`` cell's whole training step (one
    sequence of 16,384 through six KDA layers with full projections
    under the bounded gate and a head-gated latent layer at 8 of 32
    heads, a 512-wide group-limited router over 8 held experts of 768
    and a clamped shared expert in six of them, the module's latent
    block, two passes of an untied head over 19,648 ids, AdamW) through
    the TPU's compiler with nothing kept: 12.13 GB of a v5e's 16.91,
    which leaves ``remat_keep`` 4 GB to keep.  Marked slow: the one
    program takes four minutes to compile here (my run, PR 56)."""
    step = cell_steps("ling-3.0-flash", 1, 16384, False)
    counted = step.counted
    assert 12.0e9 < counted < 12.3e9, counted
    # ``remat_keep``'s estimate stands over it since PR 60 (+0.08 GB;
    # +0.69 while the unrolled expert layers' gradients were counted
    # whole; -0.09 without the module's two normed operands, [16384,
    # 2560] each from the forward to the module's backward)
    estimate = _estimate(step, 16384, False)
    assert 0 < estimate - counted < 0.5e9, (estimate, counted)
    names = _names(step.compiled.as_text())
    # six scans forward, again in each layer's backward, once back
    assert (names["kda_fwd"], names["kda_bwd"]) == (12, 6), names
    assert (names["sconv_silu_fwd"], names["sconv_silu_bwd"]) == (12, 6)
    # the latent layer and the module's block
    assert names["flash_fwd_qk192_v128"] == 4, names
    assert names["flash_bwd_qk192_v128"] == 2, names
    assert names["embed_grad"] == 1
