"""Whole training steps (loss, gradients, AdamW) of the benchmark's cells
through the TPU's own compiler, for a v5e that is described and not
attached: ``lfm2-24b-a2b``, ``smallthinker-21b-a3b``, ``trinity-mini``,
``olmo1b`` and ``xing4.0-29b-a4b``.  They alone hold ``remat_keep``'s
prediction to the compiler's byte count before a chip does.

Nothing runs, so no result or time is checked here.  Each compile is a
minute or two of one worker: one compile a question, and what several
tests read is compiled once in a module-scoped fixture.  The delta-rule
cells' steps are ``test_delta_step_compile_tpu.py``'s, the kernels' and
single layers' compiles ``test_flash_compile_tpu.py``'s: a file is the
unit ``--dist loadfile`` schedules (ROADMAP C16).
"""

import re

import jax
import pytest

from elasticdl_tpu.models import transformer as tfm
from tests.tpu_compile import (  # noqa: F401 (the fixtures)
    V5E_LIMIT, _estimate, _inventory_is_held, _model_params, _names, _step,
    _updates_in_matmuls, cell_steps, one_chip)

@pytest.fixture(scope="module")
def banded_step(cell_steps):
    """The ``smallthinker-21b-a3b.seq16384`` cell's whole training step
    compiled once for the tests that read it (a minute): what
    ``remat_keep`` chose, and the compiled program."""
    step = cell_steps("smallthinker-21b-a3b", 1, 16384, True)
    nbytes = lambda tree: sum(
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(tree))
    assert nbytes(step.params) == 4 * 656529920      # 656.5 M parameters
    return V5E_LIMIT, step.chosen, step.compiled


def test_the_banded_stacks_step_fits_a_v5e_as_remat_keep_predicts(
        banded_step):
    """The ``smallthinker-21b-a3b.seq16384`` cell's whole training step
    (one sequence of 16,384 through a full-NoPE and three windowed-RoPE
    attention layers, heads x head size 3,584 over a hidden 2,560, 16 of
    64 ReGLU experts at 6 a token, an untied head over 37,984 ids,
    AdamW) through the TPU's compiler with what ``remat_keep`` chose
    kept (every entry of its table since the step's need counts a
    layer's kept products once and an unrolled stack's weight copies
    two layers at a time: the sorted rows too, 4.06 GB in all): its
    predicted peak is over the compiler's own byte count, never
    under, and under the device's limit less the reserve (15.45 GB
    against the compiler's 15.28; PR 35's eleven names read 15.69
    against 14.39).  Both kinds of flash call are in the one program,
    and no forward runs twice."""
    from elasticdl_tpu.models import remat_keep as rk
    from elasticdl_tpu.ops import moe_dispatch

    limit, (names, kept, budget, peak), compiled = banded_step
    assert set(names) >= set(rk.ATTN_NAMES) | {
        rk.KEEP_Q, rk.KEEP_K, rk.KEEP_V, rk.KEEP_STREAM,
        moe_dispatch.KEEP_ROWS}, names
    assert kept <= budget and peak <= (1 - rk.RESERVE) * limit

    stats = compiled.memory_analysis()
    counted = stats.argument_size_in_bytes + stats.temp_size_in_bytes
    assert counted < peak and peak - counted < 0.5e9, (peak, counted)
    calls = [l.split(" = ")[0].strip().lstrip("%")
             for l in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in l]
    count = lambda name: len([c for c in calls if re.search(
        name + r"(__)?\.\d+$|" + name + "$", c)])
    assert (count("flash_fwd"), count("flash_bwd")) == (1, 1), calls
    assert (count("flash_fwd_w4096"), count("flash_bwd_w4096")) == (3, 3), \
        calls
    assert not [c for c in calls if "flash_dq" in c or "flash_dkv" in c]


def test_the_banded_stacks_step_scatters_no_row_into_the_table(
        banded_step):
    """The same compiled step: the embedding table's gradient is the one
    float32 ``[37984, 2560]`` result of the ``embed_grad`` call
    (``ops/embed_rows.py``: the lookup's own derivative), where JAX's
    derivative of the lookup left XLA a scatter of bfloat16 rows into
    ``bf16[37984,2560]`` and a convert pass, 15-17 ms of the cell's
    step on the chip (PERF.md section 6, PR 53).  The compiler's
    arguments + temporaries are the parent's 15,224,888,320 within what
    buffer assignment moved them by (15,225,532,416, +0.6 MB: the
    table's gradient stands where the step's peak is not)."""
    _, _, compiled = banded_step
    text = compiled.as_text()
    assert not re.findall(r" = \w+\[37984,2560\]\S* scatter\(", text)
    calls = [l for l in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in l
             and "embed_grad" in l.split(" = ")[0]]
    assert len(calls) == 1 and " = f32[37984,2560]{" in calls[0], calls
    stats = compiled.memory_analysis()
    counted = stats.argument_size_in_bytes + stats.temp_size_in_bytes
    assert counted <= 15224888320 + 2 ** 20, counted


def test_the_mixed_stacks_step_fits_a_v5e_as_remat_keep_predicts(
        cell_steps):
    """The ``lfm2-24b-a2b.seq8192`` cell's whole training step (4
    sequences of 8,192 through a dense conv layer and a period of
    attention + 3 conv layers over 8 of 64 experts, AdamW) through the
    TPU's compiler with the names ``remat_keep`` chose: its predicted
    peak is held to the compiler's own byte count (arguments +
    temporaries; the updated state aliases the donated one): over, never
    under.  This is the band that guards the chip: the cell runs under
    these names.
    With the names chosen, the convolutions' result and the sorted rows
    beside PR 58's ten entries since PR 60 (6.62 GB): 15.60 against
    15.37 (+0.23; PR 58's tree read 15.81 against 15.41 with 5.55 GB
    kept, the stack's 1.8 GB of gradients counted whole where the
    dispatch's temporaries stood).  The count does not grow with the
    list: 15.39 with the ten entries PR 58 kept, 14.95 with the
    convolutions' result beside them, 15.37 with the sorted rows too;
    in the first the tied head's cotangent (0.54 GB) still stands in
    the first layer back-propagated, in the second it does not
    (PERF.md section 6, PR 60).
    (The estimate with nothing kept is
    ``..step_with_nothing_kept_is_under_remat_keeps_estimate``'s, over a
    compile of its own: one compile a question.)"""
    from elasticdl_tpu.models import remat_keep as rk
    from elasticdl_tpu.ops import moe_dispatch, short_conv

    step = cell_steps("lfm2-24b-a2b", 4, 8192, True)
    names, kept, budget, peak = step.chosen
    assert kept <= budget
    assert set(names) >= set(rk.ATTN_NAMES) | {
        rk.KEEP_STREAM, rk.KEEP_GATE, rk.KEEP_UP, short_conv.KEEP_IN,
        short_conv.KEEP_OUT, moe_dispatch.KEEP_UP,
        moe_dispatch.KEEP_ROWS}, names
    assert peak <= (1 - rk.RESERVE) * V5E_LIMIT
    assert 0 < peak - step.counted < 0.5e9, (peak, step.counted, names)


def test_the_mixed_stacks_step_with_nothing_kept_is_under_remat_keeps_estimate(
        cell_steps):
    """The same cell's step with no room stated, so with nothing kept
    (no cell runs so: the trainer states the room): ``remat_keep``'s
    estimate of the step's own need, ``step_bytes``, the term every
    choice starts from, is held to the compiler's byte count apart from
    what the kept names add.  10.36 GB against the compiler's 9.80
    (+0.57) since PR 60 counts one layer's worth of this unrolled
    stack's gradients (11.80 and +2.01 while it counted all 1.8 GB of
    them): what is left over is the leading dense layer's term (gate,
    up, their product and a cotangent, 3.09 GB), which stands over the
    expert layers' (2.51) though that layer is the last back-propagated,
    where nothing of the head and no kept value is left.  A test of its
    own so that the durations tell its compile from the kept names'
    (ROADMAP C16 asks what it buys)."""
    step = cell_steps("lfm2-24b-a2b", 4, 8192, False)
    estimate = _estimate(step, 32768, False)
    assert -0.1e9 < estimate - step.counted < 0.9e9, (estimate, step.counted)


def test_the_gated_blocks_step_holds_no_update_in_a_matmul_nor_more_bytes(
        cell_steps):
    """The ``trinity-mini.seq16384`` cell's whole training step with the
    names ``remat_keep`` chose, for a described v5e: no weight-gradient
    matmul carries an AdamW update (39 did until PR 46) and the
    compiler's bytes are 15.33 GB: PR 58's 14.69 and the routed up
    product and the sorted rows that PR 60's list keeps beside PR 58's
    (0.81 GB; the guard against holding ``embed`` and ``lm_head`` apart
    as well, which read 15.82 where this read 14.72).  No ``.remat``
    stands in it: with the routed down product kept in the sorted rows'
    place, the same bytes, the compiler makes the head's logits a
    second time (``fusion.2893.remat`` and ``gte.remat``, the [16384,
    25024] product) to count 15.40, and the chip ran that step 1.1%
    slower than the parent where it runs this one 0.8% faster (PERF.md
    section 6, PR 60).  A share's down product stands behind the rows by
    what its shapes say it is worth (``remat_keep._entries``), and this
    cell's room ends before it."""
    from elasticdl_tpu.ops import moe_dispatch

    step = cell_steps("trinity-mini", 1, 16384, True)
    assert {moe_dispatch.KEEP_UP, moe_dispatch.KEEP_ROWS} <= set(
        step.chosen[0])
    assert moe_dispatch.KEEP_OUT not in step.chosen[0]
    assert abs(step.counted - 15.325e9) < 0.1e9, step.counted
    text = step.compiled.as_text()
    assert not _updates_in_matmuls(text)
    assert ".remat" not in text


# (configuration, sequences, their length, whether ``choose``'s list is
# kept) of this file's compiles of unrolled stacks with expert layers,
# the wide stream's marked slow as its own test is
EXPERT_STEPS = [
    ("smallthinker-21b-a3b", 1, 16384, True),
    ("lfm2-24b-a2b", 4, 8192, True),
    ("lfm2-24b-a2b", 4, 8192, False),
    ("trinity-mini", 1, 16384, True),
    pytest.param("xing4.0-29b-a4b", 2, 4096, False,
                 marks=pytest.mark.slow),
]


@pytest.mark.parametrize("config,batch,rows,keep", EXPERT_STEPS)
def test_the_expert_layers_inventory_is_held_to_the_compilers_count(
        cell_steps, config, batch, rows, keep):
    """``remat_keep``'s predicted peak of an unrolled stack with expert
    layers, whose layer term is the dispatch's inventory from shapes
    (``dispatch_phases``, ``_expert_layer``) beside one layer's worth of
    gradients (``grads_standing``), against the TPU compiler's own count
    of the whole step, with ``choose``'s list kept and with nothing
    kept: over it by under 0.5 GB with the list the cell runs under
    (smallthinker +0.16, lfm2 +0.23, trinity +0.25) and by under
    0.9 with nothing kept, a step no cell runs (lfm2 +0.57: its
    leading dense layer's term decides, not the inventory;
    the wide stream's +0.06).  The compiles are the ones this file's
    other tests read (``cell_steps``): no program is compiled for this
    test alone."""
    _inventory_is_held(cell_steps(config, batch, rows, keep), batch, rows,
                       keep)


def test_a_scan_of_several_turns_is_handed_to_the_compiler_as_it_was(
        one_chip, monkeypatch):
    """``olmo1b.seq2048``'s step as it is handed to the compiler: the
    stack is a scan of seven turns, whose update already runs after the
    loop on the stacked gradient, so ``_updates_apart`` holds none of
    its leaves and the program's ``opt-barrier`` are what they were,
    the head's three and ``jax.checkpoint``'s own in the backward
    loop's body (with the seven stacked gradients held as well the
    loops' bodies were the parent's too, but for four chips the
    compiler's bytes read 17.29 GB for 17.11 and the loop's all-reduces
    combined otherwise: PERF.md section 6, PR 46).  Two more loops since
    PR 53, neither the stack's: the two binary searches with which
    ``ops/embed_rows._schedule`` lists the (block of ids, chunk of sorted
    rows) pairs the embedding's gradient walks."""
    from elasticdl_tpu.ops.mode import SWITCH

    monkeypatch.setenv(SWITCH, "tpu")     # the ops' own choice on a chip
    spec = tfm.model_spec(**_model_params("olmo1b"))
    text = _step(spec, one_chip, 8, 2048).as_text(dialect="hlo")
    assert text.count(" while(") == 2 + 2
    assert text.count(" opt-barrier(") == 3 + 1


@pytest.mark.slow
def test_the_wide_streams_step_fits_a_v5e_with_nothing_kept(cell_steps):
    """The cell's whole training step (two sequences of 4,096 through a
    dense layer, four expert layers and the module's block on a stream
    four wide, 8 of 32 heads and 8 of 64 experts held, two passes of an
    untied head over 16,384 ids, AdamW; 807,416,462 parameters) through
    the TPU's compiler with nothing kept: 15.48 GB of a v5e's 16.91
    (the chip's own peak reads 15.43: ledger, PR 58),
    the configuration's condition for 8 heads and two sequences, so
    neither fallback is taken.  Scanned (``scan_periods`` at its
    default) the same step counts 18.25 GB: the four expert layers'
    stacked gradient stands whole.  Marked slow: the one program takes
    two minutes to compile here (my run, PR 54)."""
    step = cell_steps("xing4.0-29b-a4b", 2, 4096, False)
    nbytes = lambda tree: sum(
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(tree))
    assert nbytes(step.params) == 4 * 807416462
    compiled, counted = step.compiled, step.counted
    assert counted < 0.95 * V5E_LIMIT, counted
    assert 15.4e9 < counted < 15.7e9, counted
    # ``remat_keep``'s estimate stands over it since PR 60 (+0.06 GB;
    # +1.17 while the unrolled expert layers' gradients were counted
    # whole): the wide stream's four planes in a layer's backward are
    # there (the second forward's ``hc_post_fwd``, ``hc_post_bwd``'s
    # cotangent, a ``broadcast`` of zeros and the block's input beside
    # seven carries, bf16[8192, 14336] each), both passes' logits and
    # the module's two normed operands ([8192, 3584] each, outside its
    # block's checkpoint: -0.06 without them)
    estimate = _estimate(step, 2 * 4096, False)
    assert 0 < estimate - counted < 0.5e9, (estimate, counted)
    text = compiled.as_text()
    names = _names(text)
    # six layers of two sublayers: a layer's first read twice (the
    # second forward) and its last write once (its result is the next
    # block's kept input), each back-propagated once; the two narrowing
    # maps; the write and the read between a layer's sublayers one call
    # twice forward and one backward (PR 63: ``hyper_mix.post_pre``)
    assert names["hc_pre_fwd"] == 12 + 2
    assert (names["hc_post_pre_fwd"], names["hc_pre_post_bwd"]) == (12, 6)
    # their maps: made twice, back-propagated once, one call each; no
    # loop of the program's turns over the rounds' [4, 4, 8192] planes
    assert (names["hc_maps_fwd"], names["hc_maps_bwd"]) == (2 * 12, 12)
    assert not re.search(r"f32\[4,4,8192\]", text)
    assert names["hc_post_fwd"] == 6
    assert (names["hc_post_bwd"], names["hc_pre_bwd"]) == (6, 6 + 2)
    assert names["flash_fwd_qk192_v128"] == 12
    assert names["flash_bwd_qk192_v128"] == 6
    assert names["embed_grad"] == 1
