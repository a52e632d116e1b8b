"""Whole training steps (loss, gradients, AdamW) of the benchmark's cells
through the TPU's own compiler, for a v5e that is described and not
attached: ``olmo1b``'s program and ``xing4.0-29b-a4b``'s step (slow)
here.  The whole-step compiles alone hold ``remat_keep``'s prediction
to the compiler's byte count before a chip does.

Nothing runs, so no result or time is checked here.  Each compile is a
minute or two of one worker: one compile a question, and what several
tests read is compiled once in a module-scoped fixture.  A cell's step
stands last in the file of its kind of stack, because a file is the
unit ``--dist loadfile`` schedules and hands out by its number of
cases: side by side at a run's end four such compiles took half as
long again (ROADMAP C16).  ``smallthinker-21b-a3b``'s is in
``test_banded_stack.py``, ``lfm2-24b-a2b``'s in
``test_mixed_stack_rows.py``, ``trinity-mini``'s in
``test_gated_block.py``, the delta-rule cells' in
``test_delta_step_compile_tpu.py``; the kernels and single layers are
``test_flash_compile_tpu.py``'s.
"""

import re

import jax
import pytest

from elasticdl_tpu.models import transformer as tfm
from tests.tpu_compile import (  # noqa: F401 (the fixtures)
    V5E_LIMIT, _estimate, _inventory_is_held, _model_params, _names, _step,
    cell_steps, one_chip)


# the wide stream's step, slow as the test is that compiles it
@pytest.mark.parametrize("config,batch,rows,keep", [pytest.param(
    "xing4.0-29b-a4b", 2, 4096, False, marks=pytest.mark.slow)])
def test_the_expert_layers_inventory_is_held_to_the_compilers_count(
        cell_steps, config, batch, rows, keep):
    """``remat_keep``'s predicted peak of an unrolled stack with expert
    layers, whose layer term is the dispatch's inventory from shapes
    (``dispatch_phases``, ``_expert_layer``) beside one layer's worth of
    gradients (``grads_standing``), against the TPU compiler's own count
    of the whole step: over it by under 0.5 GB with the list the cell
    runs under (smallthinker +0.16, lfm2 +0.23, trinity +0.25: the
    cases of their stacks' files) and by under 0.9 with nothing kept, a
    step no cell runs (the wide stream's +0.06, a slow case).  The compiles are the
    ones this file's other tests read (``cell_steps``): no program is
    compiled for this test alone."""
    _inventory_is_held(cell_steps, config, batch, rows, keep,
                       bare=("xing4.0-29b-a4b",))


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
