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
from tests.tpu_compile import (  # noqa: F401 (one_chip: a fixture)
    _model_params, _names, _step, _updates_in_matmuls, one_chip)

@pytest.fixture(scope="module")
def banded_step(one_chip):
    """The ``smallthinker-21b-a3b.seq16384`` cell's whole training step
    compiled once for the tests that read it (a minute): what
    ``remat_keep`` chose, and the compiled program."""
    from elasticdl_tpu.models import remat_keep as rk
    from elasticdl_tpu.ops import batch_shard
    from elasticdl_tpu.ops.mode import SWITCH

    with pytest.MonkeyPatch.context() as patch:
        patch.setenv(SWITCH, "tpu")       # the ops' own choice on a chip
        spec = tfm.model_spec(**_model_params("smallthinker-21b-a3b"))
        params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
        state = jax.eval_shape(spec.optimizer.init, params)
        rows = 16384
        nbytes = lambda tree: sum(
            a.size * a.dtype.itemsize
            for a in jax.tree_util.tree_leaves(tree))
        assert nbytes(params) == 4 * 656529920      # 656.5 M parameters
        limit = 16911433728       # a v5e's bytes_limit (chip run, PR 29)
        held = 2 * nbytes(params) + nbytes(state)
        room = batch_shard.DeviceRoom(limit, limit - held)
        chosen = rk.choose(spec.config, params, rows, room)
        compiled = _step(spec, one_chip, 1, rows, room).compile()
    return limit, chosen, compiled


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


@pytest.fixture(scope="module")
def mixed_cell():
    """The ``lfm2-24b-a2b.seq8192`` cell from shapes, for the two tests
    that each compile its step: (the spec, its abstract parameters, the
    bytes the trainer holds beside the step, a v5e's limit)."""
    from elasticdl_tpu.ops.mode import SWITCH

    with pytest.MonkeyPatch.context() as patch:
        patch.setenv(SWITCH, "tpu")       # the ops' own choice on a chip
        spec = tfm.model_spec(**_model_params("lfm2-24b-a2b"))
    params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    state = jax.eval_shape(spec.optimizer.init, params)
    nbytes = lambda tree: sum(
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(tree))
    limit = 16911433728           # a v5e's bytes_limit (chip run, PR 29)
    return spec, params, 2 * nbytes(params) + nbytes(state), limit


def _counted(lowered):
    """The compiler's own byte count of a step: arguments + temporaries
    (the updated state aliases the donated one)."""
    stats = lowered.compile().memory_analysis()
    return stats.argument_size_in_bytes + stats.temp_size_in_bytes


def test_the_mixed_stacks_step_fits_a_v5e_as_remat_keep_predicts(
        one_chip, monkeypatch, mixed_cell):
    """The ``lfm2-24b-a2b.seq8192`` cell's whole training step (4
    sequences of 8,192 through a dense conv layer and a period of
    attention + 3 conv layers over 8 of 64 experts, AdamW) through the
    TPU's compiler with the names ``remat_keep`` chose: its predicted
    peak is held to the compiler's own byte count (arguments +
    temporaries; the updated state aliases the donated one): over, never
    under.  This is the band that guards the chip: the cell runs under
    these names.
    With the names chosen, the convolutions' input and the experts' up
    product among them, 5.55 GB: 15.81 against 15.41 (+0.40, inside
    -0.1 / +0.5; the parent read 15.87 against 13.43 with 3.53 GB kept:
    the dense layer's kept gate and up stood in the need as well, and
    five layers' weight copies where two stand at once).  What is left
    over is not a term of the estimate's but their sum: by the buffer
    assignment the peak is in the first expert layer back-propagated,
    where no gradient of the stack exists yet (1.8 GB counted) and the
    dispatch's temporaries and the tied head's cotangent (2.8 GB) stand
    where the estimate has the dense layer's 1.54: PERF.md section 7.
    (The estimate with nothing kept is
    ``..step_with_nothing_kept_is_under_remat_keeps_estimate``'s, over a
    compile of its own: one compile a question.)"""
    from elasticdl_tpu.models import remat_keep as rk
    from elasticdl_tpu.ops import batch_shard, moe_dispatch, short_conv
    from elasticdl_tpu.ops.mode import SWITCH

    monkeypatch.setenv(SWITCH, "tpu")     # the ops' own choice on a chip
    spec, params, held, limit = mixed_cell
    room = batch_shard.DeviceRoom(limit, limit - held)
    names, kept, budget, peak = rk.choose(spec.config, params, 32768, room)
    assert kept <= budget
    assert set(names) >= set(rk.ATTN_NAMES) | {
        rk.KEEP_STREAM, rk.KEEP_GATE, rk.KEEP_UP, short_conv.KEEP_IN,
        moe_dispatch.KEEP_UP}, names
    with_names = _counted(_step(spec, one_chip, 4, 8192, room))
    assert peak <= (1 - rk.RESERVE) * limit
    assert -0.1e9 < peak - with_names < 0.5e9, (peak, with_names, names)


def test_the_mixed_stacks_step_with_nothing_kept_is_under_remat_keeps_estimate(
        one_chip, monkeypatch, mixed_cell):
    """The same cell's step with no room stated, so with nothing kept
    (no cell runs so: the trainer states the room): ``remat_keep``'s
    estimate of the step's own need, ``step_bytes``, the term every
    choice starts from, is held to the compiler's byte count apart from
    what the kept names add.  11.80 GB against the compiler's 9.80
    (+2.01: it never holds all the gradients the trainer counted, a
    layer's AdamW update runs behind its backward; 10.34 and +1.47 until
    PR 42, whose attention layer no longer makes K and V at the query
    heads nor the token-major copies of q and the output, 0.54 GB the
    estimate never had a term for: the band's upper edge moved from 1.6
    to 2.1 with it).  A test of its own so that the durations tell its
    compile from the kept names' (ROADMAP C16 asks what it buys)."""
    from elasticdl_tpu.models import remat_keep as rk
    from elasticdl_tpu.ops.mode import SWITCH

    monkeypatch.setenv(SWITCH, "tpu")     # the ops' own choice on a chip
    spec, params, held, _ = mixed_cell
    estimate = held + rk.step_bytes(spec.config, params, 32768)
    nothing_kept = _counted(_step(spec, one_chip, 4, 8192, None))
    assert -0.1e9 < estimate - nothing_kept < 2.1e9, (
        estimate, nothing_kept)


def test_the_gated_blocks_step_holds_no_update_in_a_matmul_nor_more_bytes(
        one_chip, monkeypatch):
    """The ``trinity-mini.seq16384`` cell's whole training step with the
    names ``remat_keep`` chose, for a described v5e: no weight-gradient
    matmul carries an AdamW update (39 did until PR 46) and the
    compiler's bytes are the parent's 14.69 GB within 0.1 (14.72): the
    guard against holding ``embed`` and ``lm_head`` apart as well, which
    reads 15.82."""
    from elasticdl_tpu.ops import batch_shard
    from elasticdl_tpu.ops.mode import SWITCH

    monkeypatch.setenv(SWITCH, "tpu")     # the ops' own choice on a chip
    spec = tfm.model_spec(**_model_params("trinity-mini"))
    params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    nbytes = lambda tree: sum(
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(tree))
    limit = 16911433728           # a v5e's bytes_limit (chip run, PR 29)
    held = 2 * nbytes(params) + nbytes(
        jax.eval_shape(spec.optimizer.init, params))
    compiled = _step(spec, one_chip, 1, 16384,
                     batch_shard.DeviceRoom(limit, limit - held)).compile()
    stats = compiled.memory_analysis()
    counted = stats.argument_size_in_bytes + stats.temp_size_in_bytes
    assert abs(counted - 14.693e9) < 0.1e9, counted
    assert not _updates_in_matmuls(compiled.as_text())


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
def test_the_wide_streams_step_fits_a_v5e_with_nothing_kept(one_chip,
                                                            monkeypatch):
    """The cell's whole training step (two sequences of 4,096 through a
    dense layer, four expert layers and the module's block on a stream
    four wide, 8 of 32 heads and 8 of 64 experts held, two passes of an
    untied head over 16,384 ids, AdamW; 807,416,462 parameters) through
    the TPU's compiler with nothing kept: 15.54 GB of a v5e's 16.91
    (the chip's own peak reads 15.50, my chip runs, PR 54),
    the configuration's condition for 8 heads and two sequences, so
    neither fallback is taken.  Scanned (``scan_periods`` at its
    default) the same step counts 18.25 GB: the four expert layers'
    stacked gradient stands whole.  Marked slow: the one program takes
    two minutes to compile here (my run, PR 54)."""
    from elasticdl_tpu.models import remat_keep as rk
    from elasticdl_tpu.ops.mode import SWITCH

    monkeypatch.setenv(SWITCH, "tpu")     # the ops' own choice on a chip
    spec = tfm.model_spec(**_model_params("xing4.0-29b-a4b"))
    params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    state = jax.eval_shape(spec.optimizer.init, params)
    nbytes = lambda tree: sum(
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(tree))
    assert nbytes(params) == 4 * 807416462
    held = 2 * nbytes(params) + nbytes(state)

    compiled = _step(spec, one_chip, 2, 4096).compile()
    stats = compiled.memory_analysis()
    counted = stats.argument_size_in_bytes + stats.temp_size_in_bytes
    assert counted < 0.95 * 16911433728, counted
    assert 15.4e9 < counted < 15.7e9, counted
    # ``remat_keep``'s estimate stands over it by the stack's gradients,
    # counted whole where expert layers are unrolled (ROADMAP A3 (t))
    estimate = held + rk.step_bytes(spec.config, params, 2 * 4096)
    assert 0.9e9 < estimate - counted < 1.5e9, (estimate, counted)
    text = compiled.as_text()
    names = _names(text)
    # twelve sublayers: read twice (the second forward), written twice
    # but for each block's last (its result is the next block's kept
    # input), back-propagated once; the two narrowing maps
    assert names["hc_pre_fwd"] == 2 * 12 + 2
    # their maps: made twice, back-propagated once, one call each; no
    # loop of the program's turns over the rounds' [4, 4, 8192] planes
    assert (names["hc_maps_fwd"], names["hc_maps_bwd"]) == (2 * 12, 12)
    assert not re.search(r"f32\[4,4,8192\]", text)
    assert names["hc_post_fwd"] == 2 * 12 - 6
    assert (names["hc_post_bwd"], names["hc_pre_bwd"]) == (12, 12 + 2)
    assert names["flash_fwd_qk192_v128"] == 12
    assert names["flash_bwd_qk192_v128"] == 6
    assert names["embed_grad"] == 1
