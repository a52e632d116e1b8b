"""What ``remat=True`` keeps in a dense unrolled stack, through the TPU's
own compiler for a v5e that is described and not attached: the
``olmo-hybrid-7b.seq16384`` cell's whole step with the device's room
stated, as the trainer states it on the chip.

A file of its own beside ``test_flash_compile_tpu.py`` (whose helpers
it takes), so that ``--dist loadfile`` can give the step's compile to
another worker than that file's.  Nothing runs, so no result or time is
checked here.
"""

import re

import jax

import test_flash_compile_tpu as tpu
from elasticdl_tpu.models import remat_keep as rk, transformer as tfm
from elasticdl_tpu.ops import batch_shard
from elasticdl_tpu.ops.mode import SWITCH

one_chip = tpu.one_chip


def test_the_delta_stacks_step_keeps_its_mlps_products_in_the_room_it_has(
        one_chip, monkeypatch):
    """Of the 2.68 GB of gradients that the trainer states for the four
    unrolled dense layers none stands where the step's peak is (the
    first layer back-propagated, ``grads_standing``), so ``choose``
    takes the four MLPs' gate and up products and the delta layers'
    projection of q, k, v beside the eight names it took before PR 50:
    4.50 GB kept, a predicted peak of 16.02 GB against the compiler's
    15.72 (arguments + temporaries; the chip measured 15.706: my chip
    run, PR 50), under the limit less the reserve.  The step makes
    eight ``[16384, 3840] x [3840, 11008]`` products fewer than the
    nothing-kept step of ``test_flash_compile_tpu.py`` (the second
    forward's gate and up, a layer of four) and three of the six
    ``[16384, 3840] x [3840, 5760]``; the scan still runs twice forward
    and once backward a delta layer (its 1.51 GB do not fit), and no
    matmul carries an AdamW update."""
    monkeypatch.setenv(SWITCH, "tpu")     # the ops' own choice on a chip
    spec = tfm.model_spec(**tpu._model_params("olmo-hybrid-7b"))
    params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    nbytes = lambda tree: sum(
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(tree))
    limit = 16911433728           # a v5e's bytes_limit (chip run, PR 29)
    held = 2 * nbytes(params) + nbytes(
        jax.eval_shape(spec.optimizer.init, params))
    room = batch_shard.DeviceRoom(limit, limit - held)
    names, kept, budget, peak = rk.choose(spec.config, params, 16384, room)
    assert {rk.KEEP_GATE, rk.KEEP_UP, rk.KEEP_DELTA_IN} <= set(names)
    assert kept <= budget and peak <= (1 - rk.RESERVE) * limit

    compiled = tpu._step(spec, one_chip, 1, 16384, room).compile()
    stats = compiled.memory_analysis()
    counted = stats.argument_size_in_bytes + stats.temp_size_in_bytes
    assert counted < (1 - rk.RESERVE) * limit, counted
    assert -0.1e9 < peak - counted < 0.9e9, (peak, counted)
    text = compiled.as_text()
    assert tpu._products(text, "bf16[16384,11008]") == 4 * (2 + 2 + 1) - 8
    assert tpu._products(text, "bf16[16384,5760]") == 3
    calls = [c.split(" = ")[0].lstrip("%") for c in tpu._mosaic_calls(text)]
    count = lambda name: len([c for c in calls if re.search(
        r"(^|_)" + name + r"(__)?\.\d+$", c)])
    assert (count("gdn_fwd"), count("gdn_bwd")) == (6, 3), calls
    assert (count("flash_fwd"), count("flash_bwd")) == (1, 1), calls
    assert not tpu._updates_in_matmuls(text)
