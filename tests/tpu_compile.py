"""What the tests that compile for a described v5e share
(``test_flash_compile_tpu.py``: the kernels and single layers;
``test_step_compile_tpu.py``, ``test_delta_step_compile_tpu.py`` and
the last cases of ``test_banded_stack.py``, ``test_mixed_stack_rows.py``
and ``test_gated_block.py``: whole training steps, a file a kind of
stack): the described chip, a cell's ``model_params``, a step
lowered from shapes, and readers of a compiled program's text.

No test lives here.  Several files and not one, because under ``--dist
loadfile`` a file is one worker's and the whole-step compiles are
minutes each (ROADMAP C16 caps a file's seconds).  Each file's worker
loads the TPU's library: several workers can because the driver's
command sets ``ALLOW_MULTIPLE_LIBTPU_LOAD=1``
(``/root/TESTS_LAST_RUN.json``); without it run the files in one
process, or the other workers' fixtures skip.  The topology is
described inside a fixture, never at import.
"""

import collections
import functools
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any refusal means no compiler
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out.  And it is the
    # program a chip would run: with the optimisations conftest.py turns
    # off for the CPU's programs.
    was = {"jax_enable_compilation_cache":
           jax.config.jax_enable_compilation_cache,
           "jax_disable_most_optimizations":
           jax.config.read("jax_disable_most_optimizations")}
    for name in was:
        jax.config.update(name, False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    for name in was:
        jax.config.update(name, was[name])
    compilation_cache.reset_cache()


def _model_params(config):
    """A benchmark configuration's ``model_params``, as the cell's job
    gives them to ``model_spec``."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           config + ".json")) as fh:
        return json.load(fh)["cli"]["model_params"]


def _mosaic_calls(text):
    return [l.strip() for l in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in l]


def _names(text):
    """How many Mosaic calls of a compiled program carry each name."""
    names = [c.split(" = ")[0].lstrip("%") for c in _mosaic_calls(text)]
    return collections.Counter(
        re.sub(r"^(checkpoint_|jvp_|transpose_)+|(__)?[._]*\d+$", "", n)
        for n in names)


def _fused_computations(text):
    """name -> the instructions of every fused computation of a
    compiled program's text, each cut before its metadata."""
    bodies, body = {}, None
    for line in text.splitlines():
        head = re.match(r"%?(fused_computation[\w.\-]*) .*\{$", line)
        if head:
            body = bodies.setdefault(head.group(1), [])
        elif line.startswith("}"):
            body = None
        elif body is not None:
            body.append(line.split(", metadata=")[0].strip())
    return bodies


def _updates_in_matmuls(text):
    """The fused computations that hold both a matmul and the square
    root of AdamW's update: a weight gradient with its update as the
    epilogue."""
    return [name for name, body in _fused_computations(text).items()
            if any(" convolution(" in l for l in body)
            and any(" sqrt(" in l for l in body)]


def _products(text, result):
    """How many fused computations of a compiled program's text hold a
    matmul whose result is ``result`` (as ``bf16[16384,11008]``)."""
    return len([body for body in _fused_computations(text).values()
                if any(" = %s{" % result in l and " convolution(" in l
                       for l in body)])


EntryOp = collections.namedtuple("EntryOp", "name op results operands")

_WIDTH = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
          "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
          "u64": 8}


def _entry_ops(text):
    """The instructions of a compiled program's entry computation, in
    order: (name, opcode, its results as (type, dims) pairs, the names
    of its operands).  What stands here is what the chip runs as an
    operation of its own and a trace names: a ``copy`` or a ``reshape``
    inside a fused computation is the fusion's, and is not listed."""
    ops, inside = [], False
    for line in text.splitlines():
        if line.startswith("ENTRY "):
            inside = True
        elif line.startswith("}"):
            inside = False
        elif inside:
            made = re.match(
                r"\s*(?:ROOT )?%?(\S+) = (.*?) ([\w-]+)\((.*?)\)(?:, |$)",
                line.split(", metadata=")[0])
            if made:
                ops.append(EntryOp(
                    made.group(1), made.group(3),
                    [(kind, tuple(int(d) for d in dims.split(",") if d))
                     for kind, dims in re.findall(r"(\w+)\[([\d,]*)\]",
                                                  made.group(2))],
                    re.findall(r"%([\w.\-]+)", made.group(4))))
    return ops


def _moved_bytes(ops):
    """name -> the bytes an entry instruction reads and writes, taken
    from shapes: its results and its operands' results, each whole (a
    fusion that reads a slice of an operand is counted high).  Over
    HBM's 819 GB/s this priced the copy, reshape, broadcast, slice,
    reduce and pad classes of ``nemotron-3-nano-30b-a3b.seq16384``'s
    step to ~10% of the chip's trace (PERF.md section 6, PR 62)."""
    size = lambda results: sum(
        _WIDTH.get(kind, 0) * math.prod(dims) for kind, dims in results)
    wrote = {op.name: size(op.results) for op in ops}
    return {op.name: wrote[op.name] + sum(wrote.get(name, 0)
                                          for name in op.operands)
            for op in ops}


def _step(spec, one_chip, batch, rows, room=None):
    """A cell's whole training step (loss, gradients, AdamW) compiled
    for the described chip from shapes."""
    import optax

    from elasticdl_tpu.ops import batch_shard

    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    state = jax.eval_shape(spec.optimizer.init, params)
    tokens = jax.ShapeDtypeStruct((batch, rows), jnp.int32,
                                  sharding=one_chip)

    def step(params, state, tokens):
        def loss(p):
            with batch_shard.batch_axis(None, None, room):
                out = spec.apply_fn(p, tokens, True)
                return spec.loss_fn(out, tokens).mean()

        value, grads = jax.value_and_grad(loss)(params)
        updates, state2 = spec.optimizer.update(grads, state, params)
        return optax.apply_updates(params, updates), state2, value

    return jax.jit(step, donate_argnums=(0, 1)).lower(
        on_chip(params), on_chip(state), tokens)


# a v5e's bytes_limit (chip run, PR 29)
V5E_LIMIT = 16911433728

CellStep = collections.namedtuple(
    "CellStep", "spec params held chosen compiled counted")


def _cell_step(one_chip, config, batch, rows, keep):
    """A benchmark cell's whole training step compiled for the described
    chip, with what ``remat_keep`` chooses under a v5e's room kept
    (``keep``) or with no room stated, so with nothing kept: (the spec,
    its abstract parameters, the bytes the trainer holds beside the
    step, ``choose``'s answer under the room, the compiled program, the
    compiler's own byte count of it: arguments + temporaries, the
    updated state aliasing the donated one)."""
    from elasticdl_tpu.models import remat_keep as rk, transformer as tfm
    from elasticdl_tpu.ops import batch_shard
    from elasticdl_tpu.ops.mode import SWITCH

    with pytest.MonkeyPatch.context() as patch:
        patch.setenv(SWITCH, "tpu")       # the ops' own choice on a chip
        spec = tfm.model_spec(**_model_params(config))
        params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
        state = jax.eval_shape(spec.optimizer.init, params)
        nbytes = lambda tree: sum(
            a.size * a.dtype.itemsize
            for a in jax.tree_util.tree_leaves(tree))
        held = 2 * nbytes(params) + nbytes(state)
        room = batch_shard.DeviceRoom(V5E_LIMIT, V5E_LIMIT - held)
        chosen = rk.choose(spec.config, params, batch * rows, room)
        compiled = _step(spec, one_chip, batch, rows,
                         room if keep else None).compile()
    stats = compiled.memory_analysis()
    return CellStep(spec, params, held, chosen, compiled,
                    stats.argument_size_in_bytes + stats.temp_size_in_bytes)


@pytest.fixture(scope="module")
def cell_steps(one_chip):
    """``_cell_step`` of the module's chip, each (configuration, batch,
    rows, keep) compiled once for all the tests of a file that read it
    (a whole step is a minute or more of one worker: ROADMAP C16)."""
    return functools.lru_cache(maxsize=None)(
        functools.partial(_cell_step, one_chip))


def _estimate(step, rows, keep):
    """``remat_keep``'s predicted peak for a ``CellStep``: ``choose``'s
    with its list kept, the trainer's state and ``step_bytes`` with
    nothing kept."""
    from elasticdl_tpu.models import remat_keep as rk

    if keep:
        return step.chosen[3]
    return step.held + rk.step_bytes(step.spec.config, step.params, rows)


def _bare_estimate_is_bounded(step, rows):
    """What a compile with ``choose``'s list kept says of the estimate
    with nothing kept (``held + step_bytes``: a step no cell runs, whose
    own compile was a minute or two of tier-1, ROADMAP C16).  Keeping
    adds bytes, at most the bytes kept: the nothing-kept step counts
    between the kept step's count less those bytes and that count, and
    an estimate over it by under 0.9 GB lies between the same ends, the
    upper moved out by the band.  Wide: the kept step's band guards the
    chip (``_inventory_is_held``), and ``olmo-hybrid-7b``'s step is
    still compiled with nothing kept."""
    estimate, kept = _estimate(step, rows, False), step.chosen[1]
    assert kept > 0 and step.counted - kept < estimate < (
        step.counted + 0.9e9), (estimate, step.counted, kept)


def _inventory_is_held(cell_steps, config, batch, rows, keep, bare=()):
    """The body of the step-compile files' test of the same name:
    ``remat_keep``'s predicted peak of an unrolled stack with expert
    layers against the compiler's count of a ``CellStep``, over and
    never under: by under 0.5 GB with ``choose``'s list kept, the step a
    cell runs; with nothing kept (a step no cell runs) by under 0.9
    where the file compiles that step (``bare``: the slow cases), and
    as far as the kept compile bounds it elsewhere."""
    from elasticdl_tpu.models import remat_keep as rk

    read = keep or config not in bare     # the compile the case reads
    step = cell_steps(config, batch, rows, read)
    assert rk.dispatch_bytes(step.spec.config, batch * rows) > 0
    if read and not keep:
        return _bare_estimate_is_bounded(step, batch * rows)
    estimate = _estimate(step, batch * rows, keep)
    assert 0 < estimate - step.counted < (0.5e9 if keep else 0.9e9), (
        estimate, step.counted)
