"""The embedding lookup with a derivative of its own
(``ops/embed_rows.py``): the value is the plain lookup's bit for bit, the
table's gradient is the cotangent's rows added in float32 (by the kernel
in interpret mode and by its jnp reference), per shard of the trainer's
data axis where there is one, and the decode paths compile what they
compiled.
"""

import dataclasses
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from elasticdl_tpu.models import transformer as tfm
from elasticdl_tpu.ops import embed_rows as er
from elasticdl_tpu.ops.batch_shard import batch_axis
from elasticdl_tpu.ops.mode import SWITCH

# two blocks of the table, the second ragged; a row one vreg wide
VOCAB, DIM = 300, 128


def _ids(kind):
    """[B, T] ids below ``VOCAB - 1``: the last id never occurs."""
    rng = np.random.default_rng(7)
    if kind == "zipf_thousand_of_one_id":
        # a Zipf draw whose commonest id is over a thousand of 4,096
        p = np.arange(1, VOCAB, dtype=np.float64) ** -1.6
        ids = rng.permutation(VOCAB - 1)[
            rng.choice(VOCAB - 1, 4096, p=p / p.sum())]
        assert np.bincount(ids).max() > 1000
        return ids.reshape(1, -1)
    if kind == "unsorted":
        return rng.permutation(VOCAB - 1)[None, ::-1].copy()
    if kind == "batch_of_four":
        return rng.integers(0, VOCAB - 1, (4, 24))
    raise AssertionError(kind)


KINDS = ("zipf_thousand_of_one_id", "unsorted", "batch_of_four")


def _rows(table, tokens, dtype, mode="off", axis=None):
    return er._embed_rows(table, tokens, jnp.dtype(dtype), axis, mode)


def _table(dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(1), (VOCAB, DIM), dtype)


def _cotangent(shape, dtype):
    return jax.random.normal(jax.random.PRNGKey(2), shape).astype(dtype)


def _exact_grad(tokens, g):
    """The table's gradient in float64 on the host."""
    out = np.zeros((VOCAB, DIM), np.float64)
    np.add.at(out, np.asarray(tokens).reshape(-1),
              np.asarray(g.astype(jnp.float32), np.float64).reshape(-1, DIM))
    return out


@pytest.mark.parametrize("mode", ["off", "interpret"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", KINDS)
def test_value_and_table_gradient_are_the_plain_lookups(kind, dtype, mode):
    """Against ``jax.vjp`` of the plain float32 ``table[tokens]``: the
    value is ``table.astype(dtype)[tokens]`` bit for bit, the gradient
    the float32 one (a row no id names is exactly 0), in the table's
    dtype; by the jnp form and by the kernel in interpret mode."""
    dtype = jnp.dtype(dtype)
    table, tokens = _table(), jnp.asarray(_ids(kind), jnp.int32)
    g = _cotangent(tokens.shape + (DIM,), dtype)

    out, pull = jax.vjp(lambda t: _rows(t, tokens, dtype, mode), table)
    np.testing.assert_array_equal(out, table.astype(dtype)[tokens])
    assert out.dtype == dtype
    (grad,) = pull(g)
    assert grad.dtype == table.dtype and grad.shape == table.shape

    _, plain = jax.vjp(lambda t: t[tokens], table)
    (want,) = plain(g.astype(jnp.float32))
    # float32 sums of up to two thousand values in another order
    np.testing.assert_allclose(grad, want, rtol=1e-5, atol=5e-4)
    np.testing.assert_array_equal(grad[VOCAB - 1], np.zeros(DIM))
    np.testing.assert_allclose(grad, _exact_grad(tokens, g), rtol=1e-5,
                               atol=5e-4)


def test_a_bfloat16_table_gets_its_gradient_in_bfloat16():
    """A trainer that casts the parameters itself
    (``use_bf16_compute``): the sum is still float32's, rounded once."""
    table = _table(jnp.bfloat16)
    tokens = jnp.asarray(_ids("zipf_thousand_of_one_id"), jnp.int32)
    g = _cotangent(tokens.shape + (DIM,), jnp.bfloat16)
    (grad,) = jax.vjp(lambda t: _rows(t, tokens, "bfloat16"), table)[1](g)
    assert grad.dtype == jnp.bfloat16
    want = _exact_grad(tokens, g)
    np.testing.assert_array_equal(
        grad, jnp.asarray(want, jnp.float32).astype(jnp.bfloat16))


@pytest.mark.parametrize("mode", ["off", "interpret"])
def test_float32_sums_are_no_further_from_the_reference_than_bfloat16s(mode):
    """The duplicate-heavy case: over a thousand addends of one row.
    JAX's derivative of ``table.astype(bfloat16)[tokens]`` adds them in
    bfloat16; the op's sum is float32's."""
    table = _table()
    tokens = jnp.asarray(_ids("zipf_thousand_of_one_id"), jnp.int32)
    g = _cotangent(tokens.shape + (DIM,), jnp.bfloat16)
    want = _exact_grad(tokens, g)
    (ours,) = jax.vjp(lambda t: _rows(t, tokens, "bfloat16", mode),
                      table)[1](g)
    (parents,) = jax.vjp(
        lambda t: t.astype(jnp.bfloat16)[tokens], table)[1](g)
    err = lambda got: float(np.abs(np.asarray(got, np.float64) - want).max())
    assert err(ours) <= err(parents)
    assert err(ours) < 1e-3 < err(parents), (err(ours), err(parents))


@pytest.mark.parametrize("columns", [4096, 128])
def test_the_kernel_walks_every_block_and_chunk_of_a_larger_table(
        columns, monkeypatch):
    """1,000 ids in four blocks, 2,000 rows in eight chunks of which one
    id takes half, 256 columns in one block and in two: the kernel's
    sums are the reference's, and the schedule's live steps are at most
    blocks + chunks with every block named."""
    monkeypatch.setattr(er, "COLUMNS", columns)
    rng = np.random.default_rng(3)
    ids = rng.integers(0, 1000, 2000)
    ids[rng.random(2000) < 0.5] = 333
    tokens = jnp.asarray(ids.reshape(2, -1), jnp.int32)
    g = jax.random.normal(jax.random.PRNGKey(4), (2, 1000, 256)).astype(
        jnp.bfloat16)
    got = er.rows_added(tokens, g, 1000, interpret=True)
    np.testing.assert_allclose(got, er.rows_added_ref(tokens, g, 1000),
                               rtol=1e-5, atol=1e-5)
    padded = np.concatenate([np.sort(ids), np.full(48, er._NO_ID)])
    block, chunk, live = er._schedule(jnp.asarray(padded, jnp.int32), 1000)
    assert block.shape == chunk.shape == (4 + 8,)
    assert int(live[0]) <= 12 and set(np.asarray(block)) == {0, 1, 2, 3}
    assert (np.diff(np.asarray(block)) >= 0).all()


def _model(tied, multiplier):
    return tfm.model_spec(vocab_size=VOCAB, dim=DIM, num_heads=2,
                          num_layers=2, seq_len=24, dtype="float32",
                          tied_embeddings=tied, embed_multiplier=multiplier)


def _plain_rows(table, tokens, dtype, mesh=None):
    return table.astype(dtype)[tokens]


@pytest.mark.parametrize("mode", ["off", "interpret"])
@pytest.mark.parametrize("tied", [True, False])
@pytest.mark.parametrize("multiplier", [1.0, 5.5])
def test_a_models_loss_and_gradients_are_the_plain_lookups(
        tied, multiplier, mode, monkeypatch):
    """Through ``model_spec``, tied (the table's gradient is the op's
    plus the head's) and untied, with and without ``embed_multiplier``,
    which stays outside the op; the model's own choice of the form
    (``ops/mode.py``)."""
    monkeypatch.setenv(SWITCH, mode)
    spec = _model(tied, multiplier)
    params = jax.jit(spec.init_fn)(jax.random.PRNGKey(0))
    tokens = jnp.asarray(_ids("batch_of_four"), jnp.int32)

    def value_and_grads():
        return jax.value_and_grad(lambda p: spec.loss_fn(
            spec.apply_fn(p, tokens, True), tokens).mean())(params)

    loss, grads = value_and_grads()
    monkeypatch.setattr(tfm, "embed_rows", _plain_rows)
    plain_loss, plain = value_and_grads()
    assert float(loss) == pytest.approx(float(plain_loss), rel=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7),
        grads, plain)
    assert float(jnp.abs(grads["embed"]).max()) > 0
    np.testing.assert_array_equal(
        grads["embed"][VOCAB - 1] if not tied else 0.0, 0.0)


@pytest.mark.parametrize("mode", ["off", "interpret"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_under_a_data_axis_each_shard_adds_its_rows_and_one_sum_crosses(
        dtype, mode, monkeypatch):
    """Four shards of the trainer's data axis: the gradient is the
    unsharded one, and what is summed over the axis is one ``[V, E]``
    table in the compute dtype (the all-reduce JAX's derivative had),
    not the float32 one."""
    monkeypatch.setenv(SWITCH, mode)
    dtype = jnp.dtype(dtype)
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    table, tokens = _table(), jnp.asarray(_ids("batch_of_four"), jnp.int32)
    g = _cotangent(tokens.shape + (DIM,), dtype)

    cfg = dataclasses.replace(_model(False, 1.0).config, dtype=dtype.name)

    def grad(table, tokens, g):
        with batch_axis(mesh, "data"):
            return jax.vjp(lambda t: tfm._embed({"embed": t}, tokens, cfg),
                           table)[1](g)[0]

    sharded = NamedSharding(mesh, P("data"))
    args = (table, jax.device_put(tokens, sharded),
            jax.device_put(g, sharded))
    got = jax.jit(grad)(*args)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(
        got, _exact_grad(tokens, g), rtol=1e-5,
        atol=1e-5 if dtype == jnp.float32 else 0.05)
    text = str(jax.make_jaxpr(grad)(*args))
    sums = [line for line in text.splitlines() if " psum" in line]
    assert len(sums) == 1 and "%s[%d,%d]" % (
        {"float32": "f32", "bfloat16": "bf16"}[dtype.name], VOCAB, DIM
    ) in sums[0], sums


def test_a_model_parallel_mesh_leaves_the_sum_to_the_partitioner(
        monkeypatch):
    """``mesh`` given (the table is sharded ``P(None, "tp")``): no
    ``shard_map`` of the op's own and no kernel, whatever axis is
    declared and whatever the switch says."""
    monkeypatch.setenv(SWITCH, "interpret")
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    cfg = _model(False, 1.0).config
    tokens = jnp.asarray(_ids("batch_of_four"), jnp.int32)

    def grad(table):
        with batch_axis(mesh, "dp"):
            return jax.grad(lambda t: tfm._embed(
                {"embed": t}, tokens, cfg, mesh).sum())(table)

    text = str(jax.make_jaxpr(grad)(_table()))
    assert not any(word in text for word in (
        "shard_map", "psum", "pallas_call"))
    np.testing.assert_allclose(
        grad(_table()),
        _exact_grad(tokens, jnp.ones(tokens.shape + (DIM,))), rtol=1e-6)


def test_one_log_line_per_compiled_shape_and_none_without_a_gradient():
    lines = []
    handler = logging.Handler()
    handler.emit = lambda record: lines.append(record.getMessage())
    er.logger.addHandler(handler)
    table = _table()[:80]
    tokens = jnp.zeros((4, 12), jnp.int32)
    rows = lambda t, tok, axis=None, mode="off": _rows(
        t, tok, "float32", mode, axis).sum()
    try:
        _rows(table, tokens, "float32")             # no gradient: no line
        for _ in range(2):
            jax.jit(jax.grad(rows))(table, tokens)
        jax.grad(rows)(table, tokens[:1])
        mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
        jax.grad(rows)(table, tokens, (mesh, "data"))
        jax.grad(rows)(table, tokens, None, "interpret")
    finally:
        er.logger.removeHandler(handler)
    assert [l for l in lines if l.startswith("embed grad:")] == [
        "embed grad: tokens=48 vocab=80 dim=128 form=scatter_add "
        "acc=float32",
        "embed grad: tokens=12 vocab=80 dim=128 form=scatter_add "
        "acc=float32",
        "embed grad: tokens=24 vocab=80 dim=128 form=scatter_add "
        "acc=float32",
        "embed grad: tokens=48 vocab=80 dim=128 form=interpreter "
        "acc=float32"]


@pytest.mark.parametrize("path", ["forward", "prefill", "decode_step"])
def test_the_paths_that_take_no_gradient_compile_what_they_compiled(
        path, monkeypatch):
    """Evaluation's forward and the decode paths call the same
    ``_embed``: with the op and with the plain lookup in its place the
    compiled program's instructions are the same, name for name."""
    spec = tfm.model_spec(vocab_size=VOCAB, dim=DIM, num_heads=2,
                          num_layers=2, seq_len=24)
    cfg = spec.config
    params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, 8), jnp.int32)

    def compiled():
        if path == "forward":
            fn, args = (lambda p, t: tfm.forward(p, t, cfg)), (params, tokens)
        elif path == "prefill":
            fn, args = (lambda p, t: tfm.prefill(p, cfg, t, 16)), (
                params, tokens)
        else:
            caches = jax.eval_shape(
                lambda p, t: tfm.prefill(p, cfg, t, 16)[1], params, tokens)
            fn = lambda p, c, t: tfm.decode_step(p, cfg, c, 8, t)
            args = (params, caches, jax.ShapeDtypeStruct((2,), jnp.int32))
        text = jax.jit(fn).lower(*args).compile().as_text()
        # an instruction without the source position it was traced at
        return [line.split(", metadata=")[0] for line in text.splitlines()
                if " = " in line]

    with_op = compiled()
    monkeypatch.setattr(tfm, "embed_rows", _plain_rows)
    assert compiled() == with_op
