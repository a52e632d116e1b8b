"""The dropless MoE: the grouped-matmul kernel against its reference
(interpret mode), the sorted dispatch and the zoo's OLMoE-shaped model
against the plain reference of the benchmark
(benchmark/reference/olmoe1b7b.py), and the step statistics' way out of
CollectiveTrainer.  Float32 on the CPU at tiny widths; the kernel through
the TPU's compiler is tests/test_flash_compile_tpu.py's."""

import dataclasses
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from benchmark.lib import manifest
from elasticdl_tpu.models import transformer as tfm
from elasticdl_tpu.ops import grouped_matmul as gm
from elasticdl_tpu.worker import worker as worker_mod
from elasticdl_tpu.worker.collective_trainer import CollectiveTrainer

REF = manifest.load_named("reference", "olmoe1b7b")

# (rows, group sizes, row tile): what the dispatch can hand the kernel.
GROUPS = {
    "even": (64, [16, 16, 16, 16], 16),
    "empty_groups": (64, [10, 0, 30, 0, 24], 16),
    "one_group_holds_all": (64, [0, 64, 0, 0], 16),
    "last_group_holds_all": (64, [0, 0, 0, 64], 16),
    "ends_inside_tiles": (64, [1, 2, 29, 32], 16),
    "rows_not_a_tile_multiple": (70, [1, 0, 30, 39], 16),
    "many_groups_in_one_tile": (32, [3, 1, 0, 2, 5, 4, 9, 8], 32),
}


def _operands(m, sizes, transposed, seed=0):
    rng = np.random.default_rng(seed)
    k, n, x = 32, 48, len(sizes)
    lhs = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal(
        (x, n, k) if transposed else (x, k, n)), jnp.float32)
    return lhs, rhs, jnp.asarray(sizes, jnp.int32)


@pytest.mark.parametrize("transposed", [False, True])
@pytest.mark.parametrize("case", sorted(GROUPS))
def test_grouped_matmul_matches_reference(monkeypatch, case, transposed):
    """Forward, input gradient and weight gradient; shared tiles in two
    or four blocks of 8 rows."""
    monkeypatch.setattr(gm, "SUB_ROWS", 8)
    m, sizes, tm = GROUPS[case]
    lhs, rhs, group_sizes = _operands(m, sizes, transposed)
    cot = jnp.asarray(np.random.default_rng(1).standard_normal(
        (m, 48)), jnp.float32)

    def kernel(lhs, rhs):
        return gm.grouped_matmul(lhs, rhs, group_sizes, transposed,
                                 interpret=True, row_tile_rows=tm)

    def reference(lhs, rhs):
        return gm.grouped_matmul_ref(lhs, rhs, group_sizes, transposed)

    got, got_vjp = jax.vjp(kernel, lhs, rhs)
    want, want_vjp = jax.vjp(reference, lhs, rhs)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for g, w in zip(got_vjp(cot), want_vjp(cot)):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("seed", range(4))
def test_work_items_cover_every_row_once_and_stay_bounded(seed):
    rng = np.random.default_rng(seed)
    x, tm, tiles = 8, 16, 12
    m = tm * tiles
    cuts = np.sort(rng.integers(0, m + 1, x - 1))
    sizes = np.diff(np.concatenate([[0], cuts, [m]]))
    offsets, group_ids, tile_ids, count = jax.tree_util.tree_map(
        np.asarray, gm.work_items(jnp.asarray(sizes, jnp.int32), m, tm))
    assert count <= tiles + x - 1 == len(group_ids)
    owned = np.zeros(m, int)
    for g, t in zip(group_ids[:count], tile_ids[:count]):
        lo, hi = max(offsets[g], t * tm), min(offsets[g + 1], (t + 1) * tm)
        assert hi > lo                   # no item without rows of its own
        owned[lo:hi] += 1
    assert (owned == 1).all()
    # items past the count repeat the last one: no new block is fetched
    assert (group_ids[count:] == group_ids[count - 1]).all()
    assert (tile_ids[count:] == tile_ids[count - 1]).all()
    # what the kernel computes beyond the real rows: blocks, not tiles
    padded = int(gm.padded_rows(jnp.asarray(sizes, jnp.int32), m))
    sub = min(gm.SUB_ROWS, gm.row_tile(m))
    assert 0 <= padded <= (x - 1) * sub + -m % sub
    assert padded == sum(
        (-(-offsets[g + 1] // sub) - offsets[g] // sub) * sub - sizes[g]
        for g in range(x) if sizes[g])


def _cfg(top_k, experts=8, layers=2):
    return dict(vocab_size=128, dim=64, num_heads=2, num_layers=layers,
                seq_len=64, dtype="float32", ffn_dim=32,
                moe_experts=experts, moe_top_k=top_k, moe_norm_topk="false",
                qk_norm="true", norm_eps=1e-5, tied_embeddings="false")


def _reference_loss(params, tokens, top_k):
    return REF.loss(params, tokens, heads=2, top_k=top_k, eps=1e-5,
                    theta=10000.0)


def _tokens(b=2, t=64, seed=3):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, 128, (b, t)), jnp.int32)


def _product_loss(spec, params, tokens):
    out = spec.apply_fn(params, tokens, True)
    return spec.loss_fn(out, tokens).mean(), out["moe_load"]


@pytest.mark.parametrize("mode", ["interpret", "off"])
@pytest.mark.parametrize("top_k", [2, 8])
def test_model_loss_and_gradients_match_the_plain_reference(
        monkeypatch, top_k, mode):
    """The zoo's apply_fn + loss_fn against benchmark/reference at 1e-5,
    by the kernel (interpret) and by the reference product; top-8 of 8
    sends every token to every expert."""
    monkeypatch.setenv("ELASTICDL_FLASH", mode)
    spec = tfm.model_spec(**_cfg(top_k))
    params = jax.jit(spec.init_fn)(jax.random.PRNGKey(0))
    # unit-scale activations, and a router that really discriminates
    params["embed"] = params["embed"] * 25.0
    params["layers"]["w_router"] = params["layers"]["w_router"] * 20.0
    tokens = _tokens()
    (got, load), got_grads = jax.jit(jax.value_and_grad(
        lambda p: _product_loss(spec, p, tokens), has_aux=True))(params)
    (want, chosen), want_grads = jax.jit(jax.value_and_grad(
        lambda p: (lambda l, c: (l.mean(), c))(
            *_reference_loss(p, tokens, top_k)), has_aux=True))(params)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    # identical routing: each layer's assignments per expert
    np.testing.assert_array_equal(
        np.asarray(load)[:, :-1],
        np.stack([np.asarray(c).sum(axis=(0, 1)) for c, _ in chosen]))
    flat_got = jax.tree_util.tree_leaves_with_path(got_grads)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    for path, g in flat_got:
        w = flat_want[path]
        scale = float(jnp.abs(w).max()) or 1.0
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5 * scale,
                                   err_msg=str(path))


@pytest.mark.parametrize("held", [0, 2])
def test_a_collapsed_router_drops_no_row(monkeypatch, held):
    """Every token's first two choices are experts 0 and 1: 64 rows each
    where a capacity of T * K * 2 / X + 1 = 33 would have dropped half.
    The result must still equal the reference's.  ``held`` = 2: a model
    that holds experts 0 and 1 alone, whose bound is a quarter of the
    4 x 64 x 2 rows and every row theirs: four blocks, the reference the
    whole layer with the six absent experts' weights zeros."""
    monkeypatch.setenv("ELASTICDL_FLASH", "interpret")
    spec = tfm.model_spec(moe_experts_held=held, **_cfg(2, layers=1))
    params = jax.jit(spec.init_fn)(jax.random.PRNGKey(1))
    params["embed"] = jnp.abs(params["embed"]) * 25.0 + 0.1
    params["layers"]["ln2"] = jnp.abs(params["layers"]["ln2"])
    router = np.zeros((1, 64, 8), np.float32)
    router[..., 0], router[..., 1] = 10.0, 9.0
    params["layers"]["w_router"] = jnp.asarray(router)
    tokens = _tokens(b=4 if held else 1)
    got, load = jax.jit(lambda p: _product_loss(spec, p, tokens))(params)
    whole = dict(params, layers=dict(params["layers"], **{
        name: jnp.pad(w, ((0, 0), (0, 8 - w.shape[1]), (0, 0), (0, 0)))
        for name, w in params["layers"].items()
        if name in ("w_gate", "w_up", "w_down")}))
    want = jax.jit(lambda p: _reference_loss(p, tokens, 2)[0].mean())(whole)
    load = np.asarray(load)[0]
    if held:
        # experts 0 and 1, padded rows, moved, shards that spilled
        assert list(load[[0, 1, 3, 4]]) == [256, 256, 4 * 128, 1]
    else:
        assert load[0] == load[1] == 64 and load[2:-1].sum() == 0
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_decode_through_the_cache_matches_the_forward_pass():
    """One dispatch serves ``_decode_layer`` too, and drops nothing, so
    prefill + decode equals the full forward (it could not under a
    capacity)."""
    cfg = dataclasses.replace(
        tfm.model_spec(**_cfg(2)).config, max_seq_len=16)
    params = tfm.init_params(jax.random.PRNGKey(4), cfg)
    tokens = _tokens(b=2, t=9)
    full = tfm.forward(params, tokens, cfg)
    logits, caches = tfm.prefill(params, cfg, tokens[:, :8], 16)
    np.testing.assert_allclose(logits, full[:, 7], rtol=2e-4, atol=2e-5)
    step, _ = tfm.decode_step(params, cfg, caches, 8, tokens[:, 8])
    np.testing.assert_allclose(step, full[:, 8], rtol=2e-4, atol=2e-5)


def test_model_params_arrive_as_strings():
    spec = tfm.model_spec(**_cfg(2))
    assert spec.config.qk_norm is True
    assert spec.config.moe_norm_topk is False
    assert spec.config.tied_embeddings is False
    assert spec.config.mlp_dim == 32 and spec.config.norm_eps == 1e-5
    assert "lm_head" in jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    assert not hasattr(spec.config, "moe_capacity_factor")
    with pytest.raises(ValueError, match="qk_norm"):
        tfm.model_spec(**dict(_cfg(2), qk_norm="yes"))


@pytest.mark.parametrize("devices", [2, 4])
def test_trainer_on_a_data_mesh_matches_one_device(monkeypatch, devices):
    """The kernel per shard of the trainer's data axis, weights
    replicated: the loss and the step statistics of one device."""
    monkeypatch.setenv("ELASTICDL_FLASH", "interpret")
    spec = tfm.model_spec(**_cfg(2, layers=1))
    tokens = np.asarray(_tokens(b=4))
    one = CollectiveTrainer(spec, batch_size=4, rng_seed=0)
    mesh = Mesh(np.array(jax.devices()[:devices]), axis_names=("data",))
    many = CollectiveTrainer(spec, batch_size=4, mesh=mesh, rng_seed=0)
    for _ in range(2):
        loss_one, _ = one.train_minibatch(tokens, tokens)
        loss_many, _ = many.train_minibatch(tokens, tokens)
        np.testing.assert_allclose(float(loss_many), float(loss_one),
                                   rtol=2e-5)
        a = np.asarray(one.last_step_stats["moe_load"])
        b = np.asarray(many.last_step_stats["moe_load"])
        assert a.shape == (1, 9) and a[0, :-1].sum() == 4 * 64 * 2
        np.testing.assert_array_equal(a[:, :-1], b[:, :-1])


def test_accumulation_sums_the_step_statistics(monkeypatch):
    monkeypatch.setenv("ELASTICDL_FLASH", "off")
    spec = tfm.model_spec(**_cfg(2, layers=1))
    tokens = np.asarray(_tokens(b=4))
    whole = CollectiveTrainer(spec, batch_size=4, rng_seed=0)
    split = CollectiveTrainer(spec, batch_size=2, rng_seed=0, accum_steps=2)
    whole.train_minibatch(tokens, tokens)
    split.train_minibatch(tokens, tokens)
    np.testing.assert_array_equal(
        np.asarray(whole.last_step_stats["moe_load"]),
        np.asarray(split.last_step_stats["moe_load"]))


def test_a_spec_without_statistics_adds_no_output_to_the_step():
    """The statistics ride out as an empty tuple: the step program of
    every other model returns params, optimizer state and the loss, as it
    did before the channel."""
    from elasticdl_tpu.models.spec import load_model_spec

    spec = load_model_spec("mnist")
    assert spec.step_stats_fn is None
    trainer = CollectiveTrainer(spec, batch_size=8)
    x = np.zeros((8, 28, 28, 1), np.float32)
    y = np.zeros((8,), np.int32)
    prepared = trainer.prepare_batch(x, y)
    lowered = trainer._train_step.lower(
        trainer._params, trainer._opt_state, prepared.features,
        prepared.labels, prepared.weights)
    outs = jax.tree_util.tree_leaves(lowered.out_info)
    state = jax.tree_util.tree_leaves(
        (trainer._params, trainer._opt_state))
    assert len(outs) == len(state) + 1
    trainer.train_minibatch(x, y)
    assert trainer.last_step_stats == ()


def test_the_worker_logs_one_moe_load_line(caplog):
    load = np.array([[10, 0, 30, 24, 16], [16, 16, 16, 16, 32]], np.float32)
    # the repo's loggers do not propagate to the root one
    worker_mod.logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.INFO, logger=worker_mod.logger.name):
            worker_mod._log_step_stats(40, {"moe_load": load})
            worker_mod._log_step_stats(41, ())
    finally:
        worker_mod.logger.removeHandler(caplog.handler)
    lines = [r.getMessage() for r in caplog.records]
    assert lines == ["moe load: step=40 layers=2 rows=128 max=30 "
                     "mean=16.0 padded_rows=48 moved=128 spilled=0"]
