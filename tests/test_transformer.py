"""Flagship transformer: forward parity across parallelism layouts, and a
full 4-axis (dp/pp/tp/sp) train step on the virtual 8-device mesh."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.models import transformer as tfm
from elasticdl_tpu.parallel.mesh import build_mesh
from elasticdl_tpu.parallel.spmd_trainer import SPMDTrainer

CFG = tfm.TransformerConfig(
    vocab_size=128, dim=64, num_heads=4, num_layers=2,
    max_seq_len=32, dtype="float32",
)


def make_tokens(b=4, t=32, seed=0):
    rng = np.random.RandomState(seed)
    return rng.randint(0, CFG.vocab_size, size=(b, t)).astype(np.int32)


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(jax.random.PRNGKey(0), CFG)


def test_forward_shapes_and_finite(params):
    tokens = make_tokens()
    logits = tfm.forward(params, tokens, CFG)
    assert logits.shape == (4, 32, CFG.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.parametrize(
    "axes", [dict(dp=2, tp=2, sp=2), dict(dp=1, tp=4, sp=2),
             dict(dp=8, tp=1, sp=1), dict(dp=1, pp=2, tp=2, sp=2)]
)
def test_sharded_forward_matches_single_device(params, axes):
    tokens = make_tokens()
    ref = np.asarray(tfm.forward(params, tokens, CFG))
    mesh = build_mesh(**axes)
    sharded = tfm.shard_params(params, mesh, CFG)
    out = jax.jit(
        lambda p, t: tfm.forward(p, t, CFG, mesh=mesh)
    )(sharded, tokens)
    np.testing.assert_allclose(ref, np.asarray(out), rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("chunk", [8, 12, 32])
def test_chunked_xent_matches_dense(params, chunk):
    """next_token_loss_chunked == next_token_loss(_head(hidden)) in
    value AND gradients — incl. chunk=12 (T-1=31 pads to 36) and
    chunk=32 (single padded chunk).  This is the no-[B,T,V]-logits
    training path the flagship LM bench uses."""
    tokens = make_tokens(b=2, t=32, seed=3)

    def dense_loss(p):
        logits = tfm.forward(p, tokens, CFG)
        return tfm.next_token_loss(logits, tokens).mean()

    def chunked_loss(p):
        hidden, _aux = tfm.forward_hidden(p, tokens, CFG)
        return tfm.next_token_loss_chunked(
            p, hidden, tokens, CFG, chunk=chunk
        ).mean()

    l0, g0 = jax.jit(jax.value_and_grad(dense_loss))(params)
    l1, g1 = jax.jit(jax.value_and_grad(chunked_loss))(params)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4,
                                                atol=1e-6),
        g0, g1,
    )


def test_model_spec_xent_chunk_trains_like_dense():
    """model_spec(xent_chunk=N) is a product option: same loss as the
    dense spec through a real CollectiveTrainer minibatch."""
    from elasticdl_tpu.worker.collective_trainer import CollectiveTrainer

    kwargs = dict(vocab_size=64, dim=32, num_heads=2, num_layers=2,
                  seq_len=16, dtype="float32")
    toks = np.random.RandomState(1).randint(
        0, 64, size=(4, 16)).astype(np.int32)
    losses = {}
    for name, extra in (("dense", {}), ("chunked", {"xent_chunk": 8})):
        spec = tfm.model_spec(**kwargs, **extra)
        trainer = CollectiveTrainer(spec, batch_size=4)
        loss, _ = trainer.train_minibatch(toks, toks)
        losses[name] = float(loss)
    assert np.isfinite(losses["chunked"])
    np.testing.assert_allclose(losses["chunked"], losses["dense"],
                               rtol=1e-5)


def test_gqa_equals_mha_with_tiled_kv_weights():
    """GQA correctness by construction: a GQA forward must EXACTLY
    equal the MHA forward whose wk/wv are the GQA weights tile-repeated
    per group (k_mha = repeat(k_gqa) by definition)."""
    cfg_g = dataclasses.replace(CFG, num_kv_heads=2)  # H=4, G=2
    params_g = tfm.init_params(jax.random.PRNGKey(3), cfg_g)
    L, E = CFG.num_layers, CFG.dim
    H, D, G = CFG.num_heads, CFG.head_dim, 2
    params_m = jax.tree_util.tree_map(lambda x: x, params_g)  # copy refs
    for name in ("wk", "wv"):
        w = np.asarray(params_g["layers"][name]).reshape(L, E, G, D)
        params_m["layers"][name] = jnp.asarray(
            np.repeat(w, H // G, axis=2).reshape(L, E, H * D)
        )
    tokens = make_tokens(b=2, t=32, seed=4)
    out_g = tfm.forward(params_g, tokens, cfg_g)
    out_m = tfm.forward(params_m, tokens, CFG)
    np.testing.assert_allclose(np.asarray(out_g), np.asarray(out_m),
                               rtol=1e-6, atol=1e-6)


def test_gqa_sharded_matches_single_device():
    """GQA under dp/tp/sp sharding matches the single-device forward."""
    cfg_g = dataclasses.replace(CFG, num_kv_heads=2)
    params_g = tfm.init_params(jax.random.PRNGKey(3), cfg_g)
    tokens = make_tokens()
    ref = np.asarray(tfm.forward(params_g, tokens, cfg_g))
    mesh = build_mesh(dp=2, tp=2, sp=2)
    sharded = tfm.shard_params(params_g, mesh, cfg_g)
    out = jax.jit(
        lambda p, t: tfm.forward(p, t, cfg_g, mesh=mesh)
    )(sharded, tokens)
    np.testing.assert_allclose(ref, np.asarray(out), rtol=5e-4,
                               atol=5e-4)


def test_gqa_trains_and_validates():
    from elasticdl_tpu.worker.collective_trainer import CollectiveTrainer

    spec = tfm.model_spec(vocab_size=64, dim=32, num_heads=4,
                          num_layers=2, seq_len=16, dtype="float32",
                          num_kv_heads=2)
    assert spec.config.kv_heads == 2
    toks = make_tokens(b=4, t=16, seed=6)
    trainer = CollectiveTrainer(spec, batch_size=4)
    loss, _ = trainer.train_minibatch(toks % 64, toks % 64)
    assert np.isfinite(float(loss))
    with pytest.raises(ValueError, match="num_kv_heads"):
        tfm.model_spec(vocab_size=64, dim=32, num_heads=4,
                       num_layers=2, seq_len=16, num_kv_heads=3)


def _rollout_reference(params, cfg, prompt, max_new):
    """Teacher-forced greedy rollout through the FULL forward — the
    no-cache reference generate() must match exactly."""
    tokens = np.asarray(prompt)
    for _ in range(max_new):
        logits = tfm.forward(params, jnp.asarray(tokens), cfg)
        nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
        tokens = np.concatenate([tokens, nxt[:, None]], axis=1)
    return tokens


@pytest.mark.parametrize(
    "variant", ["dense", "gqa", "window", "gqa+window"])
def test_generate_matches_full_forward(variant):
    """KV-cache decoding == full-forward greedy rollout, token for
    token (prefill + decode through the cache vs recomputing the whole
    prefix each step)."""
    cfg = {
        "dense": CFG,
        "gqa": dataclasses.replace(CFG, num_kv_heads=2),
        "window": dataclasses.replace(CFG, window=8),
        # Grouped decode einsum x window mask is the interaction with
        # no other exact-match coverage (advisor r4).
        "gqa+window": dataclasses.replace(CFG, num_kv_heads=2,
                                          window=8),
    }[variant]
    params = tfm.init_params(jax.random.PRNGKey(7), cfg)
    prompt = make_tokens(b=2, t=5, seed=8)
    got = np.asarray(
        jax.jit(
            lambda p, t: tfm.generate(p, cfg, t, max_new_tokens=6)
        )(params, prompt)
    )
    want = _rollout_reference(params, cfg, prompt, 6)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got[:, :5], np.asarray(prompt))


def test_generate_moe_and_sampling():
    """MoE decode is finite/valid (its exactness vs forward is
    tests/test_moe_dropless.py's: the dispatch drops nothing); temperature
    sampling stays in-vocab and respects the prompt."""
    cfg = dataclasses.replace(CFG, moe_experts=2)
    params = tfm.init_params(jax.random.PRNGKey(9), cfg)
    prompt = make_tokens(b=2, t=4, seed=10)
    out = np.asarray(tfm.generate(params, cfg, prompt, max_new_tokens=5,
                                  temperature=0.8,
                                  rng=jax.random.PRNGKey(1)))
    assert out.shape == (2, 9)
    np.testing.assert_array_equal(out[:, :4], np.asarray(prompt))
    assert ((out >= 0) & (out < CFG.vocab_size)).all()


def test_generate_edge_cases():
    params = tfm.init_params(jax.random.PRNGKey(7), CFG)
    prompt = make_tokens(b=2, t=3, seed=11)
    # max_new_tokens=0 -> the prompt back
    np.testing.assert_array_equal(
        np.asarray(tfm.generate(params, CFG, prompt, 0)),
        np.asarray(prompt))
    # one new token == full-forward argmax at the last prompt position
    out = np.asarray(tfm.generate(params, CFG, prompt, 1))
    want = np.asarray(jnp.argmax(
        tfm.forward(params, jnp.asarray(prompt), CFG)[:, -1], axis=-1))
    np.testing.assert_array_equal(out[:, -1], want)
    # empty prompt is rejected with a BOS hint
    with pytest.raises(ValueError, match="BOS"):
        tfm.generate(params, CFG, np.zeros((2, 0), np.int32), 4)


def test_lm_train_export_reload_generate(tmp_path):
    """The full flagship loop: train a step, export the servable,
    reload the weights from the npz, and generate — reloaded params
    produce the exact same greedy continuation."""
    from elasticdl_tpu.models.callbacks import ModelExporter, load_export
    from elasticdl_tpu.utils.pytree import unflatten_from_names
    from elasticdl_tpu.worker.collective_trainer import CollectiveTrainer

    spec = tfm.model_spec(vocab_size=64, dim=32, num_heads=2,
                          num_layers=2, seq_len=16, dtype="float32")
    trainer = CollectiveTrainer(spec, batch_size=4)
    toks = make_tokens(b=4, t=16, seed=12) % 64
    trainer.train_minibatch(toks, toks)
    export_dir = str(tmp_path / "export")
    ModelExporter(export_dir, model_name="lm").on_train_end(trainer)

    dense, _ = load_export(export_dir)
    reloaded = unflatten_from_names(trainer.params, dense)
    prompt = toks[:2, :4]
    out_live = np.asarray(
        tfm.generate(trainer.params, spec.config, prompt, 5))
    out_reloaded = np.asarray(
        tfm.generate(reloaded, spec.config, prompt, 5))
    np.testing.assert_array_equal(out_live, out_reloaded)


def test_model_spec_remat_validation():
    """CLI model_params arrive as strings: booleans normalize, typos
    raise instead of silently enabling full remat."""
    spec = tfm.model_spec(vocab_size=64, dim=32, num_heads=2,
                          num_layers=2, seq_len=16, remat="False")
    assert spec.config.remat is False
    spec = tfm.model_spec(vocab_size=64, dim=32, num_heads=2,
                          num_layers=2, seq_len=16, remat="true")
    assert spec.config.remat is True
    with pytest.raises(ValueError, match="remat"):
        tfm.model_spec(vocab_size=64, dim=32, num_heads=2,
                       num_layers=2, seq_len=16, remat="atn")


def test_model_spec_xent_chunk_pipelined_matches_dense():
    """xent_chunk works ON the pipelined path (the head runs on merged
    hidden states outside the pipeline) — same loss as dense pipelined."""
    mesh = build_mesh(pp=2, devices=jax.devices()[:2])
    kwargs = dict(vocab_size=64, dim=32, num_heads=2, num_layers=2,
                  seq_len=16, dtype="float32", mesh=mesh,
                  pipeline_microbatches=2)
    toks = make_tokens(b=4, t=16, seed=5)
    spec_d = tfm.model_spec(**kwargs)
    spec_c = tfm.model_spec(**kwargs, xent_chunk=8)
    params_d = spec_d.init_fn(jax.random.PRNGKey(0))
    loss_d = spec_d.loss_fn(spec_d.apply_fn(params_d, toks, True), toks)
    params_c = spec_c.init_fn(jax.random.PRNGKey(0))
    loss_c = spec_c.loss_fn(spec_c.apply_fn(params_c, toks, True), toks)
    np.testing.assert_allclose(np.asarray(loss_d), np.asarray(loss_c),
                               rtol=1e-5)


def test_remat_preserves_gradients(params):
    tokens = make_tokens(b=2, t=16)
    cfg_r = dataclasses.replace(CFG, remat=True)

    def loss(cfg):
        def f(p):
            logits = tfm.forward(p, tokens, cfg)
            return tfm.next_token_loss(logits, tokens).mean()
        return f

    l0, g0 = jax.jit(jax.value_and_grad(loss(CFG)))(params)
    l1, g1 = jax.jit(jax.value_and_grad(loss(cfg_r)))(params)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4,
                                                atol=1e-5),
        g0, g1,
    )


def test_ulysses_forward_matches_single_device(params):
    # Same sharded-parity check with the all-to-all sequence-parallel
    # path selected (attention_impl="ulysses", parallel/ulysses.py).
    tokens = make_tokens()
    cfg = dataclasses.replace(CFG, attention_impl="ulysses")
    ref = np.asarray(tfm.forward(params, tokens, cfg))
    mesh = build_mesh(dp=2, tp=2, sp=2)
    sharded = tfm.shard_params(params, mesh, cfg)
    out = jax.jit(
        lambda p, t: tfm.forward(p, t, cfg, mesh=mesh)
    )(sharded, tokens)
    np.testing.assert_allclose(ref, np.asarray(out), rtol=5e-4,
                               atol=5e-4)


MOE_CFG = tfm.TransformerConfig(
    vocab_size=128, dim=64, num_heads=4, num_layers=2,
    max_seq_len=32, dtype="float32", moe_experts=4,
)


def test_moe_forward_matches_across_sharding():
    params = tfm.init_params(jax.random.PRNGKey(3), MOE_CFG)
    tokens = make_tokens(b=4)
    ref = np.asarray(tfm.forward(params, tokens, MOE_CFG))
    assert np.isfinite(ref).all()
    mesh = build_mesh(dp=1, ep=2, tp=2, sp=2)
    sharded = tfm.shard_params(params, mesh, MOE_CFG)
    out = jax.jit(
        lambda p, t: tfm.forward(p, t, MOE_CFG, mesh=mesh)
    )(sharded, tokens)
    np.testing.assert_allclose(ref, np.asarray(out), rtol=5e-4,
                               atol=5e-4)


def test_moe_ep_train_step_learns():
    mesh = build_mesh(dp=1, ep=2, tp=2, sp=2)

    def loss_fn(params, batch):
        tokens, _ = batch
        logits = tfm.forward(params, tokens, MOE_CFG, mesh=mesh)
        return tfm.next_token_loss(logits, tokens).mean()

    trainer = SPMDTrainer(
        mesh,
        init_fn=lambda rng: tfm.init_params(rng, MOE_CFG),
        loss_fn=loss_fn,
        optimizer=optax.adamw(2e-3),
        param_specs=tfm.param_specs(MOE_CFG),
        batch_spec=P("dp", "sp"),
    )
    tokens = make_tokens(b=4)
    losses = [float(trainer.train_step((tokens, tokens)))
              for _ in range(4)]
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]


def test_full_4axis_train_step():
    mesh = build_mesh(dp=1, pp=2, tp=2, sp=2)

    def loss_fn(params, batch):
        tokens, _ = batch
        logits = tfm.forward(params, tokens, CFG, mesh=mesh)
        return tfm.next_token_loss(logits, tokens).mean()

    trainer = SPMDTrainer(
        mesh,
        init_fn=lambda rng: tfm.init_params(rng, CFG),
        loss_fn=loss_fn,
        optimizer=optax.adamw(1e-3),
        param_specs=tfm.param_specs(CFG),
        batch_spec=P("dp", "sp"),
    )
    tokens = make_tokens(b=4)
    losses = [float(trainer.train_step((tokens, tokens))) for _ in range(4)]
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0], f"no learning: {losses}"


def test_loss_decreases_matches_unsharded_trajectory():
    """dp/tp/sp sharded training must follow the single-device trajectory."""
    tokens = make_tokens(b=4)
    tx = optax.sgd(0.1)

    def make_loss(mesh):
        def loss_fn(params, batch):
            toks, _ = batch
            logits = tfm.forward(params, toks, CFG, mesh=mesh)
            return tfm.next_token_loss(logits, toks).mean()
        return loss_fn

    # single device
    params = tfm.init_params(jax.random.PRNGKey(1), CFG)
    loss_single = make_loss(None)
    opt = tx.init(params)
    traj_single = []
    p = params
    step = jax.jit(jax.value_and_grad(loss_single))
    for _ in range(3):
        l, g = step(p, (tokens, tokens))
        u, opt = tx.update(g, opt, p)
        p = optax.apply_updates(p, u)
        traj_single.append(float(l))

    mesh = build_mesh(dp=2, tp=2, sp=2)
    trainer = SPMDTrainer(
        mesh,
        init_fn=lambda rng: tfm.init_params(rng, CFG),
        loss_fn=make_loss(mesh),
        optimizer=tx,
        param_specs=tfm.param_specs(CFG),
        batch_spec=P("dp", "sp"),
        rng_seed=1,
    )
    traj_sharded = [
        float(trainer.train_step((tokens, tokens))) for _ in range(3)
    ]
    np.testing.assert_allclose(traj_single, traj_sharded, rtol=2e-3)


def test_moe_aux_loss_signals_imbalance():
    """The load-balance aux over all K choices: ~K for a near-uniform
    router, ~X under collapse (every token's first choice AND all
    probability mass on one expert) — minimizing it pushes toward
    uniform utilization."""
    rng = np.random.RandomState(0)
    B, T, E, X, F = 2, 16, 8, 4, 16
    cfg = tfm.TransformerConfig(
        vocab_size=16, dim=E, num_heads=1, num_layers=1,
        mlp_ratio=2, dtype="float32", moe_experts=X, moe_top_k=2,
    )
    # positive activations so a positive router column really dominates
    h = jnp.asarray(np.abs(rng.randn(B, T, E)).astype(np.float32) + 0.1)

    def expert_weights(w_router):
        return {
            "w_router": jnp.asarray(w_router.astype(np.float32)),
            "w_gate": jnp.asarray(
                rng.randn(X, E, F).astype(np.float32) * 0.1),
            "w_up": jnp.asarray(
                rng.randn(X, E, F).astype(np.float32) * 0.1),
            "w_down": jnp.asarray(
                rng.randn(X, F, E).astype(np.float32) * 0.1),
        }

    balanced = expert_weights(rng.randn(E, X) * 0.02)
    _, aux_balanced, _, _ = tfm._moe_ffn(h, balanced, cfg, None)

    w_collapse = np.zeros((E, X))
    w_collapse[:, 0] = 10.0  # every (positive) token votes expert 0
    collapsed = expert_weights(w_collapse)
    _, aux_collapsed, _, _ = tfm._moe_ffn(h, collapsed, cfg, None)

    assert 1.9 < float(aux_balanced) < 2.3, float(aux_balanced)  # ~K=2
    assert float(aux_collapsed) > 3.0, float(aux_collapsed)  # ~X=4


def test_moe_top2_uses_second_expert():
    """Top-2 combine must weight both chosen experts: zeroing the
    second-choice path changes the output (it didn't under top-1)."""
    cfg2 = tfm.TransformerConfig(
        vocab_size=128, dim=64, num_heads=4, num_layers=2,
        max_seq_len=32, dtype="float32", moe_experts=4, moe_top_k=2,
    )
    cfg1 = tfm.TransformerConfig(
        vocab_size=128, dim=64, num_heads=4, num_layers=2,
        max_seq_len=32, dtype="float32", moe_experts=4, moe_top_k=1,
    )
    params = tfm.init_params(jax.random.PRNGKey(5), cfg2)
    tokens = make_tokens(b=2)
    out2 = np.asarray(tfm.forward(params, tokens, cfg2))
    out1 = np.asarray(tfm.forward(params, tokens, cfg1))
    assert np.isfinite(out2).all()
    assert not np.allclose(out2, out1), (
        "top-2 output identical to top-1: second expert unused"
    )


def test_moe_aux_loss_trains_toward_balance_on_ep_mesh():
    """Training with the aux term on an ep mesh reduces router
    imbalance: expert-utilization spread shrinks vs the start."""
    mesh = build_mesh(dp=1, ep=2, tp=2, sp=2)
    cfg = tfm.TransformerConfig(
        vocab_size=128, dim=64, num_heads=4, num_layers=2,
        max_seq_len=32, dtype="float32", moe_experts=4, moe_top_k=2,
        moe_aux_weight=0.5,  # strong weight so few steps move it
    )

    def loss_fn(params, batch):
        tokens, _ = batch
        logits, aux = tfm.forward(params, tokens, cfg, mesh=mesh,
                                  return_aux=True)
        return (
            tfm.next_token_loss(logits, tokens).mean()
            + cfg.moe_aux_weight * aux
        )

    trainer = SPMDTrainer(
        mesh,
        init_fn=lambda rng: tfm.init_params(rng, cfg),
        loss_fn=loss_fn,
        optimizer=optax.adamw(5e-3),
        param_specs=tfm.param_specs(cfg),
        batch_spec=P("dp", "sp"),
    )
    tokens = make_tokens(b=4)
    aux_first = aux_last = None
    for step in range(6):
        # track the aux term itself: it must go down as balance improves
        _, aux = tfm.forward(
            jax.tree_util.tree_map(np.asarray, trainer.params),
            tokens, cfg, return_aux=True,
        )
        if aux_first is None:
            aux_first = float(aux)
        aux_last = float(aux)
        trainer.train_step((tokens, tokens))
    assert np.isfinite(aux_last)
    assert aux_last <= aux_first + 1e-3, (aux_first, aux_last)
