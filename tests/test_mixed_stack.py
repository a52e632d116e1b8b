"""A stack whose layers differ (models/transformer.py: gated short
convolution and grouped-query attention by pattern, leading dense
layers, a sigmoid router with a selection bias, a share of the experts)
against the plain reference of the benchmark
(benchmark/reference/lfm2-24b-a2b.py), and what must not have moved: a
patternless model's parameters and program.  Float32 on the CPU at tiny
widths."""

import collections
import dataclasses
import functools
import json
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

from benchmark.lib import manifest
from elasticdl_tpu.models import remat_keep as rk
from elasticdl_tpu.models import transformer as tfm
from elasticdl_tpu.ops import batch_shard, grouped_matmul as gm
from elasticdl_tpu.ops import moe_dispatch as md
from elasticdl_tpu.ops import short_conv as sc
from elasticdl_tpu.worker import worker as worker_mod
from elasticdl_tpu.worker.collective_trainer import CollectiveTrainer
from tests import reference_check as rc
from tests.test_remat_keep import _pallas_calls

REF = manifest.load_named("reference", "lfm2-24b-a2b")
HERE = os.path.dirname(os.path.abspath(__file__))

TINY = dict(vocab_size=96, dim=128, num_heads=4, num_kv_heads=2,
            seq_len=32, dense_ffn_dim=80, ffn_dim=48, moe_experts=16,
            moe_top_k=4, moe_router="sigmoid_bias", moe_aux_weight=0,
            qk_norm="head", rope_theta=1e6, norm_eps=1e-5, dtype="float32")
# leading + periods + remainder: cc | accc accc | ac
STACK = dict(TINY, num_layers=12, layer_pattern="cc" + "accc" * 2 + "ac",
             dense_layers=2, moe_experts_held=4, moe_share_index=1)
SHAPE = dict(heads=4, kv_heads=2, top_k=4, eps=1e-5, theta=1e6,
             norm_topk=True, scale=1.0)


def _with_bias(params, seed=0, scale=0.1):
    """Every ``expert_bias`` drawn non-zero (the job's are zeros)."""
    rng = np.random.default_rng(seed)
    return jax.tree_util.tree_map_with_path(
        lambda path, a: (jnp.asarray(
            scale * rng.standard_normal(a.shape), jnp.float32)
            if path[-1].key == "expert_bias" else a), params)


_loss = rc.loss_of


def _tokens(spec, batch=2, seed=1):
    cfg = spec.config
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch, cfg.max_seq_len)), jnp.int32)


# -- the plan -------------------------------------------------------------

PUBLISHED = "cc" + "accc" * 9 + "ac"     # LFM2-24B-A2B's 40 layers


@pytest.mark.parametrize("pattern,dense,lead,period,periods,tail", [
    ("cc" + "accc" * 2 + "ac", 2, "cc", "accc", 2, "ac"),
    (PUBLISHED, 2, "cc", "accc", 9, "ac"),
    ("caccc", 1, "c", "accc", 1, ""),
    ("cccc", 0, "", "c", 4, ""),
    ("acacac", 0, "", "ac", 3, ""),
    ("aaa", 3, "aaa", "", 0, ""),
])
def test_stack_plan_splits_lead_periods_and_remainder(
        pattern, dense, lead, period, periods, tail):
    cfg = tfm.TransformerConfig(
        num_layers=len(pattern), layer_pattern=pattern, dense_layers=dense,
        dense_ffn_dim=64, moe_experts=8)
    plan = tfm.stack_plan(cfg)
    letters = lambda kinds: "".join(k.op for k in kinds)
    assert (letters(plan.lead), letters(plan.period), plan.periods,
            letters(plan.tail)) == (lead, period, periods, tail)
    assert all(k.dense for k in plan.lead)
    assert not any(k.dense for k in plan.period + plan.tail)
    assert letters(cfg.kinds) == pattern
    assert sum(k.dense for k in cfg.kinds) == dense


def test_a_patternless_model_has_no_plan_and_one_kind():
    assert tfm.stack_plan(tfm.TransformerConfig()) is None
    assert set(tfm.TransformerConfig(num_layers=3).kinds) == {
        tfm.Kind("a", True)}
    assert set(tfm.TransformerConfig(moe_experts=4).kinds) == {
        tfm.Kind("a", False)}


@pytest.mark.parametrize("bad,match", [
    (dict(layer_pattern="acx"), "letters"),
    (dict(layer_pattern="ac"), "letters"),
    (dict(layer_pattern="acc", dense_layers=1), "dense_layers"),
    (dict(moe_experts=8, moe_experts_held=3), "moe_experts_held"),
    (dict(moe_experts=8, moe_experts_held=4, moe_share_index=2),
     "moe_share_index"),
    (dict(moe_experts=4, moe_router="softmin"), "moe_router"),
])
def test_a_wrong_pattern_or_share_is_refused_by_name(bad, match):
    with pytest.raises(ValueError, match=match):
        spec = tfm.model_spec(vocab_size=64, dim=32, num_heads=2,
                              num_layers=3, seq_len=16, **bad)
        tokens = _tokens(spec)
        spec.apply_fn(spec.init_fn(jax.random.PRNGKey(0)), tokens, True)


# -- against the plain reference -------------------------------------------


@pytest.mark.parametrize("op,dense", [("a", True), ("c", True),
                                      ("a", False), ("c", False)])
def test_one_block_of_each_kind_matches_the_reference(op, dense):
    """Loss and every gradient of a one-layer model of that kind:
    1e-5 of a gradient's largest value, float32 both sides (the
    reference accumulates in another order)."""
    spec = tfm.model_spec(**dict(
        TINY, num_layers=1, layer_pattern=op, dense_layers=int(dense),
        moe_experts_held=8, moe_share_index=1))
    params = _with_bias(jax.jit(spec.init_fn)(jax.random.PRNGKey(2)))
    params["embed"] = params["embed"] * 25.0
    tokens = _tokens(spec)
    got, grads = jax.jit(jax.value_and_grad(_loss(spec, tokens)))(params)
    want, want_grads = jax.jit(jax.value_and_grad(lambda p: REF.loss(
        p, tokens, first=8, **SHAPE)[0].mean()))(params)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    flat = lambda tree: jax.tree_util.tree_leaves_with_path(tree)
    for (path, g), (_, w) in zip(flat(grads), flat(want_grads)):
        name = jax.tree_util.keystr(path)
        if "expert_bias" in name:
            assert float(jnp.abs(g).max()) == 0.0
            continue
        top = float(jnp.abs(w).max())
        assert top > 0, name
        assert float(jnp.abs(g - w).max()) <= 2e-5 * top + 1e-7, name


def _onto_share(params, cfg, by=5.0):
    """``expert_bias`` raised on the held experts: every token chooses
    them, so every row of every dispatch is a held expert's."""
    first, held = cfg.experts_held
    return jax.tree_util.tree_map_with_path(
        lambda path, a: (a.at[..., first:first + held].add(by)
                         if path[-1].key == "expert_bias" else a), params)


# the least depth with all of STACK's wiring: a dense conv lead, one
# whole period (a scan of one turn) and a tail, every kind of layer
SHORT = dict(STACK, num_layers=4, layer_pattern="c" + "ac" + "a",
             dense_layers=1)
# what the interpreter's case has to reach, the calls of STACK's own
# program by the names they carry: the convolution, the grouped matmul's
# three products, a share's row moves, the embedding's gradient (32
# positions are no flash tile: attention takes its reference at either
# depth, and its kernels' stacks are tests/test_banded_stack.py's and
# tests/test_gated_block.py's)
STACK_KERNELS = {"sconv_fwd", "sconv_bwd", "gmm_nn", "gmm_nt", "gmm_tn",
                 "rows_pack", "rows_gather", "rows_sum", "embed_grad"}


@functools.lru_cache(maxsize=None)
def _whole_stack(mode):
    """What a mode's two cases share, traced under the mode and the
    convolution's tiles that their caller has set: (the sizes, the spec,
    the tokens, the product's loss, load and gradients as one compiled
    program, as the trainer's is, the kernels it calls, the reference's
    loss and gradients), compiled once for both routers."""
    sizes = SHORT if mode == "interpret" else STACK
    spec = tfm.model_spec(remat=True, **sizes)
    tokens = _tokens(spec)

    def loss_and_load(p):
        out = spec.apply_fn(p, tokens, True)
        return spec.loss_fn(out, tokens).mean(), spec.step_stats_fn(out)

    traced = jax.jit(jax.value_and_grad(loss_and_load, has_aux=True)).trace(
        jax.eval_shape(spec.init_fn, jax.random.PRNGKey(4)))
    reference = jax.jit(jax.value_and_grad(lambda p: REF.loss(
        p, tokens, **dict(SHAPE, first=4))[0].mean()))
    return (sizes, spec, tokens, traced.lower().compile(),
            set(_pallas_calls(traced.jaxpr.jaxpr)), reference)


@pytest.mark.parametrize("mode", ["off", "interpret"])
@pytest.mark.parametrize("onto_share", [False, True])
def test_the_whole_stack_matches_the_reference(monkeypatch, mode,
                                               onto_share):
    """A share of 4 of 16 experts, non-zero ``expert_bias``: the loss,
    the gradients' tree and each layer's choice of experts.  ``off``,
    the jnp paths, at cc | accc x 2 | ac; ``interpret``, the kernels in
    interpret mode, at c | ac | a (``SHORT``: the arithmetic at depth
    is the ``off`` case's, each kernel's own its file's; what is left to
    show is that the model hands every kernel the right operands, and
    the program must call every one of ``STACK_KERNELS``).
    ``onto_share``: a router biased onto the held experts, so each
    dispatch's 256 rows are theirs and run as two blocks of the bound's
    128."""
    monkeypatch.setenv("ELASTICDL_FLASH", mode)
    monkeypatch.setattr(sc, "ROW_TILES", (16,))
    sizes, spec, tokens, product, calls, reference = _whole_stack(mode)
    assert calls == (STACK_KERNELS if mode == "interpret" else set())
    experts = sum(not k.dense for k in spec.config.kinds)
    params = _with_bias(jax.jit(spec.init_fn)(jax.random.PRNGKey(4)))
    params["embed"] = params["embed"] * 25.0
    if onto_share:
        params = _onto_share(params, spec.config)
    (got, load), grads = product(params)
    assert md.row_bound(2 * 32 * 4, 4, 16) == 128
    rows = np.asarray(load["moe_load"][:, :-1].sum(axis=1))
    if onto_share:
        np.testing.assert_array_equal(rows, [256] * experts)
    # moved: the bound times the blocks that held a held expert's row
    np.testing.assert_array_equal(
        load["moe_moved"], 128 * np.maximum(np.ceil(rows / 128), 1))
    np.testing.assert_array_equal(load["moe_spilled"], rows > 128)
    want, want_grads = reference(params)
    assert float(got) == pytest.approx(float(want), rel=2e-5)
    leaves = jax.tree_util.tree_leaves
    norm = lambda trees: float(jnp.sqrt(sum(
        jnp.sum(jnp.square(t)) for t in trees)))
    apart = norm([g - w for g, w in zip(leaves(grads), leaves(want_grads))])
    assert apart <= 1e-4 * norm(leaves(want_grads))
    # the reference's own routing check, through its door
    config = dict(
        vocab_size=96, seq_len=32, num_experts=4, share_index=1,
        num_attention_heads=4, num_key_value_heads=2, num_experts_per_tok=4,
        norm_eps=1e-5, rope_parameters={"rope_theta": 1e6},
        norm_topk_prob=True, routed_scaling_factor=1,
        cli={"model_zoo": "transformer", "model_params": sizes})
    REF.check_routing(config, params, tokens, REF.shape_of(config))


def test_the_bias_moves_the_choice_and_not_the_weights():
    """``moe_route`` under ``sigmoid_bias``: the chosen are the top K of
    score + bias (a large bias on expert 0 puts it in every token's
    choice), their weights the unbiased scores over their sum + 1e-6."""
    cfg = tfm.TransformerConfig(moe_experts=8, moe_top_k=2,
                                moe_router="sigmoid_bias")
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.standard_normal((2, 16, 32)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((32, 8)) * 0.2, jnp.float32)
    bias = jnp.zeros((8,)).at[0].set(5.0).at[3].set(-5.0)
    scores, gates, experts = tfm.moe_route(h, w, cfg, bias)
    plain = tfm.moe_route(h, w, cfg, jnp.zeros((8,)))[2]
    assert bool((experts == 0).any(-1).all())
    assert not bool((experts == 3).any())
    assert bool((plain == 3).any()) and not bool((plain == 0).any(-1).all())
    ref_scores, chosen = REF.route(h, w, bias, 2)
    np.testing.assert_allclose(scores, ref_scores, rtol=1e-6)
    np.testing.assert_array_equal(
        jax.nn.one_hot(experts, 8).sum(-2) > 0, chosen)
    picked = jnp.take_along_axis(ref_scores, experts, axis=-1)
    np.testing.assert_allclose(
        gates, picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    # unnormalised, scaled: the scores themselves times the factor
    raw = dataclasses.replace(cfg, moe_norm_topk=False, moe_route_scale=2.5)
    np.testing.assert_allclose(tfm.moe_route(h, w, raw, bias)[1],
                               2.5 * picked, rtol=1e-6)


@pytest.mark.parametrize("shares,onto", [(2, None), (4, None), (4, 1)])
def test_the_shares_add_up_to_the_uncut_layer(shares, onto):
    """16 experts in 2 and in 4 shares: the shares' expert-layer results
    add up to what the reference gives for the whole layer with all 16
    held (this model has nothing every share computes alike).  ``onto``:
    the bias sends most rows to that share, past its bound of 128 of
    the 192, and its further block adds what the first left."""
    whole = tfm.TransformerConfig(
        dim=64, ffn_dim=48, moe_experts=16, moe_top_k=4,
        moe_router="sigmoid_bias", dtype="float32")
    rng = np.random.default_rng(shares)
    draw = lambda *shape: jnp.asarray(
        rng.standard_normal(shape) * shape[-2] ** -0.5, jnp.float32)
    w = {"w_router": draw(64, 16), "w_gate": draw(16, 64, 48),
         "w_up": draw(16, 64, 48), "w_down": draw(16, 48, 64),
         "expert_bias": jnp.asarray(0.2 * rng.standard_normal(16),
                                    jnp.float32)}
    held = 16 // shares
    if onto is not None:     # three of a token's four choices, or so
        w["expert_bias"] = w["expert_bias"].at[
            onto * held:(onto + 1) * held - 1].add(5.0)
    h = jnp.asarray(rng.standard_normal((2, 24, 64)), jnp.float32)
    want, _ = REF.experts(h, w, 4, True, 1.0, 0)
    total, rows = 0.0, 0.0
    for index in range(shares):
        cfg = dataclasses.replace(whole, moe_experts_held=held,
                                  moe_share_index=index)
        part = dict(w, **{name: w[name][index * held:(index + 1) * held]
                          for name in ("w_gate", "w_up", "w_down")})
        out, _, _, load = tfm._moe_ffn(h, part, cfg, None)
        ref_part, _ = REF.experts(h, part, 4, True, 1.0, index * held)
        np.testing.assert_allclose(out, ref_part, rtol=1e-4, atol=1e-5)
        # the held experts' rows, the padded ones, rows moved, spills
        assert load.shape == (held + 3,)
        bound = md.row_bound(192, held, 16)
        spilled = float(load[:held].sum()) > bound
        assert spilled == (index == onto)
        assert list(load[held + 1:]) == [bound * (1 + spilled), spilled]
        total, rows = total + out, rows + float(load[:held].sum())
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    assert rows == 2 * 24 * 4          # every assignment held by one share


# -- rows no expert here takes (the dispatch under a share) -----------------


def _share_operands(seed=0, n=40, e=32, f=48, total=8, held=2, k=2,
                    onto=None):
    """``onto``: every token's choices are the experts ``onto ..``."""
    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.standard_normal((1, n, e)), jnp.float32)
    experts = jnp.asarray(
        np.stack([rng.permutation(total)[:k] if onto is None
                  else onto + rng.permutation(k) for _ in range(n)])[None],
        jnp.int32)
    gates = jnp.asarray(rng.random((1, n, k)), jnp.float32)
    weights = tuple(jnp.asarray(rng.standard_normal(s) * 0.2, jnp.float32)
                    for s in ((held, e, f), (held, e, f), (held, f, e)))
    return h, gates, experts, weights


@pytest.mark.parametrize("operands", [
    dict(), dict(n=200), dict(n=200, onto=4)],
    ids=["whole", "one-block-of-two", "two-blocks"])
def test_rows_of_absent_experts_never_reach_the_output(monkeypatch,
                                                       operands):
    """The kernels write nothing in the rows sorted past the held
    groups.  NaNs planted there (what undefined memory may hold), in the
    product and in the input's gradient, leave the layer's result and
    every gradient finite and equal to the reference path's: with 80
    rows, which are one block; with 400 and a bound of 256, where the
    held experts' ~100 leave the block's tail dead; and with all 400
    the held experts', whose second block ends in 112 dead rows."""
    h, gates, experts, weights = _share_operands(**operands)
    call = gm._gmm_call

    def planted(lhs, rhs, group_sizes, *rest):
        out = call(lhs, rhs, group_sizes, *rest)
        dead = jnp.arange(out.shape[0])[:, None] >= group_sizes.sum()
        return jnp.where(dead, jnp.nan, out)

    def run(mode):
        monkeypatch.setenv("ELASTICDL_FLASH", mode)

        def loss(h, gates, *weights):
            out, load = md.moe_experts(h, gates, experts, *weights,
                                       total=8, first=4)
            return (out * out).sum(), (out, load)

        return jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(h, gates, *weights)

    (_, (want, want_load)), want_grads = run("off")
    monkeypatch.setattr(gm, "_gmm_call", planted)
    (_, (out, load)), grads = run("interpret")
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
    for g, w in zip(grads, want_grads):
        assert bool(jnp.isfinite(g).all())
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)
    # the load counts all 8 experts, in the experts' own order
    np.testing.assert_array_equal(load[0, :8], want_load[0, :8])
    counts = np.bincount(np.asarray(experts).ravel(), minlength=8)
    np.testing.assert_array_equal(load[0, :8], counts)


@pytest.mark.parametrize("sizes", [[10, 0, 7], [0, 0, 0], [16, 16, 16]])
def test_zero_tail_zeroes_what_the_groups_do_not_cover(sizes):
    """``grouped_matmul(zero_tail=True)``: sizes that add up to less
    than the rows (to none of them; to all of them) give the
    reference's product, zeros past the groups, and its gradients."""
    rng = np.random.default_rng(1)
    lhs = jnp.asarray(rng.standard_normal((48, 32)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((3, 32, 24)), jnp.float32)
    cot = jnp.asarray(rng.standard_normal((48, 24)), jnp.float32)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    kernel = lambda lhs, rhs: gm.grouped_matmul(
        lhs, rhs, group_sizes, interpret=True, row_tile_rows=16,
        zero_tail=True)
    # by a last group of zero weights: nothing is left to the backend
    reference = lambda lhs, rhs: gm.grouped_matmul_ref(
        lhs, rhs, group_sizes, zero_tail=True)
    loss = lambda fn: lambda lhs, rhs: (fn(lhs, rhs) * cot).sum()
    out = kernel(lhs, rhs)
    for fn in (kernel, reference):
        assert float(jnp.abs(fn(lhs, rhs)[sum(sizes):]).max(
            initial=0.0)) == 0.0
    by_hand = jnp.concatenate([
        lhs[start:start + size] @ rhs[g] for g, (start, size) in enumerate(
            zip(np.cumsum([0] + sizes[:-1]), sizes))] + [
        jnp.zeros((48 - sum(sizes), 24))])
    np.testing.assert_allclose(out, by_hand, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(out, reference(lhs, rhs), rtol=1e-5,
                               atol=1e-5)
    got = jax.grad(loss(kernel), argnums=(0, 1))(lhs, rhs)
    want = jax.grad(loss(reference), argnums=(0, 1))(lhs, rhs)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)


# -- expert_bias is state ----------------------------------------------------


def test_no_gradient_reaches_expert_bias_and_adamw_leaves_it():
    spec = tfm.model_spec(**STACK)
    params = _with_bias(jax.jit(spec.init_fn)(jax.random.PRNGKey(0)))
    tokens = _tokens(spec)
    grads = jax.jit(jax.grad(_loss(spec, tokens)))(params)

    @jax.jit
    def third_update(params, grads):
        state = spec.optimizer.init(params)
        for _ in range(3):
            updates, state = spec.optimizer.update(grads, state, params)
        return optax.apply_updates(params, updates)

    after = third_update(params, grads)
    flat = lambda tree: jax.tree_util.tree_leaves_with_path(tree)
    seen = 0
    for (path, before), (_, now), (_, g) in zip(
            flat(params), flat(after), flat(grads)):
        if path[-1].key == "expert_bias":
            seen += 1
            assert float(jnp.abs(before).max()) > 0
            assert float(jnp.abs(g).max()) == 0.0
            np.testing.assert_array_equal(before, now)
        elif path[-1].key == "w_router":    # its neighbour does move
            assert float(jnp.abs(before - now).max()) > 0
    assert seen == 6     # four positions of the period, two tail layers
    # a softmax model's optimizer is the unmasked one it was
    plain = tfm.model_spec(vocab_size=64, dim=32, num_heads=2,
                           num_layers=2, seq_len=16, moe_experts=4)
    p = jax.eval_shape(plain.init_fn, jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(plain.optimizer.init(p)) == \
        jax.tree_util.tree_structure(
            optax.adamw(3e-4, weight_decay=0.01).init(p))


def test_warmup_steps_raises_the_rate_linearly_and_zero_is_constant():
    """``warmup_steps``: AdamW's first update is lr * sign(g) whatever
    the gradient's size, so the schedule is read off the updates."""
    sizes = dict(vocab_size=64, dim=32, num_heads=2, num_layers=1,
                 seq_len=16)
    params = {"w": jnp.ones((4,))}
    grads = {"w": jnp.full((4,), 1e-3)}

    def first_updates(spec, steps):
        state, out = spec.optimizer.init(params), []
        for _ in range(steps):
            update, state = spec.optimizer.update(grads, state, params)
            out.append(-float(update["w"][0]))
        return out

    warm = first_updates(tfm.model_spec(warmup_steps=10, **sizes), 12)
    flat = first_updates(tfm.model_spec(**sizes), 2)
    decay = 3e-4 * 0.01                       # weight decay on w = 1
    assert warm[0] == 0.0
    assert warm[5] == pytest.approx(0.5 * (3e-4 + decay), rel=1e-3)
    assert warm[11] == pytest.approx(3e-4 + decay, rel=1e-3)
    assert flat[0] == pytest.approx(3e-4 + decay, rel=1e-3)
    assert jax.tree_util.tree_structure(
        tfm.model_spec(**sizes).optimizer.init(params)) == \
        jax.tree_util.tree_structure(
            optax.adamw(3e-4, weight_decay=0.01).init(params))


# -- what must not have moved ------------------------------------------------


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (list, tuple))
                          else [value]):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def _primitives(jaxpr, out):
    out.update(eqn.primitive.name for eqn in _eqns(jaxpr))
    return out


PATTERNLESS = {
    "dense": dict(remat=True, num_kv_heads=2),
    "moe": dict(moe_experts=8, moe_top_k=2, qk_norm=True,
                tied_embeddings=False, norm_eps=1e-5, moe_norm_topk=False,
                remat=True),
}


@pytest.mark.parametrize("name", sorted(PATTERNLESS))
def test_a_patternless_models_tree_and_program_are_what_they_were(name):
    """A model with no pattern (the benchmark's two earlier
    configurations in small): the parameter tree, the seeded values and
    the training program's primitives, counted through every nested
    jaxpr, are those recorded from the tree before the stack learned
    kinds (tests/patternless_program.json, PR 29's commit); the
    primitive counts were recorded again at PR 42, whose projections
    write q, k, v head-major and whose ``wo`` contracts (head, width):
    6 ``dot_general`` more (RoPE's permutation product on q and k,
    forward and backward; ``dense``: 38 -> 44), RoPE's slices, pads,
    split and one concatenate a turn gone, two transposes fewer; the
    tree and the seeded values are PR 29's.  (PR 46's
    ``_updates_apart`` leaves a scan of several turns, which these
    are, as it was.)  Recorded once more at PR 53, whose embedding
    lookup has a derivative of its own (``ops/embed_rows.py``): one
    ``custom_vjp_call``, and one ``lt`` / ``add`` / ``select_n`` /
    ``broadcast_in_dim`` each with which the backward's float32
    ``scatter-add`` wraps a negative id as the gather did; ``gather``
    and ``scatter-add`` count what they counted."""
    with open(os.path.join(HERE, "patternless_program.json")) as fh:
        was = json.load(fh)[name]
    spec = tfm.model_spec(vocab_size=128, dim=64, num_heads=4,
                          num_layers=3, seq_len=32, dtype="float32",
                          **PATTERNLESS[name])
    shapes = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(lambda a: list(a.shape), shapes) \
        == was["tree"]
    tokens = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, t: _loss(spec, t)(p)))(
        shapes, tokens)
    assert dict(_primitives(jaxpr.jaxpr, collections.Counter())) \
        == was["prims"]
    params = jax.jit(spec.init_fn)(jax.random.PRNGKey(0))
    total = float(sum(jnp.abs(a).sum()
                      for a in jax.tree_util.tree_leaves(params)))
    assert total == pytest.approx(was["sum"], rel=1e-6)


def _delta_stack():
    from tests import test_delta_stack

    return test_delta_stack._spec()


def _scanned(layers):
    return lambda: tfm.model_spec(
        vocab_size=128, dim=64, num_heads=4, num_layers=layers, seq_len=32,
        dtype="float32", **PATTERNLESS["moe"])


# name -> (the model, how many of its stack's leaves are held apart)
APART = {
    # a scan of one turn: wq, wk, wv, wo and a router; all 8 experts
    # held, [1, 8, ., .]; an untied head
    "scanned_once": (_scanned(1), 5),
    # the same stack scanned three times: left as it was
    "scanned": (_scanned(3), 0),
    # ddda, a period of four scanned once: 3 x (w_qkv, delta_conv, w_a,
    # w_b, w_out_gate, wo) + wq, wk, wv, wo + 4 x 3 of a SwiGLU
    "one_period": (_delta_stack, 34),
    # cc | accc accc | ac: 2 dense conv layers of 3 + 3; the period,
    # scanned twice, left as it was; an attention layer's wq, wk, wv,
    # wo and a conv layer's three with a router each
    "lead_period_tail": (lambda: tfm.model_spec(**STACK), 21),
}


@pytest.mark.parametrize("name", sorted(APART))
def test_every_matrix_of_an_unrolled_stack_hands_its_gradient_through_a_barrier(
        monkeypatch, name):
    """``_updates_apart``: the gradient is bit for bit the gradient of
    the same model with the identity taken out, and the gradient's
    program holds one ``optimization_barrier`` more a matrix of the
    stack (a leaf of rank 2 once a scanned axis of one turn is taken
    off), on that leaf alone, at the program's top level and in no
    scan's body: none for
    ``embed``, ``lm_head``, a norm, a vector, an expert stack or a
    leaf scanned over several turns."""
    make, matrices = APART[name]
    spec = make()
    params = jax.jit(spec.init_fn)(jax.random.PRNGKey(0))
    tokens = _tokens(spec)

    def run():
        """(the gradient, its program's top-level barriers by their
        operands' shapes, the barriers of every nested jaxpr too)."""
        traced = jax.jit(jax.grad(_loss(spec, tokens))).trace(params)
        grads, jaxpr = traced.lower().compile()(params), traced.jaxpr.jaxpr
        barrier = lambda e: e.primitive.name == "optimization_barrier"
        return grads, collections.Counter(
            tuple(v.aval.shape for v in e.invars)
            for e in jaxpr.eqns if barrier(e)), sum(
                map(barrier, _eqns(jaxpr)))

    grads, barriers, everywhere = run()
    monkeypatch.setattr(tfm, "_update_apart", lambda w: w)
    plain_grads, plain_barriers, plain_everywhere = run()
    jax.tree_util.tree_map(np.testing.assert_array_equal, grads,
                           plain_grads)
    assert not plain_barriers - barriers
    plan = tfm.stack_plan(spec.config)
    layers = params["layers"]
    scanned = {"period": layers["period"]} if plan else layers
    whole = {k: layers[k] for k in ("lead", "tail")} if plan else {}
    shapes = lambda tree, rank: [
        a.shape for a in jax.tree_util.tree_leaves(tree) if a.ndim == rank]
    wanted = collections.Counter(
        (shape,) for shape in [s for s in shapes(scanned, 3) if s[0] == 1]
        + shapes(whole, 2))
    assert barriers - plain_barriers == wanted
    assert everywhere - plain_everywhere == sum(wanted.values()) == matrices


@pytest.mark.parametrize("what", ["prefill", "decode_step", "generate",
                                  "forward_pipelined", "mesh"])
def test_a_mixed_stack_refuses_what_it_cannot_run_by_name(what):
    spec = tfm.model_spec(**STACK)
    cfg = spec.config
    params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    prompt = jnp.zeros((1, 4), jnp.int32)
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2, 1, 1, 1),
                ("dp", "pp", "tp", "sp", "ep"))
    calls = {
        "prefill": lambda: tfm.prefill(params, cfg, prompt, 8),
        "decode_step": lambda: tfm.decode_step(
            params, cfg, None, 0, prompt[:, 0]),
        "generate": lambda: tfm.generate(params, cfg, prompt, 2),
        "forward_pipelined": lambda: tfm.forward_pipelined(
            params, prompt, cfg, mesh, 2),
        "mesh": lambda: tfm.model_spec(mesh=mesh, **STACK),
    }
    with pytest.raises(NotImplementedError) as refusal:
        calls[what]()
    assert STACK["layer_pattern"] in str(refusal.value)
    assert what.split("_")[0] in str(refusal.value) or what == "mesh"


def test_a_share_without_a_pattern_refuses_a_mesh_too():
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 1, 1, 1, 2),
                ("dp", "pp", "tp", "sp", "ep"))
    with pytest.raises(NotImplementedError, match="moe_experts_held"):
        tfm.model_spec(vocab_size=64, dim=32, num_heads=2, num_layers=2,
                       seq_len=16, moe_experts=4, moe_experts_held=2,
                       mesh=mesh)


# -- remat_keep per layer kind -----------------------------------------------


def test_remat_keeps_table_counts_each_kinds_layers():
    cfg = tfm.model_spec(remat=True, **dict(STACK, dtype="bfloat16")).config
    rows = 2 * 32
    entries = {label: (names, nbytes, layers)
               for label, names, nbytes, layers in rk._entries(cfg, rows)}
    # 12 layers: 3 attention + 9 conv; 2 dense + 10 with experts
    assert {l: e[2] for l, e in entries.items()} == {
        "flash": 3, "qkv": 3, "route": 10, "stream": 12, "ffn_gate": 2,
        "ffn_up": 2, "conv_in": 9, "conv_out": 9, "moe_out": 10,
        "moe_gate": 10, "moe_up": 10, "moe_rows": 10}
    assert entries["conv_in"][:2] == ((sc.KEEP_IN,), rows * 3 * 128 * 2)
    assert entries["conv_out"][:2] == ((sc.KEEP_OUT,), rows * 128 * 2)
    assert entries["ffn_gate"][1] == rows * 80 * 2       # dense_ffn_dim
    # a share's dispatch buffers have the bound's rows, not rows x K
    bound = md.row_bound(rows * 4, 4, 16)
    assert bound == 128 and entries["moe_gate"][1] == bound * 48 * 2
    assert entries["moe_rows"][1] == entries["moe_out"][1] == bound * 128 * 2
    # by what a GB of them is worth: a balanced router fills half the
    # bound, so a share's go at half their worth, its down product (the
    # matmul alone, 48 wide into 128: 48 / 128 of the up product's a
    # byte) last; all held, at all of it, the down product's with its
    # gather
    order = [label for label, _, _ in rk.table(cfg, rows)]
    assert order[4:] == ["ffn_gate", "ffn_up", "conv_in", "moe_gate",
                         "moe_up", "conv_out", "moe_rows", "moe_out"]
    held_all = dataclasses.replace(cfg, moe_experts_held=0,
                                   moe_share_index=0)
    entries_all = {e[0]: e for e in rk._entries(held_all, rows)}
    assert entries_all["moe_gate"][2] == rows * 4 * 48 * 2   # K x ffn_dim
    assert [e[0] for e in rk._entries(held_all, rows)][4:] == [
        "moe_out", "moe_gate", "moe_up", "ffn_gate", "ffn_up", "conv_in",
        "moe_rows", "conv_out"]
    # choose() sums an entry over the layers that make it
    params = jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    room = batch_shard.DeviceRoom(10 ** 12, 10 ** 12 - 1)
    names, kept, _, _ = rk.choose(cfg, params, rows, room)
    assert kept == sum(e[1] * e[2] for e in entries.values())
    assert set(names) >= {sc.KEEP_IN, sc.KEEP_OUT, rk.KEEP_GATE,
                          md.KEEP_ROWS}
    # the step's own need: the dense layer's backward beside the experts'
    need = rk.step_bytes(cfg, params, rows)
    assert need >= rows * 4 * 80 * 2 + 13 * rows * 128 * 2


@pytest.mark.parametrize("onto_share", [False, True])
def test_kept_names_change_no_gradient_of_a_mixed_stack(onto_share):
    """``onto_share``: every dispatch runs a second block, which keeps
    nothing whatever the first block's names say."""
    spec = tfm.model_spec(remat=True, **STACK)
    params = jax.jit(spec.init_fn)(jax.random.PRNGKey(0))
    if onto_share:
        params = _onto_share(params, spec.config)
    tokens = _tokens(spec)

    def grads(room):
        with batch_shard.batch_axis(None, None, room):
            return jax.jit(jax.grad(_loss(spec, tokens)))(params)

    everything = batch_shard.DeviceRoom(10 ** 12, 10 ** 12 - 1)
    for kept, none in zip(jax.tree_util.tree_leaves(grads(everything)),
                          jax.tree_util.tree_leaves(grads(None))):
        np.testing.assert_allclose(kept, none, rtol=1e-4, atol=1e-5)


# -- through the trainer and the worker's lines -------------------------------


def _log_lines(fn, *loggers):
    lines = []
    handler = logging.Handler()
    handler.emit = lambda record: lines.append(record.getMessage())
    for logger in loggers:
        logger.addHandler(handler)
    try:
        fn()
    finally:
        for logger in loggers:
            logger.removeHandler(handler)
    return lines


def test_the_published_pattern_takes_a_step_through_the_trainer():
    """LFM2-24B-A2B's 2 + 38 layers at a small width: the trainer
    builds it, steps, and hands the worker the held experts' load and
    the rows moved; the worker's and the model's lines say so."""
    from elasticdl_tpu.ops import flash_attention as fa

    tfm.announce_stack.cache_clear()
    spec = tfm.model_spec(**dict(
        TINY, dim=64, num_heads=2, num_kv_heads=1, dense_ffn_dim=48,
        ffn_dim=32, num_layers=40, layer_pattern=PUBLISHED, dense_layers=2,
        moe_experts_held=4, remat=True))
    trainer = CollectiveTrainer(spec, batch_size=2)
    tokens = np.asarray(_tokens(spec))

    def step():
        loss = trainer.train_minibatch(tokens, tokens)
        assert np.isfinite(float(loss[0] if isinstance(loss, tuple)
                                 else loss))
        worker_mod._log_step_stats(1, trainer.last_step_stats)

    lines = _log_lines(step, fa.logger, worker_mod.logger)
    stats = trainer.last_step_stats
    assert np.asarray(stats["moe_load"]).shape == (38, 4 + 1)
    # one block of the bound's 128 of the 2 * 32 * 4 rows, every layer
    np.testing.assert_array_equal(stats["moe_moved"], [128] * 38)
    np.testing.assert_array_equal(stats["moe_spilled"], [0] * 38)
    assert [l for l in lines if l.startswith("layer stack:")] == [
        "layer stack: pattern=%s lead=cc period=accc periods=9 tail=ac "
        "dense_layers=2 experts_held=4/16 a:window=0,rope=1" % PUBLISHED]
    load = [l for l in lines if l.startswith("moe load:")]
    assert len(load) == 1 and load[0].endswith(
        "moved=%d spilled=0" % (38 * 128))
    fields = dict(item.split("=") for item in load[0].split()[2:])
    assert fields["layers"] == "38"
    assert 0 < int(fields["rows"]) < int(fields["moved"])


def test_the_worker_logs_moved_rows_beside_held_rows(caplog):
    """``moved=`` is what the step handed back, ``spilled=`` ends the
    line, and the benchmark's reader takes ``1 - rows / moved`` from
    it."""
    import types

    from benchmark.lib import job

    load = np.array([[10, 0, 30], [16, 16, 32]], np.float32)
    worker_mod.logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.INFO, logger=worker_mod.logger.name):
            worker_mod._log_step_stats(8, {
                "moe_load": load, "moe_moved": np.array([64., 128.]),
                "moe_spilled": np.array([0., 1.])})
    finally:
        worker_mod.logger.removeHandler(caplog.handler)
    line, = [r.getMessage() for r in caplog.records]
    assert line == ("moe load: step=8 layers=2 rows=42 max=16 mean=10.5 "
                    "padded_rows=62 moved=192 spilled=1")
    fields = job.fields(line.split("moe load:", 1)[1])
    assert list(fields)[-2:] == ["moved", "spilled"]
    stamped = "[2026-09-28 02:00:20,000] [INFO] [worker-0] " + line
    run = types.SimpleNamespace(
        job=types.SimpleNamespace(text=stamped + "\n"),
        times={"open": job.stamp_seconds(stamped) - 1,
               "close": job.stamp_seconds(stamped) + 1})
    reader = manifest.load_named("layers", "moe.dead_row_share")
    assert reader.read(run) == pytest.approx(100 * (1 - 42 / 192))


def test_the_worker_logs_what_the_row_kernels_sums_walked(caplog):
    """``sum_terms=`` and ``sum_slots=`` end the line where the step
    handed them back (the row kernel moved the share's rows), behind
    ``group_hit=`` where that is there; the benchmark's reader takes
    their ratio, and nothing from a line without them."""
    import types

    from benchmark.lib import job

    stats = {"moe_load": np.array([[10, 0, 30], [16, 16, 32]], np.float32),
             "moe_moved": np.array([64., 128.]),
             "moe_spilled": np.array([0., 1.]),
             "moe_sum_terms": np.array([96., 160.], np.float32),
             "moe_sum_slots": np.array([512., 1024.], np.float32)}
    worker_mod.logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.INFO, logger=worker_mod.logger.name):
            worker_mod._log_step_stats(8, stats)
            worker_mod._log_step_stats(9, dict(
                stats, moe_group_hit=np.array([0.25, 0.75])))
            worker_mod._log_step_stats(10, {
                key: stats[key] for key in list(stats)[:3]})
    finally:
        worker_mod.logger.removeHandler(caplog.handler)
    lines = [r.getMessage() for r in caplog.records]
    head = " layers=2 rows=42 max=16 mean=10.5 padded_rows=62 moved=192 "
    assert lines == [
        "moe load: step=8" + head + "spilled=1 sum_terms=256 sum_slots=1536",
        "moe load: step=9" + head + "spilled=1 group_hit=0.5000 "
        "sum_terms=256 sum_slots=1536",
        "moe load: step=10" + head + "spilled=1"]
    reader = manifest.load_named("layers", "moe.sum_term_share")
    stamp = "[2026-09-28 02:00:20,000] [INFO] [worker-0] "
    run = lambda line: types.SimpleNamespace(
        job=types.SimpleNamespace(text=stamp + line + "\n"),
        times={"open": job.stamp_seconds(stamp) - 1,
               "close": job.stamp_seconds(stamp) + 1})
    assert reader.read(run(lines[1])) == pytest.approx(100 * 256 / 1536)
    assert reader.read(run(lines[2])) is None
