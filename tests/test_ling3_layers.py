"""The mechanisms ``ling-3.0-flash`` forced, each against math written
out here or in ``benchmark/reference/ling-3.0-flash.py``, at tiny sizes
on the CPU: the router limited to groups, the kda layer with full
projections under the bounded decay gate (jnp twin and the interpreted
kernels), a gate a head on latent attention's and on attention's
output, the clamp a layer, the shares of heads and experts adding up to
the uncut layer, and every caller that cannot run them saying so by
name.  The whole model against the reference: tests/test_ling3_model.py.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import manifest
from benchmark.lib.runner import merge, params_string
from elasticdl_tpu.models import transformer as tfm
from elasticdl_tpu.models.spec import load_model_spec
from elasticdl_tpu.ops import moe_dispatch
from elasticdl_tpu.ops.mode import SWITCH

REF = manifest.load_named("reference", "ling-3.0-flash")
with open(os.path.join(manifest.BENCH_DIR, "configs",
                       "ling-3.0-flash.json")) as fh:
    PUBLISHED = json.load(fh)
CONFIG = merge(PUBLISHED, PUBLISHED["rehearsal"])
SHAPE = REF.shape_of(CONFIG)
T = CONFIG["seq_len"]


def _spec(**override):
    return load_model_spec("transformer", model_params=params_string(
        dict(CONFIG["cli"]["model_params"], **override)))


def _far(got, want):
    return float(jnp.linalg.norm(got.astype(jnp.float32) - want)
                 / jnp.linalg.norm(want))


def _normal(seed, *shape):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       jnp.float32)


# -- the router limited to groups ------------------------------------------


def _loop_route(biased, groups, top_groups, k):
    """The chosen experts of one token, written as loops: a group's
    score the sum of its two largest members, the best groups (a tie to
    the lower index, as ``lax.top_k``), the k largest inside them."""
    size = len(biased) // groups
    score = []
    for g in range(groups):
        members = sorted(biased[g * size:(g + 1) * size], reverse=True)
        score.append(members[0] + members[1])
    best = sorted(range(groups), key=lambda g: (-score[g], g))[:top_groups]
    inside = [e for g in sorted(best) for e in range(g * size,
                                                      (g + 1) * size)]
    return sorted(sorted(inside, key=lambda e: (-biased[e], e))[:k])


ROUTER = dict(vocab_size=64, dim=16, num_heads=2, num_layers=1,
              moe_experts=16, moe_top_k=3, moe_router="sigmoid_bias",
              moe_route_scale=2.5, dtype="float32")


@pytest.mark.parametrize("ties", [False, True])
def test_the_group_limited_choice_is_the_loops(ties):
    """16 experts in 4 groups, 2 groups and 3 experts a token, a bias
    that is not zero: the chosen experts are the loop's, the weights the
    UNBIASED scores over their sum (+ 1e-6) times the scale.  With
    ``ties`` every expert's column is one of four, so groups and experts
    tie exactly and the lower index wins, as in the loop."""
    cfg = tfm.TransformerConfig(moe_groups=4, moe_top_groups=2, **ROUTER)
    h = _normal(0, 1, 48, 16)
    w = 0.5 * _normal(1, 16, 16)
    bias = 0.2 * _normal(2, 16)
    if ties:
        w = jnp.tile(w[:, :4], (1, 4))
        bias = jnp.tile(bias[:4], 4)
    probs, gates, experts = jax.jit(
        lambda h, w, b: tfm.moe_route(h, w, cfg, b))(h, w, bias)
    biased = np.asarray(probs + bias, np.float64)[0]
    for t in range(48):
        want = _loop_route(list(biased[t]), 4, 2, 3)
        assert sorted(np.asarray(experts[0, t]).tolist()) == want, t
    picked = jnp.take_along_axis(probs, experts, axis=-1)
    np.testing.assert_allclose(
        gates, 2.5 * picked / (picked.sum(-1, keepdims=True) + 1e-6),
        rtol=1e-6)
    # and the reference's, by explicit reshape and sort (no ties there:
    # ``>=`` keeps every tied member)
    if not ties:
        chosen = REF.route(h, w, bias, 3, 4, 2)[1]
        assert bool((jax.nn.one_hot(experts, 16).sum(-2) > 0).__eq__(
            chosen).all())


def test_a_limit_that_cannot_bind_chooses_as_no_limit_does():
    """``moe_top_groups == moe_groups`` keeps every group: the experts
    and weights of the router without groups (whose lowering is the
    parent's: tests/test_rehearsal_lowering.py), and the limit does bind
    where it can (2 of 4 groups: other experts for some token)."""
    h, w, bias = _normal(0, 1, 64, 16), _normal(1, 16, 16), _normal(2, 16)
    route = lambda **groups: tfm.moe_route(
        h, w, tfm.TransformerConfig(**ROUTER, **groups), 0.1 * bias)
    plain, slack, bound = route(), route(moe_groups=4, moe_top_groups=4), \
        route(moe_groups=4, moe_top_groups=2)
    for a, b in zip(plain, slack):
        np.testing.assert_array_equal(a, b)
    assert not bool((plain[2] == bound[2]).all())
    # every chosen expert of a token lies in at most 2 groups
    assert int(jnp.max(jax.vmap(lambda e: jnp.unique(
        e // 4, size=3, fill_value=-1).__ne__(-1).sum())(
            bound[2][0]))) <= 2


def test_no_gradient_reaches_the_bias_and_the_weights_have_theirs():
    cfg = tfm.TransformerConfig(moe_groups=4, moe_top_groups=2, **ROUTER)
    h, w, bias = _normal(0, 1, 8, 16), _normal(1, 16, 16), _normal(2, 16)
    g_w, g_b = jax.grad(
        lambda w, b: (tfm.moe_route(h, w, cfg, b)[1] ** 2).sum(),
        argnums=(0, 1))(w, bias)
    assert float(jnp.abs(g_w).max()) > 0 and not float(jnp.abs(g_b).max())


# -- the kda layer: full projections, the bounded gate ----------------------

KDA = dict(vocab_size=64, dim=64, num_heads=2, num_layers=2, seq_len=T,
           layer_pattern="dd", delta_kind="kda", delta_key_dim=32,
           delta_value_dim=32, conv_kernel=4, dtype="float32")


def _kda_case(seed=0, **fields):
    cfg = tfm.model_spec(**dict(KDA, **fields)).config
    w = tfm._init_delta(jax.random.PRNGKey(seed), cfg, ())
    w["o_norm"] = 1.0 + 0.25 * _normal(seed + 1, 32)
    return cfg, w, _normal(seed + 2, 2, T, 64)


def _kda_want(h, w, floor):
    return REF.kda_mixer(h, w, 2, 32, floor, 1e-6)


def test_a_kda_layer_without_pairs_holds_the_two_full_projections():
    """``delta_rank=0`` under ``delta_kind=kda``: ``w_a`` [dim, heads *
    key_dim] and ``w_out_gate`` [dim, heads * value_dim] in the four
    low-rank matrices' and the gate bias's place; with a rank, the
    pairs as ever."""
    cfg, w, _ = _kda_case(delta_gate_floor=-5.0)
    assert w["w_a"].shape == (64, 64) and w["w_out_gate"].shape == (64, 64)
    assert not {"w_a_down", "w_a_up", "w_g_down", "w_g_up", "b_g"} & set(w)
    assert w["dt_bias"].shape == (64,) and w["A_log"].shape == (2,)
    _, pairs, _ = _kda_case(delta_rank=8)
    assert pairs["w_a_down"].shape == (64, 8) and "w_a" not in pairs
    # the floored draw: at a zero projection the bounded gate decays as
    # the unbounded one does, -A dt
    rate = jnp.repeat(jnp.exp(w["A_log"]), 32)
    g0 = -5.0 * jax.nn.sigmoid(rate * w["dt_bias"])
    softplus = _kda_case()[1]
    np.testing.assert_allclose(
        g0, -rate * jax.nn.softplus(softplus["dt_bias"]), rtol=2e-4)


@pytest.mark.parametrize("floor", [-5.0, 0.0])
@pytest.mark.parametrize("mode", ["off", "interpret"])
def test_the_full_rank_kda_layer_is_the_recurrence(monkeypatch, mode, floor):
    """``_delta_mix`` under full projections, with the bounded gate (g
    in (floor, 0), no excess) and with the unbounded one, against the
    token-by-token recurrence: the jnp twin and the interpreted
    ``kda_fwd`` / ``kda_bwd``, value and ``jax.grad`` of every weight
    and of the input."""
    monkeypatch.setenv(SWITCH, mode)
    cfg, w, h = _kda_case(delta_gate_floor=floor)
    got, excess = jax.jit(lambda h, w: tfm._delta_mix(h, w, cfg, True))(
        h, w)
    assert _far(got, _kda_want(h, w, floor)) < 2e-5
    g = REF.kda_gates(h, w, 2, 32, floor)[0]
    if floor:
        assert float(excess) == 0.0
        # (floor, 0) closed by float32: a saturated sigmoid is 1.0
        assert floor <= float(g.min()) and float(g.max()) <= 0.0
        # and it is no decay that never bites: a channel forgets
        assert float(g.min()) < -1.0
    else:
        assert float(g.max()) < 0.0
    probe = _normal(9, 2, T, 64)
    value = lambda fn: lambda h, w: (fn(h, w) * probe).sum()
    grads = jax.jit(jax.grad(value(
        lambda h, w: tfm._delta_mix(h, w, cfg)), argnums=(0, 1)))(h, w)
    wanted = jax.jit(jax.grad(value(
        lambda h, w: _kda_want(h, w, floor)), argnums=(0, 1)))(h, w)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), want in zip(flat, jax.tree_util.tree_leaves(wanted)):
        assert _far(g, want) < 2e-4, jax.tree_util.keystr(path)


def test_the_gates_excess_leaves_the_step_with_its_statistics():
    """``kda_gate_excess`` among the step's statistics is the largest
    over the layers, 0 at the floor; a floor the decays pass (the
    statistic computed against a HIGHER floor than the gate's) reads
    the difference: the counter counts."""
    spec = tfm.model_spec(**dict(KDA, delta_gate_floor=-5.0))
    params = spec.init_fn(jax.random.PRNGKey(0))
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, T)),
                         jnp.int32)
    stats = spec.step_stats_fn(spec.apply_fn(params, tokens, True))
    assert float(stats["kda_gate_excess"]) == 0.0
    cfg, w, h = _kda_case(delta_gate_floor=-5.0)
    # the same weights under a floor of -2 times 2.5: the same decays
    scaled = dataclasses.replace(cfg, delta_gate_floor=-2.0)
    g = REF.kda_gates(h, w, 2, 32, -5.0)[0]
    low = dict(w, dt_bias=w["dt_bias"] + 10.0)     # every gate near -floor
    assert float(tfm._delta_mix(h, low, scaled, True)[1]) == 0.0
    assert float(g.min()) < -2.0      # under -2 by the -5 gate: an excess
    from elasticdl_tpu.worker.worker import _loss_fields

    assert _loss_fields({"kda_gate_excess": np.float32(0.0)}) == (
        " g_excess=0.000e+00")


# -- a gate a head -----------------------------------------------------------

LATENT = dict(vocab_size=64, dim=64, num_heads=2, num_layers=1, seq_len=T,
              kv_latent_rank=32, qk_nope_dim=32, qk_rope_dim=16,
              v_head_dim=32, rope_theta=6000000, dtype="float32")


@pytest.mark.parametrize("mode", ["off", "interpret"])
def test_latent_attention_with_a_gate_a_head_is_the_references(
        monkeypatch, mode):
    """``attn_gate=head`` on latent attention: ``w_attn_gate`` [dim,
    heads], each head's output times the sigmoid of its one gate, before
    ``W_o``; value and gradients against the reference's attention (its
    RoPE pairs neighbours on the weights mapped back), and the gate is
    no identity."""
    monkeypatch.setenv(SWITCH, mode)
    cfg = tfm.model_spec(**dict(LATENT, attn_gate="head")).config
    kind = cfg.kinds[0]
    w = tfm._init_layers(jax.random.PRNGKey(0), jax.random.PRNGKey(1), cfg,
                         kind, ())
    assert w["w_attn_gate"].shape == (64, 2)
    h = _normal(3, 2, T, 64)
    mix = lambda h, w: tfm._latent_mix(h, w, cfg, jnp.arange(T), kind)
    want = lambda h, w, **how: REF.attention(
        h, w, 2, 32, 32, 16, 32, 1e-6, 6e6, **how)
    assert _far(jax.jit(mix)(h, w), want(h, w)) < 2e-5
    assert _far(want(h, w, without=("head_gate",)), want(h, w)) > 0.3
    probe = _normal(9, 2, T, 64)
    names = ("wq", "w_kv_a", "kv_norm", "w_kv_b", "w_attn_gate", "wo")
    grads = jax.jit(jax.grad(lambda h, w: (mix(h, w) * probe).sum(),
                             argnums=(0, 1)))(h, w)
    wanted = jax.jit(jax.grad(lambda h, w: (want(h, w) * probe).sum(),
                              argnums=(0, 1)))(h, w)
    assert _far(grads[0], wanted[0]) < 2e-4
    for name in names:
        assert _far(grads[1][name], wanted[1][name]) < 2e-4, name


def test_attention_with_wk_and_wv_takes_a_gate_a_head_too():
    """``attn_gate=head`` on attention with wk and wv: a head's values
    by the sigmoid of its one gate; with every gate's weights zero the
    result is half the ungated one."""
    base = dict(vocab_size=64, dim=32, num_heads=2, num_layers=1,
                seq_len=16, dtype="float32")
    cfg = tfm.model_spec(**dict(base, attn_gate="head")).config
    kind = cfg.kinds[0]
    w = tfm._init_layers(jax.random.PRNGKey(0), jax.random.PRNGKey(1), cfg,
                         kind, ())
    assert w["w_attn_gate"].shape == (32, 2)
    h = _normal(0, 1, 16, 32)
    mix = lambda cfg, w: tfm._attention_mix(h, w, cfg, None, jnp.arange(16),
                                            kind)[0]
    plain = tfm.model_spec(**base).config
    shut = dict(w, w_attn_gate=jnp.zeros_like(w["w_attn_gate"]))
    np.testing.assert_allclose(mix(cfg, shut), 0.5 * mix(plain, w),
                               rtol=1e-5, atol=1e-6)
    # one head's gate moves that head's part of the result alone
    gate = jax.nn.sigmoid(h @ w["w_attn_gate"])               # [1, 16, 2]
    wo = w["wo"].reshape(2, 16, 32)
    heads = [mix(plain, dict(w, wo=wo.at[1 - i].set(0.0).reshape(32, 32)))
             for i in range(2)]
    np.testing.assert_allclose(
        mix(cfg, w), sum(gate[..., i, None] * heads[i] for i in range(2)),
        rtol=1e-4, atol=1e-5)


# -- the clamp a layer -------------------------------------------------------


def test_the_clamped_product_is_the_written_out_one_and_its_gradients():
    """``gated`` with a limit: ``act(min(a, L)) * clip(b, -L, L)``; no
    gradient reaches a value past its bound, the others' is the
    unclamped one's; a limit of 0 is no clamp."""
    a, b = 3.0 * _normal(0, 64, 8), 3.0 * _normal(1, 64, 8)
    L = 2.0
    got = moe_dispatch.gated("silu", a, b, L)
    np.testing.assert_allclose(
        got, jax.nn.silu(jnp.minimum(a, L)) * jnp.clip(b, -L, L), rtol=1e-6)
    np.testing.assert_array_equal(moe_dispatch.gated("silu", a, b, 0.0),
                                  jax.nn.silu(a) * b)
    assert float(jnp.abs(got - jax.nn.silu(a) * b).max()) > 1.0
    da, db = jax.grad(lambda a, b: moe_dispatch.gated("silu", a, b, L).sum(),
                      argnums=(0, 1))(a, b)
    free_a, free_b = jax.grad(
        lambda a, b: (jax.nn.silu(a) * jnp.clip(b, -L, L)).sum(),
        argnums=(0, 1))(a, b)
    assert not float(jnp.abs(jnp.where(a > L, da, 0.0)).max())
    assert not float(jnp.abs(jnp.where(jnp.abs(b) > L, db, 0.0)).max())
    np.testing.assert_allclose(jnp.where(a < L, da, 0.0),
                               jnp.where(a < L, free_a, 0.0), rtol=1e-5)
    assert float(jnp.abs(db).max()) > 0


@pytest.mark.parametrize("mode", ["off", "interpret"])
def test_an_expert_layers_clamps_are_its_own(monkeypatch, mode):
    """One expert layer of the rehearsal model with weights wide enough
    for the limits to bite: its held experts under ``ffn_limits``' entry
    and its shared expert under ``shared_limits``' against the
    reference's, the dispatch by its kernels too; the unclamped layer
    differs from both."""
    monkeypatch.setenv(SWITCH, mode)
    cfg = _spec().config
    kind = cfg.kinds[1]
    assert (kind.limit, kind.shared_limit) == (4.0, 7.0)
    w = tfm._init_layers(jax.random.PRNGKey(0), jax.random.PRNGKey(1), cfg,
                         kind, ())
    for name in ("w_gate", "w_up", "ws_gate", "ws_up"):
        w[name] = 6.0 * w[name]
    w["expert_bias"] = 0.1 * _normal(5, 16)
    w["w_router"] = 0.3 * _normal(6, 64, 16)    # a choice that varies
    u = _normal(3, 2, T, 64)
    route = tfm.moe_route(u, w["w_router"], cfg, w["expert_bias"])
    weights = (jax.nn.one_hot(route[2], 16) * route[1][..., None]).sum(-2)
    routed = lambda limit: tfm._moe_ffn(u, w, cfg, None, route, limit)[0]
    want = lambda limit: REF.held_experts(u, w, weights, 0, limit=limit)
    assert _far(routed(4.0), want(4.0)) < 2e-5
    assert _far(routed(0.0), want(REF.NO_LIMIT)) < 2e-5
    assert _far(want(REF.NO_LIMIT), want(4.0)) > 0.05
    shared = lambda limit: tfm._shared_expert(u, w, cfg, limit)
    assert _far(shared(7.0), REF.shared_expert(u, w, limit=7.0)) < 2e-5
    assert _far(shared(0.0), shared(7.0)) > 0.02
    # the layer takes them from its Kind, the module's block none
    assert (cfg.mtp_kind.limit, cfg.mtp_kind.shared_limit) == (0.0, 0.0)
    assert [k.limit for k in cfg.kinds] == [0.0, 4.0, 4.0]


# -- the shares add up to the layer ------------------------------------------

WHOLE = dict(vocab_size=64, dim=64, num_heads=8, num_layers=2, seq_len=T,
             layer_pattern="da", scan_periods=False, delta_kind="kda",
             delta_key_dim=16, delta_value_dim=16, delta_gate_floor=-5.0,
             conv_kernel=4, kv_latent_rank=32, qk_nope_dim=16, qk_rope_dim=8,
             v_head_dim=16, attn_gate="head", ffn_dim=32, moe_experts=16,
             moe_top_k=2, moe_groups=4, moe_top_groups=2,
             moe_shared_experts=1, moe_router="sigmoid_bias",
             moe_route_scale=2.5, moe_aux_weight=0, ffn_limits="1,1",
             shared_limits="1.5,1.5", dtype="float32")


def _columns(w, heads, share, of=4):
    """The columns of a head-major projection [dim, heads * width] that
    ``share`` of ``of`` holds."""
    held = heads // of
    return w.reshape(w.shape[0], heads, -1)[
        :, share * held:(share + 1) * held].reshape(w.shape[0], -1)


def test_the_head_shares_partials_add_up_to_both_uncut_mixers():
    """4 chips share 8 heads: each chip's KDA mixer and each chip's
    gated latent attention on its 2 heads' columns of every projection
    (q | k | v part by part, the decay's, the gates', their rows of
    ``W_o``; ``W_kva``, its norm and the KDA output norm's one scale
    whole on each) is its part of the ``W_o`` product, and the four
    parts add up to the uncut mixer."""
    cfg = tfm.model_spec(**WHOLE).config
    cut = tfm.model_spec(**dict(WHOLE, num_heads=2, head_shares=4)).config
    d_kind, a_kind = cfg.kinds
    k1, k2 = jax.random.PRNGKey(0), jax.random.PRNGKey(1)
    wd = tfm._init_layers(k1, k2, cfg, d_kind, ())
    wa = tfm._init_layers(k1, k2, cfg, a_kind, ())
    h = _normal(3, 1, T, 64)
    positions = jnp.arange(T)

    def d_share(i):
        qkv = wd["w_qkv"].reshape(64, 3, 8, 16)[:, :, 2 * i:2 * i + 2]
        taps = wd["delta_conv"].reshape(3, 8, 16, 4)[:, 2 * i:2 * i + 2]
        return dict(
            wd, w_qkv=qkv.reshape(64, -1),
            delta_conv=taps.reshape(-1, 4),
            w_a=_columns(wd["w_a"], 8, i),
            w_out_gate=_columns(wd["w_out_gate"], 8, i),
            w_b=wd["w_b"][:, 2 * i:2 * i + 2],
            A_log=wd["A_log"][2 * i:2 * i + 2],
            dt_bias=wd["dt_bias"].reshape(8, 16)[2 * i:2 * i + 2].ravel(),
            wo=wd["wo"].reshape(8, 16, 64)[2 * i:2 * i + 2].reshape(-1, 64))

    def a_share(i):
        return dict(
            wa, wq=_columns(wa["wq"], 8, i),
            w_kv_b=_columns(wa["w_kv_b"], 8, i),
            w_attn_gate=wa["w_attn_gate"][:, 2 * i:2 * i + 2],
            wo=wa["wo"].reshape(8, 16, 64)[2 * i:2 * i + 2].reshape(-1, 64))

    delta = jax.jit(lambda w, cfg: tfm._delta_mix(h, w, cfg),
                    static_argnums=1)
    latent = jax.jit(lambda w, cfg: tfm._latent_mix(
        h, w, cfg, positions, a_kind), static_argnums=1)
    parts = sum(delta(d_share(i), cut) for i in range(4))
    assert _far(parts, delta(wd, cfg)) < 1e-5
    whole = latent(wa, cfg)
    parts = sum(latent(a_share(i), cut) for i in range(4))
    assert _far(parts, whole) < 1e-5
    # and a share alone is no small part of it
    assert _far(latent(a_share(0), cut), whole) > 0.5


def test_the_expert_shares_under_the_group_limit_add_up_to_the_layer():
    """16 experts in 4 groups, 2 groups and 2 experts a token, 2 held a
    chip: the 8 shares' routed parts (each under the clamp, each routed
    over all 16 by the whole router) and the shared expert, counted
    once, add up to the uncut expert layer; a share's ``group_hit`` is
    the tokens whose two groups reach its own, 2 shares a group alike."""
    cfg = tfm.model_spec(**WHOLE).config
    kind = cfg.kinds[1]
    w = tfm._init_layers(jax.random.PRNGKey(0), jax.random.PRNGKey(1), cfg,
                         kind, ())
    for name in ("w_gate", "w_up", "ws_gate", "ws_up"):
        w[name] = 4.0 * w[name]
    w["expert_bias"] = 0.1 * _normal(5, 16)
    u = _normal(3, 1, T, 64)
    whole = (tfm._moe_ffn(u, w, cfg, None, limit=kind.limit)[0]
             + tfm._shared_expert(u, w, cfg, kind.shared_limit))
    parts, hits = tfm._shared_expert(u, w, cfg, kind.shared_limit), []
    for share in range(8):
        cut = dataclasses.replace(cfg, moe_experts_held=2,
                                  moe_share_index=share)
        held = {name: w[name][2 * share:2 * share + 2]
                for name in ("w_gate", "w_up", "w_down")}
        out, _, _, load = tfm._moe_ffn(u, dict(w, **held), cut, None,
                                       limit=kind.limit)
        parts = parts + out
        assert load.shape == (2 + 4,)
        hits.append(float(load[-1]))
    assert _far(parts, whole) < 1e-5
    # two groups a token of four: a group's two... four shares see the
    # same tokens, and the four groups' hits add up to 2 a token
    assert hits[0::2] == hits[1::2]
    assert abs(sum(hits[0::2]) - 2.0) < 1e-6
    # the clamp bit: the layer without it is another
    free = (tfm._moe_ffn(u, w, cfg, None)[0] + tfm._shared_expert(u, w, cfg))
    assert _far(free, whole) > 0.02


# -- what cannot run them says so by name ------------------------------------

FIELDS = {
    "moe_groups": (dict(moe_experts=8, moe_groups=4, moe_top_groups=2,
                        moe_router="sigmoid_bias"), "moe_groups=4"),
    "ffn_limits": (dict(moe_experts=4, num_layers=2, ffn_limits="4,4"),
                   "ffn_limits='4,4'"),
    "attn_gate": (dict(attn_gate="head"), "attn_gate=head"),
    "kda": (dict(num_layers=2, layer_pattern="da", delta_kind="kda",
                 delta_key_dim=16, delta_value_dim=16,
                 delta_gate_floor=-5.0), "delta_kind=kda"),
}


@pytest.mark.parametrize("what", ["prefill", "decode_step", "generate",
                                  "export_generate", "forward_pipelined",
                                  "param_specs"])
@pytest.mark.parametrize("field", sorted(FIELDS))
def test_decoding_the_pipeline_and_a_mesh_refuse_each_by_name(
        what, field, tmp_path):
    fields, word = FIELDS[field]
    cfg = tfm.TransformerConfig(vocab_size=64, dim=32, num_heads=2,
                                max_seq_len=16, **fields)
    prompt = jnp.zeros((1, 4), jnp.int32)
    calls = {
        "prefill": lambda: tfm.prefill(None, cfg, prompt, 8),
        "decode_step": lambda: tfm.decode_step(None, cfg, None, 0, None),
        "generate": lambda: tfm.generate(None, cfg, prompt, 2),
        "export_generate": lambda: tfm.export_generate(
            str(tmp_path), None, cfg, 2, 4),
        "forward_pipelined": lambda: tfm.forward_pipelined(
            None, None, cfg, None, 2),
        "param_specs": lambda: tfm.param_specs(cfg),
    }
    with pytest.raises(NotImplementedError) as refusal:
        calls[what]()
    said = str(refusal.value)
    assert word in said
    assert said.startswith({"param_specs": "a model-parallel mesh"}.get(
        what, what) + " does not run ")


def test_the_lines_state_the_new_fields():
    """``layer stack:`` says the gate's kind, the router's groups, the
    full projections, the floor and the clamps; ``delta scan:`` names
    the gate; ``latent attention:`` the gate a head; ``moe load:`` the
    group hit."""
    import logging

    from elasticdl_tpu.worker import worker

    for announce in (tfm.announce_stack, tfm.announce_delta,
                     tfm.announce_latent):
        announce.cache_clear()
    spec = _spec()
    params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((1, T), jnp.int32)
    logger = logging.getLogger("elasticdl_tpu.ops.flash_attention")
    seen = []
    handler = logging.Handler()
    handler.emit = lambda record: seen.append(record.getMessage())
    logger.addHandler(handler)
    try:
        stats = jax.eval_shape(lambda p, t: spec.step_stats_fn(
            spec.apply_fn(p, t, True)), params, tokens)
    finally:
        logger.removeHandler(handler)
    line = lambda mark: next(l for l in seen if l.startswith(mark))
    assert line("layer stack:").endswith(
        "heads_held=2/8 mtp=1 attn_gate=head route_groups=2/4 "
        "kda_rank=full gate_floor=-5.0 ffn_limits=0,4,4 "
        "shared_limits=0,7,7 a:window=0,rope=1")
    assert ("decay=channel rank=0 gate=floor-5 pairs=block states="
            in line("delta scan:"))
    assert "rope_key=shared gate=head tile=" in line("latent attention:")
    # (``mtp_loss`` joins them in ``loss_fn``)
    assert set(stats) == {"kda_gate_excess", "moe_group_hit", "moe_load",
                          "moe_moved", "moe_spilled"}
    assert stats["moe_group_hit"].shape == (3,)     # two layers, the module
    worker.logger.addHandler(handler)
    try:
        worker._log_step_stats(8, {
            "moe_load": np.ones((3, 3)), "moe_moved": np.full((3,), 8.0),
            "moe_spilled": np.zeros((3,)),
            "moe_group_hit": np.asarray([0.5, 0.25, 0.75])})
    finally:
        worker.logger.removeHandler(handler)
    assert seen[-1].endswith("moved=24 spilled=0 group_hit=0.5000")


def test_a_scan_whose_turns_differ_in_a_clamp_is_refused_by_name():
    base = dict(vocab_size=64, dim=32, num_heads=2, num_layers=4,
                layer_pattern="aaaa", moe_experts=4)
    with pytest.raises(ValueError, match="scan_periods=false"):
        tfm.TransformerConfig(ffn_limits="0,4,4,4", **base)
    # the same limit in every turn scans; unrolled, they may differ
    assert tfm.stack_plan(tfm.TransformerConfig(
        ffn_limits="4,4,4,4", **base)).periods == 4
    plan = tfm.stack_plan(tfm.TransformerConfig(
        ffn_limits="0,4,4,4", scan_periods=False, **base))
    assert plan.periods == 1 and [k.limit for k in plan.period] == [
        0.0, 4.0, 4.0, 4.0]
    assert plan.lead + plan.tail == ()
