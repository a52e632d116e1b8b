"""A block whose sublayers' outputs are normed and whose attention output
is gated (models/transformer.py: ``post_norms``, ``attn_gate``,
``embed_multiplier``; the per-head QK norm on layers with and without
RoPE, a shared expert behind a ``sigmoid_bias`` route at 8 a token)
against the plain reference of the benchmark
(benchmark/reference/trinity-mini.py).  Float32 on the CPU at tiny
widths."""

import collections
import dataclasses
import functools
import json
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
from elasticdl_tpu.ops import flash_attention as fa
from tests import reference_check as rc
from tests.test_latent_attention import _apart, _lines, _loss

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = "trinity-mini"
REF = manifest.load_named("reference", NAME)

# heads x head size = 128, the hidden size 64; a window of 8 in 32; a
# leading dense layer, then windowed, full, windowed, windowed over 4 of
# 16 experts beside one shared expert
TINY = dict(vocab_size=96, dim=64, num_heads=4, num_kv_heads=2, head_dim=32,
            num_layers=5, seq_len=32, layer_pattern="wwaww", window=8,
            rope_kinds="w", rope_theta=10000, qk_norm="head",
            attn_gate=True, post_norms=True, embed_multiplier=8.0,
            dense_layers=1, dense_ffn_dim=96, ffn_dim=48, moe_experts=16,
            moe_top_k=3, moe_experts_held=4, moe_share_index=1,
            moe_shared_experts=1, moe_router="sigmoid_bias",
            moe_norm_topk=True, moe_route_scale=2.826, moe_aux_weight=0,
            norm_eps=1e-5, tied_embeddings=False, embed_scale=0.02,
            dtype="float32")
# sizes the flash kernels take in interpret mode: the band's lower edge
# crosses sub-tiles (128 of 256)
KERNEL = dict(TINY, seq_len=256, window=128, head_dim=64, num_heads=2,
              num_kv_heads=1, dim=128, embed_multiplier=128 ** 0.5,
              num_layers=3, layer_pattern="waw")
TYPES = {"w": "sliding_attention", "a": "full_attention"}


def _shape(cfg, **over):
    """``REF.loss``'s keywords for a model of ``cfg``."""
    return dict(dict(
        heads=cfg.num_heads, kv_heads=cfg.kv_heads, head_dim=cfg.head_dim,
        top_k=cfg.moe_top_k, eps=cfg.norm_eps, theta=float(cfg.rope_theta),
        window=cfg.window,
        kinds=tuple(TYPES[letter] for letter in cfg.layer_pattern),
        norm_topk=cfg.moe_norm_topk, scale=cfg.moe_route_scale,
        multiplier=cfg.embed_multiplier, first=cfg.experts_held[0]), **over)


# model -> the Case of a model of these widths as the comparison draws
# it: a wider head, a bias on the routers, the new norms' scales off 1
DRAWN = functools.partial(rc.tiny, NAME, _shape)


# -- against the plain reference ---------------------------------------------


@pytest.mark.parametrize("case", ["off-remat", "interpret"])
def test_the_stack_matches_the_reference(case):
    """Loss and every gradient leaf (the gate's weight, both output
    norms' scales, q's and k's scales on layers with and without RoPE,
    the embedding under its multiplier among them) of a dense layer and
    expert layers, windowed and full: the jnp paths at a window of 8 in
    32 over the cell's five layers, rematerialized; the flash kernels in
    interpret mode at 128 in 256 over three (windowed and dense, full,
    windowed).  Float32 both sides: 1e-5 of the loss, 1e-4 of each
    leaf's norm (the reference sums in another order)."""
    mode, _, remat = case.partition("-")
    drawn = DRAWN(KERNEL if mode == "interpret" else TINY,
                  batch=1 + (mode == "off"))
    far, still, _, _ = rc.check(drawn, mode, 1e-5, 1e-4, remat=bool(remat))
    assert still and all("expert_bias" in name for name in still)
    assert {name.split("'")[-2] for name in far} >= {
        "w_attn_gate", "ln1_post", "ln2_post", "q_norm", "k_norm", "embed",
        "ws_gate", "w_router"}


@functools.lru_cache(maxsize=None)
def _whole():
    """(params, tokens, the reference's keywords, the product's loss,
    the reference's) of the TINY model."""
    drawn = DRAWN(TINY)
    return (drawn.params, drawn.tokens, drawn.shape,
            float(jax.jit(_loss(drawn.spec(), drawn.tokens))(drawn.params)),
            float(rc.wanted(drawn)[0][0]))


@pytest.mark.parametrize("piece", REF.PIECES)
def test_a_reference_without_one_piece_fails_the_tolerance(piece):
    """The gate, the output norms, the multiplier or the QK norm left
    out of the reference: ten times and more past the 1e-5 the float32
    product is held to (the QK norm, whose scales lie within 1 +- 0.25,
    thirty times; the others hundreds), so the tolerance sees each of
    the four."""
    params, tokens, shape, got, want = _whole()
    other = float(REF.loss(params, tokens, without=(piece,),
                           **shape)[0].mean())
    assert abs(got - want) <= 1e-5 * abs(want)
    assert abs(got - other) > 1e-4 * abs(want), piece


def test_bfloat16_where_float32_is_stated_fails_the_tolerance():
    """The same weights through the product in bfloat16: ten times and
    more past the 1e-5 the float32 product is held to."""
    params, tokens, _, _, want = _whole()
    got = float(jax.jit(_loss(DRAWN(TINY).spec(dtype="bfloat16"), tokens))(
        params))
    assert abs(got - want) > 1e-4 * abs(want)


def _file(**over):
    """A configuration's file of the TINY model, as the reference reads
    one."""
    return {
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
        "hidden_size": 64, "num_experts": 4, "num_experts_per_tok": 3,
        "rms_norm_eps": 1e-5, "rope_theta": 10000, "sliding_window": 8,
        "route_norm": True, "route_scale": 2.826, "mup_enabled": True,
        "layer_types": [TYPES[letter] for letter in "wwwaww"],
        "layers_kept": [1, 2, 3, 4, 5], "share_index": 1,
        "vocab_size": 96, "seq_len": 32,
        "cli": {"model_zoo": "transformer",
                "model_params": dict(TINY, **over)}}


def test_the_references_checks_pass_through_their_door(capsys):
    """``case`` runs the routing check and the layer check on the
    reference's own inputs; a float32 program is float32 math.  The
    file's ``layer_types`` over ``layers_kept`` and its ``mup_enabled``
    say what the program's ``layer_pattern`` and ``embed_multiplier``
    say."""
    spec = tfm.model_spec(**TINY)
    config = _file()
    assert REF.shape_of(config) == _shape(spec.config)
    got = REF.case(config, jax.jit(spec.init_fn)(jax.random.PRNGKey(3)),
                   np.random.default_rng(0), None)
    np.testing.assert_allclose(
        got[3](got[0]),
        REF.loss(got[0], got[1], **_shape(spec.config))[0], rtol=1e-6)
    said = [json.loads(line) for line in capsys.readouterr().err.split("\n")
            if line.startswith("{")]
    assert said[0] == {"routing_same_input": 1.0,
                       "floor": REF.SAME_INPUT_ROUTING_FLOOR}
    assert said[1]["ceiling"] == REF.SAME_INPUT_LAYER_CEILING
    assert sorted(said[1]["layers_same_input"]) == sorted(REF.LAYER_PARTS)
    assert max(said[1]["layers_same_input"].values()) <= 1e-5


@pytest.mark.parametrize("lower", ["program-bfloat16", "reference-float8"])
def test_a_layer_in_lower_precision_is_told_on_the_same_inputs(monkeypatch,
                                                               lower):
    """``layer_errors`` on the reference's own inputs: a bfloat16
    program stands between float32 (~1e-7) and the ceiling; the
    reference's own math in float8 is past it in every part."""
    params = DRAWN(TINY).params
    seen = rc.wanted(DRAWN(TINY))[0][1][0]
    assert len(seen) == 4 and seen[0].h.shape == (2, 32, 64)
    if lower == "reference-float8":
        errors = REF.layer_errors(_file(), rounded=jnp.float8_e4m3fn)(
            params, seen)
        assert min(errors.values()) > 2 * REF.SAME_INPUT_LAYER_CEILING
        return
    config = _file(dtype="bfloat16")
    errors = REF.layer_errors(config)(params, seen)
    assert sorted(errors) == sorted(REF.LAYER_PARTS)
    assert 1e-3 < min(errors.values())
    assert max(errors.values()) < REF.SAME_INPUT_LAYER_CEILING
    monkeypatch.setattr(REF, "SAME_INPUT_LAYER_CEILING", 1e-5)
    with pytest.raises(SystemExit, match="attention .* over 1.0e-05"):
        REF.check_layers(config, params, seen)


# -- each mechanism alone ----------------------------------------------------


def _layer(cfg, seed=0, **scales):
    """(x [2, T, E], one expert layer's weights) of a model of ``cfg``,
    the scales of its norms drawn off 1."""
    params = tfm.init_params(jax.random.PRNGKey(seed), cfg)
    w = dict(params["layers"]["tail"]["0"])
    rng = np.random.default_rng(seed)
    for name in ("ln1_post", "ln2_post", "q_norm", "k_norm"):
        if name in w:
            w[name] = jnp.asarray(1.0 + 0.25 * rng.uniform(
                -1, 1, w[name].shape), jnp.float32)
    x = jnp.asarray(rng.standard_normal((2, cfg.max_seq_len, cfg.dim)),
                    jnp.float32)
    return x, w


@pytest.mark.parametrize("subject", ["program", "reference"])
def test_the_window_sees_i_minus_w_plus_1_and_not_i_minus_w(subject):
    """Both edges of ``i - window < j <= i`` at a window of 8: a change
    of the input at position j moves attention's output at i = j + 7
    (seen) and at no i >= j + 8 (not), in the program's layer and in
    the reference's; the full layer's output moves at every i >= j."""
    cfg = tfm.model_spec(**TINY).config
    x, w = _layer(cfg)
    h = tfm._rmsnorm(x, w["ln1"], cfg.norm_eps)
    positions = jnp.arange(cfg.max_seq_len)

    def attend(h, window):
        if subject == "reference":
            return REF.attention(
                h, w, cfg.num_heads, cfg.kv_heads, cfg.head_dim,
                cfg.norm_eps, 10000.0 if window else None, window,
                lambda a: a)
        kind = tfm.Kind("a", False, window, bool(window))
        return tfm._attention_mix(h, w, cfg, None, positions, kind)[0]

    j = 5
    moved = h.at[:, j].add(1.0)
    for window in (cfg.window, 0):
        delta = jnp.abs(attend(moved, window) - attend(h, window)).max(
            axis=(0, 2))
        assert not float(delta[:j].max())                  # causal
        assert float(delta[j + 7]) > 1e-4                  # j = i - 7
        if window:
            assert not float(delta[j + 8:].max())          # j = i - 8
        else:
            assert float(delta[j + 8:].min()) > 1e-6


def test_the_gate_is_a_values_own_between_the_kernel_and_wo():
    """``attn_gate``: the ungated layer's heads, each value times the
    sigmoid of its own gate (a projection of the same normed input),
    then ``wo``; a gate of zeros halves the output."""
    cfg = tfm.model_spec(**TINY).config
    x, w = _layer(cfg)
    h = tfm._rmsnorm(x, w["ln1"], cfg.norm_eps)
    positions = jnp.arange(cfg.max_seq_len)
    kind = tfm.Kind("a", False, cfg.window, True)
    plain = dataclasses.replace(cfg, attn_gate=False)
    # the heads before wo: the ungated layer through an identity wo
    eye = dict(w, wo=jnp.eye(128, dtype=jnp.float32))
    heads = tfm._attention_mix(h, eye, dataclasses.replace(
        plain, dim=128), None, positions, kind)[0]
    want = (heads * jax.nn.sigmoid(h @ w["w_attn_gate"])) @ w["wo"]
    got = tfm._attention_mix(h, w, cfg, None, positions, kind)[0]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    shut = dict(w, w_attn_gate=jnp.zeros_like(w["w_attn_gate"]))
    np.testing.assert_allclose(
        tfm._attention_mix(h, shut, cfg, None, positions, kind)[0],
        0.5 * tfm._attention_mix(h, w, plain, None, positions, kind)[0],
        rtol=1e-5, atol=1e-6)


def test_the_output_norms_stand_before_the_add_in_one_place_each():
    """``x + n2(Op(n1(x)))`` and ``x + n4(FFN(n3(x)))``: the operator's
    and the FFN's results of the plain block, normed with the new
    scales, then added; the stream is named after the add."""
    cfg = tfm.model_spec(**TINY).config
    plain = dataclasses.replace(cfg, post_norms=False)
    x, w = _layer(cfg)
    positions = jnp.arange(cfg.max_seq_len)
    kind = tfm.Kind("a", False, cfg.window, True)
    norm = lambda y, scale: REF.rmsnorm(y, scale, cfg.norm_eps)
    op = tfm._operator(x, w, plain, None, positions, kind)[0] - x
    got = tfm._operator(x, w, cfg, None, positions, kind)[0]
    np.testing.assert_allclose(got, x + norm(op, w["ln1_post"]),
                               rtol=1e-5, atol=1e-6)
    ffn = tfm._ffn(x, w, plain, None)[0] - x
    np.testing.assert_allclose(
        tfm._ffn(x, w, cfg, None)[0], x + norm(ffn, w["ln2_post"]),
        rtol=1e-5, atol=1e-6)
    # a scale of 2 doubles what joins the stream, whatever the result's
    # own size: the norm is on the output
    twice = dict(w, ln1_post=2 * w["ln1_post"])
    np.testing.assert_allclose(
        tfm._operator(x, twice, cfg, None, positions, kind)[0] - x,
        2 * (got - x), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("rope", [False, True])
def test_the_head_norm_stands_on_a_layer_with_and_without_rope(rope):
    """``qk_norm="head"`` on both attention kinds of the stack: q and k
    are normed over each head's values before RoPE where the layer has
    it, and where it has none they are the normed projections
    themselves; head-major as the kernels take them, [B, H, T, D] and
    [B, G, T, D]."""
    cfg = tfm.model_spec(**TINY).config
    x, w = _layer(cfg)
    positions = jnp.arange(cfg.max_seq_len)
    q, k, v = (a.transpose(0, 2, 1, 3)
               for a in tfm._project_qkv(x, w, cfg, positions, rope))
    heads = lambda a, n: a.reshape(2, cfg.max_seq_len, n, cfg.head_dim)
    want_q = REF.rmsnorm(heads(x @ w["wq"], 4), w["q_norm"], cfg.norm_eps)
    want_k = REF.rmsnorm(heads(x @ w["wk"], 2), w["k_norm"], cfg.norm_eps)
    if rope:
        want_q, want_k = (REF.rope(a, 10000.0) for a in (want_q, want_k))
    np.testing.assert_allclose(q, want_q, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(k, want_k, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(v, heads(x @ w["wv"], 2), rtol=1e-6)


def test_the_multiplier_scales_the_input_and_never_the_tied_head():
    """``embed_multiplier``: a token's row times m on its way in, in
    the compute dtype; a tied head reads the table as it is; and
    ``embed_scale`` stays the standard deviation of the draw."""
    sizes = dict(vocab_size=64, dim=32, num_heads=2, num_layers=1,
                 seq_len=16, dtype="float32")
    spec = tfm.model_spec(embed_multiplier=4.0, **sizes)
    cfg, one = spec.config, tfm.model_spec(**sizes).config
    assert cfg.tied_embeddings and cfg.embed_scale == 0.02
    params = jax.jit(spec.init_fn)(jax.random.PRNGKey(0))
    assert float(params["embed"].std()) == pytest.approx(0.02, rel=0.1)
    tokens = jnp.arange(16, dtype=jnp.int32)[None]
    np.testing.assert_array_equal(tfm._embed(params, tokens, cfg),
                                  4.0 * params["embed"][tokens])
    np.testing.assert_array_equal(tfm._embed(params, tokens, one),
                                  params["embed"][tokens])
    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, 16, 32)),
                    jnp.float32)
    np.testing.assert_array_equal(tfm._head(params, x, cfg),
                                  tfm._head(params, x, one))
    half = dataclasses.replace(cfg, dtype="bfloat16")
    assert tfm._embed(params, tokens, half).dtype == jnp.bfloat16
    # a model with the multiplier alone decodes: the table's other
    # readers multiply too
    logits = tfm.forward(params, tokens, cfg)
    last, _ = tfm.prefill(params, cfg, tokens, 16)
    np.testing.assert_allclose(last, logits[:, -1], rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(logits - tfm.forward(params, tokens, one)).max()) \
        > 1e-3


def test_eight_shares_and_one_shared_expert_add_up_to_the_uncut_layer():
    """64 experts in 8 shares of 8, 8 a token behind a ``sigmoid_bias``
    route: the eight shares' ROUTED parts plus the shared expert counted
    once equal the reference's uncut expert layer (all 64 held) plus its
    shared expert; every share computes the same shared expert, so
    summing the shares' whole results would count it eight times."""
    whole = tfm.TransformerConfig(
        dim=64, ffn_dim=48, moe_experts=64, moe_top_k=8,
        moe_router="sigmoid_bias", moe_norm_topk=True,
        moe_route_scale=2.826, moe_shared_experts=1, dtype="float32")
    rng = np.random.default_rng(8)
    draw = lambda *shape: jnp.asarray(
        rng.standard_normal(shape) * shape[-2] ** -0.5, jnp.float32)
    w = {"w_router": draw(64, 64), "w_gate": draw(64, 64, 48),
         "w_up": draw(64, 64, 48), "w_down": draw(64, 48, 64),
         "ws_gate": draw(64, 48), "ws_up": draw(64, 48),
         "ws_down": draw(48, 64), "ln2": jnp.ones((64,), jnp.float32),
         "expert_bias": jnp.asarray(0.2 * rng.standard_normal(64),
                                    jnp.float32)}
    x = jnp.asarray(rng.standard_normal((2, 24, 64)), jnp.float32)
    u = REF.rmsnorm(x, w["ln2"], whole.norm_eps)
    identity = lambda a: a
    shared = REF.swiglu(u, w["ws_gate"], w["ws_up"], w["ws_down"], identity)
    want = REF.experts(u, w, 8, True, 2.826, 0)[0] + shared
    routed, rows = 0.0, 0.0
    for index in range(8):
        cfg = dataclasses.replace(whole, moe_experts_held=8,
                                  moe_share_index=index)
        part = dict(w, **{name: w[name][index * 8:(index + 1) * 8]
                          for name in ("w_gate", "w_up", "w_down")})
        out, _, _, load = tfm._ffn(x, part, cfg, None)
        # the layer's result is x + routed part + the shared expert
        np.testing.assert_allclose(
            tfm._shared_expert(u, part, cfg), shared, rtol=1e-4, atol=1e-5)
        routed = routed + (out - x - shared)
        rows += float(load[:8].sum())
    np.testing.assert_allclose(routed + shared, want, rtol=1e-4, atol=2e-5)
    assert rows == 2 * 24 * 8          # every assignment held by one share


def test_the_route_scale_of_2826_weighs_the_eight_chosen_of_128():
    cfg = tfm.TransformerConfig(
        dim=32, moe_experts=128, moe_top_k=8, moe_router="sigmoid_bias",
        moe_norm_topk=True, moe_route_scale=2.826)
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.standard_normal((1, 16, 32)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((32, 128)) * 0.2, jnp.float32)
    bias = jnp.asarray(0.1 * rng.standard_normal(128), jnp.float32)
    probs, gates, experts = tfm.moe_route(h, w, cfg, bias)
    assert gates.shape == (1, 16, 8)
    picked = jnp.take_along_axis(probs, experts, -1)
    np.testing.assert_allclose(
        gates, 2.826 * picked / (picked.sum(-1, keepdims=True) + 1e-6),
        rtol=1e-6)
    np.testing.assert_allclose(gates.sum(-1), 2.826, rtol=1e-5)
    # the published 1e-20 where the program adds 1e-6: a sum of eight
    # sigmoids is of order 1
    np.testing.assert_allclose(
        gates, 2.826 * picked / (picked.sum(-1, keepdims=True) + 1e-20),
        rtol=1e-5)
    # the reference's choice and weights on the same inputs
    scores, chosen = REF.route(h, w, bias, 8)
    np.testing.assert_array_equal(
        np.asarray(jax.nn.one_hot(experts, 128).sum(-2) > 0), chosen)
    # the bias moved the choice, not the weights
    assert not np.array_equal(
        np.sort(experts, -1), np.sort(jax.lax.top_k(probs, 8)[1], -1))


# -- the plan, the tree, the optimizer ---------------------------------------


def test_stack_plan_of_the_cells_pattern():
    """``wwaww`` behind one dense layer: the lead is the dense windowed
    layer; the shortest period the rest (``waww``) repeats is ``waw``,
    once, and the last windowed layer the remainder: the five layers in
    their order, each run once."""
    cfg = tfm.model_spec(**TINY).config
    plan = tfm.stack_plan(cfg)
    letters = lambda kinds: "".join(
        ("w" if k.window else "a") for k in kinds)
    assert (letters(plan.lead), letters(plan.period), plan.periods,
            letters(plan.tail)) == ("w", "waw", 1, "w")
    assert [k.dense for k in cfg.kinds] == [True] + [False] * 4
    assert [(k.window, k.rope) for k in cfg.kinds] == [
        (8, True), (8, True), (0, False), (8, True), (8, True)]
    assert letters(cfg.kinds) == "wwaww"


def test_the_tree_has_the_three_weights_and_adamw_decays_the_gate_alone():
    spec = tfm.model_spec(**TINY)
    params = jax.jit(spec.init_fn)(jax.random.PRNGKey(0))
    lead = params["layers"]["lead"]["0"]
    period = params["layers"]["period"]["1"]
    assert lead["w_attn_gate"].shape == (64, 128)
    assert period["w_attn_gate"].shape == (1, 64, 128)
    assert period["ln1_post"].shape == period["ln2_post"].shape == (1, 64)
    for group in params["layers"].values():
        for w in group.values():
            for name in ("ln1_post", "ln2_post"):     # drawn at 1
                np.testing.assert_array_equal(w[name],
                                              jnp.ones_like(w[name]))
            assert float(w["w_attn_gate"].std()) == pytest.approx(
                float(w["wq"].std()), rel=0.2)
    mask = tfm._decayed(params)["layers"]["period"]["1"]
    assert mask["w_attn_gate"] is True and mask["wq"] is True
    assert mask["ln1_post"] is False and mask["ln2_post"] is False
    assert mask["expert_bias"] is False
    # no experts, so no expert_bias: the mask is there for the norms,
    # whose scales a zero gradient leaves where the decay moves the gate
    soft = tfm.model_spec(vocab_size=64, dim=32, num_heads=2, num_layers=1,
                          seq_len=16, post_norms=True, attn_gate=True)
    p = jax.jit(soft.init_fn)(jax.random.PRNGKey(0))
    update = jax.jit(lambda state: soft.optimizer.update(
        jax.tree_util.tree_map(jnp.zeros_like, p), state, p)[0])
    moved = optax.apply_updates(p, update(soft.optimizer.init(p)))["layers"]
    np.testing.assert_array_equal(moved["ln1_post"], p["layers"]["ln1_post"])
    np.testing.assert_array_equal(moved["ln2_post"], p["layers"]["ln2_post"])
    assert float(jnp.abs(moved["w_attn_gate"]
                         - p["layers"]["w_attn_gate"]).max()) > 0


# -- what must not have moved ------------------------------------------------


with open(os.path.join(HERE, "plain_block_program.json")) as _fh:
    PLAIN = json.load(_fh)


@pytest.mark.parametrize("name", sorted(PLAIN))
def test_with_the_three_fields_off_tree_and_program_are_the_parents(name):
    """Small models of each benchmark cell's kind (dense, experts, the
    conv stack, the banded stack, the latent stack) with ``post_norms``,
    ``attn_gate`` and ``embed_multiplier`` at their defaults: the
    parameter tree, the training program's primitives counted through
    every nested jaxpr, and the length of the jaxpr's text are those
    recorded from the parent of the PR that gave the block its one
    ``x + post(Op(pre(x)))`` (tests/plain_block_program.json; the two
    texts were the same character for character).  The four
    equal-width models' primitives and text lengths were recorded again
    at PR 42 (q, k, v written head-major by the projections, RoPE by
    its permutation product, ``wo`` over (head, width): ``dense`` 44,604
    -> 42,783 characters); the latent stack's, which ran none of the
    changed code, were the record's as they were.  The three stacks
    whose layers differ were recorded again at PR 46, whose
    ``_updates_apart`` hands each matrix of an unrolled stack its
    gradient through a barrier of its own: their
    ``optimization_barrier`` count alone moved (by 23, 20 and 7: the
    conv and the banded stack are one period each, the latent stack's
    period is scanned twice and left alone, so its leading layer's
    alone) and the text grew by those equations (the conv
    stack 289,982 -> 291,070); with the identity taken out the program
    was the record's character for character.  All five were recorded
    again at PR 53, whose embedding lookup has a derivative of its own
    (``ops/embed_rows.py``): one ``custom_vjp_call`` more, and the
    ``lt`` / ``add`` / ``select_n`` / ``broadcast_in_dim`` with which
    the backward's one float32 ``scatter-add`` wraps a negative id as
    the forward's gather did (577 characters or so a model: ``dense``
    42,783 -> 43,342); ``gather`` and ``scatter-add`` count what they
    counted (the scatter is the op's own now, of float32 rows).  The
    trees are the record's."""
    from tests.test_mixed_stack import _eqns

    was = PLAIN[name]
    spec = tfm.model_spec(**was["model_params"])
    cfg = spec.config
    assert (cfg.post_norms, cfg.attn_gate, cfg.embed_multiplier) == (
        False, False, 1.0)
    shapes = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(lambda a: list(a.shape), shapes) \
        == was["tree"]
    tokens = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, t: _loss(spec, t)(p)))(
        shapes, tokens)
    assert dict(collections.Counter(
        e.primitive.name for e in _eqns(jaxpr.jaxpr))) == was["prims"]
    assert len(str(jaxpr)) == was["chars"]


def test_a_block_with_norms_and_gate_off_is_the_plain_block_bit_for_bit():
    """The same weights through ``_layer_body`` with the fields off and
    through the equations the block had before them, written out:
    ``x + Attention(norm(x))`` then ``x + FFN(norm(x))``."""
    cfg = dataclasses.replace(tfm.model_spec(**TINY).config,
                              post_norms=False, attn_gate=False)
    x, w = _layer(cfg)
    positions = jnp.arange(cfg.max_seq_len)
    kind = tfm.Kind("a", False, cfg.window, True)
    got = tfm._layer_body(x, w, cfg, None, positions, kind=kind)[0]
    h = tfm._rmsnorm(x, w["ln1"], cfg.norm_eps)
    q, k, v = tfm._project_qkv(h, w, cfg, positions, True)
    attn = fa.flash_attention(q, k, v, causal=True, window=cfg.window)
    mid = x + jnp.einsum("bhtk,hkd->btd", attn,
                         w["wo"].reshape(4, 32, cfg.dim))
    u = tfm._rmsnorm(mid, w["ln2"], cfg.norm_eps)
    want = mid + (tfm._moe_ffn(u, w, cfg, None)[0]
                  + tfm._shared_expert(u, w, cfg))
    np.testing.assert_array_equal(got, want)
    extra = {"w_attn_gate", "ln1_post", "ln2_post"}
    assert not extra & set(tfm.init_params(
        jax.random.PRNGKey(0), cfg)["layers"]["tail"]["0"])


# -- the projections: the kernels' planes from the matmuls themselves -------------


def _token_major_projections(h, w, cfg, positions, rope):
    """``_project_qkv`` as it was before the projections wrote the
    kernels' planes themselves: one product a weight on [B, T, heads *
    D], the whole-projection norm there, the per-head norm and RoPE on
    [B, T, heads, D]."""
    dtype = jnp.dtype(cfg.dtype)
    B, T = h.shape[:2]

    def project(name, norm, heads):
        x = h @ w[name].astype(dtype)
        if norm and cfg.qk_norm != "head":
            x = tfm._rmsnorm(x, w[norm].astype(dtype), cfg.norm_eps)
        x = x.reshape(B, T, heads, cfg.head_dim)
        if norm and cfg.qk_norm == "head":
            x = tfm._rmsnorm(x, w[norm].astype(dtype), cfg.norm_eps)
        return x

    q = project("wq", cfg.qk_norm and "q_norm", cfg.num_heads)
    k = project("wk", cfg.qk_norm and "k_norm", cfg.kv_heads)
    v = project("wv", None, cfg.kv_heads)
    if rope:
        q = tfm._rope(q, positions, cfg.rope_theta)
        k = tfm._rope(k, positions, cfg.rope_theta)
    return q, k, v


def _token_major_mix(h, w, cfg, positions, kind):
    """``_attention_mix`` over those: K and V repeated to the query
    heads, the three transposed to the op's [B, H, T, D], its output
    transposed back and flattened before the gate and ``wo``."""
    dtype = jnp.dtype(cfg.dtype)
    B, T = h.shape[:2]
    q, k, v = _token_major_projections(h, w, cfg, positions, kind.rope)
    group = cfg.num_heads // cfg.kv_heads
    k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
    attn = fa.flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=True, window=kind.window,
    ).transpose(0, 2, 1, 3).reshape(B, T, -1)
    if cfg.attn_gate:
        attn = attn * jax.nn.sigmoid(h @ w["w_attn_gate"].astype(dtype))
    return attn @ w["wo"].astype(dtype)


@pytest.mark.parametrize("gate", [False, True])
@pytest.mark.parametrize("rope", [False, True])
@pytest.mark.parametrize("qk_norm", [False, True, "head"])
def test_the_projections_equal_the_token_major_ones(monkeypatch, qk_norm,
                                                    rope, gate):
    """q, k, v (head-major, K and V at their own 2 heads for 4 query
    heads), ``_attention_mix``'s result and the gradients of every
    weight and of the input, from products that write [B, heads, T, D]
    themselves (per-head views of ``wq`` / ``wk`` / ``wv`` / the gate's
    weight, the whole-projection norm over (head, width), RoPE's halves
    swapped by a permutation product, ``wo`` over (head, width)),
    against the token-major form they replace with its repeat and its
    four transposes: float32 lands within its rounding, for each kind
    of QK norm, with and without RoPE and the gate."""
    monkeypatch.setenv("ELASTICDL_FLASH", "off")
    cfg = tfm.TransformerConfig(
        vocab_size=64, dim=64, num_heads=4, num_kv_heads=2, head_dim=32,
        num_layers=1, max_seq_len=32, qk_norm=qk_norm, attn_gate=gate,
        rope_theta=10000, norm_eps=1e-5, dtype="float32")
    H, G, D, E = 4, 2, 32, cfg.dim
    shapes = {"wq": (E, H * D), "wk": (E, G * D), "wv": (E, G * D),
              "wo": (H * D, E)}
    if gate:
        shapes["w_attn_gate"] = (E, H * D)
    if qk_norm:
        whole = qk_norm != "head"
        shapes.update(q_norm=(H * D if whole else D,),
                      k_norm=(G * D if whole else D,))
    rng = np.random.default_rng(11)
    w = {name: jnp.asarray(
        (1.0 + 0.25 * rng.uniform(-1, 1, shape)) if "norm" in name
        else rng.standard_normal(shape) * shape[0] ** -0.5, jnp.float32)
        for name, shape in shapes.items()}
    draw = lambda: jnp.asarray(rng.standard_normal((2, 32, E)), jnp.float32)
    h, g = draw(), draw()
    positions = jnp.arange(32)
    kind = tfm.Kind("a", False, 0, rope)

    got = tfm._project_qkv(h, w, cfg, positions, rope)
    want = _token_major_projections(h, w, cfg, positions, rope)
    for name, a, b, heads in zip("qkv", got, want, (H, G, G)):
        assert a.shape == (2, heads, 32, D), name
        assert _apart(a, b.transpose(0, 2, 1, 3)) <= 2e-6, name

    def run(fn):
        out, vjp = jax.vjp(
            lambda h, w: fn(h, w, cfg, positions, kind), h, w)
        return out, vjp(g)

    (out, (dh, dw)), (ref, (ref_dh, ref_dw)) = (
        jax.jit(lambda: run(lambda *a: tfm._attention_mix(
            a[0], a[1], a[2], None, *a[3:])[0]))(),
        jax.jit(lambda: run(_token_major_mix))())
    assert out.shape == (2, 32, E)
    assert _apart(out, ref) <= 2e-6
    assert _apart(dh, ref_dh) <= 2e-6
    for name in w:
        assert dw[name].shape == w[name].shape, name
        assert _apart(dw[name], ref_dw[name]) <= 2e-6, name


@pytest.mark.parametrize("remat", [False, True])
def test_the_traced_step_moves_no_activation_round_the_flash_calls(
        monkeypatch, remat):
    """The training step of a grouped-query stack (2 query heads on 1,
    windowed and full layers, per-head QK norm, RoPE, the gate) traced
    with the kernels: every ``pallas_call`` takes q at the query heads
    and K, V at their own count, the backward's dk and dv leave at that
    count, and no ``broadcast_in_dim`` anywhere in the step (forward,
    backward, a rematerialized layer's second forward) has the
    repeat's result, K / V spread to [B, G, group, T, D]: nothing
    spreads a K/V head over its group, and so nothing sums a gradient
    back over it (a table or a norm's scale broadcast to [B, H, T, D]
    is elementwise work's, fused).  Under ``off`` the same model
    repeats inside the reference (a ``broadcast_in_dim`` to [B, G,
    group, T, D]), which is what the count would catch.  (A jaxpr's ``transpose`` says nothing here: an
    einsum's is the order its matmul writes, folded by the compiler;
    that no copy stands between the projections and the calls is held
    on the TPU compiler's own program,
    tests/test_flash_compile_tpu.py::test_a_gqa_layer_moves_...)"""
    from tests.test_mixed_stack import _eqns

    B, T = 2, 256

    def step_eqns(mode):
        monkeypatch.setenv("ELASTICDL_FLASH", mode)
        spec = tfm.model_spec(**dict(KERNEL, remat=remat))
        params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
        tokens = jnp.zeros((B, T), jnp.int32)
        return spec.config, list(_eqns(jax.make_jaxpr(jax.grad(
            lambda p: _loss(spec, tokens)(p)))(params).jaxpr))

    def moved(cfg, eqns):
        wide = B * T * cfg.num_heads * cfg.head_dim
        return [(e.primitive.name, v.aval.shape) for e in eqns
                if e.primitive.name == "broadcast_in_dim"
                for v in e.outvars
                if len(v.aval.shape) == 5 and v.aval.size == wide]

    cfg, eqns = step_eqns("interpret")
    H, G, D = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    assert (H, G) == (2, 1)
    assert not moved(cfg, eqns), moved(cfg, eqns)
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    name = lambda e: e.params["name"]
    fwd = [e for e in calls if name(e).startswith("flash_fwd")]
    bwd = [e for e in calls if name(e).startswith("flash_bwd")]
    assert len(bwd) == 3 and len(fwd) == (6 if remat else 3), (
        [name(e) for e in calls])
    planes = lambda vs: [v.aval.shape for v in vs
                         if v.aval.shape[1:] == (T, D)]
    for e in fwd:       # q, k, v in; the output (and the row stats) out
        assert planes(e.invars) == [(B * H, T, D)] + 2 * [(B * G, T, D)]
    for e in bwd:       # k, v, q, dO in; dk, dv, dq out
        assert planes(e.invars) == 2 * [(B * G, T, D)] + 2 * [(B * H, T, D)]
        assert planes(e.outvars) == 2 * [(B * G, T, D)] + [(B * H, T, D)]
    assert ("broadcast_in_dim", (B, G, H // G, T, D)) in moved(
        *step_eqns("off"))


# -- what does not run it says so by name ------------------------------------


UNIFORM = dict(vocab_size=64, dim=32, num_heads=2, num_layers=2, seq_len=16,
               dtype="float32")


@pytest.mark.parametrize("fields,word", [
    (dict(post_norms=True), "post_norms=True"),
    (dict(attn_gate=True), "attn_gate=True"),
])
@pytest.mark.parametrize("what", ["prefill", "decode_step", "generate",
                                  "export_generate", "forward_pipelined",
                                  "mesh"])
def test_what_cannot_run_the_block_refuses_it_by_name(what, fields, word,
                                                      tmp_path):
    spec = tfm.model_spec(**dict(UNIFORM, **fields))
    cfg = spec.config
    assert tfm.stack_plan(cfg) is None      # the block alone is refused
    params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    prompt = jnp.zeros((1, 4), jnp.int32)
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2, 1, 1, 1),
                ("dp", "pp", "tp", "sp", "ep"))
    calls = {
        "prefill": lambda: tfm.prefill(params, cfg, prompt, 8),
        "decode_step": lambda: tfm.decode_step(
            params, cfg, None, 0, prompt[:, 0]),
        "generate": lambda: tfm.generate(params, cfg, prompt, 2),
        "export_generate": lambda: tfm.export_generate(
            str(tmp_path), params, cfg, 2, 4),
        "forward_pipelined": lambda: tfm.forward_pipelined(
            params, prompt, cfg, mesh, 2),
        "mesh": lambda: tfm.model_spec(mesh=mesh, **dict(UNIFORM,
                                                         **fields)),
    }
    with pytest.raises(NotImplementedError) as refusal:
        calls[what]()
    assert word in str(refusal.value)
    assert "w_attn_gate" in str(refusal.value)
    assert "ln1_post" in str(refusal.value)
    assert what.split("_")[0] in str(refusal.value) or what == "mesh"


@pytest.mark.parametrize("other,word", [
    (dict(kv_latent_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=8),
     "kv_latent_rank=32"),
    (dict(layer_pattern="ca"), "layer_pattern='ca'"),
])
def test_latent_attention_and_the_convolution_refuse_the_gate_by_name(
        other, word):
    with pytest.raises(ValueError, match="attn_gate") as refusal:
        tfm.model_spec(**dict(UNIFORM, attn_gate=True, **other))
    assert word in str(refusal.value)
    assert "w_attn_gate" in str(refusal.value)
    # the output norms are the block's, whatever the operator
    spec = tfm.model_spec(**dict(UNIFORM, post_norms=True, **other))
    params = jax.jit(spec.init_fn)(jax.random.PRNGKey(0))
    tokens = jnp.zeros((1, 16), jnp.int32)
    assert np.isfinite(float(_loss(spec, tokens)(params)))
    names = set()
    jax.tree_util.tree_map_with_path(
        lambda path, _: names.add(path[-1].key), params)
    assert {"ln1_post", "ln2_post"} <= names


# -- the lines ---------------------------------------------------------------


def test_the_attention_block_line_says_what_runs_and_what_the_repeat_moves(
        monkeypatch):
    """Once per compiled shape: heads, the block's pieces, and the bytes
    of K, V and their gradients beyond what the K/V heads hold where
    they are repeated to the query heads: the jnp reference does (4 x
    (heads - kv_heads) x rows x head_dim x size a layer, and K and V
    once more in a rematerialized layer's backward), the kernels read
    K/V head ``head // group`` and the line, still printed, says 0 and
    0; 0 without GQA."""
    tfm.announce_attention.cache_clear()
    monkeypatch.setenv("ELASTICDL_FLASH", "interpret")
    kernel = tfm.model_spec(**dict(KERNEL, remat=True))
    shapes = jax.eval_shape(kernel.init_fn, jax.random.PRNGKey(0))
    line, = _lines(lambda: jax.eval_shape(_loss(
        kernel, jnp.zeros((1, 256), jnp.int32)), shapes), "attention block:")
    assert line == (
        "attention block: rows=256 heads=2 kv_heads=1 head_dim=64 "
        "qk_norm=head gate=1 out_norms=1 embed_multiplier=11.3137 layers=3 "
        "kv_repeat_bytes=0 kv_repeat_again_bytes=0")
    monkeypatch.setenv("ELASTICDL_FLASH", "off")
    tfm.announce_attention.cache_clear()
    spec = tfm.model_spec(**dict(TINY, remat=True))
    params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 32), jnp.int32)
    run = lambda: jax.eval_shape(_loss(spec, tokens), params)
    lines = _lines(lambda: (run(), run()), "attention block:")
    assert lines == [
        "attention block: rows=64 heads=4 kv_heads=2 head_dim=32 "
        "qk_norm=head gate=1 out_norms=1 embed_multiplier=8 layers=5 "
        "kv_repeat_bytes=%d kv_repeat_again_bytes=%d" % (
            4 * 2 * 64 * 32 * 4, 2 * 2 * 64 * 32 * 4)]
    tfm.announce_attention.cache_clear()
    plain = tfm.model_spec(vocab_size=64, dim=32, num_heads=2, num_layers=2,
                           seq_len=16)
    shapes = jax.eval_shape(plain.init_fn, jax.random.PRNGKey(0))
    line, = _lines(lambda: jax.eval_shape(_loss(
        plain, jnp.zeros((1, 16), jnp.int32)), shapes), "attention block:")
    assert line.endswith("qk_norm=False gate=0 out_norms=0 "
                         "embed_multiplier=1 layers=2 kv_repeat_bytes=0 "
                         "kv_repeat_again_bytes=0")


# -- remat_keep --------------------------------------------------------------


def test_remat_keeps_table_has_the_gate_behind_q_k_v():
    """From shapes: the gate's projection [rows, heads * head_dim] stands
    behind q, k, v and before the stream, in every attention layer, and
    a model without the gate has no such entry."""
    cfg = tfm.model_spec(**TINY).config
    rows = 64
    table = {label: (names, nbytes)
             for label, names, nbytes in rk.table(cfg, rows)}
    order = [label for label, _, _ in rk.table(cfg, rows)]
    assert order[:5] == ["flash", "route", "qkv", "gate", "stream"]
    assert table["gate"] == ((rk.KEEP_ATTN_GATE,), rows * 4 * 32 * 4)
    assert table["qkv"][1] == rows * (4 + 2 * 2) * 32 * 4
    layers = {label: count for label, _, _, count in rk._entries(cfg, rows)}
    assert (layers["gate"], layers["shared_up"], layers["ffn_up"]) == (
        5, 4, 1)
    plain = dataclasses.replace(cfg, attn_gate=False)
    assert "gate" not in [label for label, _, _ in rk.table(plain, rows)]


def test_kept_names_change_no_gradient(monkeypatch):
    """Every name of the table kept against nothing kept: the same loss
    and gradients, and the program names the gate's projection where it
    makes it."""
    monkeypatch.setenv("ELASTICDL_FLASH", "off")
    spec, params, tokens = DRAWN(TINY, batch=1).parts(remat=True)
    names = tuple(n for _, entry, _ in rk.table(spec.config, 64)
                  for n in entry)
    assert {rk.KEEP_ATTN_GATE, rk.KEEP_Q, rk.KEEP_SHARED_GATE} <= set(names)
    grad = lambda: jax.jit(jax.value_and_grad(_loss(spec, tokens)))(params)
    bare = grad()
    monkeypatch.setattr(rk, "names_for", lambda *a: names)
    kept = grad()
    assert float(kept[0]) == pytest.approx(float(bare[0]), rel=1e-6)
    assert _apart(kept[1], bare[1]) <= 1e-5
    from tests.test_mixed_stack import _eqns

    jaxpr = jax.make_jaxpr(_loss(spec, tokens))(params)
    named = {e.params["name"] for e in _eqns(jaxpr.jaxpr)
             if e.primitive.name == "name"}
    assert rk.KEEP_ATTN_GATE in named and rk.KEEP_STREAM in named


# -- the cell's whole step for a described v5e: last in the file, since
# ``one_chip`` turns XLA's optimisations on for its module (ROADMAP C16)

from tests.tpu_compile import (  # noqa: E402,F401 (the fixtures)
    _inventory_is_held, _updates_in_matmuls, cell_steps, one_chip)


def test_the_gated_blocks_step_holds_no_update_in_a_matmul_nor_more_bytes(
        cell_steps):
    """The ``trinity-mini.seq16384`` cell's whole training step with the
    names ``remat_keep`` chose, for a described v5e: no weight-gradient
    matmul carries an AdamW update (39 did until PR 46) and the
    compiler's bytes are 15.19 GB (15.33 until PR 69, whose sums hand
    the row kernel a token's term count where its eight indices stood:
    the count fell 0.14 GB, and the chip's peak with it, 15.239 ->
    15.102): PR 58's 14.69 and the routed up
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
    assert abs(step.counted - 15.19e9) < 0.1e9, step.counted
    text = step.compiled.as_text()
    assert not _updates_in_matmuls(text)
    assert ".remat" not in text


@pytest.mark.parametrize("config,batch,rows,keep", [
    ("trinity-mini", 1, 16384, True)])
def test_the_expert_layers_inventory_is_held_to_the_compilers_count(
        cell_steps, config, batch, rows, keep):
    """``tests/test_step_compile_tpu.py``'s test of the same name for
    this cell: +0.25 GB with ``choose``'s
    list kept, the compile of the test above."""
    _inventory_is_held(cell_steps, config, batch, rows, keep)
