"""A stack whose attention layers differ (models/transformer.py: full
attention without positional encoding beside windowed attention with
RoPE by pattern, heads of a size of their own, the router read before
the operator, ReGLU experts, a share of the experts) against the plain
reference of the benchmark (benchmark/reference/smallthinker-21b-a3b.py).
Float32 on the CPU at tiny widths."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import manifest
from elasticdl_tpu.models import remat_keep as rk
from elasticdl_tpu.models import transformer as tfm
from elasticdl_tpu.ops import flash_attention as fa
from elasticdl_tpu.ops import moe_dispatch as md
from tests import reference_check as rc

REF = manifest.load_named("reference", "smallthinker-21b-a3b")

# heads x head size = 128, the hidden size 64; a window of 8 in 32
TINY = dict(vocab_size=96, dim=64, num_heads=4, num_kv_heads=2, head_dim=32,
            seq_len=32, ffn_dim=48, ffn_activation="relu", moe_experts=16,
            moe_top_k=3, moe_norm_topk=True, moe_route_before_op=True,
            moe_aux_weight=0, rope_theta=1.5e6, rope_kinds="w", window=8,
            norm_eps=1e-6, tied_embeddings=False, embed_scale=1.0,
            dtype="float32")
PERIOD = dict(TINY, num_layers=4, layer_pattern="awww", moe_experts_held=4,
              moe_share_index=1)


def _shape(cfg, **over):
    """``REF.loss``'s keywords for a model of ``cfg``."""
    kinds = cfg.kinds
    return dict(dict(
        heads=cfg.num_heads, kv_heads=cfg.kv_heads, head_dim=cfg.head_dim,
        top_k=cfg.moe_top_k, eps=cfg.norm_eps, theta=cfg.rope_theta,
        window=cfg.window, windowed=tuple(int(k.window > 0) for k in kinds),
        roped=tuple(int(k.rope) for k in kinds),
        first=cfg.experts_held[0]), **over)


_loss, _apart = rc.loss_of, rc.apart


def _tokens(spec, batch=2, seed=1):
    cfg = spec.config
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch, cfg.max_seq_len)), jnp.int32)


def _params(spec, seed=3):
    params = jax.jit(spec.init_fn)(jax.random.PRNGKey(seed))
    params["lm_head"] = params["lm_head"] * 5.0
    return params


# -- the plan ---------------------------------------------------------------


@pytest.mark.parametrize("pattern,window,rope,want", [
    ("awww", 8, "w", [(0, False), (8, True), (8, True), (8, True)]),
    ("awww", 8, "aw", [(0, True), (8, True), (8, True), (8, True)]),
    ("wa", 4, "", [(4, False), (0, False)]),
    ("", 8, "aw", [(8, True)] * 3),      # no pattern: every layer windowed
    ("", 0, "", [(0, False)] * 3),
])
def test_a_kind_carries_its_window_and_whether_it_has_rope(
        pattern, window, rope, want):
    cfg = tfm.TransformerConfig(
        num_layers=len(pattern) or 3, layer_pattern=pattern, window=window,
        rope_kinds=rope, moe_experts=4)
    assert [(k.window, k.rope) for k in cfg.kinds] == want
    assert all(k.op == "a" and not k.dense for k in cfg.kinds)
    if pattern:
        plan = tfm.stack_plan(cfg)
        assert "".join(map(tfm._letter, plan.period)) == pattern
        assert plan.periods == 1


def test_two_periods_of_the_published_pattern_are_one_scan():
    cfg = tfm.TransformerConfig(num_layers=8, layer_pattern="awww" * 2,
                                window=8, moe_experts=4)
    plan = tfm.stack_plan(cfg)
    assert (len(plan.lead), len(plan.period), plan.periods,
            len(plan.tail)) == (0, 4, 2, 0)
    params = jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    assert set(params["layers"]["period"]) == {"0", "1", "2", "3"}
    assert params["layers"]["period"]["1"]["wq"].shape[0] == 2


@pytest.mark.parametrize("bad,match", [
    (dict(layer_pattern="awww", window=0), "window"),
    (dict(layer_pattern="aaaa", window=8), "window"),
    (dict(layer_pattern="awxw", window=8), "letters"),
    (dict(rope_kinds="ac"), "rope_kinds"),
    (dict(ffn_activation="gelu"), "ffn_activation"),
])
def test_a_wrong_window_kind_or_activation_is_refused_by_name(bad, match):
    with pytest.raises(ValueError, match=match):
        tfm.model_spec(vocab_size=64, dim=32, num_heads=2, num_layers=4,
                       seq_len=16, **bad)


# -- against the plain reference ---------------------------------------------


@pytest.mark.parametrize("case", ["period", "two-periods-remat", "kernels"])
def test_the_period_matches_the_reference(monkeypatch, case):
    """Loss and every gradient of full-NoPE then three windowed-RoPE
    layers over 4 of 16 experts (the window bites: 8 of 32 positions;
    with the kernels in interpret mode 128 of 256, so the band's lower
    edge crosses sub-tiles).  Float32 both sides: 1e-5 of the loss,
    1e-4 of the gradients' norm (the reference sums in another order)."""
    sizes = dict(PERIOD)
    if case == "two-periods-remat":
        sizes.update(num_layers=8, layer_pattern="awww" * 2, remat=True)
    if case == "kernels":
        monkeypatch.setenv("ELASTICDL_FLASH", "interpret")
        sizes.update(seq_len=256, window=128, head_dim=64, num_heads=2,
                     num_kv_heads=1, dim=128, ffn_dim=128)
    spec = tfm.model_spec(**sizes)
    params, tokens = _params(spec), _tokens(spec, batch=1 + (case != "kernels"))
    got, grads = jax.jit(jax.value_and_grad(_loss(spec, tokens)))(params)
    reference = lambda **how: jax.jit(lambda p: REF.loss(
        p, tokens, **_shape(spec.config, **how))[0].mean())
    want, want_grads = jax.jit(jax.value_and_grad(reference()))(params)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert _apart(grads, want_grads) <= 1e-4
    # and the reference tells the mechanisms apart: a window left off,
    # RoPE on the NoPE layer, the route taken after attention's input
    full = reference(windowed=(0,) * spec.config.num_layers)(params)
    roped = reference(roped=(1,) * spec.config.num_layers)(params)
    for other in (full, roped):
        assert abs(float(other) - float(want)) > 2e-4 * abs(float(want))


def test_the_references_routing_check_passes_through_its_door():
    spec = tfm.model_spec(**PERIOD)
    params, tokens = _params(spec), _tokens(spec, batch=1)
    config = {
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
        "moe_num_primary_experts": 4, "moe_num_active_primary_experts": 3,
        "rms_norm_eps": 1e-6, "rope_theta": 1.5e6, "sliding_window_size": 8,
        "sliding_window_layout": [0, 1, 1, 1], "rope_layout": [0, 1, 1, 1],
        "layers_kept": [0, 1, 2, 3], "share_index": 1,
        "cli": {"model_zoo": "transformer", "model_params": PERIOD}}
    shape = REF.shape_of(config)
    assert shape == _shape(spec.config)
    REF.check_routing(config, REF.loss(params, tokens, **shape)[1],
                      shape["top_k"])
    got = REF.case(dict(config, vocab_size=96, seq_len=32),
                   jax.jit(spec.init_fn)(jax.random.PRNGKey(3)),
                   np.random.default_rng(0), None)
    np.testing.assert_allclose(
        got[3](got[0]), REF.loss(got[0], got[1], **shape)[0], rtol=1e-6)


# -- each mechanism alone -----------------------------------------------------


def _block_inputs(cfg, seed=0):
    """(x [2, T, E], the first layer's weights) of a model of ``cfg``."""
    params = tfm.init_params(jax.random.PRNGKey(seed), cfg)
    layers = params["layers"]
    w = (jax.tree_util.tree_map(lambda a: a[0], layers["period"]["0"])
         if "period" in layers else
         jax.tree_util.tree_map(lambda a: a[0], layers))
    x = jnp.asarray(np.random.default_rng(seed).standard_normal(
        (2, cfg.max_seq_len, cfg.dim)), jnp.float32)
    return x, w


@pytest.mark.parametrize("rope", [False, True])
def test_positions_move_a_rope_layer_and_not_a_nope_layer(rope):
    """Positions stretched to 0, 2, 4, ..: attention without positional
    encoding gives the same bits, RoPE's does not (a shift by a constant
    would move neither: RoPE's scores read differences alone)."""
    cfg = tfm.model_spec(**PERIOD).config
    x, w = _block_inputs(cfg)
    kind = tfm.Kind("a", False, 0, rope)
    positions = jnp.arange(cfg.max_seq_len)
    out = tfm._attention(x, w, cfg, None, positions, kind)[0]
    moved = tfm._attention(x, w, cfg, None, 2 * positions, kind)[0]
    if rope:
        assert float(jnp.abs(out - moved).max()) > 1e-3
    else:
        np.testing.assert_array_equal(out, moved)


def test_a_windowed_layer_sees_its_last_positions_alone():
    """Changing a token more than ``window`` back leaves a windowed
    layer's output at the last position as it was, and moves a full
    layer's."""
    cfg = tfm.model_spec(**PERIOD).config
    x, w = _block_inputs(cfg)
    positions = jnp.arange(cfg.max_seq_len)
    far = x.at[:, cfg.max_seq_len - 1 - cfg.window].add(1.0)
    for kind, same in ((tfm.Kind("a", False, cfg.window, True), True),
                       (tfm.Kind("a", False, 0, True), False)):
        out = tfm._attention(x, w, cfg, None, positions, kind)[0][:, -1]
        moved = tfm._attention(far, w, cfg, None, positions, kind)[0][:, -1]
        assert bool(jnp.array_equal(out, moved)) == same


@pytest.mark.parametrize("head_dim", [32, 16, 0])
def test_head_dim_is_a_size_of_its_own(head_dim):
    """heads x head size = 128, 64 (the hidden size) and the default:
    the projections' shapes, the K/V cache's, a forward pass, and a
    patternless model's decoding through the cache against its forward."""
    sizes = dict(vocab_size=64, dim=64, num_heads=4, num_kv_heads=2,
                 num_layers=2, seq_len=16, dtype="float32",
                 head_dim=head_dim)
    spec = tfm.model_spec(**sizes)
    cfg = spec.config
    d = head_dim or 16
    assert cfg.head_dim == d
    params = jax.jit(spec.init_fn)(jax.random.PRNGKey(0))
    layers = params["layers"]
    assert layers["wq"].shape == (2, 64, 4 * d)
    assert layers["wk"].shape == layers["wv"].shape == (2, 64, 2 * d)
    assert layers["wo"].shape == (2, 4 * d, 64)
    assert tfm.init_kv_cache(cfg, 3, 16)[0].shape == (2, 3, 16, 2, d)
    assert dataclasses.replace(cfg, remat=True).head_dim == d
    tokens = _tokens(spec, batch=1)
    logits = tfm.forward(params, tokens, cfg)
    assert logits.shape == (1, 16, 64)
    _, caches = tfm.prefill(params, cfg, tokens[:, :8], 16)
    step, _ = tfm.decode_step(params, cfg, caches, 8, tokens[:, 8])
    np.testing.assert_allclose(step, logits[:, 8], rtol=2e-4, atol=2e-5)
    # the flash entry of remat_keep's table counts heads x head size
    entries = dict((label, nbytes) for label, _, nbytes in rk.table(
        dataclasses.replace(cfg, dtype="bfloat16"), 100))
    assert entries["flash"] == 100 * 4 * (d * 2 + 4)
    assert entries["qkv"] == 100 * (4 + 2 * 2) * d * 2


@pytest.mark.parametrize("before", [True, False])
@pytest.mark.parametrize("held", [0, 4])
def test_decoding_routes_where_the_forward_routes(before, held):
    """A patternless MoE model decodes (``prefill`` then ``decode_step``
    through the cache, and ``generate``) what its forward computes,
    whichever input its router reads: ``_decode_layer`` takes the route
    from attention's normed input where ``_layer_body`` does.  The
    two places differ, so a route taken in the wrong one shows."""
    sizes = dict(TINY, num_layers=2, window=0, rope_kinds="aw",
                 moe_route_before_op=before, moe_experts_held=held)
    spec = tfm.model_spec(**sizes)
    cfg = spec.config
    assert tfm.stack_plan(cfg) is None
    params = _params(spec)
    tokens = _tokens(spec, batch=2)
    logits = tfm.forward(params, tokens, cfg)
    other = tfm.forward(params, tokens, dataclasses.replace(
        cfg, moe_route_before_op=not before))
    assert float(jnp.abs(logits - other).max()) > 1e-2
    first, caches = tfm.prefill(params, cfg, tokens[:, :8], 32)
    np.testing.assert_allclose(first, logits[:, 7], rtol=2e-4, atol=2e-5)
    for pos in (8, 9):
        step, caches = tfm.decode_step(params, cfg, caches, pos,
                                       tokens[:, pos])
        np.testing.assert_allclose(step, logits[:, pos], rtol=2e-4,
                                   atol=2e-5)
    # greedy generation is the forward's argmax, token after token
    out = tfm.generate(params, cfg, tokens[:, :8], 4)
    for t in range(8, 12):
        want = jnp.argmax(tfm.forward(params, jnp.pad(
            out[:, :t], ((0, 0), (0, 32 - t))), cfg)[:, t - 1], -1)
        np.testing.assert_array_equal(out[:, t], want)


@pytest.mark.parametrize("dense", [False, True])
def test_reglu_and_swiglu_differ_and_each_is_its_formula(dense):
    """The gate's activation is the model's, of the experts and of a
    dense FFN alike: relu(gate) * up against silu(gate) * up."""
    sizes = dict(TINY, num_layers=1, moe_route_before_op=False)
    if dense:
        sizes.update(moe_experts=0)
    out = {}
    for name in ("relu", "silu"):
        cfg = tfm.model_spec(**dict(sizes, ffn_activation=name)).config
        x, w = _block_inputs(cfg)
        out[name] = tfm._ffn(x, w, cfg, None, dense=dense)[0]
        h = tfm._rmsnorm(x, w["ln2"], cfg.norm_eps)
        act = {"relu": jax.nn.relu, "silu": jax.nn.silu}[name]
        if dense:
            want = x + (act(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]
        else:
            weights = REF.route(h, w["w_router"], cfg.moe_top_k)[0]
            want = x + sum(
                weights[..., e, None] * (
                    (act(h @ w["w_gate"][e]) * (h @ w["w_up"][e]))
                    @ w["w_down"][e]) for e in range(cfg.moe_experts))
        np.testing.assert_allclose(out[name], want, rtol=2e-4, atol=2e-5)
    assert float(jnp.abs(out["relu"] - out["silu"]).max()) > 1e-2


@pytest.mark.parametrize("before", [True, False])
def test_the_route_is_taken_from_the_operators_input(monkeypatch, before):
    """``moe_route_before_op``: the experts get ``moe_route`` of
    RMSNorm_1 of the block's input, taken before attention ran; without
    it, of RMSNorm_2 of the stream after attention, as ever."""
    cfg = tfm.model_spec(**dict(PERIOD, moe_route_before_op=before)).config
    x, w = _block_inputs(cfg)
    seen = []
    real = tfm.moe_experts

    def spy(h, gates, experts, *weights, **kw):
        seen.append((gates, experts))
        return real(h, gates, experts, *weights, **kw)

    monkeypatch.setattr(tfm, "moe_experts", spy)
    positions = jnp.arange(cfg.max_seq_len)
    kind = cfg.kinds[0]
    out, _ = tfm._layer_body(x, w, cfg, None, positions, kind=kind)
    (gates, experts), = seen
    first = tfm.moe_route(tfm._rmsnorm(x, w["ln1"], cfg.norm_eps),
                          w["w_router"], cfg)
    after = tfm._attention(x, w, cfg, None, positions, kind)[0]
    second = tfm.moe_route(tfm._rmsnorm(after, w["ln2"], cfg.norm_eps),
                           w["w_router"], cfg)
    want, other = (first, second) if before else (second, first)
    np.testing.assert_array_equal(experts, want[2])
    np.testing.assert_allclose(gates, want[1], rtol=1e-6)
    assert not bool(jnp.array_equal(experts, other[2]))
    # what the block returns is the stream after attention + the experts'
    # part under that route
    np.testing.assert_allclose(
        out, tfm._ffn(after, w, cfg, None, route=want)[0], rtol=1e-6)


def test_embed_scale_is_the_embeddings_own_and_a_stream_stays_a_tokens():
    """``embed_scale`` moves the embedding's draw and nothing else; at
    0.02 a token's stream after one block of random weights is mostly
    what every token shares, at 1.0 mostly its own (the cosine of two
    tokens' normed streams)."""
    sizes = dict(PERIOD, num_layers=1, layer_pattern="a", window=0,
                 seq_len=64, vocab_size=512)
    trees = {scale: jax.jit(tfm.model_spec(**dict(
        sizes, embed_scale=scale)).init_fn)(jax.random.PRNGKey(0))
             for scale in (0.02, 1.0)}
    flat = jax.tree_util.tree_leaves_with_path
    for (path, small), (_, unit) in zip(flat(trees[0.02]), flat(trees[1.0])):
        if jax.tree_util.keystr(path) == "['embed']":
            np.testing.assert_allclose(50 * small, unit, rtol=1e-6)
            assert float(unit.std()) == pytest.approx(1.0, abs=0.02)
        else:
            np.testing.assert_array_equal(small, unit)

    def likeness(scale):
        cfg = tfm.model_spec(**dict(sizes, embed_scale=scale)).config
        params = trees[scale]
        # a skewed sample: a fifth of the positions are one token
        rng = np.random.default_rng(0)
        tokens = rng.integers(0, 512, (1, 64))
        tokens[0, rng.random(64) < 0.2] = 7
        x = params["embed"][jnp.asarray(tokens)]
        w = jax.tree_util.tree_map(lambda a: a[0],
                                   params["layers"]["period"]["0"])
        after = tfm._attention(x, w, cfg, None, jnp.arange(64),
                               cfg.kinds[0])[0]
        h = tfm._rmsnorm(after, w["ln2"], cfg.norm_eps)[0]
        h = h / jnp.linalg.norm(h, axis=-1, keepdims=True)
        other = np.asarray(tokens[0]) != 7
        return float((h[other] @ h[other].T).mean())

    shared, own = likeness(0.02), likeness(1.0)
    assert shared > 0.3 and own < 0.1 and shared > 4 * own, (shared, own)


# -- the share ties to the model ---------------------------------------------


@pytest.mark.parametrize("shares", [2, 4])
def test_the_shares_parts_of_a_layer_add_up_to_the_uncut_layer(shares):
    """16 experts in 2 and in 4 shares, a windowed-RoPE layer: what
    every chip computes alike (the route, attention: the stream after
    it) counted once, the shares' expert parts added, is the reference's
    layer with all 16 experts held."""
    whole = tfm.model_spec(**dict(TINY, num_layers=1,
                                  layer_pattern="w")).config
    x, w = _block_inputs(whole)
    positions = jnp.arange(whole.max_seq_len)
    kind = whole.kinds[0]
    stream = tfm._attention(x, w, whole, None, positions, kind)[0]
    held = 16 // shares
    total = stream
    for index in range(shares):
        cfg = dataclasses.replace(whole, moe_experts_held=held,
                                  moe_share_index=index)
        part = dict(w, **{name: w[name][index * held:(index + 1) * held]
                          for name in ("w_gate", "w_up", "w_down")})
        out, _ = tfm._layer_body(x, part, cfg, None, positions, kind=kind)
        total = total + (out - stream)
    # the uncut layer, by the reference
    f = lambda a: a
    h = REF.rmsnorm(x, w["ln1"], whole.norm_eps)
    weights, _ = REF.route(h, w["w_router"], whole.moe_top_k)
    after = x + REF.attention(h, w, 4, 2, 32, whole.rope_theta,
                              whole.window, f)
    want = after + REF.experts(
        REF.rmsnorm(after, w["ln2"], whole.norm_eps), weights, w, 0)
    np.testing.assert_allclose(stream, after, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=2e-5)


# -- what the program says -----------------------------------------------------


def test_the_stack_line_says_each_kinds_window_and_rope(caplog):
    tfm.announce_stack.cache_clear()
    fa.logger.addHandler(caplog.handler)
    try:
        spec = tfm.model_spec(**PERIOD)
        spec.apply_fn(_params(spec), _tokens(spec), True)
    finally:
        fa.logger.removeHandler(caplog.handler)
    lines = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("layer stack:")]
    assert lines == [
        "layer stack: pattern=awww lead=- period=awww periods=1 tail=- "
        "dense_layers=0 experts_held=4/16 a:window=0,rope=0 "
        "w:window=8,rope=1"]


@pytest.mark.parametrize("kernel,window,want", [
    ("flash_fwd", 0, "flash_fwd"), ("flash_bwd", 4096, "flash_bwd_w4096"),
    ("flash_dkv", 512, "flash_dkv_w512")])
def test_a_flash_call_is_named_by_its_kernel_and_window(kernel, window,
                                                        want):
    assert fa._call_name(kernel, window) == want


def test_one_flash_tiles_line_a_window():
    """T = 16,384 at head size 128, the cell's two tile plans: 136 live
    tiles of 256 for a full layer, 70 under a window of 4,096."""
    full = fa.tile_census(28, 16384, 128, 1024, True, 0)
    band = fa.tile_census(28, 16384, 128, 1024, True, 4096)
    assert "window=0 tile=1024" in full and "steps=136/256" in full
    assert "window=4096 tile=1024" in band and "steps=70/256" in band


@pytest.mark.parametrize("what", ["prefill", "decode_step", "generate"])
def test_decoding_refuses_a_windowed_stack_by_name(what):
    spec = tfm.model_spec(**PERIOD)
    cfg = spec.config
    params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    prompt = jnp.zeros((1, 4), jnp.int32)
    calls = {
        "prefill": lambda: tfm.prefill(params, cfg, prompt, 8),
        "decode_step": lambda: tfm.decode_step(
            params, cfg, None, 0, prompt[:, 0]),
        "generate": lambda: tfm.generate(params, cfg, prompt, 2),
    }
    with pytest.raises(NotImplementedError) as refusal:
        calls[what]()
    assert "awww" in str(refusal.value)
    assert "windowed layer (w)" in str(refusal.value)


def test_remat_keeps_table_counts_the_share_at_six_a_token():
    """16 of 64 experts at K = 6: the dispatch's buffers are the bound's
    rows (twice the balanced share), every attention layer makes the
    flash and q, k, v entries whatever its window."""
    cfg = tfm.TransformerConfig(
        dim=2560, num_heads=28, num_kv_heads=4, head_dim=128, num_layers=4,
        layer_pattern="awww", window=4096, ffn_dim=768, moe_experts=64,
        moe_top_k=6, moe_experts_held=16, remat=True)
    rows = 16384
    bound = md.row_bound(rows * 6, 16, 64)
    assert bound == 49152
    entries = {label: (nbytes, layers)
               for label, _, nbytes, layers in rk._entries(cfg, rows)}
    assert entries["flash"] == (rows * 28 * (128 * 2 + 4), 4)
    assert entries["qkv"] == (rows * (28 + 8) * 128 * 2, 4)
    assert entries["stream"] == (rows * 2560 * 2, 4)
    assert entries["moe_out"] == (bound * 2560 * 2, 4)
    assert entries["moe_gate"] == (bound * 768 * 2, 4)
    assert "ffn_gate" not in entries and "conv_in" not in entries


# -- the cell's whole step for a described v5e: last in the file, since
# ``one_chip`` turns XLA's optimisations on for its module (ROADMAP C16)

from tests.tpu_compile import (  # noqa: E402,F401 (the fixtures)
    V5E_LIMIT, _inventory_is_held, cell_steps, one_chip)


@pytest.fixture(scope="module")
def banded_step(cell_steps):
    """The ``smallthinker-21b-a3b.seq16384`` cell's whole training step
    compiled once for the tests that read it (a minute): what
    ``remat_keep`` chose, and the compiled program."""
    step = cell_steps("smallthinker-21b-a3b", 1, 16384, True)
    nbytes = lambda tree: sum(
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(tree))
    assert nbytes(step.params) == 4 * 656529920      # 656.5 M parameters
    return V5E_LIMIT, step.chosen, step.compiled


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


@pytest.mark.parametrize("config,batch,rows,keep", [
    ("smallthinker-21b-a3b", 1, 16384, True)])
def test_the_expert_layers_inventory_is_held_to_the_compilers_count(
        cell_steps, config, batch, rows, keep):
    """``tests/test_step_compile_tpu.py``'s test of the same name for
    this cell: +0.16 GB with ``choose``'s
    list kept."""
    _inventory_is_held(cell_steps, config, batch, rows, keep)
