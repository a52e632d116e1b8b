"""Latent attention and the shared expert (models/transformer.py,
ops/flash_attention.latent_attention) against the plain reference of
the benchmark (benchmark/reference/kanana-2-30b-a3b.py), which pairs
RoPE's neighbours on the published column order and maps the product's
weights back to it.  Float32 on the CPU at tiny widths."""

import collections
import dataclasses
import functools
import json
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from benchmark.lib import manifest
from elasticdl_tpu.models import remat_keep as rk
from elasticdl_tpu.models import transformer as tfm
from elasticdl_tpu.ops import flash_attention as fa
from tests import reference_check as rc

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = "kanana-2-30b-a3b"
REF = manifest.load_named("reference", NAME)

# heads x value size = 256, the hidden size 64; a leading dense layer and
# two expert layers over 4 of 16 experts beside a shared expert of 2 x 48
TINY = dict(vocab_size=96, dim=64, num_heads=2, num_layers=3, seq_len=32,
            kv_latent_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=24,
            dense_layers=1, dense_ffn_dim=96, ffn_dim=48, moe_experts=16,
            moe_top_k=3, moe_experts_held=4, moe_share_index=1,
            moe_shared_experts=2, moe_router="sigmoid_bias",
            moe_norm_topk=True, moe_route_scale=2.448, moe_aux_weight=0,
            rope_theta=1e6, norm_eps=1e-6, tied_embeddings=False,
            embed_scale=1.0, dtype="float32")
# widths the kernels take: 128 | 64 | 128, as published
KERNEL = dict(TINY, seq_len=256, qk_nope_dim=128, qk_rope_dim=64,
              v_head_dim=128)


def _shape(cfg, **over):
    """``REF.loss``'s keywords for a model of ``cfg``."""
    rank, d_nope, d_rope, d_v = cfg.latent
    return dict(dict(
        heads=cfg.num_heads, rank=rank, d_nope=d_nope, d_rope=d_rope,
        d_v=d_v, top_k=cfg.moe_top_k, eps=cfg.norm_eps,
        theta=cfg.rope_theta, norm_topk=cfg.moe_norm_topk,
        scale=cfg.moe_route_scale, first=cfg.experts_held[0]), **over)


_loss, _apart = rc.loss_of, rc.apart
# model -> the Case of a model of these widths as the comparison draws
# it: a wider head, a bias on the routers
DRAWN = functools.partial(rc.tiny, NAME, _shape)


# -- against the plain reference ---------------------------------------------


@pytest.mark.parametrize("case", ["off", "off-remat", "interpret",
                                  "interpret-remat"])
def test_the_stack_matches_the_reference(monkeypatch, case):
    """Loss and every gradient leaf of a dense layer and two expert
    layers, latent attention in each, a shared expert beside 4 of 16
    routed ones: the jnp paths at odd widths (16 | 8 | 24) and the
    kernels in interpret mode at the published ones (128 | 64 | 128).
    Float32 both sides: 1e-5 of the loss, 1e-4 of each leaf's norm (the
    reference sums in another order).  The reference rotates RoPE's
    NEIGHBOURS on weights mapped back to the published order, the
    product the HALVES of the permuted ones: equal only if the
    permutation is the right one."""
    mode, _, remat = case.partition("-")
    drawn = DRAWN(KERNEL if mode == "interpret" else TINY,
                  batch=1 + (mode == "off"))
    _, still, _, _ = rc.check(drawn, mode, 1e-5, 1e-4, remat=bool(remat))
    assert still and all("expert_bias" in name for name in still)
    if remat:      # what follows is the reference's alone: once a width
        return
    # and the reference tells the mechanisms apart: the shared expert
    # left out; the program's column order taken for the published one
    want = float(rc.wanted(drawn)[0][0])
    other = lambda **how: float(jax.jit(drawn.reference(**how))(
        drawn.params)[0])
    assert abs(other(shared=False) - want) > 2e-4 * abs(want)
    monkeypatch.setattr(drawn.ref, "published_order", lambda cols: cols)
    assert abs(other() - want) > 2e-4 * abs(want)


def test_bfloat16_where_float32_is_stated_fails_the_tolerance():
    """The same weights through the product in bfloat16: ten times and
    more past the 1e-5 the float32 product is held to."""
    drawn = DRAWN(TINY)
    params, tokens = drawn.params, drawn.tokens
    got = float(jax.jit(_loss(drawn.spec(dtype="bfloat16"), tokens))(params))
    want = float(rc.wanted(DRAWN(TINY))[0][0])
    assert abs(got - want) > 1e-4 * abs(want)


def _file(**over):
    """A configuration's file of the TINY model, as the reference reads
    one."""
    return {
        "num_attention_heads": 2, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 24, "n_routed_experts": 4,
        "num_experts_per_tok": 3, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
        "norm_topk_prob": True, "routed_scaling_factor": 2.448,
        "share_index": 1, "vocab_size": 96, "seq_len": 32,
        "cli": {"model_zoo": "transformer",
                "model_params": dict(TINY, **over)}}


def test_the_references_checks_pass_through_their_door(capsys):
    """``case`` runs the routing check and the layer check on the
    reference's own inputs; a float32 program is float32 math."""
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
    """What a mean over the sequence could cancel in the loss shows in a
    layer's whole result: the program in bfloat16 where float32 is
    stated lies a hundred times past float32's distance in every part
    and is refused by name; the reference rounded to float8 lies past
    the ceiling a bfloat16 program is held to, in every part."""
    params = DRAWN(TINY).params
    seen = rc.wanted(DRAWN(TINY))[0][1][0]
    assert len(seen) == 2 and seen[0].h.shape == (2, 32, 64)
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


# -- the attention op alone ----------------------------------------------------


def _op_inputs(b=2, h=3, t=256, dn=128, dr=64, dv=128, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    draw = lambda key, *shape: jax.random.normal(key, shape, jnp.float32)
    return (draw(keys[0], b, h, t, dn), draw(keys[1], b, h, t, dr),
            draw(keys[2], b, h, t, dn), draw(keys[3], b, t, dr),
            draw(keys[4], b, h, t, dv)), draw(keys[5], b, h, t, dv)


def _the_long_way(q_nope, q_rope, k_nope, k_rope, v):
    """Per head: k = concat(k_nope, the one k_rope); explicit [T, T]
    scores over 192, causal softmax, P v."""
    t = q_nope.shape[2]
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, None], q_rope.shape)], -1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("mode", ["interpret", "off"])
def test_the_op_and_its_three_gradients_match_the_long_way(mode):
    """Forward, dq (both parts), dk (the no-position part a head, the
    RoPE key's SUMMED over the heads) and dv of ``latent_attention``
    against explicit per-head math, the key one plane a sequence."""
    args, g = _op_inputs()
    interpret = {"interpret": True, "off": None}[mode]
    op = functools.partial(fa.latent_attention, interpret=interpret)

    every = tuple(range(5))
    with jax.default_matmul_precision("highest"):
        got, got_grads = jax.value_and_grad(
            lambda *a: (op(*a) * g).sum(), every)(*args)
        want, want_grads = jax.value_and_grad(
            lambda *a: (_the_long_way(*a) * g).sum(), every)(*args)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for name, a, b in zip(("q_nope", "q_rope", "k_nope", "k_rope", "v"),
                          got_grads, want_grads):
        assert a.shape == b.shape, name
        assert _apart(a, b) <= 1e-5, name


def test_the_op_takes_the_rope_key_as_one_plane_alone():
    """A key repeated to the heads is refused by name, not mis-indexed."""
    args, _ = _op_inputs(b=1, h=4)
    spread = jnp.broadcast_to(args[3][:, None], args[1].shape)
    with pytest.raises(ValueError, match="one .batch, seq, D_rope. plane"):
        fa.latent_attention(*args[:3], spread, args[4], interpret=True)


def test_the_rope_keys_gradient_is_the_sum_of_the_heads_parts():
    """The backward kernel writes each head's own float32 part
    (``_pallas_bwd``'s last result); the plane's gradient is their sum,
    and a head's part is what the long way gives that head's copy of
    the key."""
    args, g = _op_inputs(b=1, h=4)
    q_nope, q_rope, k_nope, k_rope, v = args
    static = (True, 192 ** -0.5, True)
    with jax.default_matmul_precision("highest"):
        plane = jax.grad(lambda kr: (fa.latent_attention(
            q_nope, q_rope, k_nope, kr, v, interpret=True) * g).sum())(
                k_rope)
        _, res = fa._latent_fwd(*args, *static)
        heads = fa._pallas_bwd(q_nope, k_nope, v, *res[5:], g, *static,
                               rope=(q_rope, k_rope))[4]
        spread = jnp.broadcast_to(k_rope[:, None], q_rope.shape)
        want = jax.grad(lambda kr: (fa._attention_ref(
            jnp.concatenate([q_nope, q_rope], -1),
            jnp.concatenate([k_nope, kr], -1), v, True,
            192 ** -0.5) * g).sum())(spread)
    assert plane.shape == (1, 256, 64) and heads.shape == (1, 4, 256, 64)
    assert heads.dtype == jnp.float32
    np.testing.assert_allclose(heads.sum(1), plane, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(heads, want, rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(heads[:, 0] - heads[:, 1]).max()) > 1e-2


@pytest.mark.parametrize("window,blocks_of_last_tile", [
    (0, [0, 1, 2]), (100, [1, 2])])
def test_the_fused_backward_sums_dq_over_three_key_blocks_a_head(
        window, blocks_of_last_tile):
    """Scores over 128 + 64 in float32 take the 128 tile at T = 384:
    three key blocks a head, six heads.  A head's dq and dq_rope are
    kept transposed ([T / tile, 192, tile]: ``_bwd_kernel``), a query
    tile's added to at grid steps with other tiles' between them, and
    turned back at the head's last step; under a window the last query
    tile's first and last key block differ, and tile 0 is finished long
    before the head is."""
    plan = fa._tile_plan(384, 128, True, window)
    assert [ki for qi, ki in plan.k_major if qi == 2] == blocks_of_last_tile
    args, g = _op_inputs(b=2, h=3, t=384, seed=window)
    scale = 192 ** -0.5
    every = tuple(range(5))
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda *a: (fa.latent_attention(
            *a, window=window, interpret=True) * g).sum(), every)(*args)
        want = jax.grad(lambda *a: (fa._latent_ref(
            *a, True, scale, window=window) * g).sum(), every)(*args)
    for name, a, b in zip(("q_nope", "q_rope", "k_nope", "k_rope", "v"),
                          got, want):
        assert a.shape == b.shape, name
        assert _apart(a, b) <= 1e-5, name


@pytest.mark.parametrize("kernel,window,widths,want", [
    ("flash_fwd", 0, (192, 128), "flash_fwd_qk192_v128"),
    ("flash_bwd", 4096, (192, 128), "flash_bwd_w4096_qk192_v128"),
    ("flash_dq", 0, None, "flash_dq"),
])
def test_a_latent_call_is_named_by_its_kernel_and_widths(kernel, window,
                                                         widths, want):
    assert fa._call_name(kernel, window, widths) == want


def test_the_two_calls_carry_their_names_and_read_one_key_plane():
    """The traced program: two Pallas calls, the forward and the one
    backward, named by their widths; the RoPE key goes in as [b, T, 64],
    not spread to the heads."""
    args, g = _op_inputs(b=1, h=2)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: (fa.latent_attention(*a, interpret=True) * g).sum(),
        (0, 1, 2, 3, 4)))(*args)
    from tests.test_mixed_stack import _eqns

    calls = [e for e in _eqns(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"]
    names = sorted(str(e.params["name"]) for e in calls)
    assert names == ["flash_bwd_qk192_v128", "flash_fwd_qk192_v128"]
    for e in calls:
        shapes = [tuple(v.aval.shape) for v in e.invars]
        assert (1, 256, 64) in shapes and (2, 256, 64) in shapes, shapes


def test_widths_the_kernels_refuse_take_the_reference_and_say_so():
    assert fa.latent_mode(256, 128, 64, 128, interpret=True) == (
        "interpret", 256, "")
    assert fa.latent_mode(16384, 128, 64, 128, interpret=False) == (
        "tpu", 1024, "")
    mode, tile, why = fa.latent_mode(256, 128, 24, 128, interpret=False)
    assert (mode, tile) == ("off", 0) and "24" in why
    assert fa.latent_mode(250, 128, 64, 128, interpret=False)[0] == "off"


def test_equal_widths_trace_the_kernels_they_traced():
    """``flash_attention``'s forward and backward at equal widths (the
    four older cells' and the banded calls'): the primitives of the
    traced kernels, counted through every nested jaxpr, and the length
    of the jaxpr's text are those recorded from PR 41's commit, the
    child of 7ba575a that made the backward one call
    (tests/flash_equal_width_program.json; until then the record was
    the pair's, from the parent of the PR that taught the kernels two
    widths): nothing of a latent head's parts is traced here, and, K
    and V at the queries' head count being group 1, nothing of PR 42's
    ``head // group`` index or its dk / dv planes either: the record
    stands as PR 41 left it."""
    from tests.test_mixed_stack import _eqns

    with open(os.path.join(HERE, "flash_equal_width_program.json")) as fh:
        was = json.load(fh)
    shapes = {"full_d128": (2048, 128, 0, jnp.bfloat16),
              "window_d64": (1024, 64, 256, jnp.bfloat16),
              "f32_d256": (512, 256, 0, jnp.float32)}
    assert sorted(was) == sorted(shapes)
    for name, (t, d, window, dtype) in shapes.items():
        x = jax.ShapeDtypeStruct((1, 2, t, d), dtype)
        static = (True, d ** -0.5, False, window)

        def fwd_bwd(q, k, v, g):
            out, res = fa._flash_fwd(q, k, v, *static)
            return out, fa._flash_bwd(*static, res, g)

        jaxpr = jax.make_jaxpr(fwd_bwd)(x, x, x, x)
        prims = collections.Counter(
            e.primitive.name for e in _eqns(jaxpr.jaxpr))
        assert dict(prims) == was[name]["prims"], name
        assert len(str(jaxpr)) == was[name]["len"], name


# -- the projections: head-major from the matmuls themselves ----------------------


def _token_major_projections(h, w, cfg, positions, rope):
    """``_project_latent`` as it was before the projections wrote the
    kernels' planes themselves: one product a weight on [B, T, H, .],
    sliced at the no-position width in the minor dimension, RoPE on
    [B, T, H, 64], each part transposed to [B, H, T, .]."""
    dtype = jnp.dtype(cfg.dtype)
    B, T = h.shape[:2]
    H = cfg.num_heads
    rank, dn, dr, dv = cfg.latent
    q = (h @ w["wq"].astype(dtype)).reshape(B, T, H, dn + dr)
    c = h @ w["w_kv_a"].astype(dtype)
    kv = (tfm._rmsnorm(c[..., :rank], w["kv_norm"].astype(dtype),
                       cfg.norm_eps)
          @ w["w_kv_b"].astype(dtype)).reshape(B, T, H, dn + dv)
    q_rope, k_rope = q[..., dn:], c[..., None, rank:]
    if rope:
        q_rope = tfm._rope(q_rope, positions, cfg.rope_theta)
        k_rope = tfm._rope(k_rope, positions, cfg.rope_theta)
    heads_first = lambda a: a.transpose(0, 2, 1, 3)
    return (heads_first(q[..., :dn]), heads_first(q_rope),
            heads_first(kv[..., :dn]), k_rope[:, :, 0],
            heads_first(kv[..., dn:]))


def _token_major_mix(h, w, cfg, positions, kind):
    """``_latent_mix`` over those: the op's [B, H, T, Dv] output copied
    to [B, T, H * Dv] before ``W_o``."""
    B, T = h.shape[:2]
    attn = fa.latent_attention(
        *_token_major_projections(h, w, cfg, positions, kind.rope),
        causal=True, window=kind.window)
    attn = attn.transpose(0, 2, 1, 3).reshape(B, T, -1)
    return attn @ w["wo"].astype(jnp.dtype(cfg.dtype))


LATENT_WEIGHTS = ("wq", "w_kv_a", "kv_norm", "w_kv_b", "wo")


@pytest.mark.parametrize("batch", [1, 2])
@pytest.mark.parametrize("rope", [True, False])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_projections_equal_the_token_major_ones(monkeypatch, dtype,
                                                    rope, batch):
    """The five operands, ``_latent_mix``'s result and the gradients of
    the five weights and of the input, from products that write [B, H,
    T, .] themselves (two views of ``wq`` and of ``w_kv_b``, RoPE's
    halves swapped by a permutation product, ``W_o`` over (head,
    width)) against the form they replace.  A product split by output
    columns sums what it summed: float32 lands within its rounding
    (2e-7 read), and in bfloat16 every operand, the result and the
    gradients of ``wq``, ``w_kv_b`` and ``wo`` are the same bits.  An
    input gradient is now the sum of one bfloat16-rounded product a
    view where it was one product a weight, so ``dh`` and, through the
    latent, ``w_kv_a``'s and ``kv_norm``'s gradients move by 3-8e-3 of
    their norm in bfloat16: a rounding, far inside what the op is held
    to on the chip (``chip_check.py latent``: 2-4e-2)."""
    monkeypatch.setenv("ELASTICDL_FLASH", "off")
    spec = tfm.model_spec(**dict(TINY, dtype=dtype))
    cfg = spec.config
    params = jax.jit(spec.init_fn)(jax.random.PRNGKey(5))
    layer = jax.tree_util.tree_map(lambda a: a[0],
                                   params["layers"]["period"]["0"])
    w = {name: layer[name] for name in LATENT_WEIGHTS}
    T = cfg.max_seq_len
    draw = lambda seed: jax.random.normal(
        jax.random.PRNGKey(seed), (batch, T, cfg.dim), jnp.float32
    ).astype(cfg.dtype)
    h, g = draw(6), draw(7)
    positions = jnp.arange(T)
    kind = tfm.Kind("a", False, 0, rope)
    operands, mix = (1e-6, 1e-6) if dtype == "float32" else (3e-3, 2e-2)

    got = tfm._project_latent(h, w, cfg, positions, rope)
    want = _token_major_projections(h, w, cfg, positions, rope)
    for name, a, b in zip(("q_nope", "q_rope", "k_nope", "k_rope", "v"),
                          got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert _apart(a.astype(jnp.float32),
                      b.astype(jnp.float32)) <= operands, name

    def run(fn):
        out, vjp = jax.vjp(
            lambda h, w: fn(h, w, cfg, positions, kind), h, w)
        return out, vjp(g)

    f32 = lambda tree: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), tree)
    (out, (dh, dw)), (ref, (ref_dh, ref_dw)) = (
        f32(jax.jit(lambda: run(tfm._latent_mix))()),
        f32(jax.jit(lambda: run(_token_major_mix))()))
    assert out.shape == (batch, T, cfg.dim)
    assert _apart(out, ref) <= mix
    assert _apart(dh, ref_dh) <= mix
    for name in LATENT_WEIGHTS:
        assert dw[name].shape == w[name].shape, name
        assert _apart(dw[name], ref_dw[name]) <= mix, name


# -- the shared expert and the shares -------------------------------------------


def test_eight_shares_and_one_shared_expert_add_up_to_the_uncut_layer():
    """64 experts in 8 shares of 8: the eight shares' ROUTED parts plus
    the shared expert counted once equal the reference's uncut expert
    layer (all 64 held) plus its shared expert; every share computes the
    same shared expert, so summing the shares' whole results would
    count it eight times."""
    whole = tfm.TransformerConfig(
        dim=64, ffn_dim=48, moe_experts=64, moe_top_k=6,
        moe_router="sigmoid_bias", moe_route_scale=2.448,
        moe_shared_experts=2, dtype="float32")
    rng = np.random.default_rng(8)
    draw = lambda *shape: jnp.asarray(
        rng.standard_normal(shape) * shape[-2] ** -0.5, jnp.float32)
    w = {"w_router": draw(64, 64), "w_gate": draw(64, 64, 48),
         "w_up": draw(64, 64, 48), "w_down": draw(64, 48, 64),
         "ws_gate": draw(64, 96), "ws_up": draw(64, 96),
         "ws_down": draw(96, 64), "ln2": jnp.ones((64,), jnp.float32),
         "expert_bias": jnp.asarray(0.2 * rng.standard_normal(64),
                                    jnp.float32)}
    x = jnp.asarray(rng.standard_normal((2, 24, 64)), jnp.float32)
    u = REF.rmsnorm(x, w["ln2"], whole.norm_eps)
    identity = lambda a: a
    shared = REF.swiglu(u, w["ws_gate"], w["ws_up"], w["ws_down"], identity)
    want = REF.experts(u, w, 6, True, 2.448, 0)[0] + shared
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
    assert rows == 2 * 24 * 6          # every assignment held by one share


def test_a_dense_layer_has_no_shared_expert_and_an_expert_layer_has_one():
    spec = tfm.model_spec(**TINY)
    params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    lead, period = (params["layers"][g]["0"] for g in ("lead", "period"))
    assert "ws_gate" not in lead and lead["w_gate"].shape == (64, 96)
    assert period["ws_gate"].shape == (2, 64, 96)
    assert period["ws_down"].shape == (2, 96, 64)
    assert period["w_kv_a"].shape == (2, 64, 32 + 8)
    assert period["w_kv_b"].shape == (2, 32, 2 * (16 + 24))
    assert period["wq"].shape == (2, 64, 2 * (16 + 8))
    assert period["wo"].shape == (2, 2 * 24, 64)
    assert "wk" not in period and "wv" not in period
    # decayed like a dense FFN's: everything but expert_bias
    mask = tfm._decayed(params)
    assert mask["layers"]["period"]["0"]["ws_gate"] is True
    assert mask["layers"]["period"]["0"]["expert_bias"] is False


def test_the_route_scale_of_2448_weighs_the_six_chosen():
    cfg = tfm.TransformerConfig(
        dim=32, moe_experts=128, moe_top_k=6, moe_router="sigmoid_bias",
        moe_norm_topk=True, moe_route_scale=2.448)
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.standard_normal((1, 16, 32)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((32, 128)) * 0.2, jnp.float32)
    bias = jnp.asarray(0.1 * rng.standard_normal(128), jnp.float32)
    probs, gates, experts = tfm.moe_route(h, w, cfg, bias)
    assert gates.shape == (1, 16, 6)
    picked = jnp.take_along_axis(probs, experts, -1)
    np.testing.assert_allclose(
        gates, 2.448 * picked / (picked.sum(-1, keepdims=True) + 1e-6),
        rtol=1e-6)
    np.testing.assert_allclose(gates.sum(-1), 2.448, rtol=1e-5)
    # the bias moved the choice, not the weights
    assert not np.array_equal(
        np.sort(experts, -1), np.sort(jax.lax.top_k(probs, 6)[1], -1))


# -- what does not run it says so by name ----------------------------------------


@pytest.mark.parametrize("what", ["prefill", "decode_step", "generate",
                                  "export_generate", "forward_pipelined",
                                  "mesh"])
def test_what_cannot_run_latent_attention_refuses_it_by_name(what,
                                                              tmp_path):
    uniform = dict(TINY, dense_layers=0, dense_ffn_dim=0)
    spec = tfm.model_spec(**uniform)
    cfg = spec.config
    assert tfm.stack_plan(cfg) is None      # the latent alone is refused
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
        "mesh": lambda: tfm.model_spec(mesh=mesh, **dict(
            uniform, moe_experts_held=0, moe_share_index=0)),
    }
    with pytest.raises(NotImplementedError) as refusal:
        calls[what]()
    assert "latent attention" in str(refusal.value)
    assert "kv_latent_rank=32" in str(refusal.value)
    assert what.split("_")[0] in str(refusal.value) or what == "mesh"


@pytest.mark.parametrize("sizes", [
    dict(kv_latent_rank=32), dict(qk_nope_dim=16, v_head_dim=8),
    dict(kv_latent_rank=32, qk_nope_dim=16, qk_rope_dim=7, v_head_dim=8)])
def test_some_of_the_latents_sizes_without_the_others_are_refused(sizes):
    with pytest.raises(ValueError, match="latent attention needs"):
        tfm.model_spec(vocab_size=64, dim=32, num_heads=2, num_layers=2,
                       seq_len=16, **sizes)


# -- the lines ------------------------------------------------------------------


def _lines(fn, prefix):
    seen = []

    class Grab(logging.Handler):
        def emit(self, record):
            if record.getMessage().startswith(prefix):
                seen.append(record.getMessage())

    handler = Grab()
    fa.logger.addHandler(handler)
    try:
        fn()
    finally:
        fa.logger.removeHandler(handler)
    return seen


@pytest.mark.parametrize("mode,word,tile", [
    ("interpret", "interpreter", 256), ("off", "reference", 0)])
def test_the_latent_attention_line_says_what_runs(monkeypatch, mode, word,
                                                  tile):
    monkeypatch.setenv("ELASTICDL_FLASH", mode)
    tfm.announce_latent.cache_clear()
    tfm.announce_stack.cache_clear()
    spec, params, tokens = DRAWN(KERNEL, batch=1).parts()
    run = lambda: jax.eval_shape(_loss(spec, tokens), params)
    lines = _lines(lambda: (run(), run()), "latent attention:")
    assert lines == [
        "latent attention: heads=2 t=256 rank=32 qk_nope=128 qk_rope=64 "
        "v=128 rope_key=shared tile=%d %s" % (tile, word)]
    tfm.announce_stack.cache_clear()
    stack, = _lines(run, "layer stack:")
    assert " experts_held=4/16 shared_expert=96 " in stack


def test_a_stack_without_a_shared_expert_says_nothing_of_one():
    tfm.announce_stack.cache_clear()
    spec = tfm.model_spec(**dict(TINY, moe_shared_experts=0))
    params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    tokens = jnp.zeros((1, 32), jnp.int32)
    stack, = _lines(lambda: jax.eval_shape(_loss(spec, tokens), params),
                    "layer stack:")
    assert "shared_expert" not in stack


# -- remat_keep --------------------------------------------------------------------


def test_remat_keeps_table_has_the_latent_before_q_and_kv_last():
    """From shapes: the flash residuals at the value width, the latent
    with the RoPE key ([rows, 32 + 8]) before q (heads x (16 + 8)); the
    k_nope and v one matmul makes from the latent again among the
    cheapest; the shared expert's products as a dense FFN's, in the
    expert layers alone."""
    cfg = tfm.model_spec(**TINY).config
    rows = 64
    table = {label: (names, nbytes)
             for label, names, nbytes in rk.table(cfg, rows)}
    order = [label for label, _, _ in rk.table(cfg, rows)]
    assert order[:5] == ["flash", "route", "latent", "q", "stream"]
    assert order.index("kv") > order.index("shared_up")
    assert table["flash"] == (rk.ATTN_NAMES, rows * 2 * (24 * 4 + 4))
    assert table["latent"] == ((rk.KEEP_LATENT,), rows * 40 * 4)
    # q as the kernels take it: the RoPE part a plane of 128 lanes
    assert table["q"] == ((rk.KEEP_Q,), rows * 2 * (16 + 128) * 4)
    assert table["kv"] == ((rk.KEEP_KV,), rows * 2 * 40 * 4)
    assert table["shared_gate"] == ((rk.KEEP_SHARED_GATE,), rows * 96 * 4)
    assert "qkv" not in table
    layers = {label: count for label, _, _, count in rk._entries(cfg, rows)}
    assert (layers["latent"], layers["shared_up"], layers["ffn_up"]) == (
        3, 2, 1)


def test_the_steps_need_counts_latent_attentions_operands():
    """What a step needs beside the state has a term for latent
    attention's one layer: the second forward's [rows, heads, width]
    residuals that are not kept, beside the larger of the FFN's
    backward and attention's own; a kept entry leaves it by what the
    stack then holds, and a model with wk and wv has no such term."""
    spec = tfm.model_spec(**KERNEL)
    cfg = spec.config
    params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    rows, unit = 256, 256 * 2 * 4
    residuals, backward = rk._latent_layer(cfg, rows, ())
    # out in both layouts; q as taken (128 + 128) and as projected
    # (192); k_nope | v both ways
    assert residuals == unit * (2 * 128 + (256 + 192) + (256 + 256))
    assert backward == unit * (2 * 128 + 256 + 256 + 192 + 256) + (
        rows * 2 * 128 * 4)
    assert rk._latent_layer(cfg, rows, ("flash", "q"))[0] == unit * 640
    bare = rk.step_bytes(cfg, params, rows)
    assert bare - rk.step_bytes(cfg, params, rows, ("q",)) == unit * 448
    assert bare - rk.step_bytes(cfg, params, rows, ("latent",)) == 0
    plain = tfm.model_spec(**{k: v for k, v in KERNEL.items() if k not in (
        "kv_latent_rank", "qk_nope_dim", "qk_rope_dim", "v_head_dim")})
    plain_params = jax.eval_shape(plain.init_fn, jax.random.PRNGKey(0))
    assert bare - rk.step_bytes(plain.config, plain_params, rows) > (
        residuals)


@pytest.mark.parametrize("mode", ["off", "interpret"])
def test_kept_names_change_no_gradient(monkeypatch, mode):
    """Every name of the table kept against nothing kept: the same loss
    and gradients, and the kept program names its values."""
    monkeypatch.setenv("ELASTICDL_FLASH", mode)
    spec, params, tokens = DRAWN(
        KERNEL if mode == "interpret" else TINY, batch=1).parts(remat=True)
    names = tuple(n for _, entry, _ in rk.table(spec.config, 64)
                  for n in entry)
    assert {rk.KEEP_LATENT, rk.KEEP_KV, rk.KEEP_SHARED_GATE} <= set(names)
    grad = lambda: jax.jit(jax.value_and_grad(_loss(spec, tokens)))(params)
    bare = grad()
    monkeypatch.setattr(rk, "names_for", lambda *a: names)
    kept = grad()
    assert float(kept[0]) == pytest.approx(float(bare[0]), rel=1e-6)
    assert _apart(kept[1], bare[1]) <= 1e-5
