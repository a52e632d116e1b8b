"""The mechanisms ``nemotron-3-nano-30b-a3b`` forced, each against math
written out here or in ``benchmark/reference/nemotron-3-nano-30b-a3b.py``,
at tiny sizes on the CPU: the state-space scan (jnp twin and the
interpreted kernels) against the token-by-token recurrence, the bias on
the convolution's taps, the gate before the grouped norm, experts that
are MLPs of two matrices, layers of one sublayer, the sixteen expert
shares adding up to the uncut layer, and every caller that cannot run
them saying so by name.  The whole model against the reference:
tests/test_nemotron3_model.py.
"""

import dataclasses
import json
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import manifest
from benchmark.lib.runner import merge, params_string
from elasticdl_tpu.models import transformer as tfm
from elasticdl_tpu.models.spec import load_model_spec
from elasticdl_tpu.ops import grouped_matmul, moe_dispatch, short_conv, ssd
from elasticdl_tpu.ops.mode import SWITCH

REF = manifest.load_named("reference", "nemotron-3-nano-30b-a3b")
with open(os.path.join(manifest.BENCH_DIR, "configs",
                       "nemotron-3-nano-30b-a3b.json")) as fh:
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


# -- the state-space scan ----------------------------------------------------


def _operands(batch, seq, heads, groups, width, state):
    """x, B, C, the log decay and the step: heads whose decays run from
    weak (a state that outlives the sequence) to strong (one that
    forgets inside a chunk)."""
    dt = 0.3 * jax.nn.softplus(_normal(3, batch, seq, heads))
    rate = -jnp.exp(jnp.linspace(-4.0, 2.5, heads))
    return (_normal(0, batch, seq, heads, width),
            _normal(1, batch, seq, groups, state),
            _normal(2, batch, seq, groups, state), dt * rate, dt)


@pytest.mark.parametrize("how, shape, chunk", [
    # the twin: a last chunk that is padded
    ("twin", (2, 40, 4, 2, 8, 16), 16),
    # the kernels, interpreted: four chunks, two a grid step
    ("kernels", (2, 64, 4, 2, 8, 16), 16),
    # at the kernel's own chunk: two heads of 64 share a 128-lane block,
    # a group of four is two blocks
    ("kernels", (1, 256, 4, 1, 64, 128), 128),
])
def test_the_scan_is_the_token_by_token_recurrence(how, shape, chunk):
    """Value and every gradient, with groups of heads sharing B and C, a
    sequence of several chunks and decays strong and weak."""
    operands = _operands(*shape)
    weigh = _normal(4, *operands[0].shape)
    scan = (lambda *a: ssd.ssd_ref(*a, chunk=chunk)) if how == "twin" else (
        lambda *a: ssd.ssd(*a, chunk=chunk, interpret=True))
    alpha = lambda g: jnp.exp(g)

    def want(x, b, c, g, dt):
        return (REF.recurrence(x, b, c, alpha(g), dt) * weigh).sum()

    def got(x, b, c, g, dt):
        return (scan(x, b, c, g, dt) * weigh).sum()

    with jax.default_matmul_precision("highest"):
        wanted, grads = jax.value_and_grad(want, range(5))(*operands)
        value, gots = jax.value_and_grad(got, range(5))(*operands)
    assert abs(float(value - wanted)) < 1e-4 * abs(float(wanted))
    for name, a, b in zip("x B C g dt".split(), gots, grads):
        assert _far(a, b) < 1e-5, name
    # the weak heads' states crossed every chunk boundary
    keep = jnp.exp(operands[3].sum(axis=1))
    assert float(keep.max()) > 0.2 > 1e-3 > float(keep.min())


def test_a_shape_the_kernels_do_not_tile_takes_the_twin_and_says_so():
    assert ssd.ssd_mode(200, 8, 64, 128, interpret=False) == (
        "off", "seq 200 is not a multiple of the chunk 128")
    assert ssd.ssd_mode(256, 8, 64, 128, interpret=False) == ("tpu", "")
    assert "lane" in ssd.ssd_mode(256, 3, 64, 128, interpret=False)[1]
    assert ssd.ssd_mode(256, 8, 64, 64, interpret=False)[0] == "off"
    # the interpreter takes a group narrower than a lane block
    assert ssd.ssd_mode(256, 2, 16, 16, interpret=True) == ("interpret", "")


# -- the bias on the taps ----------------------------------------------------


def test_the_convolution_with_a_bias_is_the_written_out_one():
    x, taps, bias = _normal(0, 2, 64, 128), 0.5 * _normal(1, 128, 4), _normal(
        2, 128)
    weigh = _normal(3, 2, 64, 128)
    want = lambda x, w, b: (jax.nn.silu(REF.causal_conv(x, w, b))
                            * weigh).sum()
    got = lambda x, w, b: (short_conv.conv_silu(
        x, w, interpret=True, bias=b) * weigh).sum()
    wanted, grads = jax.value_and_grad(want, (0, 1, 2))(x, taps, bias)
    value, gots = jax.value_and_grad(got, (0, 1, 2))(x, taps, bias)
    assert abs(float(value - wanted)) < 1e-4
    for a, b in zip(gots, grads):
        assert _far(a, b) < 1e-5
    # and the twin; without a bias the op is what it was
    assert _far(short_conv.conv_silu_ref(x, taps, bias),
                jax.nn.silu(REF.causal_conv(x, taps, bias))) < 1e-6
    assert _far(short_conv.conv_silu(x, taps, interpret=True),
                short_conv.conv_silu_ref(x, taps)) < 1e-6
    assert _far(short_conv.conv_silu_ref(x, taps),
                short_conv.conv_silu_ref(x, taps, bias)) > 0.1


# -- the Mamba-2 mixer -------------------------------------------------------


def _mixer():
    cfg = _spec().config
    w = tfm._init_layers(jax.random.PRNGKey(0), jax.random.PRNGKey(1), cfg,
                         cfg.kinds[0], ())
    w["ssm_norm"] = 1.0 + 0.25 * _normal(7, *w["ssm_norm"].shape)
    w["ssm_D"] = 1.0 + 0.25 * _normal(8, *w["ssm_D"].shape)
    return cfg, w, _normal(5, 2, T, cfg.dim)


@pytest.mark.parametrize("mode", ["off", "interpret"])
def test_the_mixer_is_the_references(monkeypatch, mode):
    monkeypatch.setenv(SWITCH, mode)
    cfg, w, h = _mixer()
    sizes = (SHAPE["heads"], SHAPE["width"], SHAPE["d_state"],
             SHAPE["groups"], SHAPE["eps"])
    with jax.default_matmul_precision("highest"):
        got, keep = jax.jit(lambda h, w: tfm._ssm_mix(h, w, cfg))(h, w)
        want = REF.mamba(h, w, *sizes)
        assert _far(got, want) < 1e-5
        # the gate stands BEFORE the grouped norm, the skip and the bias
        # are there: each left out is another layer
        for piece in ("gate", "skip", "conv_bias"):
            assert _far(REF.mamba(h, w, *sizes, without=(piece,)),
                        want) > 0.02, piece
        # chunk_keep: exp of a published chunk's log decays, the mean
        g = REF.mamba_operands(h, w, *sizes[:4])[4]
    chunks = jnp.exp(g.reshape(2, T // 128, 128, -1).sum(axis=2))
    assert abs(float(keep - chunks.mean())) < 1e-6


def test_the_grouped_norm_is_a_groups_own():
    """A group's values normed by their own mean square: another group's
    values do not move them."""
    cfg, w, h = _mixer()
    inner = cfg.ssm_heads * cfg.ssm_head_dim
    group = inner // cfg.ssm_groups
    # scale the first group's heads' skip: the second group's part of
    # the normed output (before W_out) must not move
    w_out = jnp.eye(inner, cfg.dim)         # reads the first 64 channels
    w_late = jnp.zeros((inner, cfg.dim)).at[group:].set(
        jnp.eye(inner - group, cfg.dim))
    run = lambda w: tfm._ssm_mix(h, w, cfg)[0]
    per = cfg.ssm_heads // cfg.ssm_groups
    loud = dict(w, ssm_D=w["ssm_D"].at[:per].multiply(50.0))
    assert _far(run(dict(loud, ssm_out=w_late)),
                run(dict(w, ssm_out=w_late))) < 1e-6
    assert _far(run(dict(loud, ssm_out=w_out)),
                run(dict(w, ssm_out=w_out))) > 0.1


def _norm64(x, scale, groups, eps, cot):
    """The grouped norm written out in float64: its value and what
    ``cot`` pulls back to x and to the scale."""
    x, scale, cot = (np.asarray(a, np.float64) for a in (x, scale, cot))
    split = lambda a: a.reshape(*a.shape[:-1], groups, -1)
    xs, ss, cs = split(x), split(scale), split(cot)
    rstd = 1.0 / np.sqrt(np.square(xs).mean(-1, keepdims=True) + eps)
    pulled = cs * ss
    dx = pulled * rstd - xs * rstd ** 3 * (pulled * xs).mean(
        -1, keepdims=True)
    dscale = (cs * xs * rstd).reshape(-1, x.shape[-1]).sum(axis=0)
    return (xs * rstd * ss).reshape(x.shape), dx.reshape(x.shape), dscale


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("groups, width", [(8, 512), (1, 128), (3, 128)])
def test_the_grouped_norm_on_the_rows_is_the_norm_of_the_view(groups, width,
                                                              dtype):
    """``_group_rmsnorm`` on ``[.., groups x width]`` as it stands
    against ``_rmsnorm`` of the ``[.., groups, width]`` view (what
    ``_ssm_mix`` ran before PR 62) and against float64: the value and
    the gradients of x and the scale; rows whose sizes differ forty
    times, so a statistic that leaked between rows or groups shows."""
    eps = 1e-5
    rng = np.random.default_rng(groups)
    x = jnp.asarray(rng.standard_normal((2, 48, groups * width))
                    * rng.uniform(0.1, 4.0, (2, 48, 1)), dtype)
    scale = jnp.asarray(1 + 0.25 * rng.standard_normal(groups * width),
                        dtype)
    cot = jnp.asarray(rng.standard_normal(x.shape), dtype)

    def view(x, scale):
        y = x.reshape(*x.shape[:-1], groups, -1)
        return tfm._rmsnorm(y, scale.reshape(groups, -1), eps).reshape(
            x.shape)

    def pulled(norm):
        y, pull = jax.vjp(jax.jit(norm), x, scale)
        return (y,) + pull(cot)

    got = pulled(lambda x, scale: tfm._group_rmsnorm(x, scale, groups, eps))
    assert [a.dtype for a in got] == [x.dtype] * 3
    for what, a, b, c in zip(("y", "dx", "dscale"), got, pulled(view),
                             _norm64(x, scale, groups, eps, cot)):
        a, b = (np.asarray(v, np.float64) for v in (a, b))
        if dtype == "float32":
            assert _far(a, b) < 1e-5 and _far(a, c) < 1e-5, what
        else:
            # the same roundings in the same places: where the
            # statistic's last float32 bit tips one, one ulp of the
            # normed value (2 ** -7 of it at most) through the scale's
            # product and its rounding; in a gradient, one of the
            # largest term of a row's sums
            ulp = 2.0 ** -6 * (np.abs(b) if what == "y" else
                               np.abs(b).max(axis=-1, keepdims=True))
            assert (np.abs(a - b) <= ulp).all(), (
                what, (np.abs(a - b) / ulp).max())
            assert (a != b).mean() < 1e-3 and _far(a, c) < 2.0 ** -6, what


# -- MLPs of two matrices ----------------------------------------------------


def test_relu2_is_a_square_and_has_no_gate():
    a = jnp.asarray([-2.0, 0.0, 0.5, 3.0])
    assert moe_dispatch.gated("relu2", None, a).tolist() == [0, 0, 0.25, 9]
    assert "relu2" in moe_dispatch.GATELESS
    assert not set(moe_dispatch.GATELESS) & {"silu", "relu"}
    with pytest.raises(ValueError, match="takes 2 expert weights, got 3"):
        moe_dispatch.moe_experts(
            jnp.zeros((1, 4, 8)), jnp.zeros((1, 4, 1)),
            jnp.zeros((1, 4, 1), jnp.int32), *[jnp.zeros((2, 8, 8))] * 3,
            activation="relu2")


@pytest.mark.parametrize("held", [0, 2])
@pytest.mark.parametrize("mode", ["off", "interpret"])
def test_two_matrix_experts_are_the_loop(monkeypatch, mode, held):
    """Value and gradients, all held and a share, and the grouped matmul
    runs two products a layer where a gated layer runs three."""
    monkeypatch.setenv(SWITCH, mode)
    cfg = _spec(moe_experts_held=held).config
    kind = next(k for k in cfg.kinds if not k.dense)
    w = tfm._init_layers(jax.random.PRNGKey(0), jax.random.PRNGKey(1), cfg,
                         kind, ())
    assert not {"w_gate", "ws_gate"} & set(w)
    # scores that spread wider than the bias: tokens choose differently
    w["w_router"] = 10.0 * w["w_router"]
    w["expert_bias"] = 0.1 * _normal(5, cfg.moe_experts)
    u = _normal(3, 1, 64, cfg.dim)
    calls = []
    real = grouped_matmul.grouped_matmul
    monkeypatch.setattr(grouped_matmul, "grouped_matmul",
                        lambda *a, **k: calls.append(1) or real(*a, **k))

    def got(u, w):
        return (tfm._moe_ffn(u, w, cfg, None)[0]
                + tfm._shared_expert(u, w, cfg))

    def want(u, w):
        weights, _ = REF.route_weights(
            u, w["w_router"], w["expert_bias"], SHAPE["top_k"],
            SHAPE["norm_topk"], SHAPE["scale"])
        return (REF.held_experts(u, w, weights, cfg.experts_held[0])
                + REF.mlp(u, w["ws_up"], w["ws_down"], lambda a: a))

    with jax.default_matmul_precision("highest"):
        assert _far(got(u, w), want(u, w)) < 1e-5
        # two products a trace of the dispatch's block (a share traces
        # it twice: the first block, and the loop over further ones)
        assert len(calls) == (4 if held else 2)
        weigh = _normal(9, *u.shape)
        names = ("w_router", "w_up", "w_down", "ws_up", "ws_down")
        loss = lambda fn: lambda u, part: (
            fn(u, dict(w, **part)) * weigh).sum()
        part = {name: w[name] for name in names}
        gots = jax.grad(loss(got), (0, 1))(u, part)
        wants = jax.grad(loss(want), (0, 1))(u, part)
    assert _far(gots[0], wants[0]) < 1e-4
    for name in names:
        assert _far(gots[1][name], wants[1][name]) < 1e-4, name


def test_a_width_of_half_a_lane_tile_is_padded_where_a_kernel_runs():
    """1,856 = 14.5 tiles of 128 lanes runs as 1,920: zero columns of
    w_up meet zero rows of w_down."""
    up, down = _normal(0, 2, 8, 48), _normal(1, 2, 48, 8)
    wide = moe_dispatch.whole_lanes((up, down))
    assert [w.shape for w in wide] == [(2, 8, 128), (2, 128, 8)]
    assert float(jnp.abs(wide[0][..., 48:]).max()) == 0.0
    assert float(jnp.abs(wide[1][:, 48:]).max()) == 0.0
    whole = (jnp.zeros((2, 8, 256)),) * 2 + (jnp.zeros((2, 256, 8)),)
    assert all(a is b for a, b in zip(moe_dispatch.whole_lanes(whole), whole))


def test_a_dense_mlp_of_two_matrices():
    spec = load_model_spec("transformer", model_params=params_string(dict(
        dim=32, num_heads=2, num_layers=2, vocab_size=64, seq_len=16,
        ffn_activation="relu2", dtype="float32")))
    params = spec.init_fn(jax.random.PRNGKey(0))
    assert "w_gate" not in params["layers"]
    w = {k: v[0] for k, v in params["layers"].items()}
    h = _normal(0, 1, 16, 32)
    got = tfm._gated_mlp(h, w, spec.config, ("w_gate", "w_up", "w_down"),
                         ("ffn_gate", "ffn_up"))
    assert _far(got, jnp.square(jax.nn.relu(h @ w["w_up"])) @ w["w_down"]
                ) < 1e-6


# -- layers of one sublayer --------------------------------------------------


def test_a_layer_of_one_sublayer_has_one_norm_and_no_other_weights():
    params = jax.eval_shape(_spec().init_fn, jax.random.PRNGKey(0))
    period = params["layers"]["period"]
    mixer = {"ln1", "ssm_in", "ssm_conv", "ssm_conv_bias", "A_log",
             "dt_bias", "ssm_D", "ssm_norm", "ssm_out"}
    ffn = {"ln2", "w_router", "expert_bias", "w_up", "w_down", "ws_up",
           "ws_down"}
    assert [set(period[str(i)]) for i in range(5)] == [
        mixer, ffn, mixer, {"ln1", "wq", "wk", "wv", "wo"}, ffn]
    kinds = _spec().config.kinds
    assert [(k.op, k.ffn, k.dense) for k in kinds] == [
        ("m", False, True), ("e", True, False), ("m", False, True),
        ("a", False, True), ("e", True, False)]
    # with an FFN behind every mixer (the default) an m layer has both
    both = jax.eval_shape(_spec(mixer_ffn=True, layer_pattern="mmmam").init_fn,
                          jax.random.PRNGKey(0))["layers"]["period"]["0"]
    assert {"ln1", "ln2", "ssm_in", "w_up"} <= set(both)


def test_the_decay_mask_leaves_the_mixers_own_scalars_alone():
    params = jax.eval_shape(_spec().init_fn, jax.random.PRNGKey(0))
    mask = tfm._decayed(params)["layers"]["period"]["0"]
    assert {k for k, v in mask.items() if not v} == {
        "A_log", "dt_bias", "ssm_D", "ssm_norm", "ssm_conv_bias"}


def test_the_lines_state_the_new_fields():
    from elasticdl_tpu.ops.flash_attention import logger
    from elasticdl_tpu.worker import worker

    spec = _spec()
    params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, T), jnp.int32)
    for said in (tfm.announce_stack, tfm.announce_ssm,
                 short_conv.announce_conv):
        said.cache_clear()      # once a shape: an earlier test's
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
        "experts_held=2/8 shared_expert=96 sublayers=11111 "
        "mlp=two-matrix:relu2 a:window=0,rope=0")
    assert line("ssm scan:").startswith(
        "ssm scan: rows=%d heads=4 groups=2 head_dim=16 state=16 chunk=128 "
        "conv_taps=4 conv_bias=1 states=" % (2 * T))
    assert line("short conv:").endswith("epilogue=silu bias=1")
    assert set(stats) == {"ssm_chunk_keep", "moe_load", "moe_moved",
                          "moe_spilled"}
    assert stats["ssm_chunk_keep"].shape == ()
    assert worker._loss_fields({"ssm_chunk_keep": 0.25}) == (
        " chunk_keep=0.250000")


# -- the shares add up -------------------------------------------------------


def test_the_sixteen_expert_shares_add_up_to_the_uncut_layer():
    """32 experts, 6 a token, 2 held a chip: the 16 shares' routed parts
    (each routed over all 32 by the whole router, each an MLP of two
    matrices) and the shared expert, counted once, add up to the uncut
    expert layer."""
    cfg = _spec(moe_experts=32, moe_top_k=6, moe_experts_held=0).config
    kind = next(k for k in cfg.kinds if not k.dense)
    w = tfm._init_layers(jax.random.PRNGKey(0), jax.random.PRNGKey(1), cfg,
                         kind, ())
    w["w_router"] = 10.0 * w["w_router"]
    w["expert_bias"] = 0.1 * _normal(5, 32)
    u = _normal(3, 1, 64, cfg.dim)
    shared = tfm._shared_expert(u, w, cfg)
    whole = tfm._moe_ffn(u, w, cfg, None)[0] + shared
    parts = shared
    for share in range(16):
        cut = dataclasses.replace(cfg, moe_experts_held=2,
                                  moe_share_index=share)
        held = {name: w[name][2 * share:2 * share + 2]
                for name in ("w_up", "w_down")}
        parts = parts + tfm._moe_ffn(u, dict(w, **held), cut, None)[0]
    assert _far(parts, whole) < 1e-5
    # a share alone is no small part short of it, nor is the shared expert
    assert _far(shared, whole) > 0.3


# -- what cannot run them says so by name ------------------------------------

BASE = dict(vocab_size=64, dim=32, num_heads=2, num_layers=2)
SSM = dict(ssm_heads=2, ssm_head_dim=8, ssm_state=8)
FIELDS = {
    "mamba": (dict(layer_pattern="ma", **SSM), "a Mamba-2 layer (m)"),
    "one_sublayer": (dict(layer_pattern="ae", mixer_ffn=False),
                     "a layer of one sublayer (e, or any layer under "
                     "mixer_ffn=false)"),
    "relu2": (dict(ffn_activation="relu2"),
              "an MLP of two matrices (ffn_activation=relu2"),
}


@pytest.mark.parametrize("what", ["prefill", "decode_step", "generate",
                                  "forward_pipelined", "param_specs"])
@pytest.mark.parametrize("field", sorted(FIELDS))
def test_decoding_the_pipeline_and_a_mesh_refuse_each_by_name(what, field):
    fields, named = FIELDS[field]
    cfg = tfm.TransformerConfig(**dict(BASE, **fields))
    call = {
        "prefill": lambda: tfm.prefill({}, cfg, jnp.zeros((1, 4), jnp.int32),
                                       8),
        "decode_step": lambda: tfm.decode_step({}, cfg, (), 0, None),
        "generate": lambda: tfm.generate({}, cfg, [[1]], 2),
        "forward_pipelined": lambda: tfm.forward_pipelined(
            {}, None, cfg, None, 2),
        "param_specs": lambda: tfm.param_specs(cfg),
    }[what]
    with pytest.raises(NotImplementedError) as refused:
        call()
    assert named in str(refused.value)


@pytest.mark.parametrize("fields, match", [
    (dict(layer_pattern="ma"), "an m layer needs ssm_heads"),
    (dict(layer_pattern="ma", ssm_groups=3, **SSM), "ssm_groups that divide"),
    (dict(layer_pattern="ma", conv_kernel=8, conv_bias=True, **SSM),
     "conv_kernel <= 7"),
    (dict(conv_bias=True), "conv_bias=true is the m layer's"),
    (dict(layer_pattern="aa", mixer_ffn=False), "mixer_ffn=false"),
    (dict(mixer_ffn=False), "mixer_ffn=false"),
    (dict(layer_pattern="ax"), "e \\(no operator"),
    (dict(ffn_activation="relu2", moe_experts=4, ffn_limits="4,4"),
     "has no gate product"),
    (dict(ffn_activation="gelu"), "unknown ffn_activation"),
] + [
    (dict(layer_pattern=pattern, mixer_ffn=ffn, **SSM, **with_it),
     "is not held to")
    for pattern, ffn in (("ma", True), ("ae", False), ("ae", True))
    for with_it in (dict(attn_gate=True), dict(post_norms=True),
                    dict(hyper_streams=2), dict(mtp_modules=1),
                    dict(moe_experts=4, moe_route_before_op=True))
])
def test_a_configuration_that_cannot_be_is_refused_where_it_is_built(
        fields, match):
    with pytest.raises(ValueError, match=match):
        tfm.TransformerConfig(**dict(BASE, **fields))


def test_an_ffn_alone_beside_whole_blocks_trains():
    """An e layer in a stack whose other layers have both sublayers:
    ``mixer_ffn`` is one field, the letter another."""
    spec = load_model_spec("transformer", model_params=params_string(dict(
        BASE, num_layers=3, layer_pattern="aea", seq_len=16,
        scan_periods=False, dtype="float32")))
    params = spec.init_fn(jax.random.PRNGKey(0))
    sets = [set(params["layers"]["period"][str(i)]) for i in range(3)]
    assert sets[1] == {"ln2", "w_gate", "w_up", "w_down"}
    assert {"ln1", "ln2", "wq", "w_gate"} <= sets[0]
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 16)),
                         jnp.int32)
    grads = jax.grad(lambda p: spec.loss_fn(
        spec.apply_fn(p, tokens, True), tokens).mean())(params)
    assert all(float(jnp.abs(g).max()) > 0
               for g in jax.tree_util.tree_leaves(grads))
