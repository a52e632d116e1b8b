"""A stack of gated-delta layers and a full-attention layer
(``layer_pattern=ddda``: models/transformer._delta_mix over
ops/gated_delta.py and ops/short_conv.conv_silu, a block with norms on
its sublayers' outputs alone) against the plain reference of the
benchmark (benchmark/reference/olmo-hybrid-7b.py: the delta rule token
by token), at the configuration's rehearsal size, float32 on the CPU."""

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import manifest
from benchmark.lib.runner import params_string
from elasticdl_tpu.models import remat_keep as rk
from elasticdl_tpu.models import transformer as tfm
from elasticdl_tpu.models.spec import load_model_spec
from elasticdl_tpu.ops import batch_shard, gated_delta as gd
from elasticdl_tpu.ops import flash_attention as fa
from elasticdl_tpu.ops.mode import SWITCH
from tests import reference_check as rc

NAME = "olmo-hybrid-7b"
REF = manifest.load_named("reference", NAME)
PUBLISHED, CONFIG = rc.configuration(NAME, None), rc.configuration(NAME)
SHAPE = REF.shape_of(CONFIG)
CASE = functools.partial(rc.rehearsal, NAME)
_spec = functools.partial(rc.spec_of, NAME)
# float32 on both sides: the chunk form against the recurrence reads
# 1e-6 in the loss and 2e-5 .. 4e-4 in a gradient leaf (the decay's
# projection, whose gradient is small beside the others')
LOSS_TOLERANCE, GRAD_TOLERANCE = 2e-5, 2e-3


@pytest.mark.parametrize("mode", ["off", "interpret"])
def test_the_loss_and_every_gradient_leaf_match_the_recurrence(mode):
    """``off`` + remat: the jnp twins under ``jax.checkpoint``;
    ``interpret``: the scan's and the convolution's kernels and the
    flash kernels in the interpreter."""
    cfg = _spec().config
    assert cfg.remat and [k.op for k in cfg.kinds] == list("ddda")
    far, still, _, _ = rc.check(CASE(), mode, LOSS_TOLERANCE, GRAD_TOLERANCE)
    assert len(far) == 3 * 14 + 11 + 3 and not still


@pytest.mark.parametrize("piece", ["conv", "silu", "l2norm", "beta2",
                                   "decay", "out_norm", "gate"])
def test_the_reference_without_one_piece_fails_the_tolerance(piece):
    """Each of steps 1-6 moves the loss by more than the tolerance the
    product is held to: leaving one out is not support."""
    case = CASE()
    want = float(rc.wanted(case)[0][0])
    less = float(jax.jit(case.reference(without=(piece,)))(case.params)[0])
    # (without the L2 norm the state diverges: a NaN is not within it)
    assert not abs(less - want) <= 20 * LOSS_TOLERANCE * want, (
        piece, less, want)


@pytest.mark.parametrize("how", [dict(rounded=jnp.bfloat16),
                                 dict(state=jnp.bfloat16)],
                         ids=["operands", "state"])
def test_the_reference_in_bfloat16_fails_the_tolerance(how):
    case = CASE()
    want = float(rc.wanted(case)[0][0])
    lower = float(jax.jit(case.reference(**how))(case.params)[0])
    assert abs(lower - want) > 5 * LOSS_TOLERANCE * want, (lower, want)


# -- the shares add up ------------------------------------------------------


def _head_columns(width, heads, share, shares):
    """Columns of a [.., heads * width] projection that the heads of
    ``share`` hold."""
    held = heads // shares
    return np.arange(share * held * width, (share + 1) * held * width)


def _delta_share(w, heads, d_k, d_v, share, shares):
    """A delta layer's mixer weights cut to one share's heads."""
    held = heads // shares
    heads_of = np.arange(share * held, (share + 1) * held)
    q, k, v = (_head_columns(d_k, heads, share, shares),
               heads * d_k + _head_columns(d_k, heads, share, shares),
               2 * heads * d_k + _head_columns(d_v, heads, share, shares))
    columns = np.concatenate([q, k, v])
    out = _head_columns(d_v, heads, share, shares)
    return dict(w, w_qkv=w["w_qkv"][:, columns],
                delta_conv=w["delta_conv"][columns],
                w_a=w["w_a"][:, heads_of], w_b=w["w_b"][:, heads_of],
                A_log=w["A_log"][heads_of], dt_bias=w["dt_bias"][heads_of],
                w_out_gate=w["w_out_gate"][:, out], wo=w["wo"][out])


def _attention_share(w, heads, head_dim, share, shares):
    columns = _head_columns(head_dim, heads, share, shares)
    cut = {name: w[name][:, columns] for name in ("wq", "wk", "wv")}
    return dict(w, wo=w["wo"][columns], q_norm=w["q_norm"][columns],
                k_norm=w["k_norm"][columns], **cut)


def _uncut(kind):
    """(weights of one uncut layer of 4 heads, its input, the shape)."""
    heads = 4
    spec = _spec(num_heads=heads, num_kv_heads=heads, head_shares=1,
                 num_layers=1, layer_pattern=kind)
    params = REF.inputs(dict(CONFIG, seq_len=64), jax.jit(spec.init_fn)(
        jax.random.PRNGKey(5)), np.random.default_rng(5))[0]
    w = REF.layers_of(params)[0]
    x = jnp.asarray(np.random.default_rng(6).standard_normal((2, 64, 64)),
                    jnp.float32)
    return w, x, heads


def test_the_shares_of_a_delta_layer_add_up_to_the_uncut_layer():
    """2 x 2 of 4 heads: the held heads' parts of the ``W_o`` product
    sum to the uncut mixer's, and with the norm on the sublayer's output
    taken of the SUM and the MLP counted once that is the uncut
    reference's layer.  The program's mixer of a share is the same
    part."""
    w, x, heads = _uncut("d")
    d_k, d_v, eps = SHAPE["d_k"], SHAPE["d_v"], SHAPE["eps"]
    mixer = lambda held: jax.jit(
        lambda w: REF.delta_mixer(x, w, held, d_k, d_v, eps, True))
    whole, part = mixer(heads)(w), mixer(heads // 2)
    parts = [part(_delta_share(w, heads, d_k, d_v, i, 2)) for i in range(2)]
    np.testing.assert_allclose(parts[0] + parts[1], whole, atol=2e-5)
    assert float(jnp.abs(parts[0] - whole).max()) > 1e-2
    # the layer: norm of the sum, then the MLP once
    x1 = x + REF.rmsnorm(sum(parts), w["ln1_post"], eps)
    layer = x1 + REF.rmsnorm(REF.swiglu(x1, w), w["ln2_post"], eps)
    shape = dict(SHAPE, delta_heads=heads, kinds=("linear_attention",))
    head = jnp.zeros((64, 256), jnp.float32)
    tokens = jnp.zeros((2, 64), jnp.int32)
    seen = []
    real = REF.swiglu
    try:
        REF.swiglu = lambda u, w, r=None: seen.append(u) or real(u, w)
        params = {"embed": jnp.zeros((256, 64)), "ln_f": jnp.ones((64,)),
                  "lm_head": head, "layers": {
                      "lead": {"0": w}, "period": {}, "tail": {}}}
        # the uncut reference's own walk of the layer, from the stream x
        REF.loss(dict(params, embed=x.reshape(-1, 64)),
                 jnp.arange(128).reshape(2, 64), **shape)
    finally:
        REF.swiglu = real
    np.testing.assert_allclose(seen[0], x1, atol=2e-5)
    # the program's held-heads mixer is the reference's part
    cfg = _spec(num_layers=1, layer_pattern="d").config
    got = tfm._delta_mix(x, _delta_share(w, heads, d_k, d_v, 1, 2), cfg)
    np.testing.assert_allclose(got, parts[1], atol=3e-5)
    assert layer.shape == x.shape


def test_the_shares_of_the_full_layer_add_up_given_the_pairs_exchange():
    """The QK norm runs over the whole projection, so the held heads'
    parts add up to the uncut mixer once each share norms with the mean
    square of all 4 heads' values (what the pair would exchange); with
    its own 2 heads' mean square, as the cell runs it, a share is off
    by that statistic alone."""
    w, x, heads = _uncut("a")
    head_dim, eps = SHAPE["head_dim"], SHAPE["eps"]
    whole = REF.attention(x, w, heads, head_dim, eps)
    stat = tuple(jnp.mean(jnp.square(x @ w[name]), axis=-1, keepdims=True)
                 for name in ("wq", "wk"))
    share = lambda i, **kw: REF.attention(
        x, _attention_share(w, heads, head_dim, i, 2), heads // 2,
        head_dim, eps, **kw)
    exchanged = share(0, qk_stat=stat) + share(1, qk_stat=stat)
    np.testing.assert_allclose(exchanged, whole, atol=2e-5)
    alone = share(0) + share(1)
    assert float(jnp.abs(alone - whole).max()) > 1e-3
    # the program's held-heads mixer is the reference's part, its own
    # mean square
    cfg = _spec(num_layers=1, layer_pattern="a").config
    got = tfm._attention_mix(
        x, _attention_share(w, heads, head_dim, 1, 2), cfg, None,
        jnp.arange(64), cfg.kinds[0])[0]
    np.testing.assert_allclose(got, share(1), atol=3e-5)


# -- the plan, the tree, the refusals ---------------------------------------


def test_stack_plan_of_ddda_is_one_period_and_its_letters_round_trip():
    cfg = _spec().config
    plan = tfm.stack_plan(cfg)
    assert (plan.lead, plan.periods, plan.tail) == ((), 1, ())
    assert "".join(map(tfm._letter, plan.period)) == "ddda"
    assert plan.period[0] == tfm.Kind("d", True)
    assert plan.period[3] == tfm.Kind("a", True, 0, False)    # NoPE
    eight = tfm.stack_plan(_spec(num_layers=8,
                                 layer_pattern="dddaddda").config)
    assert eight.periods == 2 and len(eight.period) == 4


def test_the_parameter_tree_and_the_decay_mask():
    spec = _spec()
    params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    period = params["layers"]["period"]
    assert sorted(period["0"]) == sorted([
        "A_log", "delta_conv", "dt_bias", "ln1_post", "ln2_post", "o_norm",
        "w_a", "w_b", "w_down", "w_gate", "w_out_gate", "w_qkv", "w_up",
        "wo"])
    assert sorted(period["3"]) == sorted([
        "k_norm", "ln1_post", "ln2_post", "q_norm", "w_down", "w_gate",
        "w_up", "wk", "wo", "wq", "wv"])       # no ln1, no ln2
    heads, d_k, d_v = 2, 16, 32
    assert period["0"]["w_qkv"].shape == (1, 64, heads * (2 * d_k + d_v))
    assert period["0"]["delta_conv"].shape == (1, heads * (2 * d_k + d_v), 4)
    assert period["0"]["o_norm"].shape == (1, d_v)
    mask = tfm._decayed(params)
    spared = {jax.tree_util.keystr(path[-1:]) for path, keep in
              jax.tree_util.tree_flatten_with_path(mask)[0] if not keep}
    assert spared == {"['A_log']", "['dt_bias']", "['o_norm']",
                      "['delta_conv']", "['ln1_post']", "['ln2_post']"}


def test_the_published_count_of_parameters():
    spec = load_model_spec("transformer", model_params=params_string(
        PUBLISHED["cli"]["model_params"]))
    params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree_util.tree_leaves(params)) == (
        766241946)


@pytest.mark.parametrize("what,call", [
    ("decode_step", lambda spec, p: tfm.decode_step(
        p, spec.config, None, 0, jnp.zeros((1,), jnp.int32))),
    ("a model-parallel mesh", lambda spec, p: tfm.param_specs(spec.config)),
    ("forward_pipelined", lambda spec, p: tfm.forward_pipelined(
        p, jnp.zeros((2, 64), jnp.int32), spec.config, None, 2)),
])
def test_what_cannot_run_the_stack_refuses_it_by_name(what, call):
    spec = _spec()
    with pytest.raises(NotImplementedError) as refusal:
        call(spec, None)
    text = str(refusal.value)
    assert text.startswith(what + " does not run")
    assert "layer_pattern='ddda'" in text and "recurrent state" in text
    assert "pre_norms=False" in text


@pytest.mark.parametrize("bad,match", [
    (dict(layer_pattern="dd", num_layers=2), "delta_key_dim"),
    (dict(layer_pattern="dd", num_layers=2, delta_key_dim=8,
          delta_value_dim=8, conv_kernel=18), "conv_kernel"),
    (dict(pre_norms=False), "pre_norms=false needs post_norms"),
    (dict(layer_pattern="dx", num_layers=2), "gated delta rule"),
])
def test_a_delta_layer_without_its_sizes_is_refused_by_name(bad, match):
    with pytest.raises(ValueError, match=match):
        tfm.TransformerConfig(**bad)


# -- what is kept ------------------------------------------------------------

DELTA_ROWS = ["delta_decay", "delta_gate", "delta", "delta_in", "delta_qkv"]


def test_remat_keeps_table_has_the_delta_layers_rows():
    cfg = _spec().config
    rows = 2 * 64
    table = {label: (names, nbytes) for label, names, nbytes in rk.table(
        cfg, rows)}
    assert [label for label in table if label.startswith("delta")] == (
        DELTA_ROWS)
    heads, d_k, d_v, size = 2, 16, 32, 4
    assert table["delta_in"] == ((rk.KEEP_DELTA_IN,),
                                 rows * heads * (2 * d_k + d_v) * size)
    assert table["delta_qkv"][1] == table["delta_in"][1]
    assert table["delta_decay"] == ((rk.KEEP_DELTA_DECAY,), rows * heads * 8)
    assert table["delta_gate"] == ((rk.KEEP_DELTA_GATE,),
                                   rows * heads * d_v * size)
    # the output, a float32 state a chunk a head, 128 lanes a row, and
    # two chunks' inverses side by side in the compute dtype
    assert table["delta"] == (
        (gd.KEEP_OUT, gd.KEEP_STATES, gd.KEEP_INVERSE),
        rows * heads * d_v * size
        + rows // gd.CHUNK * heads * d_k * 128 * 4
        + rows // 128 * heads * gd.CHUNK * 128 * size)
    layers = {label: n for label, _, _, n in rk._entries(cfg, rows)}
    assert layers["delta"] == 3 and layers["flash"] == 1
    assert layers["stream"] == layers["ffn_gate"] == 4
    assert "conv_in" not in layers


@pytest.mark.parametrize("mode", ["interpret", "off"])
def test_kept_names_change_no_gradient(monkeypatch, mode):
    monkeypatch.setenv(SWITCH, mode)
    spec, params, tokens = CASE().parts()
    held = 16 * sum(a.size for a in jax.tree_util.tree_leaves(params))

    def grads(room):
        with batch_shard.batch_axis(None, None, room):
            return jax.jit(jax.grad(rc.loss_of(spec, tokens)))(params)

    everything = batch_shard.DeviceRoom(2 ** 40, 2 ** 40 - held)
    names = rk.choose(spec.config, params, tokens.size, everything)[0]
    assert {gd.KEEP_OUT, gd.KEEP_STATES, gd.KEEP_INVERSE, rk.KEEP_DELTA_IN,
            rk.KEEP_DELTA_QKV, rk.KEEP_DELTA_DECAY,
            rk.KEEP_DELTA_GATE} <= set(names)
    for a, b in zip(jax.tree_util.tree_leaves(grads(everything)),
                    jax.tree_util.tree_leaves(grads(None))):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)


@pytest.mark.parametrize("kept,forwards", [
    ("everything", 3), ("nothing", 6), ("all_but_the_inverse", 6)])
def test_a_policy_that_keeps_the_delta_row_runs_the_forward_once_a_layer(
        monkeypatch, kept, forwards):
    """The gradient's jaxpr of three delta layers under ``remat=true``:
    with the ``delta`` row's three names kept ``gdn_fwd`` stands once a
    layer, with nothing kept twice (the backward's second forward), and
    twice as well were the row to leave out the inverse the backward
    reads: ``gdn_bwd`` once a layer always."""
    monkeypatch.setenv(SWITCH, "interpret")
    spec, params, tokens = CASE().parts()
    held = 16 * sum(a.size for a in jax.tree_util.tree_leaves(params))
    room = None if kept == "nothing" else batch_shard.DeviceRoom(
        2 ** 40, 2 ** 40 - held)
    if kept == "all_but_the_inverse":
        entries = rk._entries
        monkeypatch.setattr(rk, "_entries", lambda cfg, rows: [
            (label, tuple(n for n in names if n != gd.KEEP_INVERSE), *rest)
            for label, names, *rest in entries(cfg, rows)])
    with batch_shard.batch_axis(None, None, room):
        whole = jax.make_jaxpr(jax.grad(rc.loss_of(spec, tokens)))(params)
    calls = collections.Counter()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                calls[eqn.params["name"]] += 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(whole.jaxpr)
    assert (calls["gdn_fwd"], calls["gdn_bwd"]) == (forwards, 3)


# -- the lines ---------------------------------------------------------------


def _lines(fn, *prefixes):
    import logging

    seen = []

    class Handler(logging.Handler):
        def emit(self, record):
            seen.append(record.getMessage())

    handler = Handler(level=logging.INFO)
    fa.logger.addHandler(handler)
    for announce in (tfm.announce_delta, tfm.announce_stack):
        announce.cache_clear()
    try:
        fn()
    finally:
        fa.logger.removeHandler(handler)
    return [line for line in seen if line.startswith(prefixes)]


@pytest.mark.parametrize("mode,ran", [("off", "reference"),
                                      ("interpret", "interpreter")])
def test_the_delta_scan_and_layer_stack_lines(monkeypatch, mode, ran):
    monkeypatch.setenv(SWITCH, mode)
    spec, params, tokens = CASE().parts()
    held = 16 * sum(a.size for a in jax.tree_util.tree_leaves(params))
    room = batch_shard.DeviceRoom(2 ** 40, 2 ** 40 - held)

    def trace(room):
        def run():
            with batch_shard.batch_axis(None, None, room):
                jax.eval_shape(rc.loss_of(spec, tokens), params)
        return _lines(run, "delta scan:", "layer stack:")

    stack, scan = trace(None)
    assert stack == (
        "layer stack: pattern=ddda lead=- period=ddda periods=1 tail=- "
        "dense_layers=0 experts_held=0/0 heads_held=2/4 "
        "a:window=0,rope=0")
    assert scan == (
        "delta scan: rows=128 heads=2 key_dim=16 value_dim=32 chunk=64 "
        "conv_taps=4 neg_eigval=1 decay=head states=recomputed " + {
            "reference": "inverse=twin ",
            "interpreter": "inverse=forward inverse_mb=0.1 "}[ran] + ran)
    # with room for everything the states are among the kept names
    assert trace(room)[1] == scan.replace("recomputed", "kept")


# -- a softmax layer then three KDA layers, every FFN an expert layer -------
# (``layer_pattern=addd``, ``delta_kind=kda``: benchmark/reference/
# solar-open2-250b.py, the recurrence under a decay a channel)

KNAME = "solar-open2-250b"
KREF = manifest.load_named("reference", KNAME)
KPUBLISHED, KCONFIG = rc.configuration(KNAME, None), rc.configuration(KNAME)
KSHAPE = KREF.shape_of(KCONFIG)
KCASE = functools.partial(rc.rehearsal, KNAME)
_kspec = functools.partial(rc.spec_of, KNAME)
# what the interpreter's case has to reach, as at full depth: the
# vector-decay scan, the dispatch's products and a share's row moves (64
# positions are no flash tile, and the convolution takes its reference)
KDA_KERNELS = {"kda_fwd", "kda_bwd", "gmm_nn", "gmm_nt", "gmm_tn",
               "rows_pack", "rows_gather", "rows_sum"}


@pytest.mark.parametrize("mode", ["off", "interpret"])
def test_the_addd_expert_stack_matches_the_recurrence_a_channel(mode):
    """Loss and every gradient leaf of the cut ``solar-open2-250b``
    model at its rehearsal size (2 of 16 heads of both kinds, the
    softmax layer's on 1 K/V head, 2 of 8 experts beside a shared one
    under a sigmoid router with a bias) against the plain reference:
    ``off`` the jnp twins under ``jax.checkpoint``, ``interpret`` the
    vector-decay scan's, the convolution's, the flash and the dispatch's
    kernels in the interpreter.  No gradient reaches ``expert_bias``."""
    cfg = _kspec().config
    assert cfg.remat and [k.op for k in cfg.kinds] == list("addd")
    assert not any(k.dense for k in cfg.kinds) and cfg.delta_kind == "kda"
    assert (cfg.num_heads, cfg.kv_heads, cfg.head_shares) == (2, 1, 8)
    # the interpreter's case at the least depth with both kinds of
    # layer, ``ad``: the arithmetic at depth is the ``off`` case's
    short = mode == "interpret"
    case = KCASE(layers_kept=(0, 1), model=(
        ("num_layers", 2), ("layer_pattern", "ad"))) if short else KCASE()
    far, still, _, _ = rc.check(
        case, mode, LOSS_TOLERANCE, GRAD_TOLERANCE,
        KDA_KERNELS if short else ())
    # no gradient reaches the bias; a layer whose router sends the held
    # experts no token leaves theirs, and the router's (which a share
    # reaches through its experts alone), zero on both sides
    assert all("expert_bias" in name or name.split("'")[-2] in (
        "w_gate", "w_up", "w_down", "w_router") for name in still), still
    # a KDA layer's 12 mixer leaves and the softmax layer's 5, 2 norms
    # and 7 FFN leaves (the bias apart) each, and embed, ln_f, lm_head;
    # at most one layer's held experts idle
    assert len(far) >= (1 if short else 3) * (12 + 9) + (5 + 9) + 3 - 4


@pytest.mark.parametrize("piece", KREF.PIECES)
def test_the_kda_reference_without_one_piece_fails_the_tolerance(piece):
    """Each piece of the KDA layer and the softmax layer's gate moves
    the loss by more than the tolerance the product is held to;
    ``channels`` puts a head's mean log decay on every channel: a scalar
    decay in the vector's place is not support."""
    case = KCASE()
    want = float(rc.wanted(case)[0][0])
    less = float(jax.jit(case.reference(without=(piece,)))(case.params)[0])
    assert not abs(less - want) <= 20 * LOSS_TOLERANCE * want, (
        piece, less, want)


def _kda_share(w, heads, d, share, shares):
    """A KDA layer's mixer weights cut to one share's heads: their
    columns of every projection (the low-rank pairs' first halves feed
    every head and stay whole), their rows of W_o."""
    held = heads // shares
    heads_of = np.arange(share * held, (share + 1) * held)
    one = _head_columns(d, heads, share, shares)
    columns = np.concatenate([one, heads * d + one, 2 * heads * d + one])
    return dict(w, w_qkv=w["w_qkv"][:, columns],
                delta_conv=w["delta_conv"][columns],
                w_b=w["w_b"][:, heads_of], A_log=w["A_log"][heads_of],
                w_a_up=w["w_a_up"][:, one], dt_bias=w["dt_bias"][one],
                w_g_up=w["w_g_up"][:, one], b_g=w["b_g"][one],
                wo=w["wo"][one])


def _gqa_share(w, heads, kv_heads, head_dim, share, shares):
    q = _head_columns(head_dim, heads, share, shares)
    kv = _head_columns(head_dim, kv_heads, share, shares)
    return dict(w, wq=w["wq"][:, q], w_attn_gate=w["w_attn_gate"][:, q],
                wk=w["wk"][:, kv], wv=w["wv"][:, kv], wo=w["wo"][q])


def _kuncut(kind, heads, kv_heads=0, **more):
    """(the weights of one uncut layer, its normed input)."""
    spec = _kspec(num_heads=heads, num_kv_heads=kv_heads or heads,
                  head_shares=1, num_layers=1, layer_pattern=kind, **more)
    params = KREF.inputs(dict(KCONFIG, seq_len=64), jax.jit(spec.init_fn)(
        jax.random.PRNGKey(5)), np.random.default_rng(5))[0]
    x = jnp.asarray(np.random.default_rng(6).standard_normal((2, 64, 64)),
                    jnp.float32)
    return spec.config, KREF.layers_of(params)[0], x


def test_the_eight_head_shares_of_a_kda_layer_add_up_to_the_uncut_mixer():
    """8 x 1 of 8 heads: the held head's parts of the ``W_o`` product
    sum to the uncut mixer's (a head has its own q, k, v, decay a
    channel, write strength, state, output norm and gate; the low-rank
    pairs' down projections feed every head and are whole on a chip),
    and the program's mixer of a share is the reference's part."""
    heads, d, eps = 8, KSHAPE["d_k"], KSHAPE["eps"]
    _, w, x = _kuncut("d", heads)
    mixer = lambda held: jax.jit(
        lambda w: KREF.kda_mixer(x, w, held, d, d, eps, True))
    whole, part = mixer(heads)(w), mixer(1)      # one compile, 8 shares
    parts = [part(_kda_share(w, heads, d, i, 8)) for i in range(8)]
    np.testing.assert_allclose(sum(parts), whole, atol=3e-5)
    assert float(jnp.abs(parts[0] - whole).max()) > 1e-2
    cfg = _kspec(num_heads=1, num_layers=1, layer_pattern="d").config
    got = tfm._delta_mix(x, _kda_share(w, heads, d, 3, 8), cfg)
    np.testing.assert_allclose(got, parts[3], atol=3e-5)


def test_the_eight_head_shares_of_the_softmax_layer_add_up():
    """16 query heads on 8 K/V heads, 8 shares of one K/V group each (2
    query heads on 1 K/V head, as the cell's 8 on 1): no statistic
    crosses heads (no QK norm), so the parts of the ``W_o`` product add
    up to the uncut mixer, gate and all; the program's ``head // group``
    read of a share's one K/V head is the reference's repeat."""
    heads, kv, head_dim = 16, 8, KSHAPE["head_dim"]
    _, w, x = _kuncut("a", heads, kv)
    whole = KREF.attention(x, w, heads, kv, head_dim)
    parts = [KREF.attention(x, _gqa_share(w, heads, kv, head_dim, i, 8),
                            2, 1, head_dim) for i in range(8)]
    np.testing.assert_allclose(sum(parts), whole, atol=3e-5)
    cfg = _kspec(num_layers=1, layer_pattern="a").config
    assert (cfg.num_heads, cfg.kv_heads) == (2, 1)
    got = tfm._attention_mix(
        x, _gqa_share(w, heads, kv, head_dim, 5, 8), cfg, None,
        jnp.arange(64), cfg.kinds[0])[0]
    np.testing.assert_allclose(got, parts[5], atol=3e-5)


def test_the_expert_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """4 shares of 2 of 8 experts: every share routes over all 8 and
    multiplies its own 2, and their routed parts plus the shared expert
    counted ONCE are the uncut reference's FFN (all 8 held); the
    program's expert layer of a share (``_ffn`` of a ``d`` layer's kind)
    is that share's routed part plus the shared expert."""
    cfg, w, u = _kuncut("d", 2, moe_experts_held=0)
    assert w["w_gate"].shape[0] == 8
    route = dict(top_k=KSHAPE["top_k"], norm_topk=KSHAPE["norm_topk"],
                 scale=KSHAPE["scale"])
    whole, chosen = KREF.experts(u, w, first=0, **route)
    cut = lambda i: dict(w, **{name: w[name][2 * i:2 * i + 2]
                               for name in ("w_gate", "w_up", "w_down")})
    parts = [KREF.experts(u, cut(i), first=2 * i, **route)[0]
             for i in range(4)]
    np.testing.assert_allclose(sum(parts), whole, atol=3e-5)
    assert int(chosen.sum(-1).min()) == int(chosen.sum(-1).max()) == 2
    shared = KREF.shared_expert(u, w)
    assert float(jnp.abs(shared).max()) > 1e-2
    share = _kspec(num_layers=1, layer_pattern="d", moe_share_index=3).config
    got = tfm._ffn(u, dict(cut(3), ln2=jnp.ones_like(w["ln2"])), share,
                   None, dense=False)[0]
    # ``_ffn`` returns the stream: u + FFN(norm(u)); hand it a normed u
    normed = KREF.rmsnorm(u, jnp.ones_like(w["ln2"]), KSHAPE["eps"])
    want = u + KREF.experts(normed, cut(3), first=6, **route)[0] + (
        KREF.shared_expert(normed, w))
    np.testing.assert_allclose(got, want, atol=3e-5)


def test_the_kda_stacks_tree_count_and_decay_mask():
    """The cut model's 840,875,672 parameters (the configuration's
    ``reduced_why``), a KDA layer's leaves, and AdamW's mask: the decay
    rates, the step biases a channel, the gate's bias, the output norm,
    the taps and the router's bias are not decayed."""
    spec = load_model_spec("transformer", model_params=params_string(
        KPUBLISHED["cli"]["model_params"]))
    shapes = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    count = lambda tree: sum(
        int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))
    assert count(shapes) == 840875672
    layers = shapes["layers"]["period"]
    ffn = ("w_router", "expert_bias", "w_gate", "w_up", "w_down", "ws_gate",
           "ws_up", "ws_down", "ln1", "ln2")
    mixer = lambda w: count({k: v for k, v in w.items() if k not in ffn})
    assert mixer(layers["0"]) == 13631488
    assert mixer(layers["1"]) == 18135176
    assert layers["1"]["dt_bias"].shape == (1, 8 * 128)
    assert layers["1"]["w_a_up"].shape == (1, 128, 8 * 128)
    assert "w_a" not in layers["1"] and "w_out_gate" not in layers["1"]
    mask = tfm._decayed(shapes)["layers"]["period"]["1"]
    assert {k for k, v in mask.items() if not v} == {
        "A_log", "dt_bias", "b_g", "o_norm", "delta_conv", "expert_bias"}


@pytest.mark.parametrize("mode,ran", [("off", "reference"),
                                      ("interpret", "interpreter")])
def test_the_kda_stacks_lines(monkeypatch, mode, ran):
    monkeypatch.setenv(SWITCH, mode)
    spec, params, tokens = KCASE().parts()

    def run():
        jax.eval_shape(rc.loss_of(spec, tokens), params)

    stack, scan = _lines(run, "delta scan:", "layer stack:")
    assert stack == (
        "layer stack: pattern=addd lead=- period=addd periods=1 tail=- "
        "dense_layers=0 experts_held=2/8 shared_expert=48 heads_held=2/16 "
        "a:window=0,rope=0")
    assert scan == (
        "delta scan: rows=128 heads=2 key_dim=32 value_dim=32 chunk=64 "
        "conv_taps=4 neg_eigval=1 decay=channel rank=16 gate=softplus "
        "pairs=columns states=recomputed "
        + {"reference": "inverse=twin ",
           "interpreter": "inverse=forward inverse_mb=0.1 "}[ran] + ran)
