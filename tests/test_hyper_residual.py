"""A residual stream four wide (``hyper_streams``: ops/hyper_mix.py,
models/transformer.py) with the query latent and YaRN that came with
it, against the plain reference of the benchmark
(benchmark/reference/xing4.0-29b-a4b.py).  Float32 on the CPU at tiny
widths; the kernels in interpret mode."""

import collections
import contextlib
import functools
import dataclasses
import json
import logging
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from benchmark.lib import manifest
from elasticdl_tpu.models import remat_keep as rk
from elasticdl_tpu.models import transformer as tfm
from elasticdl_tpu.ops import hyper_mix as hm
from tests import reference_check as rc

NAME = "xing4.0-29b-a4b"
REF = manifest.load_named("reference", NAME)

# a leading dense layer and two expert layers over 4 of 16 experts
# beside a shared expert, latent attention with a query latent under
# YaRN (16 original positions of 32), a stream 4 x 128, one module
TINY = dict(vocab_size=96, dim=128, num_heads=2, num_layers=3, seq_len=32,
            kv_latent_rank=32, q_latent_rank=24, qk_nope_dim=16,
            qk_rope_dim=8, v_head_dim=24, rope_scaling="64,16,32,1",
            rope_theta=1e4, dense_layers=1, dense_ffn_dim=96, ffn_dim=48,
            moe_experts=16, moe_top_k=3, moe_experts_held=4,
            moe_share_index=1, moe_shared_experts=1,
            moe_router="sigmoid_bias", moe_norm_topk=True,
            moe_route_scale=2.0, moe_aux_weight=0, norm_eps=1e-6,
            tied_embeddings=False, embed_scale=1.0, hyper_streams=4,
            mtp_modules=1, dtype="float32")
N = 4


def shape_of(cfg, **over):
    """``REF.loss``'s keywords for a model of ``cfg``."""
    rank, d_nope, d_rope, d_v = cfg.latent
    return dict(dict(
        heads=cfg.num_heads, rank=rank, q_rank=cfg.q_latent_rank,
        d_nope=d_nope, d_rope=d_rope, d_v=d_v, top_k=cfg.moe_top_k,
        eps=cfg.norm_eps, theta=cfg.rope_theta,
        yarn=tfm.yarn_of(cfg.rope_scaling), norm_topk=cfg.moe_norm_topk,
        scale=cfg.moe_route_scale, first=cfg.experts_held[0],
        streams=cfg.hyper_streams, iters=cfg.hyper_sinkhorn_iters,
        sk_eps=tfm.HYPER_SINKHORN_EPS, mtp_weight=cfg.mtp_weight), **over)


product_loss, apart = rc.loss_of, rc.apart
# model -> the Case of a model of these widths as the comparison draws
# it: a wider head, a bias on the routers, maps off their initial values
case = functools.partial(rc.tiny, NAME, shape_of, hc_mult=N)


def sublayer(seed=0, rows=(2, 32), c=128):
    """(x [B, T, 4 c], y [B, T, c], the maps' weights) drawn generic."""
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape),
                                      jnp.float32)
    w = {"hc1_phi": draw(N * c, hm.columns(N)) * (N * c) ** -0.5,
         "hc1_alpha": jnp.asarray([1.0, 0.7, 1.3], jnp.float32),
         "hc1_bias": 0.5 * draw(hm.columns(N))}
    return draw(*rows, N * c), draw(*rows, c), w


def mixed(x, y, w, interpret, iters=20):
    u, through, maps, err = hm.pre(
        x, w["hc1_phi"], w["hc1_alpha"], w["hc1_bias"], N, iters, 1e-6,
        1e-6, interpret=interpret)
    return u, hm.post(through, y, maps, N, interpret=interpret), maps, err


# -- the mixing ----------------------------------------------------------------


@pytest.mark.parametrize("interpret", [None, True],
                         ids=["reference", "kernels"])
def test_one_sublayer_matches_the_plain_reference(interpret):
    x, y, w = sublayer()
    u, out, _, _ = mixed(x, y, w, interpret)
    X = x.reshape(2, 32, N, -1)
    h_pre, h_post, h_res = REF.hyper_maps(X, w, "hc1", 1e-6, 20, 1e-6)
    np.testing.assert_allclose(u, REF.read(X, h_pre), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        out.reshape(X.shape), REF.write(X, y, h_post, h_res), rtol=2e-5,
        atol=2e-5)


def test_the_sinkhorn_maps_rows_and_columns_sum_to_one():
    """From the values a new model starts at (``_init_hyper``) 20
    float32 rounds leave every column sum within 2e-6 of 1 (the last
    half round divides by it, ``+ hc_eps``) and every row sum within
    1e-4, and the op says how far; not 1e-5: next to the identity the
    rounds close a row's gap by a part in a few hundred each, and
    float64 leaves the same 4.5e-5.  From generic logits they leave
    1e-3, and it says that."""
    spec = tfm.model_spec(**TINY)
    w = jax.jit(spec.init_fn)(jax.random.PRNGKey(0))["layers"]["lead"]["0"]
    x, y, _ = sublayer()
    _, _, maps, err = mixed(x, y, w, None)
    h_res = np.asarray(maps[..., 2 * N:2 * N + N * N]).reshape(2, 32, N, N)
    worst = max(np.abs(h_res.sum(-1) - 1).max(),
                np.abs(h_res.sum(-2) - 1).max())
    assert np.abs(h_res.sum(-2) - 1).max() <= 2e-6
    assert worst <= 1e-4 and float(err) == pytest.approx(worst, abs=1e-7)
    # H_pre = 1 / 4 a stream, H_post = 1, H_res the identity to 1e-3
    np.testing.assert_allclose(maps[..., N:2 * N], 1.0, atol=0.02)
    np.testing.assert_allclose(h_res, np.broadcast_to(np.eye(N), h_res.shape),
                               atol=2e-3)
    _, _, _, generic = mixed(x, y, sublayer()[2], None)
    assert 1e-6 < float(generic) < 5e-3
    # one round is not twenty
    assert float(mixed(x, y, sublayer()[2], None, iters=1)[3]) > float(
        generic)


@pytest.mark.parametrize("rows", [(2, 32), (2, 64)],
                         ids=["maps_jnp", "maps_kernel"])
def test_the_kernels_derivatives_are_jax_grads_of_the_jnp_form(rows):
    """A whole block, ``pre`` -> sublayer -> ``post``, differentiated:
    the kernels' against ``pre_ref`` / ``maps_of`` / ``post_ref``'s.  64
    tokens tile for the four stream calls alone (the maps fall back to
    ``maps_of``), 128 for the maps' two calls too."""
    assert (hm.maps_mode(rows[0] * rows[1], N, hm.LANES, True)[0]
            == ("interpret" if rows[1] == 64 else "off"))
    x, y, w = sublayer(seed=1, rows=rows)
    weigh = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)

    def scalar(interpret):
        def f(x, y, phi, alpha, bias):
            u, out, _, _ = mixed(x, y, dict(
                hc1_phi=phi, hc1_alpha=alpha, hc1_bias=bias), interpret,
                iters=3)
            return (out * weigh).sum() + (u * u).sum()

        return jax.jit(jax.grad(f, argnums=(0, 1, 2, 3, 4)))(
            x, y, w["hc1_phi"], w["hc1_alpha"], w["hc1_bias"])

    for got, want in zip(scalar(True), scalar(None)):
        assert apart(got, want) <= 1e-5


def test_narrow_reads_the_streams_through_one_map():
    x, _, w = sublayer(seed=2)
    w = {"hc_out_phi": w["hc1_phi"][:, :N], "hc_out_alpha": jnp.ones((1,)),
         "hc_out_bias": w["hc1_bias"][:N]}
    X = x.reshape(2, 32, N, -1)
    want = REF.read(X, REF.hyper_maps(X, w, "hc_out", 1e-6, 20, 1e-6)[0])
    for interpret in (None, True):
        got = hm.narrow(x, w["hc_out_phi"], w["hc_out_alpha"],
                        w["hc_out_bias"], N, 1e-6, interpret=interpret)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_shapes_the_kernels_refuse_take_the_reference(monkeypatch):
    monkeypatch.setenv("ELASTICDL_FLASH", "interpret")
    assert hm.hyper_mode(64, 4, 128)[:2] == ("interpret", 64)
    assert hm.hyper_mode(8192, 4, 3584)[1] == 128
    assert hm.hyper_mode(24, 4, 128)[0] == "off"       # rows % 16
    assert hm.hyper_mode(64, 4, 96)[0] == "off"        # no whole lanes
    assert hm.hyper_mode(64, 12, 128)[0] == "off"      # 168 logits
    monkeypatch.setenv("ELASTICDL_FLASH", "off")
    assert hm.hyper_mode(64, 4, 128) == ("off", None, "")


# -- the maps: one call each way ----------------------------------------------


@contextlib.contextmanager
def lines_of(logger):
    """The messages ``logger`` takes inside the block (it hands nothing
    up to the root, so ``caplog`` sees none)."""
    seen = []
    handler = logging.Handler()
    handler.emit = lambda record: seen.append(record.getMessage())
    logger.addHandler(handler)
    try:
        yield seen
    finally:
        logger.removeHandler(handler)


def logits(rows=(2, 64), seed=7, width=hm.LANES):
    """(z [B, T, width] float32 with the 24 logits of a few units, the
    first eight tokens' H_res logits beyond both clamps; bias [24]; a
    cotangent for the maps)."""
    rng = np.random.default_rng(seed)
    live = hm.columns(N)
    z = np.zeros((*rows, width), np.float32)
    z[..., :live] = 3.0 * rng.standard_normal((*rows, live))
    z[0, :8, 2 * N:live] = 40.0 * np.sign(z[0, :8, 2 * N:live])
    bias = 0.5 * rng.standard_normal(live)
    weigh = rng.standard_normal((*rows, hm.LANES))
    return tuple(jnp.asarray(a, jnp.float32) for a in (z, bias, weigh))


def maps_and_gradients(maps, z, bias, weigh):
    def loss(z, bias):
        out, err = maps(z, bias)
        return (out * weigh).sum() + err, (out, err)

    (_, (out, err)), (dz, dbias) = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True))(z, bias)
    return out, err, dz, dbias


@pytest.mark.parametrize("iters", [20, 1])
@pytest.mark.parametrize("form", ["interpreter", "jnp"])
def test_the_maps_op_is_maps_of_in_value_and_gradient(form, iters,
                                                      monkeypatch):
    """``hyper_maps`` against ``maps_of`` differentiated by JAX: the
    maps, the error they state, the logits' and the bias's gradients,
    each to 1e-6 of its largest value; logits beyond both clamps pass
    no gradient on, and ``err`` none at all."""
    monkeypatch.setenv("ELASTICDL_FLASH",
                       "interpret" if form == "interpreter" else "off")
    z, bias, weigh = logits()
    assert float(jnp.abs(z[..., 2 * N:] + 0).max()) > 30 > float(
        jnp.abs(z[0, 8:, 2 * N:hm.columns(N)]).min())
    got = maps_and_gradients(
        lambda z, b: hm.hyper_maps(z, b, N, iters, 1e-6), z, bias, weigh)
    want = maps_and_gradients(
        lambda z, b: hm.maps_of(z, b, N, iters, 1e-6), z, bias, weigh)
    for name, g, w in zip(("maps", "err", "dz", "dbias"), got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert float(jnp.abs(g - w).max()) <= 1e-6 * float(
            jnp.abs(w).max()), name
    maps, _, dz, _ = got
    # H_pre's columns and the tile's padding hold nothing, either way
    assert not float(jnp.abs(maps[..., :N]).max())
    assert not float(jnp.abs(maps[..., hm.columns(N):]).max())
    assert not float(jnp.abs(dz[..., :N]).max())
    assert not float(jnp.abs(dz[0, :8, 2 * N:hm.columns(N)]).max())
    assert float(jnp.abs(dz[0, 8:, N:hm.columns(N)]).min()) > 0


@pytest.mark.parametrize("rows,width", [((2, 50), hm.LANES), ((2, 64), 24)],
                         ids=["rows", "width"])
def test_maps_that_do_not_tile_fall_back_and_say_so(rows, width):
    """100 tokens are no whole 128-lane plane and 24 logits no tile of
    ``pre``'s: asked for the compiled kernel, the op runs ``maps_of``
    and an ``attention fallback:`` line says why."""
    from elasticdl_tpu.ops import flash_attention

    flash_attention._announce_once.cache_clear()
    z, bias, _ = logits(rows, width=width)
    assert hm.maps_mode(rows[0] * rows[1], N, width, False)[:2] == (
        "off", None)
    with lines_of(flash_attention.logger) as seen:
        got = hm.hyper_maps(z, bias, N, 20, 1e-6, interpret=False)
    want = hm.maps_of(z, bias, N, 20, 1e-6)
    np.testing.assert_array_equal(got[0], want[0])
    assert float(got[1]) == float(want[1])
    said, = [m for m in seen if m.startswith("attention fallback: hyper_mix")]
    assert "maps: rows % 128" in said and str(z.shape) in said


def test_the_maps_tiles_are_whole_planes():
    assert hm.maps_mode(8192, 4, 128, True) == ("interpret", 1024, "")
    assert hm.maps_mode(384, 4, 128, True)[1] == 128
    assert hm.maps_mode(8192, 12, 128, True)[0] == "off"   # 168 logits
    assert hm.maps_mode(8192, 4, 128, None) == ("off", None, "")


def test_the_hyper_maps_line_is_said_once_a_shape(monkeypatch):
    from elasticdl_tpu.ops import flash_attention

    monkeypatch.setenv("ELASTICDL_FLASH", "interpret")
    hm.announce_maps.cache_clear()
    with lines_of(flash_attention.logger) as seen:
        for rows in ((2, 64), (2, 64), (2, 32), (2, 64)):
            z, bias, _ = logits(rows)
            jax.eval_shape(lambda z, b: hm.hyper_maps(z, b, N, 20, 1e-6),
                           z, bias)
    assert [m for m in seen if m.startswith("hyper maps:")] == [
        "hyper maps: rows=128 n=4 iters=20 tile=128 form=interpreter",
        "hyper maps: rows=64 n=4 iters=20 tile=- form=jnp"]


def test_the_cells_rehearsal_step_names_the_two_calls_and_scans_no_round(
        monkeypatch):
    """The benchmark configuration's step at its rehearsal widths (two
    sequences of 64 tokens, three layers and the module's block: eight
    sublayers), lowered for the TPU as a chip would lower it: every
    sublayer's maps are ``hc_maps_fwd`` twice (the second forward) and
    ``hc_maps_bwd`` once, and no loop of the program's carries the
    rounds' ``[4, 4, rows]`` planes; with the kernels off the scan is
    there.  Each layer's inner write and read are the fused pair's one
    call each way; its first read and last write keep their own."""
    from benchmark.lib.runner import merge, params_string
    from elasticdl_tpu.models.spec import load_model_spec

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "xing4.0-29b-a4b.json")) as fh:
        config = json.load(fh)
    config = merge(config, config["rehearsal"])
    tokens = jax.ShapeDtypeStruct((2, config["seq_len"]), jnp.int32)

    def lowered(switch):
        monkeypatch.setenv("ELASTICDL_FLASH", switch)
        spec = load_model_spec(
            config["cli"]["model_zoo"],
            model_params=params_string(config["cli"]["model_params"]))
        assert spec.config.hyper_sinkhorn_iters == 20

        def step(params, tokens):
            def loss(p):
                out = spec.apply_fn(p, tokens, True)
                return (spec.loss_fn(out, tokens).mean(),
                        spec.step_stats_fn(out))

            return jax.value_and_grad(loss, has_aux=True)(params)

        params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
        return jax.jit(step).trace(params, tokens).lower(
            lowering_platforms=("tpu",)).as_text()

    planes = "tensor<4x4x%dxf32>" % (2 * config["seq_len"])
    text = lowered("tpu")
    # the jitted calls lower once a tracing context and are called from
    # each sublayer's place
    calls = collections.Counter(
        re.findall(r"call @_(\w+?_(?:fwd|bwd))(?:_\d+)?\(", text))
    assert (calls["maps_fwd"], calls["maps_bwd"]) == (16, 8)
    assert (calls["post_bwd"], calls["pre_bwd"]) == (4, 4 + 2)
    assert (calls["post_pre_fwd"], calls["post_pre_bwd"]) == (2 * 4, 4)
    assert {"hc_maps_fwd", "hc_maps_bwd", "hc_pre_fwd", "hc_post_bwd",
            "hc_post_pre_fwd", "hc_pre_post_bwd"} <= set(
        re.findall(r'kernel_name = "(\w+)"', text))
    assert planes not in text
    off = lowered("off")
    assert planes in off and "kernel_name" not in off


# -- a write and the read behind it: one call each way -----------------------


def pair(fused, x, y, w, then, weigh, interpret=True):
    """A sublayer's read and write and the next sublayer's read, the
    inner write and read ``fused`` or apart -> ((X', u', maps', err'),
    the gradients of a scalar of all four by x, y, the maps that write
    (a zero added to them), the sublayer's phi, alpha and bias (through
    those maps) and the next sublayer's)."""
    def f(x, y, shift, phi, alpha, bias, phi2, alpha2, bias2):
        u, through, maps, _ = hm.pre(x, phi, alpha, bias, N, 3, 1e-6, 1e-6,
                                     interpret=interpret)
        maps = maps + shift
        mix = (phi2, alpha2, bias2, N, 3, 1e-6, 1e-6)
        if fused:
            u2, x2, maps2, err = hm.post_pre(through, y, maps, *mix,
                                             rk.KEEP_STREAM,
                                             interpret=interpret)
        else:
            u2, x2, maps2, err = hm.pre(
                hm.post(through, y, maps, N, interpret=interpret), *mix,
                interpret=interpret)
        # the next sublayer's write reads X' again: two cotangents meet
        out = hm.post(x2, u2 + u2, maps2, N, interpret=interpret)
        scalar = ((out.astype(jnp.float32) * weigh).sum()
                  + jnp.square(u.astype(jnp.float32)).sum())
        return scalar, (x2, u2, maps2, err)

    args = (x, y, jnp.zeros(x.shape[:2] + (hm.LANES,), jnp.float32),
            w["hc1_phi"], w["hc1_alpha"], w["hc1_bias"],
            then["hc1_phi"], then["hc1_alpha"], then["hc1_bias"])
    (_, values), grads = jax.jit(jax.value_and_grad(
        f, argnums=tuple(range(9)), has_aux=True))(*args)
    return values, grads


@pytest.mark.parametrize("rows", [(1, 128), (1, 16)],
                         ids=["rows128", "rows16"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_fused_pair_is_the_two_calls_bit_for_bit(dtype, rows):
    """``post_pre`` against ``post`` then ``pre`` in interpret mode, at
    both ends of ``ROW_TILES``: X', u', the logits z' (the kernels'
    own, and the maps and error made of them) and every gradient are
    the same bits, because X' is rounded to the stream's dtype before
    the read's half takes it and its cotangent before the write's."""
    x, y, w = sublayer(seed=4, rows=rows)
    then = sublayer(seed=5, rows=rows)[2]
    x, y = x.astype(dtype), y.astype(dtype)
    weigh = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)
    tile = hm.hyper_mode(rows[1], N, 128, True)[1]
    assert tile == rows[1]
    assert hm.back_tile(rows[1], N, 128, x.dtype.itemsize, tile) == tile
    same = lambda got, want: (got.dtype == want.dtype and np.array_equal(
        np.asarray(got, np.float32), np.asarray(want, np.float32)))
    got, want = (pair(fused, x, y, w, then, weigh) for fused in (True, False))
    for name, g, v in zip(("X'", "u'", "maps'", "err'"), got[0], want[0]):
        assert same(g, v), name
    for name, g, v in zip(("x", "y", "maps", "phi", "alpha", "bias",
                           "next phi", "next alpha", "next bias"),
                          got[1], want[1]):
        assert float(jnp.abs(v.astype(jnp.float32)).max()) > 0, name
        assert same(g, v), name
    # the calls themselves: X', u' and z' of the same operands
    flat = lambda a: a.reshape(-1, a.shape[-1])
    maps = hm.pre(x, w["hc1_phi"], w["hc1_alpha"], w["hc1_bias"], N, 3, 1e-6,
                  1e-6, interpret=True)[2]
    phi, bias = hm._tiled(hm._folded(then["hc1_phi"], then["hc1_alpha"], N),
                          then["hc1_bias"], x.dtype)
    out, u, z = hm._post_pre(flat(x), flat(y), flat(maps), phi, bias, N,
                             1e-6, (tile, tile), True)
    apart_out = hm._post(flat(x), flat(y), flat(maps), N, tile, True)
    for g, v in zip((out, u, z), (apart_out, *hm._pre(
            apart_out, phi, bias, N, 1e-6, tile, True)[:2])):
        assert same(g, v)


def test_the_backwards_tile_steps_down_where_its_blocks_do_not_fit():
    """The cell's shape holds the backward's four wide blocks twice
    over at 128 rows inside the 64 MB the calls ask for (61 MB by the
    count; the TPU's compiler agrees: tests/test_flash_compile_tpu.py);
    a float32 stream of that width holds 64 rows, one twice as wide 32
    (``phi`` is twice as large too), from the shapes alone; where
    nothing fits, the smallest tile there is."""
    assert hm.back_tile(8192, 4, 3584, 2, 128) == 128
    assert hm.back_tile(8192, 4, 3584, 4, 128) == 64
    assert hm.back_tile(8192, 4, 7168, 2, 128) == 32
    assert hm.back_tile(8192, 8, 7168, 4, 128) == 16
    assert hm.back_tile(48, 4, 128, 2, 16) == 16


def layer_of(mode, monkeypatch, ffn=True):
    """(loss and gradients, the jaxpr's text) of one dense layer of the
    zoo's model on a stream four wide, with or without its FFN, under
    ``ELASTICDL_FLASH=mode``."""
    monkeypatch.setenv("ELASTICDL_FLASH", mode)
    drawn = case(dict(TINY, num_layers=1, mtp_modules=0, seq_len=64,
                      hyper_sinkhorn_iters=3))
    cfg = drawn.spec().config
    w = drawn.params["layers"]["lead"]["0"]
    x = sublayer(seed=6, rows=(2, 64))[0]
    kind = cfg.kinds[0]._replace(ffn=ffn)

    def loss(x, w):
        (out, err), _ = tfm._layer_body((x, jnp.float32(0.0)), w, cfg, None,
                                        jnp.arange(64), kind=kind)
        return jnp.square(out).mean() + err

    return (jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(x, w),
            str(jax.make_jaxpr(jax.grad(loss))(x, w)))


def calls_of(jaxpr):
    """How often a jaxpr's text calls each of the op's jitted halves
    (``_pre_fwd`` .. ``_post_pre_bwd``: a kernel is printed once however
    often its jitted caller is called)."""
    return collections.Counter(
        re.findall(r"name=_(\w+_(?:fwd|bwd))\b", jaxpr))


def test_a_layer_fuses_its_inner_write_and_read_and_matches_the_reference(
        monkeypatch):
    """One layer of two sublayers: the operator's write and the FFN's
    read are the pair's one call each way (the layer's first read and
    last write keep theirs), and loss and every gradient are the
    reference path's to the tolerance the whole model's test holds."""
    hm.announce_pair.cache_clear()
    from elasticdl_tpu.ops import flash_attention

    with lines_of(flash_attention.logger) as seen:
        (got, got_grads), text = layer_of("interpret", monkeypatch)
        (want, want_grads), plain = layer_of("off", monkeypatch)
    assert calls_of(text) == {
        "pre_fwd": 1, "post_pre_fwd": 1, "post_fwd": 1, "post_bwd": 1,
        "post_pre_bwd": 1, "pre_bwd": 1, "maps_fwd": 2, "maps_bwd": 2}
    assert {"hc_post_pre_fwd", "hc_pre_post_bwd"} <= set(
        re.findall(r"name=(hc_\w+)", text))
    assert not calls_of(plain) and "name=hc_p" not in plain
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    flat = jax.tree_util.tree_flatten_with_path(got_grads)[0]
    for (path, leaf), ref in zip(flat, jax.tree_util.tree_leaves(want_grads)):
        assert apart(leaf, ref) <= 1e-4, jax.tree_util.keystr(path)
    # one line a compiled shape and form, however often it is traced
    assert [m for m in seen if m.startswith("hyper pair:")] == [
        "hyper pair: tokens=128 streams=4 width=128 tile=128/128 interpreter",
        "hyper pair: tokens=128 streams=4 width=128 tile=- reference"]


def test_a_layer_of_one_sublayer_makes_no_fused_call(monkeypatch):
    """No FFN behind the operator: the write is the layer's last, and
    stays ``hc_post_fwd``'s."""
    hm.announce_pair.cache_clear()
    from elasticdl_tpu.ops import flash_attention

    with lines_of(flash_attention.logger) as seen:
        _, text = layer_of("interpret", monkeypatch, ffn=False)
    assert calls_of(text) == {
        "pre_fwd": 1, "post_fwd": 1, "post_bwd": 1, "pre_bwd": 1,
        "maps_fwd": 1, "maps_bwd": 1}
    assert "hc_post_pre_fwd" not in text and "hc_pre_post_bwd" not in text
    assert not [m for m in seen if m.startswith("hyper pair:")]


# -- the whole model -----------------------------------------------------------


@pytest.mark.parametrize("mode", ["off", "interpret-remat"])
def test_the_model_matches_the_reference(mode):
    """Loss (main + 0.1 module) and every gradient leaf of a dense layer,
    an expert layer and the module's block on a stream four wide: 1e-5
    of the loss, 1e-4 of each leaf's norm.  In interpret mode the
    mixing's kernels run (attention's widths take its reference).
    Three Sinkhorn rounds, not twenty: each is unrolled into the
    program six times over, and the CPU compiles them one by one."""
    kernels, _, remat = mode.partition("-")
    drawn = case(dict(TINY, num_layers=2, hyper_sinkhorn_iters=3))
    _, still, _, _ = rc.check(drawn, kernels, 1e-5, 1e-4, remat=bool(remat))
    assert still and all("expert_bias" in name for name in still)
    want = rc.wanted(drawn)[0][0]
    # and the reference tells the mechanisms apart: one Sinkhorn round
    # for twenty; no YaRN; the query latent's norm left out
    for other in (dict(iters=1), dict(yarn=(1.0, 16.0, 32.0, 1.0))):
        moved = jax.jit(drawn.reference(**other))(drawn.params)[0]
        assert abs(float(moved) - float(want)) > 1e-4 * abs(float(want))


def test_a_new_model_starts_as_the_plain_residual_on_equal_streams():
    """At the initial maps the four streams stay (nearly) equal and the
    model is ``x + F(norm(x))``: its loss is a one-wide model's with the
    same weights, to the 1e-3 the maps start off the identity by."""
    wide = tfm.model_spec(**dict(TINY, mtp_modules=0))
    plain = tfm.model_spec(**dict(TINY, mtp_modules=0, hyper_streams=0))
    params = jax.jit(wide.init_fn)(jax.random.PRNGKey(5))
    tokens = case(TINY).tokens
    strip = lambda tree: {k: strip(v) if isinstance(v, dict) else v
                          for k, v in tree.items() if not k.startswith("hc")}
    got = jax.jit(product_loss(wide, tokens))(params)
    want = jax.jit(product_loss(plain, tokens))(strip(params))
    assert float(got) == pytest.approx(float(want), rel=5e-3)
    assert float(got) != float(want)


def test_the_step_statistics_carry_the_sinkhorn_error_and_the_modules_loss():
    spec, params, tokens = case(TINY).parts()
    out = spec.apply_fn(params, tokens, True)
    loss = spec.loss_fn(out, tokens)
    stats = spec.step_stats_fn(out)
    assert set(stats) == {"hc_err", "mtp_loss", "moe_load", "moe_moved",
                          "moe_spilled"}
    assert 0 < float(stats["hc_err"]) < 1e-2
    main = tfm.head_loss(params, out["hidden"], tokens, spec.config)
    np.testing.assert_allclose(
        loss, main + 0.1 * out["mtp_loss"], rtol=1e-6)
    # two expert layers and the module's block
    assert stats["moe_load"].shape[0] == 3


def test_the_loss_line_says_both_fields_and_nothing_without_them():
    from elasticdl_tpu.worker import worker

    assert worker._loss_fields({"mtp_loss": jnp.float32(5.5),
                                "hc_err": jnp.float32(3e-6)}) == (
        " mtp=5.500000 hc_err=3.000e-06")
    assert worker._loss_fields({"moe_load": 1}) == ""
    assert worker._loss_fields(()) == worker._loss_fields(None) == ""


# -- YaRN and the query latent ------------------------------------------------


def test_yarn_at_factor_one_is_rope_to_the_bit_and_at_64_the_references():
    positions = jnp.arange(48)
    plain = tfm._rope_tables(64, positions, 1e4)
    one = tfm._rope_tables(64, positions, 1e4, "1,4096,32,1")
    assert all(np.array_equal(a, b) for a, b in zip(plain, one))
    cos, sin = tfm._rope_tables(64, positions, 1e4, "64,4096,32,1")
    freqs = REF.yarn_frequencies(64, 1e4, (64.0, 4096.0, 32.0, 1.0))
    angles = np.arange(48, dtype=np.float32)[:, None] * np.asarray(freqs)
    np.testing.assert_allclose(cos, np.cos(angles), atol=2e-6)
    np.testing.assert_allclose(sin, np.sin(angles), atol=2e-6)
    # the fastest frequencies are RoPE's own, the slowest RoPE's / 64
    base = 1e4 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(freqs[:8], base[:8], rtol=1e-6)
    np.testing.assert_allclose(freqs[-4:], base[-4:] / 64, rtol=1e-6)
    assert tfm.yarn_scale("64,4096,32,1") == pytest.approx(
        1.41589 ** 2, rel=1e-5)
    assert 192 ** -0.5 * tfm.yarn_scale("64,4096,32,1") == pytest.approx(
        0.144680, rel=1e-5)


@pytest.mark.parametrize("bad,match", [
    (dict(rope_scaling="64,4096"), "rope_scaling"),
    (dict(rope_scaling="64,4096,32,1", kv_latent_rank=0, qk_nope_dim=0,
          qk_rope_dim=0, v_head_dim=0, q_latent_rank=0), "rope_scaling"),
    (dict(q_latent_rank=8, kv_latent_rank=0, qk_nope_dim=0, qk_rope_dim=0,
          v_head_dim=0, rope_scaling=""), "q_latent_rank"),
    (dict(hyper_streams=1), "hyper_streams"),
    (dict(hyper_sinkhorn_iters=0), "hyper_streams"),
    (dict(moe_route_before_op=True), "hyper_streams"),
])
def test_a_new_option_without_what_it_needs_is_refused_where_it_is_built(
        bad, match):
    with pytest.raises(ValueError, match=match):
        tfm.model_spec(**dict(TINY, **bad))


def test_latent_attention_with_a_query_latent_matches_the_reference():
    """``q = RMSNorm(h W_qa) W_qb`` under YaRN and its softmax scale,
    the product's halves against the reference's neighbours."""
    cfg = case(TINY).spec().config
    w = case(TINY).params["layers"]["lead"]["0"]
    h = jnp.asarray(np.random.default_rng(4).standard_normal((2, 32, 128)),
                    jnp.float32)
    got = tfm._latent_mix(h, w, cfg, jnp.arange(32), cfg.kinds[0])
    s = shape_of(cfg)
    want = REF.attention(h, w, s["heads"], s["rank"], s["q_rank"],
                         s["d_nope"], s["d_rope"], s["d_v"], s["eps"],
                         s["theta"], s["yarn"], lambda a: a)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    assert {"w_q_a", "q_norm", "w_q_b"} <= set(w) and "wq" not in w
    assert w["w_q_a"].shape == (128, 24) and w["w_q_b"].shape == (24, 48)
    # the scale is YaRN's: without it the result is another
    bare = dataclasses.replace(cfg, rope_scaling="")
    assert apart(tfm._latent_mix(h, w, bare, jnp.arange(32), cfg.kinds[0]),
                 want) > 1e-2


# -- the shares ------------------------------------------------------------------


def test_four_head_shares_and_eight_expert_shares_add_up_to_the_uncut_layer():
    """The deployment's cut, on one layer: the four head shares' parts of
    the ``W_o`` product (each with its heads' columns of W_qb and W_kvb
    and rows of W_o; both latents' down-projections and norms whole on
    every share, so counted once) add up to the reference's attention
    with all 8 heads; the eight expert shares' routed parts and the
    shared expert, counted once, to its uncut expert layer."""
    whole = tfm.TransformerConfig(
        dim=64, num_heads=8, kv_latent_rank=32, q_latent_rank=24,
        qk_nope_dim=16, qk_rope_dim=8, v_head_dim=24,
        rope_scaling="64,16,32,1", ffn_dim=48, moe_experts=64, moe_top_k=4,
        moe_router="sigmoid_bias", moe_route_scale=2.0,
        moe_shared_experts=1, dtype="float32")
    rng = np.random.default_rng(8)
    draw = lambda *shape: jnp.asarray(
        rng.standard_normal(shape) * shape[-2] ** -0.5, jnp.float32)
    w = {"w_q_a": draw(64, 24), "q_norm": 1 + 0.1 * draw(1, 24)[0],
         "w_q_b": draw(24, 8 * 24), "w_kv_a": draw(64, 40),
         "kv_norm": 1 + 0.1 * draw(1, 32)[0], "w_kv_b": draw(32, 8 * 40),
         "wo": draw(8 * 24, 64),
         "w_router": draw(64, 64), "w_gate": draw(64, 64, 48),
         "w_up": draw(64, 64, 48), "w_down": draw(64, 48, 64),
         "ws_gate": draw(64, 48), "ws_up": draw(64, 48),
         "ws_down": draw(48, 64), "ln2": jnp.ones((64,), jnp.float32),
         "expert_bias": jnp.asarray(0.2 * rng.standard_normal(64),
                                    jnp.float32)}
    h = jnp.asarray(rng.standard_normal((2, 24, 64)), jnp.float32)
    identity = lambda a: a
    yarn = tfm.yarn_of(whole.rope_scaling)
    want = REF.attention(h, w, 8, 32, 24, 16, 8, 24, 1e-6, 1e4, yarn,
                         identity)
    held = dataclasses.replace(whole, num_heads=2, head_shares=4)
    parts = 0.0
    for share in range(4):
        heads = lambda a, width, axis: jnp.take(
            a.reshape(a.shape[:axis] + (8, width) + a.shape[axis + 1:]),
            jnp.arange(2 * share, 2 * share + 2), axis=axis)
        part = dict(
            w, w_q_b=heads(w["w_q_b"], 24, 1).reshape(24, -1),
            w_kv_b=heads(w["w_kv_b"], 40, 1).reshape(32, -1),
            wo=heads(w["wo"], 24, 0).reshape(-1, 64))
        parts = parts + tfm._latent_mix(h, part, held, jnp.arange(24),
                                        held.kinds[0])
    np.testing.assert_allclose(parts, want, rtol=2e-4, atol=2e-5)
    # the experts: as kanana-2-30b-a3b's, 8 shares of 8 of 64
    u = REF.rmsnorm(h, w["ln2"], whole.norm_eps)
    shared = REF.swiglu(u, w["ws_gate"], w["ws_up"], w["ws_down"], identity)
    want = REF.experts(u, w, 4, True, 2.0, 0)[0] + shared
    routed, rows = 0.0, 0.0
    for index in range(8):
        cfg = dataclasses.replace(whole, moe_experts_held=8,
                                  moe_share_index=index)
        part = dict(w, **{name: w[name][index * 8:(index + 1) * 8]
                          for name in ("w_gate", "w_up", "w_down")})
        out, _, _, load = tfm._ffn(h, part, cfg, None)
        routed = routed + (out - h - shared)
        rows += float(load[:8].sum())
    np.testing.assert_allclose(routed + shared, want, rtol=1e-4, atol=2e-5)
    assert rows == 2 * 24 * 4


# -- what does not run it says so by name ---------------------------------------


@pytest.mark.parametrize("feature,said", [
    ("hyper", "a residual stream 4 wide"),
    ("mtp", "multi-token prediction (mtp_modules=1")])
@pytest.mark.parametrize("what", ["prefill", "decode_step", "generate",
                                  "export_generate", "forward_pipelined",
                                  "mesh"])
def test_what_cannot_run_a_wide_stream_or_a_module_refuses_it_by_name(
        what, feature, said, tmp_path):
    plain = dict(vocab_size=64, dim=128, num_heads=2, num_layers=2,
                 seq_len=16, dtype="float32")
    plain.update(dict(hyper_streams=4) if feature == "hyper"
                 else dict(mtp_modules=1))
    spec = tfm.model_spec(**plain)
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
        "export_generate": lambda: tfm.export_generate(
            str(tmp_path), params, cfg, 2, 4),
        "forward_pipelined": lambda: tfm.forward_pipelined(
            params, prompt, cfg, mesh, 2),
        "mesh": lambda: tfm.param_specs(cfg),
    }
    with pytest.raises(NotImplementedError) as refusal:
        calls[what]()
    assert said in str(refusal.value)
    assert what.split("_")[0] in str(refusal.value) or what == "mesh"


# -- remat -------------------------------------------------------------------


def test_remat_counts_a_stream_four_wide_and_the_modules_block():
    spec = tfm.model_spec(**dict(TINY, remat=True))
    cfg = spec.config
    params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    plain = dataclasses.replace(cfg, hyper_streams=0, mtp_modules=0)
    rows = 64
    entries = {label: (names, per_layer, layers) for label, names,
               per_layer, layers in rk._entries(cfg, rows)}
    was = {label: (names, per_layer, layers) for label, names, per_layer,
           layers in rk._entries(plain, rows)}
    # the stream's carry four wide, a block more of everything
    assert entries["stream"][1:] == (4 * was["stream"][1], 4)
    assert entries["flash"][2] == was["flash"][2] + 1 == 4
    assert entries["route"][2] == was["route"][2] + 1 == 3
    # the latent's entry holds the query latent too
    assert entries["latent"][0] == (rk.KEEP_LATENT, rk.KEEP_Q_LATENT)
    assert entries["latent"][1] == rows * (32 + 8 + 24) * 4
    # what the mixing may keep: a sublayer's read, two a layer
    assert entries["hc_read"] == (
        (hm.KEEP_U, hm.KEEP_Z), 2 * rows * (128 * 4 + 128 * 4), 4)
    assert "hc_read" not in was
    # the step's need: five carries four wide, two logits buffers, four
    # streams in a layer's backward
    stripped = {k: v for k, v in params.items() if k != "mtp"}
    need = rk.step_bytes(cfg, params, rows)
    less = rk.step_bytes(dataclasses.replace(cfg, mtp_modules=0), stripped,
                         rows)
    stream = rows * 4 * 128 * 4
    assert need - less >= stream
    assert rk.step_bytes(dataclasses.replace(cfg, mtp_modules=0), stripped,
                         2 * rows) > less + 8 * stream
