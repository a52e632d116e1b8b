"""``remat=True`` keeps, by name, the residuals that fit
(models/remat_keep.py): the kept values change no gradient, the flash
forward leaves the backward when its two results are kept, the choice
follows the stated room per shard, what the step needs shrinks by the
kept products of a layer's own, the estimate is held to the eleven
cells' measured peaks, and a refused compile falls back to nothing
kept."""

import dataclasses
import functools
import json
import logging
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from elasticdl_tpu.models import remat_keep as rk, transformer as tfm
from elasticdl_tpu.ops import gated_delta as gd
from elasticdl_tpu.ops import (flash_attention as fa, moe_dispatch as md,
                               short_conv as sc, ssd)
from elasticdl_tpu.ops.batch_shard import DeviceRoom, batch_axis
from elasticdl_tpu.worker import collective_trainer as ct

GB = 10 ** 9
# bytes_limit of a v5e chip (15.75 GiB), as its backend states it
V5E_LIMIT = 16911433728
ROWS = 2 * 128


def _cfg(moe, **kw):
    return tfm.TransformerConfig(
        vocab_size=96, dim=128, num_heads=2, num_layers=2, max_seq_len=128,
        dtype="float32", ffn_dim=128, remat=True, moe_experts=4 * moe,
        moe_top_k=2, qk_norm=bool(moe), tied_embeddings=not moe, **kw)


def _problem(moe, **kw):
    cfg = _cfg(moe, **kw)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 96)

    def loss(params, room):
        with batch_axis(None, "data", room):
            hidden, aux = tfm.forward_hidden(params, tokens, cfg)
            return (tfm.head_loss(params, hidden, tokens, cfg).mean()
                    + 0.01 * aux)

    return cfg, params, loss


def _room_for(cfg, params, entries):
    """A room whose budget is exactly the first ``entries`` of the table
    (the step's need is the need with those kept)."""
    first = rk.table(cfg, ROWS)[:entries]
    kept = sum(b for _, _, b in first)
    need = rk.step_bytes(cfg, params, ROWS, [label for label, _, _ in first])
    return DeviceRoom(GB, int(need + kept * cfg.num_layers
                              + rk.RESERVE * GB))


def _pallas_calls(jaxpr, found=None):
    """The name each ``pallas_call`` of a jaxpr carries, sub-jaxprs
    (scan, remat, custom_vjp, shard_map) included."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(str(eqn.params["name"]))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _pallas_calls(sub, found)
    return found


DENSE = ["flash", "qkv", "stream", "ffn_gate", "ffn_up"]
MOE = ["flash", "route", "qkv", "stream", "moe_out", "moe_gate", "moe_up",
       "moe_rows"]


def test_the_table_is_ordered_and_sized_from_shapes():
    cfg = _cfg(0)
    assert [label for label, _, _ in rk.table(cfg, ROWS)] == DENSE
    assert [label for label, _, _ in rk.table(_cfg(1), ROWS)] == MOE
    sizes = dict((label, b) for label, _, b in rk.table(cfg, ROWS))
    assert sizes["flash"] == ROWS * 2 * (64 * 4 + 4)   # out, and lse f32
    assert sizes["qkv"] == 3 * ROWS * 128 * 4
    assert sizes["ffn_gate"] == ROWS * 128 * 4
    # grouped-query attention keeps G heads of k and v, not H
    gqa = dataclasses.replace(cfg, num_kv_heads=1)
    assert dict((l, b) for l, _, b in rk.table(gqa, ROWS))["qkv"] == (
        ROWS * (2 + 1 + 1) * 64 * 4)
    # at OLMo-1B's widths, 16,384 rows: the issue's table
    wide = tfm.TransformerConfig(vocab_size=50304, dim=2048, num_heads=16,
                                 num_layers=7)
    sizes = dict((l, b) for l, _, b in rk.table(wide, 16384))
    assert sizes["stream"] == 67108864 and sizes["qkv"] == 3 * 67108864
    assert sizes["ffn_gate"] + sizes["ffn_up"] == 536870912


@functools.lru_cache(maxsize=None)
def _nothing_kept(mode, moe):
    """``_problem(moe)``'s gradients with no room stated, traced under
    the mode its caller has set: once for every prefix of the list."""
    _, params, loss = _problem(moe)
    return jax.jit(jax.grad(lambda p: loss(p, None)))(params)


@pytest.mark.parametrize("mode", ["interpret", "off"])
@pytest.mark.parametrize("moe,entries", [(0, n + 1) for n in range(5)]
                         + [(1, n + 1) for n in range(8)])
def test_gradients_equal_the_nothing_kept_ones(monkeypatch, mode, moe,
                                               entries):
    """Every prefix of the list: the kept values are the ones the second
    forward would have produced, so only float32 round-off differs."""
    monkeypatch.setenv("ELASTICDL_FLASH", mode)
    cfg, params, loss = _problem(moe)
    room = _room_for(cfg, params, entries)
    names = rk.choose(cfg, params, ROWS, room)[0]
    want = sum((n for _, n, _ in rk.table(cfg, ROWS)[:entries]), ())
    assert names == want
    base = _nothing_kept(mode, moe)
    kept = jax.jit(jax.grad(lambda p: loss(p, room)))(params)
    for a, b in zip(jax.tree_util.tree_leaves(kept),
                    jax.tree_util.tree_leaves(base)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)


def test_keeping_out_and_lse_takes_the_flash_forward_out_of_the_backward(
        monkeypatch):
    """The calls by the names they carry: with nothing kept the forward
    runs in the forward scan and again in the backward's, before the
    one backward call; with the two names kept, once.  (The embedding
    table's gradient is a call of its own since PR 53, outside the
    stack: ``ops/embed_rows.py``.)"""
    monkeypatch.setenv("ELASTICDL_FLASH", "interpret")
    cfg, params, loss = _problem(0)
    base = jax.make_jaxpr(jax.grad(lambda p: loss(p, None)))(params)
    assert sorted(_pallas_calls(base.jaxpr)) == [
        "embed_grad", "flash_bwd", "flash_fwd", "flash_fwd"]
    room = _room_for(cfg, params, 1)
    kept = jax.make_jaxpr(jax.grad(lambda p: loss(p, room)))(params)
    assert sorted(_pallas_calls(kept.jaxpr)) == [
        "embed_grad", "flash_bwd", "flash_fwd"]


def test_every_moe_name_kept_leaves_the_backward_its_own_calls(monkeypatch):
    """15 kernel calls of the stack's with nothing kept (flash 1 + 3
    grouped matmuls, twice, and flash's 1 + the matmuls' 6 backward
    calls); 11 with every name kept; and the embedding table's gradient,
    one call either way (PR 53)."""
    monkeypatch.setenv("ELASTICDL_FLASH", "interpret")
    cfg, params, loss = _problem(1)
    base = jax.make_jaxpr(jax.grad(lambda p: loss(p, None)))(params)
    room = _room_for(cfg, params, len(MOE))
    kept = jax.make_jaxpr(jax.grad(lambda p: loss(p, room)))(params)
    assert len(_pallas_calls(base.jaxpr)) == 15 + 1
    assert len(_pallas_calls(kept.jaxpr)) == 11 + 1


def test_no_room_stated_is_the_program_without_the_names(monkeypatch):
    """With no ``DeviceRoom`` the layer is checkpointed with no policy,
    and a ``checkpoint_name`` lowers to nothing: the step is, letter for
    letter, the program of a tree in which no value is named (but for
    the running numbers JAX gives the functions it emits)."""
    cfg, params, loss = _problem(1)

    def lowered():
        text = jax.jit(jax.grad(lambda p: loss(p, None))).lower(
            params).as_text()
        return re.sub(r"@(\w+?)_\d+\b", r"@\1", text)

    jaxpr = str(jax.make_jaxpr(jax.grad(lambda p: loss(p, None)))(params))
    assert "policy=None" in jaxpr and "save_only" not in jaxpr
    named = lowered()
    for module in (tfm, fa, md):
        monkeypatch.setattr(module, "checkpoint_name", lambda x, name: x)
    assert lowered() == named


def test_budget_zero_keeps_nothing_and_huge_keeps_everything():
    for moe in (0, 1):
        cfg, params, _ = _problem(moe)
        need = rk.step_bytes(cfg, params, ROWS)
        spent = int(need + rk.RESERVE * GB)
        names, kept, budget, peak = rk.choose(
            cfg, params, ROWS, DeviceRoom(GB, spent))
        assert (names, kept, budget) == ((), 0, 0)
        assert peak == GB - spent + need
        # a refused room (free == 0) and one already overdrawn
        assert rk.choose(cfg, params, ROWS, DeviceRoom(GB, 0))[0] == ()
        assert rk.choose(cfg, params, ROWS, DeviceRoom(GB, -5))[0] == ()
        names, kept, _, _ = rk.choose(
            cfg, params, ROWS, DeviceRoom(100 * GB, 99 * GB))
        table = rk.table(cfg, ROWS)
        assert names == sum((n for _, n, _ in table), ())
        assert kept == cfg.num_layers * sum(b for _, _, b in table)


def test_an_entry_that_does_not_fit_is_passed_over_not_the_rest():
    cfg, params, _ = _problem(0)
    sizes = [b * cfg.num_layers for _, _, b in rk.table(cfg, ROWS)]
    need = rk.step_bytes(cfg, params, ROWS)
    # room for flash and the stream, not for q, k, v between them
    budget = sizes[0] + sizes[2] + 8
    assert budget < sizes[0] + sizes[1]
    names = rk.choose(cfg, params, ROWS, DeviceRoom(
        GB, int(need + rk.RESERVE * GB + budget)))[0]
    assert names == rk.ATTN_NAMES + (rk.KEEP_STREAM,)


ATTENTION = rk.ATTN_NAMES + (rk.KEEP_Q, rk.KEEP_K, rk.KEEP_V,
                             rk.KEEP_STREAM)
ROUTED = rk.ATTN_NAMES + (rk.KEEP_ROUTE, md.KEEP_SORT) + ATTENTION[2:]
SHARED = ["flash", "route", "qkv", "stream"]
EXPERTS = ["moe_out", "moe_gate", "moe_up", "moe_rows"]
# cell -> (configuration, rows a step, chips, a ``trainer.peak_hbm_gb``
# of the ledger or of the builder's chip runs, the entries kept when it
# was measured: none in PR 28's two, the lists this tree chooses in the
# others; the names this tree keeps)
CELLS = {
    "olmo1b.seq2048": ("olmo1b", 8, 1, 12.035, [], ATTENTION),
    "olmo1b.seq2048-dp4": ("olmo1b", 32, 4, 11.992, [], ATTENTION),
    # one expert layer, every expert held: every entry of its table
    # (ledger, PR 58: 12.132 GB)
    "olmoe1b7b.seq4096": (
        "olmoe1b7b", 4, 1, 12.132, SHARED + EXPERTS,
        ROUTED + (md.KEEP_OUT, md.KEEP_GATE, md.KEEP_UP, md.KEEP_ROWS)),
    # every entry of its table since PR 60: the convolutions' result and
    # the sorted rows beside PR 58's ten, 6.62 GB (my chip runs, PR 60,
    # ``g1``: 15.148 GB; 15.189 with 5.55 GB kept on the ledger's PR 58
    # line)
    "lfm2-24b-a2b.seq8192": (
        "lfm2-24b-a2b", 4, 1, 15.148,
        SHARED + ["ffn_gate", "ffn_up", "conv_in", "moe_out", "moe_gate",
                  "moe_up", "conv_out", "moe_rows"],
        ROUTED + (rk.KEEP_GATE, rk.KEEP_UP, sc.KEEP_IN, md.KEEP_GATE,
                  md.KEEP_UP, sc.KEEP_OUT, md.KEEP_OUT, md.KEEP_ROWS)),
    # every entry of its table (ledger, PR 58: 15.126 GB), a share's
    # down product last since PR 60 (``_entries``: the same names)
    "smallthinker-21b-a3b.seq16384": (
        "smallthinker-21b-a3b", 1, 1, 15.126, SHARED + EXPERTS,
        ROUTED + (md.KEEP_GATE, md.KEEP_UP, md.KEEP_ROWS, md.KEEP_OUT)),
    # latent attention: the latent and q for q, k, v; the shared
    # expert's gate fits, its up product does not, the routed gate does
    # (my chip runs, PR 37: 16.005 GB on six seeds; PR 38, the
    # projections head-major: 16.002-16.004)
    "kanana-2-30b-a3b.seq16384": (
        "kanana-2-30b-a3b", 1, 1, 16.005,
        ["flash", "route", "latent", "q", "stream", "ffn_gate", "ffn_up",
         "shared_gate", "moe_gate"],
        rk.ATTN_NAMES + (rk.KEEP_ROUTE, md.KEEP_SORT, rk.KEEP_LATENT,
                         rk.KEEP_Q, rk.KEEP_STREAM, rk.KEEP_GATE,
                         rk.KEEP_UP, rk.KEEP_SHARED_GATE, md.KEEP_GATE)),
    # the gate's projection behind q, k, v; both of the shared expert's
    # products, the routed gate and, since PR 60, the routed up product
    # and the sorted rows, 4.31 GB, which leave the routed down product
    # (0.54 GB, a share's least worthy byte at f = e / 2) no room (my
    # chip runs, PR 60, ``h1``: 15.239 GB; 14.920 with 3.51 GB kept on
    # the ledger's PR 58 line, where the count of PR 60 read 0.15 under)
    "trinity-mini.seq16384": (
        "trinity-mini", 1, 1, 15.239,
        ["flash", "route", "qkv", "gate", "stream", "ffn_gate", "ffn_up",
         "shared_gate", "shared_up", "moe_gate", "moe_up", "moe_rows"],
        ROUTED[:7] + (rk.KEEP_ATTN_GATE, rk.KEEP_STREAM, rk.KEEP_GATE,
                      rk.KEEP_UP, rk.KEEP_SHARED_GATE, rk.KEEP_SHARED_UP,
                      md.KEEP_GATE, md.KEEP_UP, md.KEEP_ROWS)),
    # three gated-delta layers and a full one, no experts, one unrolled
    # period: the flash residuals, q, k, v, the stream, the decays, the
    # output gate's projection, the four MLPs' gate and up products and
    # the delta layers' projection of q, k, v (4.50 GB); the scan's
    # output, states and inverses (1.51 GB) and the convolved projection
    # do not fit (my chip runs, PR 50: 15.706 GB traced and untraced;
    # 12.838 with the first five entries, 1.04 GB, which is all that
    # fitted while the stack's 2.68 GB of gradients were counted whole)
    "olmo-hybrid-7b.seq16384": (
        "olmo-hybrid-7b", 1, 1, 15.706,
        ["flash", "qkv", "stream", "delta_decay", "delta_gate", "ffn_gate",
         "ffn_up", "delta_in"],
        rk.ATTN_NAMES + (rk.KEEP_Q, rk.KEEP_K, rk.KEEP_V, rk.KEEP_STREAM,
                         rk.KEEP_DELTA_DECAY, rk.KEEP_DELTA_GATE,
                         rk.KEEP_GATE, rk.KEEP_UP, rk.KEEP_DELTA_IN)),
    # a gated softmax layer and three KDA layers, every FFN an expert
    # layer under a 1/40 share: the flash residuals, the route, q, k, v,
    # the gate's projection, the stream, the KDA layers' two [rows, 128]
    # low-rank products and, since PR 60, the scans' outputs, states and
    # inverses (0.55 GB: ``delta scan: .. states=kept``), both of the
    # shared expert's products and the routed up product beside the
    # routed gate (1.79 GB); the next entry, the KDA layers' [rows,
    # 3072] projection, is 0.30 GB for the 0.04 left (my chip runs, PR
    # 60, ``g1``, ``f2``: 15.557 GB; 15.540 without the up product in
    # ``a1``'s fourteen runs; 15.081 with 1.00 GB kept on the ledger's
    # PR 58 line)
    "solar-open2-250b.seq16384": (
        "solar-open2-250b", 1, 1, 15.557,
        ["flash", "route", "qkv", "gate", "stream", "delta_rank", "delta",
         "shared_gate", "shared_up", "moe_gate", "moe_up"],
        ROUTED[:7] + (rk.KEEP_ATTN_GATE, rk.KEEP_STREAM, rk.KEEP_DELTA_RANK,
                      gd.KEEP_OUT, gd.KEEP_STATES, gd.KEEP_INVERSE,
                      rk.KEEP_SHARED_GATE, rk.KEEP_SHARED_UP, md.KEEP_GATE,
                      md.KEEP_UP)),
    # a stream four wide through a dense layer, four expert layers and a
    # multi-token-prediction module's block, all unrolled, two sequences
    # of 4,096: beside a state of 12.92 GB, six layer inputs of 235 MB,
    # two logits buffers and the module's two normed operands the head
    # of the list fits since PR 60, the flash residuals, the route, both
    # latents, q and the dense layer's gate product (0.60 GB; its up
    # product is 0.15 GB for the 0.05 left), and the budget is not
    # negative (my chip runs, PR 60, ``f2``: 15.752 GB; 15.634 with the
    # up product too in ``b1``; 15.431 with nothing kept on the ledger's
    # PR 58 line, where the estimate read 16.64)
    "xing4.0-29b-a4b.seq4096": (
        "xing4.0-29b-a4b", 2, 1, 15.752,
        ["flash", "route", "latent", "q", "ffn_gate"],
        rk.ATTN_NAMES + (rk.KEEP_ROUTE, md.KEEP_SORT, rk.KEEP_LATENT,
                         rk.KEEP_Q_LATENT, rk.KEEP_Q, rk.KEEP_GATE)),
    # six KDA layers with full projections under the bounded gate, a
    # head-gated latent layer and the module's latent block, all
    # unrolled, a 1/64 share: a state of 10.47 GB leaves room for every
    # entry but the KDA layers' convolved projection since PR 60 (4.75
    # GB kept: the layers' [rows, 3072] projection and the sorted rows
    # beside PR 58's list; my chip run, PR 60, ``g1``: 15.438 GB; 15.198
    # with 4.00 GB kept on the ledger's PR 58 line)
    "ling-3.0-flash.seq16384": (
        "ling-3.0-flash", 1, 1, 15.438,
        ["flash", "route", "latent", "q", "gate", "stream", "delta",
         "delta_gate", "ffn_gate", "ffn_up", "shared_gate", "shared_up",
         "delta_in", "moe_out", "delta_decay", "moe_gate", "moe_up",
         "moe_rows", "kv"],
        rk.ATTN_NAMES + (rk.KEEP_ROUTE, md.KEEP_SORT, rk.KEEP_LATENT,
                         rk.KEEP_Q, rk.KEEP_ATTN_GATE, rk.KEEP_STREAM,
                         gd.KEEP_OUT, gd.KEEP_STATES, gd.KEEP_INVERSE,
                         rk.KEEP_DELTA_GATE, rk.KEEP_GATE, rk.KEEP_UP,
                         rk.KEEP_SHARED_GATE, rk.KEEP_SHARED_UP,
                         rk.KEEP_DELTA_IN, rk.KEEP_DELTA_DECAY,
                         md.KEEP_GATE, md.KEEP_UP, md.KEEP_ROWS,
                         md.KEEP_OUT, rk.KEEP_KV)),
    # nine layers of ONE sublayer, all unrolled: four Mamba-2 mixers,
    # four expert layers of two-matrix MLPs under a 1/16 share and a GQA
    # layer; a state of 10.67 GB leaves room for the flash residuals,
    # the route, q, k, v, the scans' decays, the gate z, the shared
    # expert's one product, the scans' outputs and states (1.61 GB), the
    # projection of x | B | C and the routed up and down products (4.25
    # GB kept: ``ssm scan: .. states=kept``); the convolved x | B | C
    # (0.81 GB) and the sorted rows do not fit (my chip runs, PR 61,
    # ``t3`` traced and ``six`` six untraced seeds: 15.666 GB each;
    # 15.625 on every run of PR 62, the grouped norm's float32 planes
    # round its ``[.., 8, 512]`` view gone: ``c1``, one traced and two
    # untraced seeds; 15.488 on every run of PR 67, the share's rows of
    # 2,688 x bfloat16 moved by the row kernel as 1,408 words and the
    # jnp moves' float32 [R, e] and [n, e] buffers gone, the same
    # fifteen names kept: ``a``, ``b``, one traced and eight untraced
    # seeds)
    "nemotron-3-nano-30b-a3b.seq16384": (
        "nemotron-3-nano-30b-a3b", 1, 1, 15.488,
        ["flash", "route", "qkv", "ssm_decay", "ssm_gate", "shared_up",
         "ssm", "ssm_in", "moe_up", "moe_out"],
        ROUTED[:7] + (rk.KEEP_SSM_DECAY, rk.KEEP_SSM_GATE,
                      rk.KEEP_SHARED_UP, ssd.KEEP_OUT, ssd.KEEP_STATES,
                      rk.KEEP_SSM_IN, md.KEEP_UP, md.KEEP_OUT)),
    # six dense layers run four times on one set of weights, scanned
    # inside a scan over the turns, one sequence of 8,192: a state of
    # 8.15 GB, 37 planes of 33.5 MB, the stacked gradient twice and one
    # call's logits leave room for the flash residuals, the three
    # earlier calls' logits (2.42 GB, gone before the stack's backward)
    # and q, k, v (5.65 GB kept; the head's place is the peak: the
    # stream's 0.81 GB more would pass the reserve there, and ran 11%
    # slower on the chip; my chip runs, PR 66, ``probe2``: 15.320 GB,
    # and 11.443 with nothing kept where the estimate reads 11.615)
    "ouro-2.6b.seq8192": (
        "ouro-2.6b", 1, 1, 15.320, ["flash", "logits", "qkv"],
        rk.ATTN_NAMES + (rk.KEEP_LOGITS, rk.KEEP_Q, rk.KEEP_K, rk.KEEP_V)),
}

# The cells whose unrolled stack has expert layers: their estimate, the
# dispatch's inventory beside a layer's worth of gradients, is held to
# -0.1 / +0.6 GB of the chip's peak at the list each runs (+0.26 to +0.53), the
# others' to -0.1 / +0.9 (before PR 60 four of the six read +0.86 to
# +1.21 over, the stack's 1.8-2.8 GB of gradients counted whole where
# the dispatch's temporaries stood, and one of them found a negative
# budget on a chip with 1.4 GB free).
UNROLLED_EXPERTS = {
    "lfm2-24b-a2b.seq8192", "smallthinker-21b-a3b.seq16384",
    "trinity-mini.seq16384", "solar-open2-250b.seq16384",
    "xing4.0-29b-a4b.seq4096", "ling-3.0-flash.seq16384",
    "nemotron-3-nano-30b-a3b.seq16384"}

# tokens a chip a step in each configuration's cells
ROWS_OF = {"olmo1b": 16384, "olmoe1b7b": 16384, "lfm2-24b-a2b": 32768,
           "smallthinker-21b-a3b": 16384, "kanana-2-30b-a3b": 16384,
           "trinity-mini": 16384, "olmo-hybrid-7b": 16384,
           "solar-open2-250b": 16384, "xing4.0-29b-a4b": 8192,
           "ling-3.0-flash": 16384, "nemotron-3-nano-30b-a3b": 16384,
           "ouro-2.6b": 8192}


def _cell(config, **override):
    """(cfg, shapes of the parameters, what the trainer would state it
    holds: 16 B a parameter, the sequence length) of a configuration of
    the benchmark, no arrays."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           config + ".json")) as fh:
        model_params = json.load(fh)["cli"]["model_params"]
    spec = tfm.model_spec(**dict(model_params, **override))
    params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    state = jax.eval_shape(spec.optimizer.init, params)
    held = 2 * ct._device_bytes(params) + ct._device_bytes(state)
    return spec.config, params, held, model_params["seq_len"]


def _estimate(cfg, params, held, rows, labels):
    """The peak ``remat_keep`` states with the entries ``labels`` kept:
    the trainer's state, the kept bytes and what the step needs beside
    them."""
    kept = sum(b * n for label, _, b, n in rk._entries(cfg, rows)
               if label in labels)
    return held + rk.step_bytes(cfg, params, rows, labels) + kept


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_estimate_is_held_to_the_cells_measured_peaks(cell):
    """The twelve cells at their real shapes, no arrays: what the trainer
    would state and what the model adds, with the entries kept that
    were kept when the chip measured, lands within -0.1 / +0.9 GB of
    that peak, and within -0.1 / +0.6 in the six cells whose unrolled
    stack has expert layers (+0.23 and +0.27 with nothing kept in the
    two oldest; +0.57 in the cell of one expert layer; +0.03 in the
    scanned latent cell; +0.31 in the dense hybrid cell; the six, which
    read +0.32 to +1.21 before PR 60, +0.26 to +0.53), picks the names the PR
    reports, and predicts
    a peak under the limit less the reserve, from a budget that is not
    negative."""
    config, batch, chips, measured, labels, names = CELLS[cell]
    cfg, params, held, seq_len = _cell(config)
    rows = batch * seq_len // chips
    assert rows == ROWS_OF[config]
    estimate = _estimate(cfg, params, held, rows, labels)
    over = 0.6 if cell in UNROLLED_EXPERTS else 0.9
    assert -0.1 < estimate / GB - measured < over
    room = DeviceRoom(V5E_LIMIT, V5E_LIMIT - held)
    got, kept, budget, peak = rk.choose(cfg, params, rows, room)
    assert got == names
    chosen = [label for label, entry, _ in rk.table(cfg, rows)
              if set(entry) <= set(got)]
    # the list the chip measured is the list this tree runs
    assert not labels or sorted(chosen) == sorted(labels)
    assert 0 <= kept <= budget
    assert peak == held + rk.step_bytes(cfg, params, rows, chosen) + kept
    assert peak <= (1 - rk.RESERVE) * V5E_LIMIT
    # today's chips state a limit 2 MiB under PR 29's: no choice of a
    # cell's is so close that they choose otherwise
    less = DeviceRoom(V5E_LIMIT - 2097664, V5E_LIMIT - 2097664 - held)
    assert rk.choose(cfg, params, rows, less)[0] == names


# ``kanana-2-30b-a3b``'s step alone on the chip with six kept lists in
# turn, the program whose projections write the kernels' planes
# themselves (my chip run, PR 38, call ``c3``): how many of
# ``KANANA_MORE`` are kept beyond flash, route, latent, q, stream,
# ffn_gate and ffn_up, and the chip's peak in GB.  Two more is the list
# ``choose`` takes.
KANANA_MORE = ("shared_gate", "moe_gate", "shared_up", "moe_up", "moe_out",
               "moe_rows")
KANANA_PEAKS = {0: 15.653, 2: 16.003, 3: 16.066, 4: 16.067, 5: 16.404,
                6: 16.921}


@pytest.mark.parametrize("more", sorted(KANANA_PEAKS))
def test_latent_attentions_term_describes_the_chip_over_six_lists(more):
    """The estimate with ``_latent_layer``'s term as it stands reads
    +0.03 .. +0.39 GB over the chip on every list, over and never
    under.  Without the term's ``flat_*`` widths (the token-major
    layouts the program no longer makes) it reads 0.6-1.0 GB UNDER on
    every one, and ``choose`` would take three names more, which the
    chip measured at 16.40 GB and 5 ms a step slower: the widths stay
    until the place of the peak is known (PERF.md section 7)."""
    cfg, params, held, _ = _cell("kanana-2-30b-a3b")
    labels = ("flash", "route", "latent", "q", "stream", "ffn_gate",
              "ffn_up") + KANANA_MORE[:more]
    estimate = _estimate(cfg, params, held, 16384, labels)
    assert 0 < estimate / GB - KANANA_PEAKS[more] < 0.5


def _expert_term(cfg, rows, *kept):
    """The largest of the expert kinds' ``rk._expert_layer`` terms with
    the entries ``kept`` kept."""
    sizes = {label: nbytes for label, _, nbytes in rk.table(cfg, rows)}
    return max(rk._expert_layer(cfg, rows, kept, kind, sizes)
               for kind in set(cfg.kinds) if not kind.dense)


def test_a_dense_layers_kept_products_leave_what_the_step_needs():
    """``lfm2-24b-a2b`` at its cell's rows: with nothing kept the
    leading dense layer's backward (gate, up, their product and a
    cotangent, 4 x 0.772 GB) is the larger place.  Kept, gate and up
    are read from the stack and not made again: the need falls by their
    bytes until the expert layers' term stands over the dense layer's
    (2.51 GB: the dispatch's inventory, four planes of the stream and
    the convolution's input and result), and no further; no entry that
    is not a product of that layer, nor one every layer makes, moves
    it."""
    cfg, params, _, _ = _cell("lfm2-24b-a2b")
    rows = 32768
    one = rows * cfg.dense_ffn_dim * 2
    need = lambda *kept: rk.step_bytes(cfg, params, rows, kept)
    experts = _expert_term(cfg, rows)
    assert one == 771751936 and 3 * one < experts < 4 * one
    assert need() - need("ffn_gate") == 4 * one - experts
    assert need("ffn_gate", "ffn_up") == need("ffn_gate")
    for label, _, _ in rk.table(cfg, rows):
        if label not in rk.DENSE_PRODUCTS + ("stream",):
            assert need(label) == need(), label
    # a share's kept products leave the expert layers' term where it was
    assert need("ffn_gate", "ffn_up", "moe_out") == need("ffn_gate",
                                                         "ffn_up")


def test_the_need_does_not_fall_below_the_next_kinds_term():
    """A dense layer narrow enough that an expert layer's backward is
    the larger place: its kept products move nothing, and under a share
    the experts' own kept products do not either (the further blocks'
    loop keeps nothing).  Where every expert is held the term is the
    experts' alone and a kept product leaves it: one array by its bytes,
    the three products by the rows and two [R, f] planes that the
    largest phase then lacks."""
    cfg, params, _, _ = _cell("lfm2-24b-a2b")
    cfg = dataclasses.replace(cfg, dense_ffn_dim=2048)
    rows = 32768
    need = lambda *kept: rk.step_bytes(cfg, params, rows, kept)
    assert rows * 4 * 2048 * 2 < _expert_term(cfg, rows)
    assert need() == need("ffn_gate", "ffn_up")
    assert need() == need("ffn_gate", "ffn_up", *rk.EXPERT_PRODUCTS)
    cfg, params, _, _ = _cell("olmoe1b7b")
    wide, narrow = 16384 * 8 * 2048 * 2, 16384 * 8 * 1024 * 2
    need = lambda *kept: rk.step_bytes(cfg, params, 16384, kept)
    assert need() - need("moe_gate") == narrow
    assert need() - need(*rk.EXPERT_PRODUCTS) == wide + 2 * narrow


@pytest.mark.parametrize("config,rows,width", [
    ("trinity-mini", 16384, 2048), ("trinity-mini", 16384, 1920),
    ("trinity-mini", 16384, 1984), ("olmoe1b7b", 16384, 2048)])
def test_the_dispatchs_inventory_from_shapes(config, rows, width):
    """``dispatch_phases`` at four shapes, hand-counted, no arrays.
    Under a share (16 of 128 experts, ``row_bound``'s 32,768 rows for
    16,384 tokens) whose rows the row kernel moves: the products' phase
    is the largest (the sorted rows, two [R, f] cotangents, ``d_xs``
    twice, three weight gradients), the float32 cotangent, the gates'
    gradients ([n, K] and, at the router, [n, X] float32) and the
    further blocks' accumulators stand through all of them, 1.15 GB,
    and no kept entry moves a phase.  The same share 1,920 wide, fifteen
    lane tiles that the kernel moves as 1,024 words since PR 67: the
    same phases at that width.  The same share 1,984 wide, rows that are
    no whole 128 lanes, so the jnp moves' (``moe dispatch: ..
    rows=reference``): the combine's pullback holds the
    float32 [R, e] rows the cotangent is gathered into where the kernel
    had its pack, the gather's the float32 copy of ``d_xs`` and the
    float32 [n, e] sum it is scattered into where the kernel had
    ``d_xs``'s pack, and the combine's phase is the largest.  With every
    expert held (64, 131,072 rows): no accumulators, a weight gradient
    leaves in its phase, and a kept array is out of the phases that
    follow its last reader."""
    cfg, _, _, _ = _cell(config, dim=width)
    assert cfg.dim == width
    bound = md.row_bound(rows * cfg.moe_top_k, cfg.experts_held[1],
                         cfg.moe_experts)
    wide, narrow = bound * cfg.dim * 2, bound * cfg.mlp_dim * 2
    tokens, g = rows * cfg.dim * 2, rows * cfg.dim * 4
    weight = cfg.experts_held[1] * cfg.dim * cfg.mlp_dim * 2
    phases, through = rk.dispatch_phases(cfg, rows)
    assert list(phases) == ["combine", "down", "gate", "products", "gather"]
    choices, router = 4 * rows * cfg.moe_top_k, 4 * rows * cfg.moe_experts
    labels = ("moe_rows", "moe_gate", "moe_up", "moe_out")
    if config == "trinity-mini":
        by_kernel = md.rows_by_kernel(rows, bound, width, cfg.dtype,
                                      cfg.moe_top_k, "tpu")
        assert by_kernel == (width % 128 == 0)
        assert (bound, through) == (
            32768, g + choices + router + tokens + 3 * weight + choices)
        assert phases["products"] == 3 * wide + 2 * narrow + 3 * weight
        assert rk.dispatch_phases(cfg, rows, labels) == (phases, through)
        if by_kernel:
            assert phases["combine"] == 2 * wide + 2 * narrow + g + wide
            assert phases["gather"] == 2 * wide + tokens + 3 * weight
            assert max(phases, key=phases.get) == "products"
            assert rk.dispatch_bytes(cfg, rows) == {
                2048: 1150287872, 1920: 1087373312}[width]
            return
        rows32 = bound * width * 4        # float32 [R, e]
        assert phases["combine"] == 2 * wide + 2 * narrow + rows32 + wide
        assert phases["gather"] == (wide + rows32 + g + tokens
                                    + 3 * weight)
        assert max(phases, key=phases.get) == "combine"
        assert rk.dispatch_bytes(cfg, rows) == phases["combine"] + through
        return
    assert (bound, through) == (rows * 8, g + choices + router)
    assert phases["combine"] == 2 * wide + 2 * narrow + 2 * wide
    assert phases["products"] == 3 * wide + 2 * narrow + 2 * weight
    kept = rk.dispatch_phases(cfg, rows, labels)[0]
    assert kept["combine"] == 2 * wide
    assert kept["down"] == 2 * narrow + weight
    assert kept["products"] == wide + 2 * weight
    assert rk.dispatch_bytes(cfg, rows, labels) == (
        2 * wide + g + choices + router)
    assert rk.dispatch_bytes(dataclasses.replace(
        cfg, moe_experts=0, moe_top_k=0), rows) == 0


def test_an_untied_embedding_is_counted_at_neither_place():
    """Its compute-dtype copy is read by the forward's first gather
    alone and its gradient is the last thing the backward makes: 6 B a
    weight that ``copies`` and the trainer counted; a tied one is the
    head's weight and stays."""
    for config, tied in (("olmoe1b7b", False), ("olmo1b", True)):
        cfg, params, _, _ = _cell(config)
        assert cfg.tied_embeddings == tied
        wider = jax.tree_util.tree_map(lambda a: a, params)
        wider["embed"] = jax.ShapeDtypeStruct(
            (2 * cfg.vocab_size, cfg.dim), params["embed"].dtype)
        more = (rk.step_bytes(cfg, wider, 16384)
                - rk.step_bytes(cfg, params, 16384))
        assert more == (2 * cfg.vocab_size * cfg.dim if tied else
                        -4 * cfg.vocab_size * cfg.dim)


@pytest.mark.parametrize("config,layers,standing", [
    ("olmo1b", 7, 7), ("olmoe1b7b", 1, 1), ("lfm2-24b-a2b", 5, 2),
    ("smallthinker-21b-a3b", 4, 2)])
def test_the_weight_copies_that_stand_at_once(config, layers, standing):
    """A scan of several turns has all its layers' compute-dtype copies
    at once (XLA hoists the cast out of the loop: ``olmo1b``'s seven);
    a stack XLA unrolls (one layer, or leading layers and one period)
    has the two largest layers': the one running and the one fetched
    ahead."""
    cfg, params, _, _ = _cell(config)
    assert cfg.num_layers == layers
    size = jnp.dtype(cfg.dtype).itemsize
    leaves = jax.tree_util.tree_leaves
    copy = lambda tree: sum(a.size * size for a in leaves(tree))
    stack = params["layers"]
    if "period" in stack:
        each = [copy(layer) for group in ("lead", "period", "tail")
                for layer in stack[group].values()]
    else:
        each = [copy(stack) // layers] * layers
    assert len(each) == layers
    want = sum(sorted(each)[-standing:])
    assert rk._weight_copies(stack, copy) == want
    # parameters already in the compute dtype have no copies
    none = lambda tree: 0
    assert rk._weight_copies(stack, none) == 0


# (bytes kept, budget, predicted peak) of the ``remat keep:`` line at the
# cell's shapes: ``olmo1b``'s the same since PR 35, ``olmoe1b7b``'s
# since PR 60
TODAY = {
    "olmo1b": (2356150272, 3800754581, 14621257732),
    "olmoe1b7b": (1953497344, 5318592917, 12700766468),
}


@pytest.mark.parametrize("config", sorted(TODAY))
def test_the_older_cells_keep_what_they_kept(config):
    """``olmo1b`` keeps no product of its layers' own and its peak is at
    the head: bytes, budget and predicted peak are the parent's to the
    byte (``ffn_gate``, 1.879 GB, must not fit: the chip would stand at
    16.35 GB).  ``olmoe1b7b`` kept every entry and keeps them: the same
    names and bytes; its predicted peak is 12.70 GB since PR 60 counts
    its one layer's dispatch from shapes (1.21 GB with every entry
    kept) and none of its one layer's gradients (13.27 while they were
    counted whole beside half of ``row_bound x (dim + 2 mlp_dim)``), and
    stays over the 12.132 GB the chip measured (ledger, PR 58) and over
    the TPU compiler's 12.56 for a described v5e."""
    cfg, params, held, _ = _cell(config)
    room = DeviceRoom(V5E_LIMIT, V5E_LIMIT - held)
    names, kept, budget, peak = rk.choose(cfg, params, 16384, room)
    assert (kept, budget, peak) == TODAY[config]
    if config == "olmo1b":
        assert rk.KEEP_GATE not in names
        return
    assert names == sum((n for _, n, _ in rk.table(cfg, 16384)), ())
    labels = [label for label, _, _ in rk.table(cfg, 16384)]
    assert rk.grads_standing(cfg, params, 16384, labels) == 0
    assert rk.dispatch_bytes(cfg, 16384, labels) == 1212678144
    assert 12.562 * GB < peak < (12.132 + 0.9) * GB


def test_the_latent_cell_keeps_what_it_kept_to_the_byte():
    """``kanana-2-30b-a3b`` after its projections went head-major (PR
    38): the eleven names, 3,246,917,632 bytes and a predicted peak of
    16,034,369,544 of the parent's ``remat keep:`` line, since the
    chip's peak did not move with the layouts (16.003 GB for 16.005)
    and a longer list buys no millisecond (734.1-734.7 ms a step over
    2.9-3.6 GB kept, 740-754 over 4.0-4.4)."""
    cfg, params, held, _ = _cell("kanana-2-30b-a3b")
    room = DeviceRoom(V5E_LIMIT, V5E_LIMIT - held)
    names, kept, budget, peak = rk.choose(cfg, params, 16384, room)
    assert names == CELLS["kanana-2-30b-a3b.seq16384"][5]
    assert (kept, peak) == (3246917632, 16034369544)
    assert kept <= budget < kept + 201326592      # shared_up does not fit


# configuration -> (bytes kept, budget, predicted peak) of ``choose``
# under a v5e's room at the cell's rows.  ``olmo1b``'s and
# ``kanana-2-30b-a3b``'s are PR 49's tree's (commit d5697ee) to the byte:
# a scan of several turns, which PR 50 and PR 60 leave alone.  The five
# others' are PR 60's: an unrolled stack with expert layers, whose need
# is the dispatch's inventory beside a layer's worth of gradients
PARENT = {
    "olmo1b": (2356150272, 3800754581, 14621257732),
    "olmoe1b7b": (1953497344, 5318592917, 12700766468),
    "lfm2-24b-a2b": (6620709888, 7085192593, 15601379336),
    "smallthinker-21b-a3b": (4055368704, 4736341393, 15384889352),
    "kanana-2-30b-a3b": (3246917632, 3278410129, 16034369544),
    "trinity-mini": (4311746560, 4798294417, 15579314184),
    "solar-open2-250b": (1787302912, 1827766577, 16025398376),
}
# the stacks whose gradients the estimate takes to stand whole
SCANNED = {"olmo1b", "kanana-2-30b-a3b"}


@pytest.mark.parametrize("cell", sorted(
    cell for cell in CELLS if CELLS[cell][0] in PARENT))
def test_the_other_cells_choose_what_the_parent_chose_to_the_byte(cell):
    """Names, bytes, budget and predicted peak of ``choose`` at the
    cell's shapes, pinned.  A scan of several turns (``olmo1b``, both
    cells; ``kanana-2-30b-a3b``'s period of expert layers behind its
    dense lead): PR 49's, so the step those cells trace is that
    program, and ``grads_standing`` is the stack's whole gradients
    whatever is kept.  An unrolled stack with expert layers: PR 60's,
    and what stands of its gradients is a layer's worth at most (none
    of ``olmoe1b7b``'s one layer's)."""
    config, batch, chips, _, _, names = CELLS[cell]
    cfg, params, held, seq_len = _cell(config)
    rows = batch * seq_len // chips
    room = DeviceRoom(V5E_LIMIT, V5E_LIMIT - held)
    got = rk.choose(cfg, params, rows, room)
    assert got == (names,) + PARENT[config]
    stack = ct._device_bytes(params["layers"])
    layers = params["layers"]
    each = [ct._device_bytes(layer) for group in ("lead", "period", "tail")
            for layer in layers.get(group, {}).values()]
    for kept in ((), [label for label, _, _ in rk.table(cfg, rows)]):
        standing = rk.grads_standing(cfg, params, rows, kept)
        if config in SCANNED:
            assert standing == stack
        elif kept or config == "olmoe1b7b":
            assert standing < max(each or [stack])
        else:
            assert standing == max(each)


@pytest.mark.parametrize("cell", [
    "lfm2-24b-a2b.seq8192", "ling-3.0-flash.seq16384",
    "smallthinker-21b-a3b.seq16384", "solar-open2-250b.seq16384",
    "trinity-mini.seq16384", "xing4.0-29b-a4b.seq4096"])
def test_a_shares_down_product_is_worth_what_its_shapes_say(cell):
    """Under a share the kept ``moe_out`` is the down product alone,
    [R, f] x [f, e]: the gate's and the up product's operations for
    e / f times their bytes, so it stands behind them in the table, and
    behind the sorted rows where f / e < 8 / 12 (every share cell but
    ``lfm2-24b-a2b``, 1,792 of 2,048).  ``choose`` is one walk of that
    table by one count: what it takes is what a walk by ``step_bytes``
    here takes, the dispatch's entries like the others.
    ``trinity-mini`` so keeps the up product and the sorted rows, which
    leave the down product (0.54 GB) no room; with the down product in
    the rows' place, the same bytes, XLA made the head's logits a second
    time and the chip ran the step slower than the parent (PERF.md
    section 6, PR 60)."""
    config, batch, chips, _, _, names = CELLS[cell]
    cfg, params, held, seq_len = _cell(config)
    rows = batch * seq_len // chips
    assert cfg.experts_held[1] != cfg.moe_experts
    order = [label for label, _, _ in rk.table(cfg, rows)]
    routed = [label for label in order if label in EXPERTS]
    behind_rows = 12 * cfg.mlp_dim / cfg.dim < 8
    assert behind_rows == (config != "lfm2-24b-a2b")
    assert routed == ["moe_gate", "moe_up"] + (
        ["moe_rows", "moe_out"] if behind_rows else ["moe_out", "moe_rows"])
    cap = (1 - rk.RESERVE) * V5E_LIMIT
    labels, kept = [], 0
    for label, _, per_layer, layers in rk._entries(cfg, rows):
        need = rk.step_bytes(cfg, params, rows, labels + [label])
        if held + need + kept + per_layer * layers <= cap:
            labels.append(label)
            kept += per_layer * layers
    room = DeviceRoom(V5E_LIMIT, V5E_LIMIT - held)
    got = rk.choose(cfg, params, rows, room)[0]
    assert got == names == sum(
        (entry for label, entry, _ in rk.table(cfg, rows)
         if label in labels), ())
    if cell == "trinity-mini.seq16384":
        assert labels[-3:] == ["moe_gate", "moe_up", "moe_rows"]


def test_every_expert_held_keeps_the_down_product_first():
    """Where every expert is held (``olmoe1b7b``) the kept ``moe_out``
    is the down product moved back to the tokens' order, a matmul and a
    gather: 12 f / e + 8 = 14 at its widths, the number the table had
    from the cell's trace, ahead of the gate's and the up product's 12,
    and the cell's list names the four in that order as it did."""
    cfg, params, held, _ = _cell("olmoe1b7b")
    assert cfg.experts_held[1] == cfg.moe_experts
    assert 12 * cfg.mlp_dim / cfg.dim + 8 == 14
    order = [label for label, _, _ in rk.table(cfg, 16384)]
    assert [label for label in order if label in EXPERTS] == [
        "moe_out", "moe_gate", "moe_up", "moe_rows"]
    room = DeviceRoom(V5E_LIMIT, V5E_LIMIT - held)
    names = rk.choose(cfg, params, 16384, room)[0]
    kept = [name for name in names if name in (
        md.KEEP_OUT, md.KEEP_GATE, md.KEEP_UP, md.KEEP_ROWS)]
    assert kept == [md.KEEP_OUT, md.KEEP_GATE, md.KEEP_UP, md.KEEP_ROWS]


def _stack(pattern, **kw):
    """(cfg, shapes of the parameters, the bytes of each layer's
    gradients in the stack's order) of a small float32 stack."""
    spec = tfm.model_spec(vocab_size=96, dim=128, num_heads=2, seq_len=128,
                          ffn_dim=256, dtype="float32", remat=True,
                          num_layers=len(pattern), layer_pattern=pattern,
                          **kw)
    params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    nbytes = ct._device_bytes
    layers = params["layers"]
    turns = tfm.stack_plan(spec.config).periods
    each = ([nbytes(layer) for layer in layers["lead"].values()]
            + [nbytes(layer) // turns
               for layer in layers["period"].values()] * turns
            + [nbytes(layer) for layer in layers["tail"].values()])
    assert sum(each) == nbytes(layers) and len(each) == len(pattern)
    return spec.config, params, each


def test_the_gradients_that_stand_at_the_layer_place_from_shapes():
    """``grads_standing``, no arrays.  A scan of several turns: whole,
    whatever is kept.  An unrolled group: one layer's worth, the
    largest (the layer whose update is in flight), less what a layer
    done gives back of the kept entries that every layer makes; nothing
    of what one kind alone makes.  A scanned period with a tail: the
    period's whole and the tail's one layer.  ``step_bytes`` falls by
    what is absent.  (A stack of expert layers:
    ``test_an_expert_stacks_gradients_stand_by_its_structure``.)"""
    rows = 2 * 128
    # cacaca: a period of two layers, three turns
    cfg, params, each = _stack("cacaca")
    labels = [label for label, _, _ in rk.table(cfg, rows)]
    for kept in ((), labels):
        assert rk.grads_standing(cfg, params, rows, kept) == sum(each)
    # caw: one turn, unrolled, every layer dense
    cfg, params, each = _stack("caw", window=64)
    assert len(set(each)) == 2 and tfm.stack_plan(cfg).periods == 1
    sizes = {label: (b, n) for label, _, b, n in rk._entries(cfg, rows)}
    assert sizes["stream"][1] == sizes["ffn_gate"][1] == 3
    assert sizes["qkv"][1] == 2 and sizes["conv_in"][1] == 1
    assert rk.grads_standing(cfg, params, rows) == max(each)
    assert rk.grads_standing(cfg, params, rows, ["qkv", "conv_in"]) == (
        max(each))
    back = sizes["stream"][0] + sizes["ffn_gate"][0]
    assert 0 < back < max(each)
    assert rk.grads_standing(cfg, params, rows, ["stream", "ffn_gate"]) == (
        max(each) - back)
    big = 64 * rows
    assert sizes["stream"][0] * 64 > max(each)
    assert rk.grads_standing(cfg, params, big, ["stream"]) == 0
    # the need falls by the gradients that do not stand, where a
    # layer's backward is the larger place
    absent = sum(each) - max(each)
    head = big * cfg.vocab_size * 4 * 2
    layer = big * 4 * cfg.mlp_dim * 4
    assert layer - absent > head - sum(each)
    assert (rk.step_bytes(cfg, params, big)
            - rk.step_bytes(cfg, params, big, ["stream"])) == max(each)
    # acaca: the period scanned twice, and a tail of one layer
    cfg, params, each = _stack("acaca")
    plan = tfm.stack_plan(cfg)
    assert (plan.periods, len(plan.tail)) == (2, 1)
    assert rk.grads_standing(cfg, params, rows) == sum(each)
    assert rk.grads_standing(cfg, params, big, ["stream"]) == sum(each[:4])


@pytest.mark.parametrize("pattern,standing", [
    ("acaca", "whole"), ("caw", "a layer's"), ("a", "none")])
def test_an_expert_stacks_gradients_stand_by_its_structure(pattern,
                                                           standing):
    """``grads_standing`` for a stack of expert layers, no arrays.  A
    period scanned twice: whole, its tail's with it, whatever is kept.
    The same kinds unrolled (one turn): one layer's worth, the largest,
    less what a layer done gives back of the kept entries that every
    layer makes (``moe_out``, not ``qkv``, which two of three make),
    down to none.  One unrolled layer alone: none."""
    rows = 2 * 128
    cfg, params, each = _stack(pattern, window=64 * ("w" in pattern),
                               moe_experts=4, moe_top_k=2)
    assert not any(kind.dense for kind in cfg.kinds)
    labels = [label for label, _, _ in rk.table(cfg, rows)]
    sizes = {label: (b, n) for label, _, b, n in rk._entries(cfg, rows)}
    standing_with = lambda kept, rows=rows: rk.grads_standing(
        cfg, params, rows, kept)
    if standing == "whole":
        assert tfm.stack_plan(cfg).periods == 2
        for kept in ((), labels):
            assert standing_with(kept, 16 * rows) == sum(each)
    elif standing == "a layer's":
        assert tfm.stack_plan(cfg).periods == 1
        assert sizes["moe_out"][1] == 3 and sizes["qkv"][1] == 2
        assert standing_with(()) == max(each)
        assert standing_with(["qkv", "moe_out"]) == (
            max(each) - sizes["moe_out"][0])
        assert standing_with(labels, 16 * rows) == 0
    else:
        assert len(each) == 1
        for kept in ((), labels):
            assert standing_with(kept) == 0


@pytest.mark.parametrize("pattern,standing", [
    ("aa", "whole"), ("aw", "a layer's"), ("aw", "none")])
def test_the_line_says_what_of_the_gradients_stands(pattern, standing):
    """``grads_standing=`` on the ``remat keep:`` line: the stack's whole
    gradients for a scan of two turns; for an unrolled dense stack one
    layer's while nothing is kept, and 0 once the kept entries a layer
    gives back are more than that."""
    rk.announce_keep.cache_clear()
    cfg, params, each = _stack(pattern, window=64 * ("w" in pattern))
    rows = 4 * 128 if standing == "none" else 128
    need = rk.step_bytes(cfg, params, rows)
    free = 99 * GB if standing == "none" else int(need + rk.RESERVE * GB)
    with batch_axis(None, "data", DeviceRoom(100 * GB, free)):
        lines = _lines(lambda: rk.names_for(
            cfg, params, (rows // 128, 128)))
    fields = _fields(lines[0])
    assert (fields["names"] == "-") == (standing != "none")
    assert int(fields["grads_standing"]) == {
        "whole": sum(each), "a layer's": max(each), "none": 0}[standing]
    assert int(fields["predicted_peak"]) == (
        100 * GB - free + int(fields["need"]) + int(fields["bytes"]))


def test_a_looped_stack_counts_a_layer_a_turn_and_says_its_turns():
    """``ut_steps`` = R: every entry a layer makes is made R times (the
    turns' scan stacks what the layers' scan keeps), the R - 1 earlier
    heads' logits are an entry, tried right behind the flash kernel's,
    that is gone where a layer's backward is the peak, the stacked
    weights' gradient stands twice there, and the ``remat keep:`` line
    ends ``turns=R``."""
    rk.announce_keep.cache_clear()
    build = lambda turns: tfm.model_spec(
        vocab_size=96, dim=128, num_heads=2, seq_len=128, ffn_dim=256,
        dtype="float32", remat=True, num_layers=2, tied_embeddings=False,
        ut_steps=turns)
    once, looped = build(1).config, build(3).config
    params = jax.eval_shape(build(3).init_fn, jax.random.PRNGKey(0))
    plain = {k: v for k, v in params.items() if "gate" not in k}
    rows = 256
    one = {label: (nbytes, layers)
           for label, _, nbytes, layers in rk._entries(once, rows)}
    three = {label: (nbytes, layers)
             for label, _, nbytes, layers in rk._entries(looped, rows)}
    assert set(three) - set(one) == {"logits"}
    assert list(three)[:3] == ["flash", "logits", "qkv"]
    assert three["logits"] == (rows * 96 * 4, 2)
    assert all(three[label] == (nbytes, 3 * layers)
               for label, (nbytes, layers) in one.items())
    stack = ct._device_bytes(params["layers"])
    assert rk.grads_standing(looped, params, rows) == 2 * stack
    assert rk.grads_standing(once, plain, rows) == stack
    need = lambda *kept: rk.step_bytes(looped, params, rows, kept)
    stream = rows * 128 * 4
    # a carry a layer a turn more, three [R, rows, dim] planes, the
    # gate's float32 [R - 1, rows, dim], and the stack's gradient once
    # more where a layer's backward is the peak
    assert need() - rk.step_bytes(once, plain, rows) == (
        (2 * 2 + 3 * 3) * stream + 2 * rows * 128 * 4 + stack)
    # that place is the peak here: kept logits leave it whole
    assert need() - need("logits") == 2 * three["logits"][0]
    with batch_axis(None, "data", DeviceRoom(100 * GB, 99 * GB)):
        line, = _lines(lambda: rk.names_for(looped, params, (2, 128)))
    fields = _fields(line)
    assert fields["turns"] == "3" and fields["layers"] == "2"
    assert fields["names"].split(",")[:3] == ["flash_out", "flash_lse",
                                              "head_logits"]
    assert int(fields["grads_standing"]) == 2 * stack
    with batch_axis(None, "data", DeviceRoom(100 * GB, 99 * GB)):
        said, = _lines(lambda: rk.names_for(once, plain, (2, 128)))
    assert "turns=" not in said


@pytest.mark.parametrize("experts", [0, 4])
def test_the_line_states_the_dispatchs_inventory(experts):
    """``dispatch=`` on the ``remat keep:`` line: ``dispatch_bytes`` with
    the chosen list kept, 0 for a model without experts, so that a
    run's log shows which count chose its list."""
    rk.announce_keep.cache_clear()
    cfg, params, _ = _stack("aw", window=64, moe_experts=experts,
                            moe_top_k=2 * bool(experts))
    with batch_axis(None, "data", DeviceRoom(100 * GB, 99 * GB)):
        lines = _lines(lambda: rk.names_for(cfg, params, (1, 128)))
    fields = _fields(lines[0])
    labels = [label for label, _, _ in rk.table(cfg, 128)]
    assert fields["names"].split(",") == [
        name for _, names, _ in rk.table(cfg, 128) for name in names]
    assert int(fields["dispatch"]) == rk.dispatch_bytes(cfg, 128, labels)
    assert (int(fields["dispatch"]) > 0) == bool(experts)
    assert lines[0].index(" grads_standing=") < lines[0].index(
        " dispatch=") < lines[0].index(" layers=")


@pytest.mark.parametrize("config", ["olmo1b", "olmoe1b7b", "lfm2-24b-a2b",
                                    "smallthinker-21b-a3b",
                                    "kanana-2-30b-a3b", "trinity-mini",
                                    "olmo-hybrid-7b", "solar-open2-250b",
                                    "xing4.0-29b-a4b", "ling-3.0-flash"])
@pytest.mark.parametrize("share", [1.0, 0.9, 0.8])
def test_no_predicted_peak_passes_the_limit_less_the_reserve(config, share):
    """Every configuration of the benchmark, at the chip's limit and at
    smaller ones (another process's share, a smaller chip): what is
    chosen never predicts a peak over ``(1 - RESERVE) * limit`` while
    the state and the step with nothing kept fit under it, and a list
    is never longer under less room."""
    cfg, params, held, _ = _cell(config)
    rows = ROWS_OF[config]
    limit = int(share * V5E_LIMIT)
    names, kept, budget, peak = rk.choose(
        cfg, params, rows, DeviceRoom(limit, limit - held))
    fits = held + rk.step_bytes(cfg, params, rows) <= (
        1 - rk.RESERVE) * limit
    assert fits == (budget >= 0)
    if fits:
        assert peak <= (1 - rk.RESERVE) * limit and kept <= budget
    else:
        assert names == ()
    whole = rk.choose(cfg, params, rows,
                      DeviceRoom(V5E_LIMIT, V5E_LIMIT - held))[0]
    assert set(names) <= set(whole)


@pytest.mark.parametrize("held", [8, 64])
def test_the_routed_entries_have_the_bounds_rows(held):
    """LFM2-24B-A2B's cell at its real shapes, no arrays: 32,768 tokens x
    4 choices over 64 experts.  With 8 held the dispatch's four entries
    have ``row_bound``'s 32,768 rows and go by half their worth (a
    balanced router fills half the bound), so the list the chip's room
    takes holds the convolution's input before the gate and up
    products where the whole-buffer entries, four times the bytes,
    fitted none, and since PR 60 the convolution's result, the down
    product behind it (a share's: the matmul alone, f / e of the up
    product's worth) and the sorted rows; with all 64 held they have
    every row and their whole worth, the down product's with its
    gather."""
    cfg, params, used, _ = _cell("lfm2-24b-a2b", moe_experts_held=held)
    assert _cell("lfm2-24b-a2b")[0].experts_held[1] == 8
    rows, k, e, f = 4 * 8192, 4, 2048, 1536
    bound = md.row_bound(rows * k, held, 64)
    assert bound == (32768 if held == 8 else rows * k)
    table = {label: (names, nbytes)
             for label, names, nbytes in rk.table(cfg, rows)}
    assert table["moe_rows"] == ((md.KEEP_ROWS,), bound * e * 2)
    assert table["moe_out"] == ((md.KEEP_OUT,), bound * e * 2)
    assert table["moe_gate"] == ((md.KEEP_GATE,), bound * f * 2)
    assert table["moe_up"] == ((md.KEEP_UP,), bound * f * 2)
    order = list(table)
    assert order[:4] == ["flash", "route", "qkv", "stream"]
    assert order[4:] == (
        ["ffn_gate", "ffn_up", "conv_in", "moe_gate", "moe_up", "conv_out",
         "moe_out", "moe_rows"] if held == 8 else
        ["moe_out", "moe_gate", "moe_up", "ffn_gate", "ffn_up", "conv_in",
         "moe_rows", "conv_out"])
    if held == 64:
        assert used > V5E_LIMIT       # no chip holds all 64 of a layer
        return
    names, kept, budget, peak = rk.choose(
        cfg, params, rows, DeviceRoom(V5E_LIMIT, V5E_LIMIT - used))
    assert names == CELLS["lfm2-24b-a2b.seq8192"][5]
    assert kept <= budget and peak <= (1 - rk.RESERVE) * V5E_LIMIT


def _lines(fn, prefix="remat keep:"):
    """The ``remat keep:`` lines logged while ``fn`` runs (the repo's
    loggers do not propagate to the root one)."""
    lines = []
    handler = logging.Handler()
    handler.emit = lambda record: lines.append(record.getMessage())
    loggers = (fa.logger, ct.logger)
    for logger in loggers:
        logger.addHandler(handler)
    try:
        fn()
    finally:
        for logger in loggers:
            logger.removeHandler(handler)
    return [line for line in lines if line.startswith(prefix)]


def _spec(**kw):
    return tfm.model_spec(vocab_size=96, dim=128, num_heads=2, num_layers=2,
                          seq_len=128, ffn_dim=128, dtype="float32",
                          remat=True, **kw)


def _fields(line):
    return dict(part.split("=") for part in line.split()[2:])


def test_the_trainer_states_the_room_and_the_model_logs_once(monkeypatch):
    """limit - (parameters + gradients + optimizer state), stated around
    the model's trace; one line per compiled shape however many steps."""
    rk.announce_keep.cache_clear()
    monkeypatch.setattr(ct, "_bytes_limit", lambda devices: GB)
    trainer = ct.CollectiveTrainer(_spec(), batch_size=2)
    params = ct._device_bytes(trainer._params)
    assert trainer._room == DeviceRoom(
        GB, GB - 2 * params - ct._device_bytes(trainer._opt_state))
    tokens = np.zeros((2, 128), np.int32)

    def train():
        for _ in range(3):
            trainer.train_minibatch(tokens, tokens)

    lines = _lines(train)
    assert len(lines) == 1
    fields = _fields(lines[0])
    assert fields["names"] == ",".join(sum(
        (n for _, n, _ in rk.table(trainer._spec.config, ROWS)), ()))
    assert fields["rows"] == str(ROWS) and fields["fallback"] == "0"
    assert int(fields["bytes"]) <= int(fields["budget"])
    assert int(fields["predicted_peak"]) < GB
    # gradient accumulation holds a second gradient tree
    trainer.set_accum_steps(2)
    assert trainer._room.free == GB - 3 * params - ct._device_bytes(
        trainer._opt_state)


def test_no_limit_stated_no_room_and_no_line():
    """The CPU states no ``bytes_limit``: tier-1 trains as it did."""
    rk.announce_keep.cache_clear()
    trainer = ct.CollectiveTrainer(_spec(), batch_size=2)
    assert trainer._room is None
    tokens = np.zeros((2, 128), np.int32)
    assert _lines(lambda: trainer.train_minibatch(tokens, tokens)) == []


def test_on_a_mesh_the_bytes_are_one_shards(monkeypatch):
    """Four devices, 32 rows a step: 8 a device; ZeRO-1 states a
    quarter of the optimizer state."""
    rk.announce_keep.cache_clear()
    monkeypatch.setattr(ct, "_bytes_limit", lambda devices: GB)
    mesh = Mesh(np.array(jax.devices()[:4]), axis_names=("data",))
    trainer = ct.CollectiveTrainer(_spec(), batch_size=32, mesh=mesh)
    tokens = np.zeros((32, 128), np.int32)
    lines = _lines(lambda: trainer.train_minibatch(tokens, tokens))
    assert len(lines) == 1
    fields = _fields(lines[0])
    assert fields["rows"] == str(8 * 128)
    table = rk.table(trainer._spec.config, 8 * 128)
    assert int(fields["bytes"]) == 2 * sum(b for _, _, b in table)
    sharded = ct.CollectiveTrainer(_spec(), batch_size=32, mesh=mesh,
                                   zero1=True)
    state = ct._device_bytes(trainer._opt_state)
    assert ct._device_bytes(sharded._opt_state) < 0.3 * state
    assert sharded._room.free > trainer._room.free + 0.7 * state


def test_a_model_parallel_mesh_keeps_nothing():
    cfg = _cfg(0)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((2, 128), jnp.int32)
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1, 1, 1),
                ("dp", "tp", "sp", "pp"))
    with batch_axis(None, "data", DeviceRoom(100 * GB, 99 * GB)):
        jaxpr = str(jax.make_jaxpr(jax.grad(
            lambda p: tfm.forward_hidden(p, tokens, cfg, mesh=mesh)[0]
            .sum()))(params))
    assert "policy=None" in jaxpr and "save_only" not in jaxpr


class _Refuse:
    """A step whose compile ends as the TPU's does when the program
    does not fit (the message is the backend's, PR 23)."""

    def __init__(self, message):
        self.calls, self.message = 0, message

    def __call__(self, *args):
        self.calls += 1
        raise jax.errors.JaxRuntimeError(self.message)


OOM = ("RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
       "memory in memory space hbm. Used 16.23G of 15.75G hbm.")


def test_a_refused_compile_rebuilds_once_with_nothing_kept(monkeypatch):
    rk.announce_keep.cache_clear()
    monkeypatch.setattr(ct, "_bytes_limit", lambda devices: GB)
    trainer = ct.CollectiveTrainer(_spec(), batch_size=2)
    refuse = trainer._train_step = _Refuse(OOM)
    tokens = np.zeros((2, 128), np.int32)
    lines = _lines(lambda: trainer.train_minibatch(tokens, tokens))
    assert refuse.calls == 1 and trainer._room == DeviceRoom(GB, 0)
    assert len(lines) == 2
    assert lines[0].startswith("remat keep: fallback=1")
    fields = _fields(lines[1])
    assert fields["names"] == "-" and fields["fallback"] == "1"
    loss, version = trainer.train_minibatch(tokens, tokens)
    assert version == 2 and np.isfinite(float(loss))
    # every later build states none left; a second refusal is the job's
    trainer.set_accum_steps(2)
    assert trainer._room == DeviceRoom(GB, 0)
    trainer._train_step = _Refuse(OOM)
    tokens = np.zeros((4, 128), np.int32)
    with pytest.raises(jax.errors.JaxRuntimeError):
        trainer.train_minibatch(tokens, tokens)


@pytest.mark.parametrize("case", ["another error", "no room stated",
                                  "arguments consumed"])
def test_what_is_not_a_refused_estimate_is_raised(monkeypatch, case):
    if case != "no room stated":
        monkeypatch.setattr(ct, "_bytes_limit", lambda devices: GB)
    trainer = ct.CollectiveTrainer(_spec(), batch_size=2)
    message = "INTERNAL: something else" if case == "another error" else OOM
    refuse = trainer._train_step = _Refuse(message)
    if case == "arguments consumed":
        # the step ran and failed: its donated arguments are gone
        jax.tree_util.tree_leaves(trainer._params)[0].delete()
    tokens = np.zeros((2, 128), np.int32)
    with pytest.raises(jax.errors.JaxRuntimeError):
        trainer.train_minibatch(tokens, tokens)
    assert refuse.calls == 1 and not trainer._room_refused


def test_a_kda_layers_rows_and_the_steps_bytes_with_them():
    """``solar-open2-250b`` at its cell's rows: the decays are a channel
    each, [rows, heads * key_dim + heads] float32 (66 times a scalar
    decay's), the scan's row is the scalar decay's names and bytes, the
    two [rows, rank] products that the low-rank pairs start from are a
    row of their own and the dearest byte of the layer; the step needs
    four float32 planes of the decays beside the FFN's term, three with
    the decays kept; a gdn stack has neither the row nor the term."""
    cfg, params, _, _ = _cell("solar-open2-250b")
    rows = 16384
    entries = {label: (names, nbytes, layers)
               for label, names, nbytes, layers in rk._entries(cfg, rows)}
    assert entries["delta_decay"] == (
        (rk.KEEP_DELTA_DECAY,), rows * 8 * (128 + 1) * 4, 3)
    assert entries["delta_rank"] == (
        (rk.KEEP_DELTA_RANK,), rows * 2 * 128 * 2, 3)
    assert entries["delta"][0] == (gd.KEEP_OUT, gd.KEEP_STATES,
                                   gd.KEEP_INVERSE)
    assert entries["delta"][1] == (
        rows * 8 * 128 * 2 + rows // 64 * 8 * 128 * 128 * 4
        + gd.inverse_bytes(rows, 8, 2))
    order = [label for label, _, _ in rk.table(cfg, rows)]
    assert order.index("delta_rank") < order.index("delta") < order.index(
        "shared_gate") < order.index("delta_gate") < order.index(
            "delta_decay")
    plane = rows * 8 * 128 * 4
    need = lambda *kept: rk.step_bytes(cfg, params, rows, kept)
    gdn = dataclasses.replace(cfg, delta_kind="gdn", delta_rank=0)
    # both operators' entries kept, so that the decays' planes alone
    # tell the two kinds' needs apart: three of them with the decays
    # kept
    delta = ["flash", "qkv", "gate"] + [
        label for label in rk.OPERATOR_ENTRIES["d"] if label != "delta_rank"]
    assert need(*delta, "delta_rank") - rk.step_bytes(
        gdn, params, rows, delta) == 3 * plane
    others = [label for label in delta if label != "delta_decay"]
    # the log decays are the entry's plane, counted once
    assert need(*others) - need(*delta) == entries["delta_decay"][1]
    assert "delta_rank" not in [
        label for label, _, _ in rk.table(gdn, rows)]
    # with the stream kept the operator's second forward runs behind
    # the FFN's pullback: the term is the larger of the two, not their
    # sum, and the dispatch's inventory is the larger here, less the
    # plane of the stream that the stack now holds
    sizes = {label: nbytes for label, _, nbytes in rk.table(cfg, rows)}
    kind = next(kind for kind in cfg.kinds if kind.op == "d")
    beside = rk._expert_layer(cfg, rows, (), kind, sizes)
    behind = rk._expert_layer(cfg, rows, ("stream",), kind, sizes)
    # the entries, the log decays' plane among them, and the three
    # other planes of the decays
    operator = 3 * plane + sum(sizes[label]
                               for label in rk.OPERATOR_ENTRIES["d"])
    assert beside - behind == operator + rows * cfg.dim * 2
    assert operator < behind
