"""Plain reference of the ``ling-3.0-flash`` configuration's loss.

Ling-3.0-flash's layer equations (``model_type: bailing_hybrid``) as its
public config gives them and, where the config has no key, as the
configuration's ``assumed`` lists them, in straightforward ``jax.numpy``
and float32, with no kernel, no chunk, no sort-and-gather dispatch, no
remat.  Written from those equations (Kimi Delta Attention's recurrence,
arXiv:2510.26692; DeepSeek-V3's latent attention and group-limited
router), not from ``models/transformer.py``, and it imports nothing from
``ops/``.

 - ``x = E[token]``; a block, every layer, pre-norm: ``x = x + Mix(n1(
   x))`` then ``x = x + FFN(n2(x))`` (RMSNorm, eps 1e-6); no bias.
 - Layer ``i`` of the published 42 is a latent-attention layer where
   ``(i + 1) % layer_group_size == 0``, else a KDA layer
   (``layers_kept`` names the published layers that run here).
 - a KDA layer, ``H`` heads of ``d_k = d_v`` = 128, on ``h = n1(x)``:
   1. ``q~, k~, v~ = h W_q, h W_k, h W_v`` (``w_qkv`` = [q heads | k
      heads | v heads]); each channel passes a causal convolution of 4
      taps (zeros before the sequence's start, no bias), then SiLU;
   2. a head at a time ``q <- q / |q|_2 * d_k^-1/2``, ``k <- k / |k|_2``;
   3. ``beta = sigmoid(h W_b)`` a head (not doubled);
   4. a log decay a CHANNEL through ONE full projection
      (``no_kda_lora``) and the bounded gate (``kda_safe_gate``): ``g =
      kda_lower_bound x sigmoid(exp(A_log[head]) x (h W_f + dt_bias))``
      in (-5, 0), ``alpha = exp(g)``;
   5. the state ``S`` [d_v, d_k] a head, zero before the first token,
      TOKEN BY TOKEN (``lax.scan`` over T, no chunks): ``S' = S
      Diag(alpha_t)``; ``u_t = beta_t (v_t - S' k_t)``; ``S = S' + u_t
      k_t^T``; ``o_t = S q_t``;
   6. ``y = concat_heads(RMSNorm_128(o) * sigmoid(h W_g)) W_o``: one
      learned scale of 128 that the heads share, ``W_g`` full and
      without a bias.
 - a latent-attention layer (no query latent): ``q = h W_q``: heads of
   192, ``q_nope`` 128 | ``q_rope`` 64.  ``c = h W_kva``: 512 + 64;
   ``c_kv = RMSNorm(c[:512])``, ``k_rope = c[512:]``, ONE key of 64 for
   all the heads; ``c_kv W_kvb``: heads of ``k_nope`` 128 | ``v`` 128.
   RoPE (theta 6,000,000, no scaling) turns ``q_rope`` and ``k_rope``
   alone and pairs NEIGHBOURS ``(2i, 2i + 1)`` (``rope_interleave``);
   causal softmax over the whole sequence at ``192^-1/2`` in blocks of
   ``QUERY_BLOCK`` queries; A GATE A HEAD: ``o_h <- o_h x sigmoid((h
   W_gate)_h)``; ``concat(heads) W_o``.
 - FFN: the leading dense layer a SwiGLU of 6,144; the others ``s =
   sigmoid(u W_r)`` over all 512 experts in float32; ``s' = s + bias``;
   the 512 in 8 groups of 64 neighbours, a group's score the sum of its
   two largest ``s'``, the 4 best groups kept, the 8 largest ``s'``
   inside them chosen (explicit reshape and sort); weights the unbiased
   ``s`` of the chosen over their sum, times ``routed_scaling_factor``
   2.5; experts SwiGLUs of 768, PLUS one shared SwiGLU of 768.  THE
   CLAMP: where a layer's entry ``L`` of ``expert_swiglu_limit_list`` is
   > 0 its routed experts compute ``(SiLU(min(a, L)) x clip(b, -L, L))
   W_down`` of the gate product a and the up product b;
   ``share_expert_swiglu_limit_list`` the same for its shared expert.
 - one RMSNorm after the last layer, an untied head, the mean
   next-token cross entropy; one multi-token-prediction module
   (``num_nextn_predict_layers`` 1, ``mtp_use_kda`` false): ``h' =
   [RMSNorm(h_t) ; RMSNorm(E[token_(t+1)])] W_proj`` of the hidden state
   before the final norm, one latent-attention expert-layer block
   without a clamp, the SHARED final norm and head, the mean cross
   entropy of ``token_(t+2)``; ``loss = main + 0.1 mtp``, each returned
   apart.

Departures from the published model, each the configuration's
(``configs/ling-3.0-flash.json``: ``reduced``, ``deployment``,
``assumed``):

 - the shares: the weights hold heads ``0 .. 8`` of 32 of both mixers (a
   mixer's result is their part of the ``W_o`` product), experts ``first
   .. first + held`` of the router's 512 and a slice of the vocabulary;
   what the absent heads and experts would add is left out, here as in
   the program, and that partial result goes on;
 - published layers 0 and 36-41 of the 42;
 - the division by the chosen scores' sum adds 1e-6 (the program's);
 - position t of the module's second input past the sequence's end
   takes the sequence's first tokens' embeddings, as the program's
   ``roll`` does: no loss reads those positions and causal attention
   lets no other see them;
 - ``mtp_loss_scaling_factor`` 0.1 where the release says 0; no update
   of the bias and no balance loss.

``params`` is the program's own tree, so the same seeded weights go
through both.  The program turns the two HALVES of a RoPE part, so it
holds the RoPE columns of ``wq`` and ``w_kv_a`` evens first, then odds:
``published_order`` maps them back before this file's RoPE pairs
neighbours.  The caller sets ``jax.default_matmul_precision("highest")``.
"""

import collections
import contextlib
import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

# The total loss's largest relative difference.  Two readings on the
# chip at the published widths and T = 16,384 (``tools/
# ling3_precision.py``, seeds 11, 3000000019, 77, and three comparisons
# of the cell's; my chip runs, PR 56, calls ``c2``, ``c1b``, ``cT``;
# PERF.md section 6): the product, bfloat16 as the configuration states,
# reads 8.0e-5, 1.03e-4, 1.58e-4 and 9.2e-5, 1.15e-4, 1.73e-4, either
# sign (a loss of ~23.4 under the comparison's
# five-times-wider head: 1e-4 of it is 2.3e-3 nats; seven blocks deep,
# the hidden state's ~1% distance times a top logit of ~21, averaged
# over 16,383 positions: a zero-mean draw of rms 1.25e-4); this file with
# every matmul operand outside the routers rounded to float8 (e4m3), the
# nearest precision below, 4.16e-4, 6.45e-4 and 1.04e-3: draws of a
# wider distribution, not a floor.  The limit stands 2.0 times over the
# product's largest (2.8 of its rms: their geometric middle, 2.7e-4,
# would refuse a sound run in thirty) and 1.2 times under float8's
# smallest; what refuses float8 on every seed is the layers' ceilings
# below, five of them by 2.3 to 40 times (PERF.md section 7 (8): one
# mean over a sequence is a blunt scalar).  A module that fell out of
# the loss moves it by 9.1e-2 on each seed, the clamp left out by 1.5e-4
# to 1.6e-3 (its layers by 0.32 and 0.59), the group limit left out by
# 6e-5 to 3.5e-4 (the routing agreement reads 0.82).
TOLERANCE = 3.5e-4
# As ``solar-open2-250b``'s: both routers float32 at the highest
# precision, only exact ties may differ (512 experts wide here, the
# choice limited to 4 groups of 8).
SAME_INPUT_ROUTING_FLOOR = 0.9998
# The largest relative distance (norms over a part's whole result, so no
# mean over the sequence cancels anything) of the program's KDA mixer,
# gated latent attention, shared expert and held experts from this
# file's float32 math on the same inputs, the worst of the layers.
# Readings (``c2``, three seeds, every layer): the program kda 6.8-7.0e-3
# against float8's 8.8-9.4e-2, attention 5.1-5.2e-3 against 0.79-0.80,
# the shared expert 4.13e-3 against 4.83-4.85e-2, the held experts
# 4.74-4.76e-3 against 6.92e-2: one limit for the four, 2.9 times over
# the product's largest and 2.4 times under float8's smallest.  Without
# the clamp the shared expert reads 0.32 and the held experts 0.59.
SAME_INPUT_LAYER_CEILING = 2e-2
# The same of the multi-token-prediction module's hidden state, which is
# no one layer: two norms and a projection and a whole block behind one
# another, each in bfloat16 on the last one's bfloat16 result, and its
# router may break a near-tie the other way (``xing4.0-29b-a4b``'s).
# Readings: 7.9e-3, 1.03e-2, 1.39e-2 (``c2``) and 1.29e-2 (``c1b``)
# against float8's 8.0-8.7e-2: 2.5 times over the one, 2.3 under the
# other.  Read by the precision tool (``every``): the harness's own
# comparison leaves the module to the loss (``layer_errors``).
SAME_INPUT_MTP_CEILING = 3.5e-2
# The same of the delta rule ALONE on a probe that remembers: the first
# KDA layer's own q, k, v with its log decays times ``PROBE_DECAY`` and
# its write strengths times ``PROBE_WRITE``, so that a state lives
# thousands of tokens and what a rounding of it leaves adds up (at the
# layer's own decays a state forgets a rounding within dozens of tokens
# and a bfloat16 state reads under the bfloat16 program's own distance:
# ``solar-open2-250b``'s finding, PERF.md section 7: here too, 3.9-4.9e-3
# in the kda part under the program's 6.8-7.0e-3).  Over the last
# quarter of the sequence.  Readings (``c2``): the program's kernels
# (float32 state, bfloat16 q, k, v) 2.7e-3, 2.7e-3, 3.1e-3; this file
# with the log decays and the state in bfloat16 1.14e-2, 1.92e-2,
# 3.01e-2: the limit is their geometric middle, 1.9 times of room on
# either side, and refuses a bfloat16 gate and state on three seeds of
# three, which no other limit of this file does.
SAME_INPUT_STATE_CEILING = 6e-3
PROBE_DECAY, PROBE_WRITE = 0.02, 0.1
# what ``loss`` can round apart, and what ``layer_errors`` compares
PARTS = ("kda", "attention", "dense", "experts", "shared", "head", "mtp")
LAYER_PARTS = ("kda", "attention", "shared_expert", "routed_experts", "mtp",
               "kda_state")
# what ``loss`` can leave out (``without``): what a test shows the
# limits to see.  "floor": the unbounded softplus gate in the bounded
# one's place; "groups": the plain top-K of all the experts; "clamp":
# no limit in any layer; "head_gate": latent attention ungated;
# "module": the loss without the multi-token-prediction module's.
PIECES = ("floor", "groups", "clamp", "head_gate", "module")
# what ``loss`` saw of a layer: the router's choice [B, T, X] bool (None
# for the dense layer), the mixer's and the FFN's normed inputs
Seen = collections.namedtuple("Seen", "chosen h u")
# and of the model: the hidden state before the final norm and the
# embeddings
Probe = collections.namedtuple("Probe", "hidden embedded")
MICROBATCH = 1
HEAD_SCALE = 5.0
BIAS_SCALE = 0.1
NORM_SPREAD = 0.25
# what ``inputs`` multiplies the experts' gate and up projections by, so
# that the published limits bite: at the job's draw a product is N(0, 1)
# and a limit of 4 touches 6e-5 of them, which no tolerance could tell
# from no clamp; three times wider, 18% lie past 4, 10% past 5, 2% past 7
CLAMP_SCALE = 3.0
QUERY_BLOCK = 2048
HEAD_BLOCK = 2048
L2_EPS = 1e-6
ROUTE_EPS = 1e-6
NO_LIMIT = float("inf")


def shape_of(config):
    """What ``loss`` needs of the configuration's file."""
    kept = config["layers_kept"]
    limit = lambda name, i: float(config[name][i]) or NO_LIMIT
    return dict(
        heads=config["num_attention_heads"], d_k=config["head_dim"],
        floor=float(config["kda_lower_bound"]) if config["kda_safe_gate"]
        else 0.0,
        rank=config["kv_lora_rank"], d_nope=config["qk_nope_head_dim"],
        d_rope=config["qk_rope_head_dim"], d_v=config["v_head_dim"],
        theta=float(config["rope_theta"]), eps=config["rms_norm_eps"],
        kinds=tuple("latent" if (i + 1) % config["layer_group_size"] == 0
                    else "linear" for i in kept),
        module="linear" if config["mtp_use_kda"] else "latent",
        limits=tuple((limit("expert_swiglu_limit_list", i),
                      limit("share_expert_swiglu_limit_list", i))
                     for i in kept),
        top_k=config["num_experts_per_tok"], groups=config["n_group"],
        top_groups=config["topk_group"], norm_topk=config["norm_topk_prob"],
        scale=float(config["routed_scaling_factor"]),
        first=config.get("share_index", 0) * config["num_experts"],
        mtp_weight=float(config["mtp_loss_scaling_factor"]))


def inputs(config, params, rng):
    """(params, tokens [MICROBATCH, seq_len]) as both sides shall use
    them.  The head is drawn 5 times wider than the product's 0.02, so
    that the loss is not ln(V) whatever the network computes; every
    ``expert_bias`` (zeros in the job) at 0.1, so that the biased choice
    and the unbiased weights are compared too; the KDA output norm's
    scale (ones in the job) within 1 +- 0.25; the experts' gate and up
    projections ``CLAMP_SCALE`` times wider, so that the limits bite."""
    tokens = jnp.asarray(rng.integers(
        0, config["vocab_size"], (MICROBATCH, config["seq_len"])), jnp.int32)
    params["lm_head"] = params["lm_head"] * HEAD_SCALE

    def redraw(w):
        for name in list(w):
            if isinstance(w[name], dict):
                redraw(w[name])
            elif name == "expert_bias":
                w[name] = jnp.asarray(
                    BIAS_SCALE * rng.standard_normal(w[name].shape),
                    jnp.float32)
            elif name == "o_norm":
                w[name] = jnp.asarray(1.0 + NORM_SPREAD * rng.uniform(
                    -1.0, 1.0, w[name].shape), jnp.float32)
            elif name in ("ws_gate", "ws_up") or (
                    name in ("w_gate", "w_up") and "w_router" in w):
                w[name] = w[name] * CLAMP_SCALE

    redraw(params)
    return params, tokens


def case(config, params, rng, key):
    """See benchmark/lib/compare.py.  The reference runs once, here, at
    the highest matmul precision: its loss is what the returned function
    hands back; what it saw of its layers is what the routing check and
    the layer check read (stderr; each raises past its limit)."""
    params, tokens = inputs(config, params, rng)
    shape = shape_of(config)
    took = {}
    with _timed(took, "reference_loss"), jax.default_matmul_precision(
            "highest"):
        main, mtp, seen, probe = loss(params, tokens, **shape)
        main_loss, mtp_loss = float(main.mean()), float(mtp.mean())
    with _timed(took, "routing"):
        check_routing(config, params, seen)
    with _timed(took, "layers"):
        check_layers(config, params, seen, probe)
    # the seconds of the 300 the harness gives the comparison that this
    # file's part took (the product's own forward comes after it)
    print(json.dumps({"main_loss": main_loss, "mtp_loss": mtp_loss,
                      "seconds": took}), file=sys.stderr, flush=True)
    per_record = main + shape["mtp_weight"] * mtp
    return params, tokens, tokens, lambda p: per_record


@contextlib.contextmanager
def _timed(took, name):
    start = time.time()
    yield
    took[name] = round(time.time() - start, 1)


def layers_of(params):
    """The weights of each layer in order, one dict a layer."""
    groups = params["layers"]
    take = lambda group: [group[str(i)] for i in range(len(group))]
    out = take(groups["lead"])
    period = take(groups["period"])
    periods = period[0]["ln1"].shape[0] if period else 0
    for p in range(periods):
        out += [{k: v[p] for k, v in w.items()} for w in period]
    return out + take(groups["tail"])


def published_order(columns):
    """[.., D_rope] RoPE columns as the program holds them (evens first,
    then odds) -> the published order, neighbours (2i, 2i + 1) a pair."""
    half = columns.shape[-1] // 2
    return jnp.stack([columns[..., :half], columns[..., half:]],
                     axis=-1).reshape(columns.shape)


def rmsnorm(x, scale, eps):
    return scale * x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                                + eps)


def rope_pairs(x, theta):
    """x: [B, T, H, D]; rotate each pair of neighbours (2i, 2i + 1) of D
    by position, pair i at ``theta ** (-2 i / D)``."""
    d = x.shape[-1]
    freqs = jnp.asarray(theta ** (-np.arange(0, d, 2, dtype=np.float64) / d),
                        jnp.float32)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def causal_conv(x, taps):
    """x [B, T, C], taps [C, K]: ``y_t = sum_k taps[:, k] x_(t - (K - 1
    - k))``, zeros before the sequence's start, tap by tap."""
    T, K = x.shape[1], taps.shape[1]
    y = jnp.zeros_like(x)
    for k in range(K):
        back = K - 1 - k
        moved = x if not back else jnp.concatenate(
            [jnp.zeros_like(x[:, :back]), x[:, :T - back]], axis=1)
        y = y + taps[:, k] * moved
    return y


def recurrence(q, k, v, alpha, beta, r=lambda a: a):
    """o [B, T, H, d_v] of the delta rule token by token under a decay a
    channel; q, k, alpha [B, T, H, d_k], v [B, T, H, d_v], beta [B, T,
    H]; the state [B, H, d_v, d_k] passes ``r`` after every token (a
    lower precision's state).  Also the state after the last token."""
    B, T, H, d_k = q.shape

    def token(S, x):
        q, k, v, alpha, beta = x
        S = S * alpha[..., None, :]              # S Diag(alpha_t)
        u = beta[..., None] * (v - jnp.einsum("bhvk,bhk->bhv", S, k))
        S = r(S + u[..., :, None] * k[..., None, :])
        return S, jnp.einsum("bhvk,bhk->bhv", S, q)

    first = lambda a: jnp.moveaxis(a, 1, 0)
    S, o = jax.lax.scan(token, jnp.zeros((B, H, v.shape[-1], d_k), q.dtype),
                        tuple(map(first, (q, k, v, alpha, beta))))
    return jnp.moveaxis(o, 0, 1), S


def kda_gates(h, w, heads, d_k, floor, r=lambda a: a, without=()):
    """(g [B, T, H, d_k] the log decays, beta [B, T, H]) of the normed
    input: the bounded gate, or with ``floor`` 0 (or ``without``
    "floor") the unbounded ``-exp(A_log) softplus(..)``."""
    B, T, _ = h.shape
    beta = jax.nn.sigmoid(r(h) @ r(w["w_b"]))
    a = (r(h) @ r(w["w_a"]) + w["dt_bias"]).reshape(B, T, heads, d_k)
    rate = jnp.exp(w["A_log"])[:, None]
    if floor and "floor" not in without:
        return floor * jax.nn.sigmoid(rate * a), beta
    return -rate * jax.nn.softplus(a), beta


def kda_operands(h, w, heads, d_k, r):
    """(q, k, v [B, T, H, d_k]) as the recurrence takes them: projected,
    convolved, SiLU'd, q and k normalised."""
    B, T, _ = h.shape
    x = jax.nn.silu(causal_conv(r(h) @ r(w["w_qkv"]), w["delta_conv"]))
    q, k, v = (x[..., i * heads * d_k:(i + 1) * heads * d_k].reshape(
        B, T, heads, d_k) for i in range(3))
    unit = lambda a: a / jnp.sqrt(
        jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS)
    return unit(q) / np.sqrt(d_k), unit(k), v


def kda_mixer(h, w, heads, d_k, floor, eps, r=lambda a: a, without=(),
              state=lambda a: a):
    """The Kimi Delta Attention mixer of the normed input [B, T, E] ->
    [B, T, E]: steps 1-6 of the module's text.  ``r`` rounds every
    matmul operand, ``state`` the log decays and the recurrence's state
    after every token."""
    B, T, _ = h.shape
    q, k, v = kda_operands(h, w, heads, d_k, r)
    g, beta = kda_gates(h, w, heads, d_k, floor, r, without)
    o, _ = recurrence(r(q), r(k), r(v), jnp.exp(state(g)), beta, state)
    o = rmsnorm(o, w["o_norm"], eps).reshape(B, T, heads * d_k)
    o = o * jax.nn.sigmoid(r(h) @ r(w["w_out_gate"]))
    # departure (the head share): ``wo`` holds the held heads' rows, so
    # this is their part of the W_o product; the absent heads' part is
    # not added, and nothing stands in for the group's all-reduce
    return r(o) @ r(w["wo"])


def attention(h, w, heads, rank, d_nope, d_rope, d_v, eps, theta,
              r=lambda a: a, without=()):
    """Causal latent attention of the normed input with a gate a head, a
    block of queries at a time: the held heads' part of the ``W_o``
    product."""
    B, T, _ = h.shape
    wq = w["wq"].reshape(-1, heads, d_nope + d_rope)
    wq = jnp.concatenate(
        [wq[..., :d_nope], published_order(wq[..., d_nope:])],
        axis=-1).reshape(w["wq"].shape)
    w_kv_a = jnp.concatenate(
        [w["w_kv_a"][:, :rank], published_order(w["w_kv_a"][:, rank:])],
        axis=-1)
    q = (r(h) @ r(wq)).reshape(B, T, heads, d_nope + d_rope)
    c = r(h) @ r(w_kv_a)                               # [B, T, rank + Dr]
    c_kv = rmsnorm(c[..., :rank], w["kv_norm"], eps)
    kv = (r(c_kv) @ r(w["w_kv_b"])).reshape(B, T, heads, d_nope + d_v)
    k_nope, v = kv[..., :d_nope], kv[..., d_nope:]
    q_rope = rope_pairs(q[..., d_nope:], theta)
    k_rope = rope_pairs(c[..., None, rank:], theta)          # one head
    q = jnp.concatenate([q[..., :d_nope], q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (B, T, heads, d_rope))], axis=-1)
    # a block of queries at a time against every key, the later keys
    # masked: one loop body to compile, whatever the sequence's length
    size = min(QUERY_BLOCK, T)
    starts = jnp.arange(0, T, size)

    def scored(start):
        rows = jax.lax.dynamic_slice_in_dim(q, start, size, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", r(rows),
                            r(k)) / np.sqrt(d_nope + d_rope)
        causal = (start + jnp.arange(size)[:, None]
                  >= jnp.arange(T)[None, :])
        scores = jnp.where(causal, scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", r(jax.nn.softmax(scores, -1)),
                          r(v))

    out = jnp.moveaxis(jax.lax.map(scored, starts), 0, 1).reshape(
        B, T, heads, d_v)
    if "head_gate" not in without:     # one gate a head
        out = out * jax.nn.sigmoid(r(h) @ r(w["w_attn_gate"]))[..., None]
    return r(out.reshape(B, T, heads * d_v)) @ r(w["wo"])


def swiglu(u, gate, up, down, r, limit=NO_LIMIT):
    """``(SiLU(min(a, L)) * clip(b, -L, L)) W_down``; no clamp at L =
    inf."""
    a, b = r(u) @ r(gate), r(u) @ r(up)
    return r(jax.nn.silu(jnp.minimum(a, limit))
             * jnp.clip(b, -limit, limit)) @ r(down)


def shared_expert(u, w, r=lambda a: a, limit=NO_LIMIT):
    return swiglu(u, w["ws_gate"], w["ws_up"], w["ws_down"], r, limit)


def route(u, w_router, bias, top_k, groups=0, top_groups=0):
    """(scores [B, T, X], chosen [B, T, X] bool) of float32 inputs: the
    ``top_k`` largest of sigmoid + bias, with ``groups`` inside the
    ``top_groups`` groups whose two largest sum highest."""
    scores = jax.nn.sigmoid(u @ w_router)
    biased = scores + bias
    if groups:
        B, T, X = biased.shape
        members = biased.reshape(B, T, groups, X // groups)
        group_score = jnp.sort(members, axis=-1)[..., -2:].sum(axis=-1)
        least = jnp.sort(group_score, axis=-1)[..., -top_groups]
        kept = jnp.repeat(group_score >= least[..., None], X // groups,
                          axis=-1)
        biased = jnp.where(kept, biased, -jnp.inf)
    kth = jnp.sort(biased, axis=-1)[..., -top_k]
    return scores, biased >= kth[..., None]


def held_experts(u, w, weights, first, r=lambda a: a, limit=NO_LIMIT):
    """The held experts' part of the routed result [B, T, E]: every held
    expert's SwiGLU on every token, weighted by ``weights`` [B, T, X],
    a token's weight of each of all X experts (0 where not chosen); an
    expert at a time (one loop body to compile)."""
    held = w["w_gate"].shape[0]
    mine = jnp.moveaxis(weights[..., first:first + held], -1, 0)

    def add(y, expert):
        gate, up, down, weight = expert
        return y + weight[..., None] * swiglu(u, gate, up, down, r,
                                              limit), None

    return jax.lax.scan(add, jnp.zeros_like(u), (
        w["w_gate"], w["w_up"], w["w_down"], mine))[0]


def route_weights(u, w_router, bias, top_k, groups, top_groups, norm_topk,
                  scale):
    """(a token's weight of each of all X experts [B, T, X], chosen
    [B, T, X] bool) of the normed input, by the routing over all X."""
    scores, chosen = route(u, w_router, bias, top_k, groups, top_groups)
    weights = jnp.where(chosen, scores, 0.0)
    if norm_topk:
        weights = weights / (weights.sum(-1, keepdims=True) + ROUTE_EPS)
    return weights * scale, chosen


def head_loss(x, head, tokens, r, shift=1):
    """Per-sequence mean cross entropy of the token ``shift`` on, of the
    normed stream ``x`` [B, T, E], the logits a block of rows at a time
    (one loop body to compile; ``shift`` may be traced: position t's
    target is ``tokens[t + shift]``, the last ``shift`` positions have
    none)."""
    B, T, _ = x.shape
    size = min(HEAD_BLOCK, T)
    targets = jnp.roll(tokens, -shift, axis=1)
    counted = (jnp.arange(T) < T - shift).astype(jnp.float32)

    def block_sum(start):
        rows = jax.lax.dynamic_slice_in_dim(x, start, size, axis=1)
        logp = jax.nn.log_softmax(r(rows) @ r(head), axis=-1)
        picked = jnp.take_along_axis(logp, jax.lax.dynamic_slice_in_dim(
            targets, start, size, axis=1)[..., None], axis=-1)[..., 0]
        return -(picked * jax.lax.dynamic_slice_in_dim(
            counted, start, size)).sum(axis=-1)

    return jax.lax.map(block_sum, jnp.arange(0, T, size)).sum(axis=0) / (
        T - shift)


def rounding(rounded):
    """a -> a through dtype ``rounded`` and back; the identity for None.
    bfloat16 by ``reduce_precision``: a convert to bfloat16 and back is
    a pair XLA's TPU backend may drop as excess precision."""
    if rounded is None:
        return lambda a: a
    if jnp.dtype(rounded) == jnp.bfloat16:
        return lambda a: jax.lax.reduce_precision(a, 8, 7)
    return lambda a: a.astype(rounded).astype(jnp.float32)


def mixer(kind, h, w, shape, r=lambda a: a, without=(), state=lambda a: a):
    """The mixer of a layer of ``kind`` ("linear" | "latent")."""
    s = shape
    if kind == "linear":
        return kda_mixer(h, w, s["heads"], s["d_k"], s["floor"], s["eps"],
                         r, without, state)
    assert kind == "latent", kind
    return attention(h, w, s["heads"], s["rank"], s["d_nope"], s["d_rope"],
                     s["d_v"], s["eps"], s["theta"], r, without)


MIXER_WEIGHTS = {
    "linear": ("w_qkv", "delta_conv", "w_b", "w_a", "dt_bias", "A_log",
               "o_norm", "w_out_gate", "wo"),
    "latent": ("wq", "w_kv_a", "kv_norm", "w_kv_b", "w_attn_gate", "wo"),
}


def mtp_input(w, hidden, embedded, eps, r):
    """What the multi-token-prediction module's block starts from:
    ``[RMSNorm(h_t) ; RMSNorm(E[token_(t+1)])] W_proj`` [B, T, E]."""
    joined = jnp.concatenate(
        [rmsnorm(hidden, w["norm_h"], eps),
         rmsnorm(jnp.roll(embedded, -1, axis=1), w["norm_e"], eps)], axis=-1)
    return r(joined) @ r(w["proj"])


Pieces = collections.namedtuple(
    "Pieces", "norm mix dense route held shared head mtp_in")


@functools.lru_cache(maxsize=None)
def pieces(shape, rounded=None, parts=PARTS, without=(), state=None):
    """``loss``'s pieces, each a program of its own, from which ``block``
    puts a layer together: the norm; a mixer a kind (h, its weights);
    the dense SwiGLU; the route (u, w_router, bias) -> (weights, chosen);
    the held experts (u, their weights, the route's weights, limit); the
    shared expert (.., limit); (hidden, ln_f, lm_head, tokens, shift) ->
    the head's loss; the module's input (module, hidden, embedded).
    ``shape``: ``shape_of``'s items as a sorted tuple.  The limits and
    the head's shift are arguments, so that six KDA mixers, eight expert
    FFNs and both passes of the head compile once each, and
    ``layer_errors`` runs the very programs ``loss`` compiled on its own
    side of a comparison: whole, this file's part took the comparison
    past the 300 s the harness gives it on the chip (my runs, PR 56,
    calls ``c1``, ``c1c``).  Under a caller's ``jit`` the pieces are
    inlined and nothing changes."""
    s = dict(shape)
    r = {part: rounding(rounded if part in parts else None)
         for part in PARTS}
    groups = 0 if "groups" in without else s["groups"]
    part_of = {"linear": "kda", "latent": "attention"}
    return Pieces(
        jax.jit(lambda x, scale: rmsnorm(x, scale, s["eps"])),
        {kind: jax.jit(functools.partial(
            lambda kind, h, w: mixer(kind, h, w, s, r[part_of[kind]],
                                     without, rounding(state)), kind))
         for kind in MIXER_WEIGHTS},
        jax.jit(lambda u, gate, up, down: swiglu(u, gate, up, down,
                                                 r["dense"])),
        jax.jit(lambda u, w_router, bias: route_weights(
            u, w_router, bias, s["top_k"], groups, s["top_groups"],
            s["norm_topk"], s["scale"])),
        jax.jit(lambda u, w, weights, limit: held_experts(
            u, w, weights, s["first"], r["experts"], limit)),
        jax.jit(lambda u, w, limit: shared_expert(u, w, r["shared"], limit)),
        jax.jit(lambda hidden, ln_f, lm_head, tokens, shift: head_loss(
            rmsnorm(hidden, ln_f, s["eps"]), lm_head, tokens, r["head"],
            shift)),
        jax.jit(lambda w, hidden, embedded: mtp_input(
            w, hidden, embedded, s["eps"], r["mtp"])))


def of(w, names):
    return {name: w[name] for name in names}


def block(x, w, kind, limits, run, without=()):
    """One block on the stream x [B, T, E] -> (x', Seen), put together
    from the programs ``run`` (``pieces``).  ``limits`` (routed, shared):
    the layer's clamps, inf for none."""
    if "clamp" in without:
        limits = (NO_LIMIT, NO_LIMIT)
    routed, shared = (jnp.float32(limit) for limit in limits)
    h = run.norm(x, w["ln1"])
    x = x + run.mix[kind](h, of(w, MIXER_WEIGHTS[kind]))
    u = run.norm(x, w["ln2"])
    if "w_router" not in w:       # the leading dense layer: no clamp
        return x + run.dense(u, w["w_gate"], w["w_up"],
                             w["w_down"]), Seen(None, h, u)
    weights, chosen = run.route(u, w["w_router"], w["expert_bias"])
    # departure (the expert share): the held experts alone
    y = run.held(u, of(w, ("w_gate", "w_up", "w_down")), weights, routed)
    y = y + run.shared(u, of(w, ("ws_gate", "ws_up", "ws_down")), shared)
    return x + y, Seen(chosen, h, u)


def _float32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                  tree)


def loss(params, tokens, rounded=None, parts=PARTS, without=(), state=None,
         **shape):
    """(main loss [B], the module's loss [B] before its weight (zeros
    ``without`` "module"), [Seen of each layer, the module's last],
    Probe); tokens [B, T] int32.  ``rounded`` is a dtype through which
    every matmul operand outside the routers is rounded first, in the
    ``parts`` named; ``state`` a dtype through which the log decays and
    the delta rule's state pass; ``without`` names the PIECES to leave
    out (what a test tells apart)."""
    params = _float32(params)
    run = pieces(tuple(sorted(shape.items())), rounded, tuple(parts),
                 tuple(without), state)
    # departures (the cut): the ids, the logits and the loss are over a
    # slice of the vocabulary; ``kinds`` are the kept layers' alone
    embedded = params["embed"][tokens]
    x, seen = embedded, []
    for kind, limits, w in zip(shape["kinds"], shape["limits"],
                               layers_of(params), strict=True):
        x, saw = block(x, w, kind, limits, run, without)
        seen.append(saw)
    head = lambda hidden, shift: run.head(
        hidden, params["ln_f"], params["lm_head"], tokens, jnp.int32(shift))
    main = head(x, 1)
    module = params["mtp"]["0"]
    h, saw = block(
        run.mtp_in(of(module, ("norm_h", "norm_e", "proj")), x, embedded),
        module["layer"], shape["module"], (NO_LIMIT, NO_LIMIT), run)
    seen.append(saw)
    mtp = jnp.zeros_like(main) if "module" in without else head(h, 2)
    return main, mtp, seen, Probe(x, embedded)


def all_layers(params):
    """The weights of each layer, the module's block last, in float32,
    as ``loss`` lists what it saw of them."""
    return [_float32(w) for w in layers_of(params)] + [
        _float32(params["mtp"]["0"]["layer"])]


def scan_statistics(config, params, seen):
    """The quartiles over (token, head, channel) of ``alpha``, over
    (token, head) of ``beta`` and, over the heads, of the Frobenius norm
    of the state after the last token, in each KDA layer at the weights
    as drawn: what says whether the scan the comparison holds is a
    trivial one (every decay ~0 or ~1)."""
    shape = shape_of(config)
    quartiles = lambda a: [float(x) for x in np.quantile(
        np.asarray(a, np.float64).ravel(), (0.25, 0.5, 0.75))]

    @jax.jit
    def stats(h, w):
        g, beta = kda_gates(h, w, shape["heads"], shape["d_k"],
                            shape["floor"])
        q, k, v = kda_operands(h, w, shape["heads"], shape["d_k"],
                               lambda a: a)
        _, S = recurrence(q, k, v, jnp.exp(g), beta)
        return jnp.exp(g), beta, jnp.sqrt(jnp.sum(S * S, axis=(-1, -2)))

    out = []
    for s, w, kind in zip(seen, all_layers(params), shape["kinds"]):
        if kind != "linear":
            continue
        with jax.default_matmul_precision("highest"):
            alpha, beta, norms = stats(s.h, w)
        out.append({"alpha": quartiles(alpha), "beta": quartiles(beta),
                    "state_norm": quartiles(norms)})
    return out


def program_config(config):
    """The program's ``TransformerConfig`` of the configuration's file."""
    from benchmark.lib.runner import params_string
    from elasticdl_tpu.models.spec import load_model_spec

    return load_model_spec(
        config["cli"]["model_zoo"],
        model_params=params_string(config["cli"]["model_params"])).config


def check_routing(config, params, seen, without=()):
    """The program's router against this file's on the same inputs: the
    reference's own router inputs of each layer with experts (``seen``),
    rounded to the program's compute dtype as the program's are.  One
    JSON line on stderr; raises under SAME_INPUT_ROUTING_FLOOR."""
    from elasticdl_tpu.models import transformer as tfm

    cfg = program_config(config)
    shape = shape_of(config)
    groups = 0 if "groups" in without else shape["groups"]

    @jax.jit
    def both(u, w_router, bias):
        u = u.astype(jnp.dtype(cfg.dtype))
        theirs = jax.nn.one_hot(tfm.moe_route(u, w_router, cfg, bias)[2],
                                cfg.moe_experts).sum(-2) > 0
        ours = route(u.astype(jnp.float32), w_router, bias, shape["top_k"],
                     groups, shape["top_groups"])[1]
        return (theirs & ours).sum() / theirs.sum()

    with jax.default_matmul_precision("highest"):
        same_input = min(
            float(both(s.u, w["w_router"], w["expert_bias"]))
            for s, w in zip(seen, all_layers(params)) if "w_router" in w)
    print(json.dumps({"routing_same_input": same_input,
                      "floor": SAME_INPUT_ROUTING_FLOOR}),
          file=sys.stderr, flush=True)
    if same_input < SAME_INPUT_ROUTING_FLOOR:
        raise SystemExit(
            "the program's router chose other experts than a float32 "
            "router on the same inputs: %.5f of the pairs agree, under "
            "%.4f" % (same_input, SAME_INPUT_ROUTING_FLOOR))
    return same_input


def layer_errors(config, rounded=None, state=None, without=()):
    """A function of (params, seen, probe, every=False) that gives
    {part: |got - want| / |want|, the norms over a part's whole result}
    of LAYER_PARTS (one KDA expert layer, the latent layer and the
    probe; with ``every`` the largest over all the layers, and the
    module) on the same inputs: the reference's own
    (``seen``, ``probe``), rounded to the program's compute dtype as the
    program's are.  ``want`` is this file's float32 math; ``got`` the
    program's own functions (``models/transformer._delta_mix`` with its
    convolution and scan kernels, ``_latent_mix`` with its flash kernels
    and gate, ``_shared_expert``, ``_moe_ffn``, ``_mtp_module``,
    ``ops/gated_delta.gated_delta`` on the probe: the kernels where
    kernels run) or, with ``rounded``, ``state`` or ``without``, this
    file's in that precision or with those PIECES left out.  The routed
    part takes the program's route on both sides (``check_routing``
    holds the route itself); the module's block routes for itself on
    both, so a near-tie can reach it."""
    from elasticdl_tpu.models import transformer as tfm
    from elasticdl_tpu.ops import gated_delta

    cfg = program_config(config)
    shape = shape_of(config)
    key = tuple(sorted(shape.items()))
    dtype = jnp.dtype(cfg.dtype)
    lower = rounded is not None or state is not None or bool(without)
    # this file's programs: ``loss``'s own (its call, argument for
    # argument: the cache's key), and the lowered ones
    exact = pieces(key, None, PARTS, (), None)
    lowered = pieces(key, rounded, PARTS, tuple(without), state)
    r, rstate = rounding(rounded), rounding(state)
    cast = jax.jit(lambda a: a.astype(dtype).astype(jnp.float32))
    clamps = lambda pair, bare=False: tuple(
        jnp.float32(NO_LIMIT if bare or not limit else limit)
        for limit in pair)

    @jax.jit
    def distance(got, want):
        norm = lambda a: jnp.sqrt(jnp.sum(jnp.square(a)))
        return norm(got.astype(jnp.float32) - want) / norm(want)

    @functools.partial(jax.jit, static_argnums=2)
    def mixed(h, w, kind):
        """The program's mixer of a layer (keyed by the operator alone:
        six KDA layers compile once)."""
        h = h.astype(dtype)
        if kind.op == "d":
            return tfm._delta_mix(h, w, cfg)
        return tfm._latent_mix(h, w, cfg, jnp.arange(h.shape[1]), kind)

    @functools.partial(jax.jit, static_argnums=2)
    def fed(u, w, limits):
        """(the program's route as weights [B, T, X], its shared expert,
        its held experts) of a layer with experts, by its clamps."""
        u = u.astype(dtype)
        route_ = tfm.moe_route(u, w["w_router"], cfg, w["expert_bias"])
        weights = (jax.nn.one_hot(route_[2], cfg.moe_experts)
                   * route_[1][..., None]).sum(-2)
        if lower:
            return weights, ()
        return weights, (tfm._shared_expert(u, w, cfg, limits[1]),
                         tfm._moe_ffn(u, w, cfg, None, route_, limits[0])[0])

    def module(w, hidden, embedded, run):
        """This file's module by the programs ``run``."""
        return block(run.mtp_in(of(w, ("norm_h", "norm_e", "proj")),
                                cast(hidden), cast(embedded)),
                     w["layer"], shape["module"], (NO_LIMIT, NO_LIMIT),
                     run)[0]

    @jax.jit
    def module_program(w, hidden, embedded):
        positions = jnp.arange(hidden.shape[1])
        layer = lambda x, w1: tfm._layer_body(
            x, w1, cfg, None, positions, kind=cfg.mtp_kind)
        return tfm._mtp_module(w, hidden.astype(dtype),
                               embedded.astype(dtype), 0, cfg,
                               lambda kind: layer)[0]

    def slow(h, w):
        """The probe's operands: the layer's own q, k, v, its log decays
        times PROBE_DECAY and its write strengths times PROBE_WRITE."""
        q, k, v = kda_operands(h, w, shape["heads"], shape["d_k"],
                               lambda a: a)
        g, beta = kda_gates(h, w, shape["heads"], shape["d_k"],
                            shape["floor"])
        return q, k, v, PROBE_DECAY * g, PROBE_WRITE * beta

    @jax.jit
    def remembered(h, w):
        """(what the program's scan (or this file's, lowered) and this
        file's float32 recurrence remember of the probe, the last
        quarter of the sequence)."""
        # this file's side at the highest precision whatever the
        # caller's; the kernel's dots as the caller (the product's run)
        # has them
        with jax.default_matmul_precision("highest"):
            q, k, v, g, beta = slow(h.astype(dtype).astype(jnp.float32), w)
            want = recurrence(q, k, v, jnp.exp(g), beta)[0]
            if lower:
                got = recurrence(r(q), r(k), r(v), jnp.exp(rstate(g)), beta,
                                 rstate)[0]
        if not lower:
            first = lambda a: jnp.moveaxis(a, 2, 1)      # [B, H, T, ..]
            got = jnp.moveaxis(gated_delta.gated_delta(
                *(first(a).astype(dtype) for a in (q, k, v)), first(g),
                first(beta)), 1, 2)
        late = 3 * want.shape[1] // 4
        return got[:, late:], want[:, late:]

    def errors(params, seen, probe, every=False):
        worst = dict.fromkeys(LAYER_PARTS, 0.0)
        at = lambda precision: jax.default_matmul_precision(precision)
        program = "highest" if lower else "default"
        kinds = cfg.kinds + (cfg.mtp_kind,)
        saids = shape["kinds"] + (shape["module"],)
        layers = all_layers(params)
        # the last KDA expert layer (both clamps) and the latent layer
        # behind it, unless ``every`` layer is asked for: each kind and
        # each pair of clamps is a program more of the program's to
        # compile, and the harness gives the whole comparison 300 s
        held = range(len(seen)) if every else (
            len(shape["kinds"]) - 2, len(shape["kinds"]) - 1)
        for i, (s, w, said, kind) in enumerate(
                zip(seen, layers, saids, kinds, strict=True)):
            if i not in held:
                continue
            mine = of(w, MIXER_WEIGHTS[said])
            # the program's side as lib/compare.py runs the product: at
            # the default precision; this file's math at the highest
            with at(program):
                got = lowered.mix[said](cast(s.h), mine) if lower else mixed(
                    s.h, w, kind._replace(dense=False, limit=0.0,
                                          shared_limit=0.0))
            part = "kda" if said == "linear" else "attention"
            with at("highest"):
                worst[part] = max(worst[part], float(distance(
                    got, exact.mix[said](cast(s.h), mine))))
            if "w_router" not in w or (said == "latent" and not every):
                continue      # one clamped expert layer: the KDA one's
            limits = (kind.limit, kind.shared_limit)
            routed, shared = clamps(limits)
            ours = of(w, ("w_gate", "w_up", "w_down"))
            whole = of(w, ("ws_gate", "ws_up", "ws_down"))
            u = cast(s.u)
            with at(program):
                weights, got = fed(s.u, w, limits)
                if lower:
                    bare = "clamp" in without
                    got = (lowered.shared(u, whole, clamps(limits, bare)[1]),
                           lowered.held(u, ours, weights,
                                        clamps(limits, bare)[0]))
            with at("highest"):
                want = (exact.shared(u, whole, shared),
                        exact.held(u, ours, weights, routed))
                for name, g, w_ in zip(("shared_expert", "routed_experts"),
                                       got, want):
                    worst[name] = max(worst[name], float(distance(g, w_)))
        if every:
            # the module's whole block is a program more on the program's
            # side (35 s of the comparison's 300 on the chip, my run, PR
            # 56): ``lib/compare.py``'s run leaves it to the loss, which
            # a module 0.2% off moves past TOLERANCE, and to the latent
            # layer's and the expert layer's checks above
            w_mtp = _float32(params["mtp"]["0"])
            with at(program):
                got = (module(w_mtp, probe.hidden, probe.embedded, lowered)
                       if lower else module_program(
                           w_mtp, probe.hidden, probe.embedded))
            with at("highest"):
                worst["mtp"] = float(distance(got, module(
                    w_mtp, probe.hidden, probe.embedded, exact)))
        else:
            del worst["mtp"]
        first = shape["kinds"].index("linear")
        with at(program):
            got, want = remembered(seen[first].h, layers[first])
        worst["kda_state"] = float(distance(got, want))
        return worst

    return errors


def ceilings():
    own = {"mtp": SAME_INPUT_MTP_CEILING,
           "kda_state": SAME_INPUT_STATE_CEILING}
    return {part: own.get(part, SAME_INPUT_LAYER_CEILING)
            for part in LAYER_PARTS}


def check_layers(config, params, seen, probe):
    """The program's KDA mixer, gated latent attention, shared expert
    and held experts (the last KDA expert layer and the latent layer)
    and the delta rule on the probe that remembers against this file's
    on the same inputs (``layer_errors``; every layer and the module:
    ``tools/ling3_precision.py``).  One JSON line on stderr; raises over
    a part's ceiling."""
    errors = layer_errors(config)(params, seen, probe)
    limits = ceilings()
    print(json.dumps({"layers_same_input": errors, "ceilings": limits}),
          file=sys.stderr, flush=True)
    over = {part: error for part, error in errors.items()
            if not error <= limits[part]}
    if over:
        raise SystemExit(
            "the program's layers lie further from float32 math on the "
            "same inputs than the stated precision allows: %s, over %s"
            % (", ".join("%s %.2e" % item for item in sorted(over.items())),
               limits))
