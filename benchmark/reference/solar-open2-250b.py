"""Plain reference of the ``solar-open2-250b`` configuration's loss.

Solar-Open2-250B's layer equations (``model_type: solar_open2``) as its
public config gives them and, where the config has no key, as the
configuration's ``assumed`` lists them, in straightforward ``jax.numpy``
and float32, with no kernel, no chunk, no sort, no remat.  Written from
those equations (Kimi Delta Attention's recurrence, arXiv:2510.26692),
not from ``models/transformer.py``, and it imports nothing from
``ops/gated_delta.py``.

 - ``x = E[token]``.
 - a block, every layer, pre-norm: ``x = x + Mix(n1(x))`` then ``x = x +
   FFN(n2(x))`` (``ln1``, ``ln2``; RMSNorm, eps 1e-5, ``w * x /
   rms(x)``); no bias but the one named below.
 - a softmax layer (``gqa_layers``): ``h = n1(x)``; q of ``H`` heads of
   128, k and v of ``G``; no positional encoding at all (``use_rope``
   false), no QK norm; K and V repeated to the query heads the long way
   (query head i reads K/V head i // (H / G)); causal softmax over the
   whole sequence at ``128^-1/2``, explicit scores a block of
   ``QUERY_BLOCK`` queries at a time; ``o = (concat_heads(P v) *
   sigmoid(h W_g)) W_o`` (``use_gqa_gate``: a gate a value).
 - a linear layer (Kimi Delta Attention), ``H`` heads of ``d_k`` = 128
   keys and ``d_v`` = 128 values, on ``h = n1(x)``:
   1. ``q~, k~, v~ = h W_q, h W_k, h W_v`` (the program keeps the three
      side by side, ``w_qkv`` = [q heads | k heads | v heads]); each
      channel passes a causal convolution of 4 taps (zeros before the
      sequence's start, no bias) and then SiLU;
   2. a head at a time ``q <- q / |q|_2 * d_k^-1/2``, ``k <- k / |k|_2``;
   3. ``beta = 2 sigmoid(h W_b)`` a head (``kda_allow_neg_eigval``);
   4. a log decay a CHANNEL: ``g = -exp(A_log[head]) softplus((h
      W_a_down) W_a_up + dt_bias)`` in R^{d_k} a head, ``alpha =
      exp(g)`` (``kda_use_full_proj`` false: the low-rank pair);
   5. the state ``S`` [d_v, d_k] a head, zero before the first token,
      TOKEN BY TOKEN (``lax.scan`` over T, no chunks): ``S' = S
      Diag(alpha_t)``; ``u_t = beta_t (v_t - S' k_t)``; ``S = S' + u_t
      k_t^T``; ``o_t = S q_t``;
   6. ``y = concat_heads(RMSNorm_128(o) * sigmoid((h W_g_down) W_g_up +
      b_g)) W_o``: one learned scale of 128 that the heads share.
 - the FFN of every layer: ``u = n2(x)``; ``s = sigmoid(u W_r)`` over
   all 320 experts; the 8 largest of ``s + expert_bias`` chosen, their
   weights the unbiased ``s`` over their sum (``norm_topk_prob``) times
   ``routed_scaling_factor`` 1; SwiGLU experts of 1,280; PLUS one shared
   SwiGLU of 1,280 on the same ``u``.
 - one RMSNorm after the last layer, an untied head, the mean
   next-token cross entropy, its logits ``HEAD_BLOCK`` rows at a time.

Departures from the published model, each the configuration's
(``configs/solar-open2-250b.json``: ``reduced``, ``deployment``,
``assumed``):

 - the head share: the weights hold ``H`` = 8 of the 64 heads of both
   mixer kinds (the softmax layer's 8 query heads are one K/V group: 1
   of 8 K/V heads) with their columns of every projection and their
   rows of ``W_o``; a mixer's result is the held heads' part of the
   ``W_o`` product, what the absent heads would add is left out, here
   as in the program, and goes on to the next layer;
 - the expert share: the weights hold experts ``first .. first + held``
   of the router's 320; every HELD expert is applied to every token and
   masked by the routing over all 320, what the absent ones would add is
   left out; the shared expert is whole;
 - a slice of the vocabulary; layers 0-3 of the 48;
 - the division by the chosen scores' sum adds 1e-6 (the program's);
 - no update of ``expert_bias`` and no balance loss.

``params`` is the program's own tree (``layers`` = {"lead", "period",
"tail"}, a period's weights stacked over the periods), so the same
seeded weights go through both; which layer is of which kind is the
configuration's ``gqa_layers`` to say, not the weights'.  The caller
sets ``jax.default_matmul_precision("highest")``.
"""

import collections
import functools
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

# The mean loss's largest relative difference.  On the chip at the
# published widths and T = 16,384 (my chip runs, PR 49: two traced runs
# of the cell and ``tools/solar_open2_precision.py`` on three seeds) the
# bfloat16 product reads 5.7e-7 .. 2.24e-5 from this file over five
# seeds (a loss of ~26.8 under the comparison's five-times-wider head,
# so 1e-5 of it is 2.7e-4 nats), and this limit is nine times the
# largest; this file with every matmul operand outside the router
# rounded to float8 (e4m3), the nearest precision below the bfloat16 the
# configuration states, reads 4.65e-4 .. 6.91e-4 on three seeds, 2.3
# times the limit and more.  One mean over 16,383 positions is still a
# blunt scalar (PERF.md section 7 (8)): what fails float8 in every part
# on every seed is SAME_INPUT_LAYER_CEILING below.
TOLERANCE = 2e-4
# The least share of (token, choice) pairs on which the program's router
# and this file's, given the same inputs and the same bias, must choose
# the same expert: both float32 at the highest precision, so only exact
# ties may differ (``trinity-mini``'s floor; 320 experts wide here).
SAME_INPUT_ROUTING_FLOOR = 0.9998
# The largest relative distance (``layer_errors``: norms over a layer's
# whole [T, 4096] result, so no mean over the sequence cancels anything)
# of the program's KDA mixer, gated attention, shared expert and held
# experts from this file's float32 math on the same inputs, the worst of
# the layers of a kind.  The same calls, three seeds: the bfloat16
# program with its kernels reads 6.95-7.39e-3 / 5.21-5.29e-3 / 4.24-
# 4.25e-3 / 4.85-4.86e-3 (kda / attention / shared / routed: the KDA
# mixer is three bfloat16 matmuls deep in a chunk where the others are
# one or two), this file in float8 5.03-5.24e-2 / 0.505-0.523 / 4.67-
# 4.68e-2 / 7.79-7.80e-2: the ceiling is 2.7 times the former's largest
# and 0.43 of the latter's smallest, and float8 is past it in every part
# on three seeds of three.  A SCALAR decay in the vector's place (a
# head's mean log decay on every channel, ``without=("channels",)``:
# what the scalar kernel would compute) reads 0.58-0.60 in the kda part,
# thirty times the ceiling.  NOT held by it: the delta rule's state
# alone in bfloat16 reads 5.6-6.8e-3 in the kda part, under the bfloat16
# program's own reading, as in ``olmo-hybrid-7b`` and for its reason (a
# median alpha of 0.90-0.95 a channel a token forgets a state's rounding
# within a few dozen tokens: PERF.md section 7).
SAME_INPUT_LAYER_CEILING = 2e-2
# what ``loss`` can round apart, and what ``layer_errors`` compares
PARTS = ("kda", "attention", "experts", "shared", "head")
LAYER_PARTS = ("kda", "attention", "shared_expert", "routed_experts")
# what ``loss`` can leave out (``without``): what a test shows the
# tolerance to see.  "channels": every channel of a head decays by the
# head's mean log decay (a scalar decay in the vector's place).
PIECES = ("conv", "silu", "l2norm", "beta2", "decay", "channels",
          "out_norm", "kda_gate", "gate_bias", "attn_gate")
# what ``loss`` saw of a layer: the router's choice [B, T, X] bool, the
# mixer's and the FFN's normed inputs [B, T, E]
Seen = collections.namedtuple("Seen", "chosen h u")
MICROBATCH = 1
HEAD_SCALE = 5.0
BIAS_SCALE = 0.1
NORM_SPREAD = 0.25
QUERY_BLOCK = 1024
HEAD_BLOCK = 2048
L2_EPS = 1e-6
ROUTE_EPS = 1e-6
KINDS = {True: "softmax", False: "linear"}


def shape_of(config):
    """What ``loss`` needs of the configuration's file."""
    linear = config["linear_attn_config"]
    return dict(
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        delta_heads=linear["num_heads"], d_k=linear["head_dim"],
        d_v=linear["head_dim"],
        neg_eigval=config["kda_allow_neg_eigval"],
        eps=config["rms_norm_eps"],
        kinds=tuple(KINDS[i in config["gqa_layers"]]
                    for i in config["layers_kept"]),
        top_k=config["num_experts_per_tok"],
        norm_topk=config["norm_topk_prob"],
        scale=float(config["routed_scaling_factor"]),
        first=config.get("share_index", 0) * config["n_routed_experts"])


def inputs(config, params, rng):
    """(params, tokens [MICROBATCH, seq_len]) as both sides shall use
    them.  The head is drawn 5 times wider than the product's 0.02, so
    that the loss is not ln(V) whatever the network computes; every
    ``expert_bias`` (zeros in the job) at 0.1 and every ``b_g`` (zeros
    in the job) at 0.25, so that the biased choice, the unbiased
    weights and the gate's bias are compared too; the KDA output norm's
    scale (ones in the job) within 1 +- 0.25."""
    tokens = jnp.asarray(rng.integers(
        0, config["vocab_size"], (MICROBATCH, config["seq_len"])), jnp.int32)
    params["lm_head"] = params["lm_head"] * HEAD_SCALE
    normal = lambda scale, a: jnp.asarray(
        scale * rng.standard_normal(a.shape), jnp.float32)
    for group in params["layers"].values():
        for w in group.values():
            w["expert_bias"] = normal(BIAS_SCALE, w["expert_bias"])
            if "b_g" in w:
                w["b_g"] = normal(NORM_SPREAD, w["b_g"])
                w["o_norm"] = jnp.asarray(1.0 + NORM_SPREAD * rng.uniform(
                    -1.0, 1.0, w["o_norm"].shape), jnp.float32)
    return params, tokens


def case(config, params, rng, key):
    """See benchmark/lib/compare.py.  The reference runs once, here, at
    the highest matmul precision: its loss is what the returned function
    hands back, its layers' normed inputs what the routing check and the
    layer check read (stderr; each raises past its limit)."""
    params, tokens = inputs(config, params, rng)
    shape = shape_of(config)
    with jax.default_matmul_precision("highest"):
        per_record, seen = jax.jit(
            lambda p: loss(p, tokens, **shape))(params)
    check_routing(config, params, seen, shape["top_k"])
    check_layers(config, params, seen)
    return params, tokens, tokens, lambda p: per_record


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


def rmsnorm(x, scale, eps):
    return scale * x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                                + eps)


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


def kda_gates(h, w, heads, d_k, neg_eigval, without=()):
    """(alpha [B, T, H, d_k], beta [B, T, H]) of the normed input."""
    B, T, _ = h.shape
    beta = jax.nn.sigmoid(h @ w["w_b"])
    if neg_eigval and "beta2" not in without:
        beta = 2.0 * beta
    step = jax.nn.softplus((h @ w["w_a_down"]) @ w["w_a_up"] + w["dt_bias"])
    g = -jnp.exp(w["A_log"])[:, None] * step.reshape(B, T, heads, d_k)
    if "channels" in without:     # a scalar a head in the vector's place
        g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
    alpha = jnp.ones_like(g) if "decay" in without else jnp.exp(g)
    return alpha, beta


def kda_operands(h, w, heads, d_k, d_v, r, without=()):
    """(q, k [B, T, H, d_k], v [B, T, H, d_v]) as the recurrence takes
    them: projected, convolved, SiLU'd, q and k normalised."""
    B, T, _ = h.shape
    x = r(h) @ r(w["w_qkv"])
    if "conv" not in without:
        x = causal_conv(x, w["delta_conv"])
    if "silu" not in without:
        x = jax.nn.silu(x)
    q = x[..., :heads * d_k].reshape(B, T, heads, d_k)
    k = x[..., heads * d_k:2 * heads * d_k].reshape(B, T, heads, d_k)
    v = x[..., 2 * heads * d_k:].reshape(B, T, heads, d_v)
    if "l2norm" not in without:
        unit = lambda a: a / jnp.sqrt(
            jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS)
        q, k = unit(q), unit(k)
    return q / np.sqrt(d_k), k, v


def kda_mixer(h, w, heads, d_k, d_v, eps, neg_eigval, r=lambda a: a,
              without=(), state=lambda a: a):
    """The Kimi Delta Attention mixer of the normed input [B, T, E] ->
    [B, T, E]: steps 1-6 of the module's text.  ``r`` rounds every
    matmul operand, ``state`` the recurrence's state after every token."""
    B, T, _ = h.shape
    q, k, v = kda_operands(h, w, heads, d_k, d_v, r, without)
    alpha, beta = kda_gates(h, w, heads, d_k, neg_eigval, without)
    o, _ = recurrence(r(q), r(k), r(v), alpha, beta, state)
    if "out_norm" not in without:
        o = rmsnorm(o, w["o_norm"], eps)
    o = o.reshape(B, T, heads * d_v)
    if "kda_gate" not in without:
        gate = r(r(h) @ r(w["w_g_down"])) @ r(w["w_g_up"])
        if "gate_bias" not in without:
            gate = gate + w["b_g"]
        o = o * jax.nn.sigmoid(gate)
    # departure (the head share): ``wo`` holds the held heads' rows, so
    # this is their part of the W_o product; the absent heads' part is
    # not added, and nothing stands in for the group's all-reduce
    return r(o) @ r(w["wo"])


def attention(h, w, heads, kv_heads, head_dim, r=lambda a: a, without=()):
    """Gated causal attention of the normed input, no positional
    encoding, no QK norm, a block of queries at a time."""
    B, T, _ = h.shape
    q = (r(h) @ r(w["wq"])).reshape(B, T, heads, head_dim)
    k = (r(h) @ r(w["wk"])).reshape(B, T, kv_heads, head_dim)
    v = (r(h) @ r(w["wv"])).reshape(B, T, kv_heads, head_dim)
    # the long way: every query head its own copy of its K/V head
    k = jnp.repeat(k, heads // kv_heads, axis=2)
    v = jnp.repeat(v, heads // kv_heads, axis=2)
    out = []
    for start in range(0, T, QUERY_BLOCK):
        stop = min(start + QUERY_BLOCK, T)
        scores = jnp.einsum("bqhd,bkhd->bhqk", r(q[:, start:stop]),
                            r(k[:, :stop])) / np.sqrt(head_dim)
        seen = (jnp.arange(stop)[None, :]
                <= jnp.arange(start, stop)[:, None])
        scores = jnp.where(seen, scores, -jnp.inf)
        out.append(jnp.einsum("bhqk,bkhd->bqhd",
                              r(jax.nn.softmax(scores, -1)),
                              r(v[:, :stop])))
    out = jnp.concatenate(out, axis=1).reshape(B, T, heads * head_dim)
    if "attn_gate" not in without:     # a value's own gate
        out = out * jax.nn.sigmoid(r(h) @ r(w["w_attn_gate"]))
    # departure (the head share): the held heads' part of the W_o product
    return r(out) @ r(w["wo"])


def swiglu(u, gate, up, down, r):
    return r(jax.nn.silu(r(u) @ r(gate)) * (r(u) @ r(up))) @ r(down)


def shared_expert(u, w, r=lambda a: a):
    return swiglu(u, w["ws_gate"], w["ws_up"], w["ws_down"], r)


def route(u, w_router, bias, top_k):
    """(scores [B, T, X], chosen [B, T, X] bool) of float32 inputs: the
    ``top_k`` largest of sigmoid + bias."""
    scores = jax.nn.sigmoid(u @ w_router)
    biased = scores + bias
    kth = jnp.sort(biased, axis=-1)[..., -top_k]
    return scores, biased >= kth[..., None]


def held_experts(u, w, weights, first, r=lambda a: a):
    """The held experts' part of the routed result [B, T, E]: every held
    expert's SwiGLU on every token, weighted by ``weights`` [B, T, X],
    a token's weight of each of all X experts (0 where not chosen)."""
    y = jnp.zeros_like(u)
    for e in range(w["w_gate"].shape[0]):
        y = y + weights[..., first + e, None] * swiglu(
            u, w["w_gate"][e], w["w_up"][e], w["w_down"][e], r)
    return y


def experts(u, w, top_k, norm_topk, scale, first, r=lambda a: a):
    """(the held experts' part of the routed result [B, T, E], chosen
    [B, T, X]) of the normed input, by the routing over all X experts."""
    scores, chosen = route(u, w["w_router"], w["expert_bias"], top_k)
    weights = jnp.where(chosen, scores, 0.0)
    if norm_topk:
        weights = weights / (weights.sum(-1, keepdims=True) + ROUTE_EPS)
    # departure (the expert share): the held experts alone
    return held_experts(u, w, weights * scale, first, r), chosen


def head_loss(x, head, tokens, r):
    """Per-sequence mean next-token cross entropy of the normed stream
    ``x`` [B, T, E], the logits taken a block of rows at a time."""
    total = 0.0
    T = x.shape[1]
    for start in range(0, T - 1, HEAD_BLOCK):
        stop = min(start + HEAD_BLOCK, T - 1)
        logp = jax.nn.log_softmax(r(x[:, start:stop]) @ r(head), axis=-1)
        picked = jnp.take_along_axis(
            logp, tokens[:, start + 1:stop + 1, None], axis=-1)[..., 0]
        total = total - picked.sum(axis=-1)
    return total / (T - 1)


def rounding(rounded):
    """a -> a through dtype ``rounded`` and back; the identity for None.
    bfloat16 by ``reduce_precision``: a convert to bfloat16 and back is
    a pair XLA's TPU backend may drop as excess precision."""
    if rounded is None:
        return lambda a: a
    if jnp.dtype(rounded) == jnp.bfloat16:
        return lambda a: jax.lax.reduce_precision(a, 8, 7)
    return lambda a: a.astype(rounded).astype(jnp.float32)


def mixer(kind, h, w, heads, kv_heads, head_dim, delta_heads, d_k, d_v,
          neg_eigval, eps, r=lambda a: a, without=(), state=lambda a: a):
    """The mixer of a layer of ``kind`` ("linear" | "softmax")."""
    if kind == "linear":
        return kda_mixer(h, w, delta_heads, d_k, d_v, eps, neg_eigval, r,
                         without, state)
    assert kind == "softmax", kind
    return attention(h, w, heads, kv_heads, head_dim, r, without)


def loss(params, tokens, heads, kv_heads, head_dim, delta_heads, d_k, d_v,
         neg_eigval, eps, kinds, top_k, norm_topk, scale, first,
         rounded=None, parts=PARTS, without=(), state=None):
    """(per-sequence loss [B], [Seen of each layer]); tokens [B, T]
    int32.  ``rounded`` is a dtype through which every matmul operand
    outside the router is rounded first, in the ``parts`` named (all of
    PARTS: what this model would give computed in that precision,
    PERF.md's second reading); ``state`` a dtype through which the delta
    rule's state passes after every token.  ``without`` names the PIECES
    to leave out (what a test tells apart)."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    r = {part: rounding(rounded if part in parts else None)
         for part in PARTS}
    # departures (the cut): the ids, the logits and the loss are over a
    # slice of the vocabulary; ``kinds`` are published layers 0-3 alone
    x = f32(params["embed"])[tokens]
    seen = []
    for kind, w in zip(kinds, layers_of(params), strict=True):
        w = {k: f32(v) for k, v in w.items()}
        part = "kda" if kind == "linear" else "attention"
        h = rmsnorm(x, w["ln1"], eps)
        x = x + mixer(kind, h, w, heads, kv_heads, head_dim, delta_heads,
                      d_k, d_v, neg_eigval, eps, r[part], without,
                      rounding(state))
        u = rmsnorm(x, w["ln2"], eps)
        y, chosen = experts(u, w, top_k, norm_topk, scale, first,
                            r["experts"])
        seen.append(Seen(chosen, h, u))
        x = x + y + shared_expert(u, w, r["shared"])
    x = rmsnorm(x, f32(params["ln_f"]), eps)
    return head_loss(x, f32(params["lm_head"]), tokens, r["head"]), seen


def scan_statistics(config, params, seen):
    """The quartiles over (token, head, channel) of ``alpha``, over
    (token, head) of ``beta`` and, over the heads, of the Frobenius norm
    of the state after the last token, in each linear layer at the
    weights as drawn: what says whether the scan the comparison holds is
    a trivial one (every decay ~0 or ~1)."""
    shape = shape_of(config)
    quartiles = lambda a: [float(x) for x in np.quantile(
        np.asarray(a, np.float64).ravel(), (0.25, 0.5, 0.75))]
    out = []
    for s, w, kind in zip(seen, layers_of(params), shape["kinds"]):
        if kind != "linear":
            continue
        w = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}

        @jax.jit
        def stats(h, w):
            alpha, beta = kda_gates(h, w, shape["delta_heads"],
                                    shape["d_k"], shape["neg_eigval"])
            q, k, v = kda_operands(h, w, shape["delta_heads"],
                                   shape["d_k"], shape["d_v"], lambda a: a)
            _, S = recurrence(q, k, v, alpha, beta)
            return alpha, beta, jnp.sqrt(jnp.sum(S * S, axis=(-1, -2)))

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


def _float32_layers(params):
    return [{k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
            for w in layers_of(params)]


def check_routing(config, params, seen, top_k):
    """The program's router against this file's on the same inputs: the
    reference's own router inputs of each layer (``seen``), rounded to
    the program's compute dtype as the program's are.  One JSON line on
    stderr; raises under SAME_INPUT_ROUTING_FLOOR."""
    from elasticdl_tpu.models import transformer as tfm

    cfg = program_config(config)

    @jax.jit
    def both(u, w_router, bias):
        u = u.astype(jnp.dtype(cfg.dtype))
        theirs = jax.nn.one_hot(tfm.moe_route(u, w_router, cfg, bias)[2],
                                cfg.moe_experts).sum(-2) > 0
        ours = route(u.astype(jnp.float32), w_router, bias, top_k)[1]
        return (theirs & ours).sum() / theirs.sum()

    with jax.default_matmul_precision("highest"):
        same_input = min(
            float(both(s.u, w["w_router"], w["expert_bias"]))
            for s, w in zip(seen, _float32_layers(params)))
    print(json.dumps({"routing_same_input": same_input,
                      "floor": SAME_INPUT_ROUTING_FLOOR}),
          file=sys.stderr, flush=True)
    if same_input < SAME_INPUT_ROUTING_FLOOR:
        raise SystemExit(
            "the program's router chose other experts than a float32 "
            "router on the same inputs: %.5f of the pairs agree, under "
            "%.4f" % (same_input, SAME_INPUT_ROUTING_FLOOR))


def layer_errors(config, rounded=None, state=None, without=()):
    """A function of (params, seen) that gives {part: the largest over
    the layers of |got - want| / |want|, the norms over a layer's whole
    [B, T, E] result, which no mean over the sequence can cancel} of
    LAYER_PARTS on the same inputs: the reference's own normed inputs of
    each layer's mixer and FFN (``seen``), rounded to the program's
    compute dtype as the program's are.  ``want`` is this file's float32
    math; ``got`` the program's own functions
    (``models/transformer._delta_mix`` with its convolution and scan
    kernels, ``_attention_mix`` with its flash kernels and gate,
    ``_shared_expert``, ``_moe_ffn``) or, with ``rounded``, ``state`` or
    ``without``, this file's with every matmul operand rounded through
    the one dtype, the delta rule's state through the other, the PIECES
    named left out.  The routed part takes the program's route on both
    sides (``check_routing`` holds the route itself)."""
    from elasticdl_tpu.models import transformer as tfm

    cfg = program_config(config)
    shape = shape_of(config)
    mix = {k: shape[k] for k in ("heads", "kv_heads", "head_dim",
                                 "delta_heads", "d_k", "d_v", "neg_eigval",
                                 "eps")}
    dtype = jnp.dtype(cfg.dtype)
    lower = rounded is not None or state is not None or bool(without)
    r = rounding(rounded)

    @functools.partial(jax.jit, static_argnums=(3, 4))
    def program(h, u, w, said, kind):
        h, u = h.astype(dtype), u.astype(dtype)
        route = tfm.moe_route(u, w["w_router"], cfg, w["expert_bias"])
        weights = (jax.nn.one_hot(route[2], cfg.moe_experts)
                   * route[1][..., None]).sum(-2)
        if lower:
            f32 = lambda a: a.astype(jnp.float32)
            return weights, (
                mixer(said, f32(h), w, r=r, state=rounding(state),
                      without=without, **mix),
                shared_expert(f32(u), w, r),
                held_experts(f32(u), w, weights, shape["first"], r))
        if kind.op == "d":
            mixed = tfm._delta_mix(h, w, cfg)
        else:
            positions = jnp.arange(h.shape[1])
            mixed = tfm._attention_mix(h, w, cfg, None, positions, kind)[0]
        return weights, (mixed, tfm._shared_expert(u, w, cfg),
                         tfm._moe_ffn(u, w, cfg, None, route)[0])

    @functools.partial(jax.jit, static_argnums=5)
    def apart(h, u, w, weights, got, said):
        h, u = (a.astype(dtype).astype(jnp.float32) for a in (h, u))
        want = (mixer(said, h, w, **mix), shared_expert(u, w),
                held_experts(u, w, weights, shape["first"]))
        norm = lambda a: jnp.sqrt(jnp.sum(jnp.square(a)))
        return [norm(g.astype(jnp.float32) - w_) / norm(w_)
                for g, w_ in zip(got, want)]

    def errors(params, seen):
        worst = dict.fromkeys(LAYER_PARTS, 0.0)
        for s, w, said, kind in zip(seen, _float32_layers(params),
                                    shape["kinds"], cfg.kinds, strict=True):
            # the program's side as lib/compare.py runs the product: at
            # the default precision; this file's math at the highest
            with jax.default_matmul_precision(
                    "highest" if lower else "default"):
                weights, got = program(s.h, s.u, w, said, kind)
            with jax.default_matmul_precision("highest"):
                found = apart(s.h, s.u, w, weights, got, said)
            part = "kda" if said == "linear" else "attention"
            for name, error in zip(
                    (part, "shared_expert", "routed_experts"), found):
                worst[name] = max(worst[name], float(error))
        return worst

    return errors


def check_layers(config, params, seen):
    """The program's KDA mixer, gated attention, shared expert and held
    experts against this file's on the same inputs (``layer_errors``).
    One JSON line on stderr; raises over SAME_INPUT_LAYER_CEILING."""
    errors = layer_errors(config)(params, seen)
    print(json.dumps({"layers_same_input": errors,
                      "ceiling": SAME_INPUT_LAYER_CEILING}),
          file=sys.stderr, flush=True)
    over = {part: error for part, error in errors.items()
            if not error <= SAME_INPUT_LAYER_CEILING}
    if over:
        raise SystemExit(
            "the program's layers lie further from float32 math on the "
            "same inputs than the stated precision allows: %s, over %.1e"
            % (", ".join("%s %.2e" % item for item in sorted(over.items())),
               SAME_INPUT_LAYER_CEILING))
