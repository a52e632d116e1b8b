"""Plain reference of the ``xing4.0-29b-a4b`` configuration's loss.

Xing4.0-29B-A4B's layer equations (``model_type: xing4_0``) as its public
config gives them, in straightforward ``jax.numpy`` and float32, with no
kernel, no scan, no sort, no remat.  A token's residual stream is ``X``
in R^{4 x C} (``hc_mult`` 4; here ``[B, T, 4, C]``), ``X_0`` the
embedding four times.  For a block with input ``X``, each of its two
sublayers ``F`` (attention with ``RMSNorm_1``, the FFN with
``RMSNorm_2``) owns ``phi`` [4 C, 4 + 4 + 16], three scalars ``alpha``
and a bias of 24 (manifold-constrained hyper-connections,
arXiv:2512.24880):

 - ``x~ = vec(X) / rms(vec(X))``; ``[p, q, r] = x~ phi``;
   ``H_pre = sigmoid(alpha_pre p + b_pre)``;
   ``H_post = 2 sigmoid(alpha_post q + b_post)``;
   ``H_res = SK(exp(clamp(alpha_res mat(r) + b_res, -30, 30)))``, ``SK``
   20 rounds (``hc_sinkhorn_iters``) of each row over its sum, then each
   column over its sum, ``+ hc_eps`` = 1e-6: a Python loop here;
 - ``u = sum_i H_pre[i] X[i]``; ``y = F(RMSNorm(u))``;
   ``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y``.

Attention (``kanana-2-30b-a3b``'s latent attention plus a query latent
and YaRN): ``c_q = RMSNorm(h W_qa)`` of 768 (``q_lora_rank``), ``q = c_q
W_qb``: heads of 192, ``q_nope`` 128 | ``q_rope`` 64.  ``c = h W_kva``:
512 + 64; ``c_kv = RMSNorm(c[:512])``, ``k_rope = c[512:]``, ONE key of
64 for all the heads; ``c_kv W_kvb``: heads of ``k_nope`` 128 | ``v``
128.  RoPE (theta 10,000) turns ``q_rope`` and ``k_rope`` alone, pairs
NEIGHBOURS ``(2i, 2i + 1)``, its frequencies under YaRN (``factor`` 64
over ``original_max_position_embeddings`` 4,096, ``beta_fast`` 32,
``beta_slow`` 1: ``yarn_frequencies``); ``mscale = mscale_all_dim = 1``,
so cos and sin are unscaled and the softmax scale is ``192^-1/2 m^2``,
``m = 0.1 ln 64 + 1``.  Causal softmax over the whole sequence in blocks
of ``QUERY_BLOCK`` queries; ``concat(heads) W_o``.

FFN: the leading dense layer a SwiGLU of 9,216; the others ``s =
sigmoid(u W_r)`` over all 64 experts in float32, the 4 largest of ``s +
e_score_correction_bias`` chosen, their weights the unbiased ``s`` over
their sum times ``routed_scaling_factor`` 2, experts SwiGLUs of 1,024,
PLUS one shared SwiGLU of 1,024: ``sum_e w_e Expert_e(u) + Shared(u)``.

After the last layer the stream is read through one ``H_pre``-like map
(``hc_out``), then RMSNorm, an untied head, the mean next-token cross
entropy.  One multi-token-prediction module (``num_nextn_predict_layers``
1, DeepSeek-V3's form): ``h' = [RMSNorm(h_t) ; RMSNorm(E[token_(t+1)])]
W_proj`` (7168 x 3584) of the hidden state ``h`` before the final norm,
four times as a stream of its own, one whole expert-layer block, its own
``hc_out`` map, the SHARED final norm and head, the mean cross entropy of
``token_(t+2)`` over the T - 2 positions that have one; ``loss = main +
0.1 mtp``, each returned apart (``loss``'s first two results).

Departures from the published model, each the configuration's
(``configs/xing4.0-29b-a4b.json``: ``reduced``, ``assumed``):

 - the shares: the weights hold heads ``0 .. 8`` of 32 (a layer's
   attention is their part of the ``W_o`` product), experts ``first ..
   first + held`` of the router's 64 and a slice of the vocabulary;
   what the absent heads and experts would add is left out, here as in
   the program, and that partial result goes on;
 - the division by the chosen scores' sum adds 1e-6 (the program's);
 - position t of the module's second input past the sequence's end
   takes the sequence's first tokens' embeddings, as the program's
   ``roll`` does: no loss reads those positions and causal attention
   lets no other see them;
 - no balance loss (the config gives no coefficient).

``params`` is the program's own tree, so the same seeded weights go
through both.  The program holds the stream as ``[B, T, 4 C]`` (stream i
is columns ``i C .. (i + 1) C``: ``vec(X)``'s order, so ``phi`` is the
same matrix) and turns the two HALVES of a RoPE part, so it holds the
RoPE columns of ``w_q_b`` and ``w_kv_a`` evens first, then odds:
``published_order`` maps them back before this file's RoPE pairs
neighbours.  The caller sets ``jax.default_matmul_precision("highest")``.
"""

import collections
import functools
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

# The total loss's largest relative difference (mean over the two
# sequences).  Two readings on the chip at the published widths and the
# timed sizes, 2 x 4,096 tokens (``tools/xing4_precision.py``, seeds 11,
# 3000000019, 77; my chip run, PR 54, call ``p1``; PERF.md section 6):
# the product, bfloat16 as the configuration states, reads 7.7e-6,
# 1.7e-4 and 2.4e-5; this reference with every matmul operand outside
# the routers and the maps rounded to float8 (e4m3), the nearest
# precision below, 9.8e-4, 1.2e-3 and 1.7e-3.  The limit is their
# geometric middle, 2.4 times of room on either side.  The mean over
# 8,190 losses is a zero-mean draw, so this limit is the narrowest of
# the five between its readings; the layers' limits below stand 1.7 to
# 10 times from theirs, and float8 fails every one of them on every
# seed, as a bfloat16 Sinkhorn fails SINKHORN_COLUMN_CEILING.  A module
# that fell out of the loss moves it by ``0.1 mtp / (main + 0.1 mtp)``,
# 9.1e-2 on each seed: two hundred times this limit.
TOLERANCE = 4e-4
# As ``kanana-2-30b-a3b``'s: both routers float32 at the highest
# precision, only exact ties may differ.
SAME_INPUT_ROUTING_FLOOR = 0.9998
# The largest relative distance (norms over a part's whole result, so no
# mean over the sequence cancels anything) of the program's latent
# attention, shared expert and held experts from this file's float32
# math on the same inputs, the worst of the layers.  Readings (call
# ``p1``, three seeds, each within 1% of the others): attention 9.1e-3
# against float8's 1.57e-1, the shared expert 4.2e-3 against 4.67e-2,
# the held experts 4.9e-3 against 7.59e-2.  One limit for the three, as
# ``kanana-2-30b-a3b``'s: 1.7 times over the product's largest and 3.1
# times under float8's smallest.
SAME_INPUT_LAYER_CEILING = 1.5e-2
# The same of the multi-token-prediction module's hidden state, which is
# no one layer: two norms and a projection, a whole block (attention, a
# router of its own, held and shared experts, four maps) and a narrowing
# map behind one another, each in bfloat16 on the last one's bfloat16
# result, and its router may break a near-tie the other way.  Readings:
# 1.54e-2, 1.65e-2, 1.76e-2 (``p1``) and 1.94e-2 (``c1``'s seed) against
# float8's 6.9e-2, 7.4e-2, 8.6e-2; the limit 1.8 times over the one and
# 2.0 times under the other.
SAME_INPUT_MTP_CEILING = 3.5e-2
# The same of ONE mixing sublayer (read X, write X' round a given y: the
# program's ``ops/hyper_mix.pre`` and ``post``, the kernels where
# kernels run) on the reference's own stream.  Readings: 1.70e-3 to
# 1.73e-3 against 1.90e-2 to 1.95e-2 with the stream and the result
# rounded to float8: 4.6 times over the one, 2.4 under the other.
SAME_INPUT_MIXING_CEILING = 8e-3
# The largest ``|column sum - 1|`` of that sublayer's H_res, any token.
# The last half round divides every column by its sum ``+ hc_eps``, so
# float32 rounds leave 1.25e-6 (``p1``, every seed) whatever the rows'
# convergence (which is the algorithm's: 20 rounds leave the ROWS 1e-4
# .. 1e-2 off in float64 too) and bfloat16 rounds 3.906e-3: a
# precision's mark and not the iteration's.  The stream's distance above
# does not tell them: the rounds pull a rounding error back, and a
# bfloat16 Sinkhorn moves X' by 2.1e-3, what the program's own bfloat16
# stream moves it by (its loss by 8e-6 to 9e-5, under TOLERANCE).
SINKHORN_COLUMN_CEILING = 1e-4
PARTS = ("attention", "dense", "experts", "shared", "head", "mtp")
LAYER_PARTS = ("attention", "shared_expert", "routed_experts", "mtp",
               "mixing", "sinkhorn_columns")
# what ``loss`` saw of a layer with experts: the router's choice
# [B, T, X] bool, attention's and the FFN's normed inputs [B, T, E]
Seen = collections.namedtuple("Seen", "chosen h u")
# and of the model: one sublayer's stream [B, T, 4, C] and result [B,
# T, C] (the second layer's attention), the hidden state before the
# final norm and the embeddings
Probe = collections.namedtuple("Probe", "stream result hidden embedded")
MICROBATCH = 2
HEAD_SCALE = 5.0
BIAS_SCALE = 0.1
# what ``inputs`` draws the maps' alpha and biases at: a dynamic part of
# order 1 and an H_res that is no identity, so that the mixing is
# compared and not its initial value
HYPER_ALPHA = 1.0
HYPER_BIAS_SCALE = 0.5
QUERY_BLOCK = 1024
HEAD_BLOCK = 2048
ROUTE_EPS = 1e-6
CLAMP = (-30.0, 30.0)


def shape_of(config):
    """What ``loss`` needs of the configuration's file."""
    scaling = config["rope_scaling"]
    return dict(
        heads=config["num_attention_heads"],
        rank=config["kv_lora_rank"], q_rank=config["q_lora_rank"],
        d_nope=config["qk_nope_head_dim"],
        d_rope=config["qk_rope_head_dim"],
        d_v=config["v_head_dim"],
        top_k=config["num_experts_per_tok"],
        eps=config["rms_norm_eps"], theta=float(config["rope_theta"]),
        yarn=(float(scaling["factor"]),
              float(scaling["original_max_position_embeddings"]),
              float(scaling["beta_fast"]), float(scaling["beta_slow"])),
        norm_topk=config["norm_topk_prob"],
        scale=float(config["routed_scaling_factor"]),
        first=config.get("share_index", 0) * config["n_routed_experts"],
        streams=config["hc_mult"], iters=config["hc_sinkhorn_iters"],
        sk_eps=config["hc_eps"],
        mtp_weight=float(config["mtp_loss_factor"]))


def inputs(config, params, rng):
    """(params, tokens [MICROBATCH, seq_len]) as both sides shall use
    them.  The head is drawn 5 times wider than the product's 0.02 and
    every ``expert_bias`` at 0.1, as ``kanana-2-30b-a3b``'s reference
    argues; every hyper-connection's ``alpha`` is set to 1 and its bias
    moved by 0.5 a value (the job starts them at 0.01 and at the
    identity, where a wrong map changes little)."""
    tokens = jnp.asarray(rng.integers(
        0, config["vocab_size"], (MICROBATCH, config["seq_len"])), jnp.int32)
    params["lm_head"] = params["lm_head"] * HEAD_SCALE

    def redraw(w):
        for name in list(w):
            if name == "expert_bias":
                w[name] = jnp.asarray(
                    BIAS_SCALE * rng.standard_normal(w[name].shape),
                    jnp.float32)
            elif name.endswith("_alpha"):
                w[name] = jnp.full_like(w[name], HYPER_ALPHA)
            elif name.startswith("hc") and name.endswith("_bias"):
                w[name] = w[name] + jnp.asarray(
                    HYPER_BIAS_SCALE * rng.standard_normal(w[name].shape),
                    jnp.float32)
                if w[name].shape[-1] > config["hc_mult"]:
                    # H_res: off the identity it starts at
                    n = config["hc_mult"]
                    w[name] = w[name].at[..., 2 * n:].multiply(0.25)
            elif isinstance(w[name], dict):
                redraw(w[name])

    redraw(params)
    return params, tokens


def case(config, params, rng, key):
    """See benchmark/lib/compare.py.  The reference runs once, here, at
    the highest matmul precision: its loss is what the returned function
    hands back; what it saw of its layers is what the routing check and
    the layer check read (stderr; each raises past its limit)."""
    params, tokens = inputs(config, params, rng)
    shape = shape_of(config)
    with jax.default_matmul_precision("highest"):
        main, mtp, seen, probe = loss(params, tokens, **shape)
    check_routing(config, params, seen, shape["top_k"])
    check_layers(config, params, seen, probe, tokens)
    print(json.dumps({"main_loss": float(main.mean()),
                      "mtp_loss": float(mtp.mean())}),
          file=sys.stderr, flush=True)
    per_record = main + shape["mtp_weight"] * mtp
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


def published_order(columns):
    """[.., D_rope] RoPE columns as the program holds them (evens first,
    then odds) -> the published order, neighbours (2i, 2i + 1) a pair."""
    half = columns.shape[-1] // 2
    return jnp.stack([columns[..., :half], columns[..., half:]],
                     axis=-1).reshape(columns.shape)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def yarn_frequencies(d, theta, yarn):
    """[d / 2] float32: RoPE's frequencies for a part of ``d`` under
    YaRN, as the deepseek_v3 family's rotary embedding computes them:
    ``inter = extra / factor``; the correction dimensions ``low =
    floor(dim(beta_fast))``, ``high = ceil(dim(beta_slow))``, ``dim(n) =
    d ln(original / (2 pi n)) / (2 ln theta)``; ``mask = 1 - clip((i -
    low) / (high - low), 0, 1)``; ``inter (1 - mask) + extra mask``."""
    factor, original, fast, slow = yarn
    extra = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    inter = extra / factor
    dim = lambda turns: d * np.log(original / (turns * 2 * np.pi)) / (
        2 * np.log(theta))
    low, high = max(np.floor(dim(fast)), 0), min(np.ceil(dim(slow)), d - 1)
    if low == high:
        high += 0.001
    mask = 1.0 - np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    return jnp.asarray(inter * (1 - mask) + extra * mask, jnp.float32)


def rope_pairs(x, theta, yarn):
    """x: [B, T, H, D]; rotate each pair of neighbours (2i, 2i + 1) of D
    by position, pair i at its YaRN frequency."""
    freqs = yarn_frequencies(x.shape[-1], theta, yarn)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def softmax_scale(d_nope, d_rope, yarn):
    m = 0.1 * np.log(yarn[0]) + 1.0
    return float(m * m / np.sqrt(d_nope + d_rope))


def route(u, w_router, bias, top_k):
    """(scores [B, T, X], chosen [B, T, X] bool) of float32 inputs: the
    ``top_k`` largest of sigmoid + bias."""
    scores = jax.nn.sigmoid(u @ w_router)
    biased = scores + bias
    kth = jnp.sort(biased, axis=-1)[..., -top_k]
    return scores, biased >= kth[..., None]


def attention(h, w, heads, rank, q_rank, d_nope, d_rope, d_v, eps, theta,
              yarn, r):
    """Causal latent attention of the normed input, a block of queries
    at a time: the held heads' part of the ``W_o`` product."""
    B, T, _ = h.shape
    w_q_b = w["w_q_b"].reshape(q_rank, heads, d_nope + d_rope)
    w_q_b = jnp.concatenate(
        [w_q_b[..., :d_nope], published_order(w_q_b[..., d_nope:])],
        axis=-1).reshape(w["w_q_b"].shape)
    w_kv_a = jnp.concatenate(
        [w["w_kv_a"][:, :rank], published_order(w["w_kv_a"][:, rank:])],
        axis=-1)
    c_q = rmsnorm(r(h) @ r(w["w_q_a"]), w["q_norm"], eps)
    q = (r(c_q) @ r(w_q_b)).reshape(B, T, heads, d_nope + d_rope)
    c = r(h) @ r(w_kv_a)                               # [B, T, rank + Dr]
    c_kv = rmsnorm(c[..., :rank], w["kv_norm"], eps)
    kv = (r(c_kv) @ r(w["w_kv_b"])).reshape(B, T, heads, d_nope + d_v)
    k_nope, v = kv[..., :d_nope], kv[..., d_nope:]
    q_rope = rope_pairs(q[..., d_nope:], theta, yarn)
    k_rope = rope_pairs(c[..., None, rank:], theta, yarn)    # one head
    q = jnp.concatenate([q[..., :d_nope], q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (B, T, heads, d_rope))], axis=-1)
    scale = softmax_scale(d_nope, d_rope, yarn)
    out = []
    for start in range(0, T, QUERY_BLOCK):
        stop = min(start + QUERY_BLOCK, T)
        scores = jnp.einsum("bqhd,bkhd->bhqk", r(q[:, start:stop]),
                            r(k[:, :stop])) * scale
        causal = (jnp.arange(start, stop)[:, None]
                  >= jnp.arange(stop)[None, :])
        scores = jnp.where(causal, scores, -jnp.inf)
        out.append(jnp.einsum("bhqk,bkhd->bqhd",
                              r(jax.nn.softmax(scores, -1)), r(v[:, :stop])))
    out = jnp.concatenate(out, axis=1).reshape(B, T, heads * d_v)
    return r(out) @ r(w["wo"])


def swiglu(u, gate, up, down, r):
    return r(jax.nn.silu(r(u) @ r(gate)) * (r(u) @ r(up))) @ r(down)


def held_experts(u, w, weights, first, r=lambda a: a):
    """The held experts' part of the routed result [B, T, E]: every held
    expert's SwiGLU on every token, weighted by ``weights`` [B, T, X]."""
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
    return held_experts(u, w, weights * scale, first, r), chosen


def sinkhorn(m, iters, eps, dtype=None):
    """m [.., n, n] positive -> ``iters`` rounds of rows over their
    sums, then columns over their sums.  ``dtype``: a lower precision
    the rounds run in (what a precision tool tells apart)."""
    if dtype is not None:
        m = m.astype(dtype)
    for _ in range(iters):
        m = m / (m.sum(axis=-1, keepdims=True) + eps)
        m = m / (m.sum(axis=-2, keepdims=True) + eps)
    return m.astype(jnp.float32)


def hyper_maps(X, w, name, eps, iters, sk_eps, sinkhorn_dtype=None):
    """(H_pre [B, T, n], H_post [B, T, n], H_res [B, T, n, n]) of the
    stream X [B, T, n, C] and the maps ``<name>_*`` of ``w``; a map that
    only reads (``hc_out``) gives H_pre alone."""
    B, T, n, C = X.shape
    x = X.reshape(B, T, n * C)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    z = x @ w[name + "_phi"]
    alpha, bias = w[name + "_alpha"], w[name + "_bias"]
    h_pre = jax.nn.sigmoid(alpha[0] * z[..., :n] + bias[:n])
    if z.shape[-1] == n:
        return h_pre, None, None
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * z[..., n:2 * n] + bias[n:2 * n])
    logits = (alpha[2] * z[..., 2 * n:] + bias[2 * n:]).reshape(B, T, n, n)
    h_res = sinkhorn(jnp.exp(jnp.clip(logits, *CLAMP)), iters, sk_eps,
                     sinkhorn_dtype)
    return h_pre, h_post, h_res


def read(X, h_pre):
    return jnp.einsum("btn,btnc->btc", h_pre, X)


def write(X, y, h_post, h_res):
    return (jnp.einsum("btij,btjc->btic", h_res, X)
            + h_post[..., None] * y[:, :, None, :])


def head_loss(x, head, tokens, r, shift=1):
    """Per-sequence mean cross entropy of the token ``shift`` on, of the
    normed stream ``x`` [B, T, E], the logits taken a block of rows at a
    time."""
    total = 0.0
    T = x.shape[1]
    for start in range(0, T - shift, HEAD_BLOCK):
        stop = min(start + HEAD_BLOCK, T - shift)
        logp = jax.nn.log_softmax(r(x[:, start:stop]) @ r(head), axis=-1)
        picked = jnp.take_along_axis(
            logp, tokens[:, start + shift:stop + shift, None],
            axis=-1)[..., 0]
        total = total - picked.sum(axis=-1)
    return total / (T - shift)


def rounding(rounded):
    """a -> a through dtype ``rounded`` and back; the identity for None."""
    if rounded is None:
        return lambda a: a
    return lambda a: a.astype(rounded).astype(jnp.float32)


def block(X, w, shape, r, sinkhorn_dtype=None, shared=True):
    """One block on the stream X [B, T, n, C] -> (X', Seen or None of a
    layer with experts, (the attention sublayer's stream and result))."""
    s = shape
    maps = lambda X, name: hyper_maps(X, w, name, s["eps"], s["iters"],
                                      s["sk_eps"], sinkhorn_dtype)
    h_pre, h_post, h_res = maps(X, "hc1")
    h = rmsnorm(read(X, h_pre), w["ln1"], s["eps"])
    y = attention(h, w, s["heads"], s["rank"], s["q_rank"], s["d_nope"],
                  s["d_rope"], s["d_v"], s["eps"], s["theta"], s["yarn"],
                  r["attention"])
    probe = (X, y)
    X = write(X, y, h_post, h_res)
    h_pre, h_post, h_res = maps(X, "hc2")
    u = rmsnorm(read(X, h_pre), w["ln2"], s["eps"])
    if "w_router" not in w:       # a leading dense layer
        y = swiglu(u, w["w_gate"], w["w_up"], w["w_down"], r["dense"])
        return write(X, y, h_post, h_res), None, probe
    y, chosen = experts(u, w, s["top_k"], s["norm_topk"], s["scale"],
                        s["first"], r["experts"])
    if shared:
        y = y + swiglu(u, w["ws_gate"], w["ws_up"], w["ws_down"],
                       r["shared"])
    return write(X, y, h_post, h_res), Seen(chosen, h, u), probe


def widen(x, n):
    return jnp.broadcast_to(x[:, :, None, :], x.shape[:2] + (n,)
                            + x.shape[2:])


def narrow(X, w, shape):
    return read(X, hyper_maps(X, w, "hc_out", shape["eps"], shape["iters"],
                              shape["sk_eps"])[0])


def mtp_input(w, hidden, embedded, shape, r):
    """What the multi-token-prediction module's block starts from:
    ``[RMSNorm(h_t) ; RMSNorm(E[token_(t+1)])] W_proj`` [B, T, C]."""
    joined = jnp.concatenate(
        [rmsnorm(hidden, w["norm_h"], shape["eps"]),
         rmsnorm(jnp.roll(embedded, -1, axis=1), w["norm_e"],
                 shape["eps"])], axis=-1)
    return r["mtp"](joined) @ r["mtp"](w["proj"])


def mtp_module(w, hidden, embedded, shape, r, sinkhorn_dtype=None):
    """The multi-token-prediction module's hidden state [B, T, C]
    before the shared final norm -> (it, its block's Seen)."""
    x = mtp_input(w, hidden, embedded, shape, r)
    X, seen, _ = block(widen(x, shape["streams"]), w["layer"], shape, r,
                       sinkhorn_dtype)
    return narrow(X, w, shape), seen


def out_map(w):
    """The ``hc_out`` map among the weights ``w``."""
    return {k: v for k, v in w.items() if k.startswith("hc_out")}


@functools.lru_cache(maxsize=None)
def pieces(shape, rounded=None, parts=PARTS, shared=True,
           sinkhorn_dtype=None):
    """``loss``'s pieces, each a program of its own: (one block (X, w)
    -> ``block``'s results; the stream read through an ``hc_out`` map;
    (hidden, ln_f, lm_head, tokens, shift) -> the head's loss; the
    module's input (module, hidden, embedded)).  ``shape``:
    ``shape_of``'s items as a sorted tuple.  So that the four expert
    layers and the module's block compile once between them, and so
    that ``layer_errors`` runs the very programs ``loss`` compiled:
    whole, ``loss`` took the TPU's compiler 85 s and the module's check
    25 more of the 300 s the harness gives the comparison (my chip
    runs, PR 54, calls ``c3``, ``c4``).  Under a caller's ``jit`` the
    pieces are inlined and nothing changes."""
    shape = dict(shape)
    r = {part: rounding(rounded if part in parts else None)
         for part in PARTS}
    return (
        jax.jit(lambda X, w: block(X, w, shape, r, sinkhorn_dtype, shared)),
        jax.jit(lambda X, w: narrow(X, w, shape)),
        jax.jit(lambda hidden, ln_f, lm_head, tokens, shift: head_loss(
            rmsnorm(hidden, ln_f, shape["eps"]), lm_head, tokens,
            r["head"], shift), static_argnums=4),
        jax.jit(lambda w, hidden, embedded: mtp_input(
            {k: w[k] for k in ("norm_h", "norm_e", "proj")}, hidden,
            embedded, shape, r)))


def loss(params, tokens, rounded=None, parts=PARTS, shared=True,
         sinkhorn_dtype=None, **shape):
    """(main loss [B], the module's loss [B] before its weight, [Seen of
    each layer with experts, the module's last], Probe); tokens [B, T]
    int32.  ``rounded`` is a dtype through which every matmul operand
    outside the routers and the maps is rounded first, in the ``parts``
    named; ``sinkhorn_dtype`` a dtype the Sinkhorn rounds run in;
    ``shared`` False leaves the shared expert out."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    params = jax.tree_util.tree_map(f32, params)
    run, read_out, head, mtp_in = pieces(
        tuple(sorted(shape.items())), rounded, tuple(parts), shared,
        sinkhorn_dtype)
    embedded = params["embed"][tokens]
    n = shape["streams"]
    X = widen(embedded, n)
    seen, probe = [], None
    for i, w in enumerate(layers_of(params)):
        X, saw, sub = run(X, w)
        if saw is not None:
            seen.append(saw)
        if i == 1:
            probe = sub
    hidden = read_out(X, out_map(params))
    main = head(hidden, params["ln_f"], params["lm_head"], tokens, 1)
    module = params["mtp"]["0"]
    X, saw, _ = run(widen(mtp_in(module, hidden, embedded), n),
                    module["layer"])
    seen.append(saw)
    mtp = head(read_out(X, out_map(module)), params["ln_f"],
               params["lm_head"], tokens, 2)
    return main, mtp, seen, Probe(*probe, hidden, embedded)


def expert_layers(params):
    """The weights of each layer with experts, the module's last, in
    float32, as ``loss`` lists what it saw of them."""
    f32 = lambda w: jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), w)
    return [f32(w) for w in layers_of(params) if "w_router" in w] + [
        f32(params["mtp"]["0"]["layer"])]


def program_config(config):
    """The program's ``TransformerConfig`` of the configuration's file."""
    from benchmark.lib.runner import params_string
    from elasticdl_tpu.models.spec import load_model_spec

    return load_model_spec(
        config["cli"]["model_zoo"],
        model_params=params_string(config["cli"]["model_params"])).config


def check_routing(config, params, seen, top_k):
    """The program's router against this file's on the same inputs (the
    reference's own router inputs of each layer, rounded to the
    program's compute dtype as the program's are).  One JSON line on
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
            for s, w in zip(seen, expert_layers(params)))
    print(json.dumps({"routing_same_input": same_input,
                      "floor": SAME_INPUT_ROUTING_FLOOR}),
          file=sys.stderr, flush=True)
    if same_input < SAME_INPUT_ROUTING_FLOOR:
        raise SystemExit(
            "the program's router chose other experts than a float32 "
            "router on the same inputs: %.5f of the pairs agree, under "
            "%.4f" % (same_input, SAME_INPUT_ROUTING_FLOOR))


def layer_errors(config, rounded=None, sinkhorn_dtype=None):
    """A function of (params, seen, probe, tokens) that gives {part: the
    largest over the layers of |got - want| / |want|, the norms over a
    part's whole result} of LAYER_PARTS on the same inputs: the
    reference's own (``seen``, ``probe``), rounded to the program's
    compute dtype as the program's are.  ``want`` is this file's float32
    math; ``got`` the program's own functions (``models/transformer.
    _latent_mix``, ``_shared_expert``, ``_moe_ffn``, ``_mtp_module``,
    ``ops/hyper_mix.pre`` / ``post``: the kernels where kernels run) or,
    with ``rounded`` / ``sinkhorn_dtype``, this file's in that
    precision.  The routed part takes the program's route on both
    sides (``check_routing`` holds the route itself); the module's
    block routes for itself on both, so a near-tie can reach it."""
    from elasticdl_tpu.models import transformer as tfm
    from elasticdl_tpu.ops import hyper_mix

    cfg = program_config(config)
    shape = shape_of(config)
    dtype = jnp.dtype(cfg.dtype)
    lower = rounded is not None or sinkhorn_dtype is not None
    r = rounding(rounded)
    rs = dict.fromkeys(PARTS, r)
    n = shape["streams"]
    attend = lambda h, w, r: attention(
        h, w, shape["heads"], shape["rank"], shape["q_rank"],
        shape["d_nope"], shape["d_rope"], shape["d_v"], shape["eps"],
        shape["theta"], shape["yarn"], r)
    share = lambda u, w, r: swiglu(u, w["ws_gate"], w["ws_up"],
                                   w["ws_down"], r)
    cast = lambda a: a.astype(dtype).astype(jnp.float32)

    def columns_off(h_res):
        return jnp.max(jnp.abs(h_res.sum(axis=-2) - 1.0))

    def mix(X, y, w, sk):
        """One sublayer's read and write, this file's: (u, X', how far
        H_res's columns are from summing to 1)."""
        h_pre, h_post, h_res = hyper_maps(X, w, "hc1", shape["eps"],
                                          shape["iters"], shape["sk_eps"], sk)
        return (read(X, h_pre), write(X, y, h_post, h_res),
                columns_off(h_res))

    @functools.partial(jax.jit, static_argnums=3)
    def program(h, u, w, kind):
        h, u = h.astype(dtype), u.astype(dtype)
        route = tfm.moe_route(u, w["w_router"], cfg, w["expert_bias"])
        weights = (jax.nn.one_hot(route[2], cfg.moe_experts)
                   * route[1][..., None]).sum(-2)
        if lower:
            f32 = lambda a: a.astype(jnp.float32)
            return weights, (
                attend(f32(h), w, r), share(f32(u), w, r),
                held_experts(f32(u), w, weights, shape["first"], r))
        positions = jnp.arange(h.shape[1])
        return weights, (
            tfm._latent_mix(h, w, cfg, positions, kind),
            tfm._shared_expert(u, w, cfg),
            tfm._moe_ffn(u, w, cfg, None, route)[0])

    @jax.jit
    def apart(h, u, w, weights, got):
        h, u = cast(h), cast(u)
        want = (attend(h, w, rounding(None)), share(u, w, rounding(None)),
                held_experts(u, w, weights, shape["first"]))
        return [distance(g, w_) for g, w_ in zip(got, want)]

    def distance(got, want):
        norm = lambda a: jnp.sqrt(jnp.sum(jnp.square(a)))
        return norm(got.astype(jnp.float32) - want) / norm(want)

    @jax.jit
    def module(w, hidden, embedded):
        if lower:
            return mtp_module(w, cast(hidden), cast(embedded), shape, rs,
                              sinkhorn_dtype)[0]
        positions = jnp.arange(hidden.shape[1])
        layer = lambda x, w1: tfm._layer_body(
            x, w1, cfg, None, positions, kind=cfg.mtp_kind)
        return tfm._mtp_module(w, hidden.astype(dtype),
                               embedded.astype(dtype), 0, cfg,
                               lambda kind: layer)[0]

    def module_apart(w, hidden, embedded, got):
        # this file's module by the programs ``loss`` ran
        # (``loss``'s own call, argument for argument: the cache's key)
        run, read_out, _, mtp_in = pieces(
            tuple(sorted(shape.items())), None, PARTS, True, None)
        X = run(widen(mtp_in(w, cast(hidden), cast(embedded)), n),
                w["layer"])[0]
        return jax.jit(distance)(got, read_out(X, out_map(w)))

    @jax.jit
    def mixing(w, X, y):
        B, T = y.shape[:2]
        if lower:
            return mix(r(cast(X)), r(cast(y)), w, sinkhorn_dtype)
        flat = X.reshape(B, T, -1).astype(dtype)
        u, through, maps, _ = hyper_mix.pre(
            flat, w["hc1_phi"], w["hc1_alpha"], w["hc1_bias"], n,
            shape["iters"], shape["eps"], shape["sk_eps"])
        out = hyper_mix.post(through, y.astype(dtype), maps, n)
        h_res = maps[..., 2 * n:2 * n + n * n].reshape(B, T, n, n)
        return u, out.reshape(B, T, n, -1), columns_off(h_res)

    @jax.jit
    def mixing_apart(w, X, y, got):
        want = mix(cast(X), cast(y), w, None)
        return jnp.maximum(distance(got[0], want[0]),
                           distance(got[1], want[1]))

    def errors(params, seen, probe, tokens):
        del tokens
        # a layer's Kind is the static argument: equal kinds compile once
        kinds = [kind for kind, w in zip(cfg.kinds, layers_of(params))
                 if "w_router" in w] + [cfg.mtp_kind]
        worst = dict.fromkeys(LAYER_PARTS, 0.0)
        at = lambda precision: jax.default_matmul_precision(precision)
        for s, w, kind in zip(seen, expert_layers(params), kinds):
            # the program's side as lib/compare.py runs the product: at
            # the default precision; this file's math at the highest
            with at("highest" if lower else "default"):
                weights, got = program(s.h, s.u, w, kind)
            with at("highest"):
                found = apart(s.h, s.u, w, weights, got)
            for part, error in zip(LAYER_PARTS, found):
                worst[part] = max(worst[part], float(error))
        f32 = lambda w: jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float32), w)
        w_mtp = f32(params["mtp"]["0"])
        with at("highest" if lower else "default"):
            got = module(w_mtp, probe.hidden, probe.embedded)
        with at("highest"):
            worst["mtp"] = float(module_apart(
                w_mtp, probe.hidden, probe.embedded, got))
        w_mix = f32(layers_of(params)[1])
        with at("highest" if lower else "default"):
            got = mixing(w_mix, probe.stream, probe.result)
        with at("highest"):
            worst["mixing"] = float(mixing_apart(
                w_mix, probe.stream, probe.result, got))
        worst["sinkhorn_columns"] = float(got[2])
        return worst

    return errors


def ceilings():
    own = {"mixing": SAME_INPUT_MIXING_CEILING,
           "mtp": SAME_INPUT_MTP_CEILING,
           "sinkhorn_columns": SINKHORN_COLUMN_CEILING}
    return {part: own.get(part, SAME_INPUT_LAYER_CEILING)
            for part in LAYER_PARTS}


def check_layers(config, params, seen, probe, tokens):
    """The program's latent attention, shared expert, held experts,
    multi-token-prediction module and one mixing sublayer against this
    file's on the same inputs (``layer_errors``).  One JSON line on
    stderr; raises over a part's ceiling."""
    errors = layer_errors(config)(params, seen, probe, tokens)
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
