"""Plain reference of the ``lfm2-24b-a2b`` configuration's loss.

LFM2-24B-A2B's layer equations as its public config and the family's
``modeling_lfm2_moe.py`` give them, in straightforward ``jax.numpy`` and
float32, with no kernel, no scan, no sort, no remat:

 - block: ``x = x + Op(RMSNorm(x))``; ``x = x + FFN(RMSNorm(x))``; after
   the last layer one RMSNorm, then the tied head.  RMSNorm (eps 1e-5)
   has a learned scale.
 - Op = attention: q of 32 heads of 64, k and v of 8, no bias; RMSNorm
   over each head's 64 values with one learned scale of 64 that the
   heads share, on q and on k, before RoPE (rotate-half, theta 1e6);
   causal softmax attention at scale 64^-0.5, query head i reading K/V
   head i // 4.  Computed in blocks of ``QUERY_BLOCK`` queries: all
   [32, T, T] float32 scores of one sequence of 8,192 are 8.6 GB.
 - Op = gated short convolution: ``B, C, u = split3(x W_in)``; ``g = B *
   u``; ``c_t = w_0 g_(t-2) + w_1 g_(t-1) + w_2 g_t`` per channel, zero
   before the sequence's start; ``out = (C * c) W_out``.
 - FFN = dense SwiGLU in the leading layers; elsewhere ``s = sigmoid(x
   W_r)`` over all the experts, the K chosen are the largest of ``s +
   expert_bias``, their weights the unbiased ``s`` of the chosen over
   their sum + 1e-6, times ``routed_scaling_factor``; every HELD
   expert's SwiGLU applied to every token and masked by the routing.

The share: the weights hold experts ``first .. first + held`` of the
router's ``published.num_experts`` and a slice of the vocabulary; what
the absent experts would add is left out, here as in the program, and
that partial result goes on to the next layer.  No balance loss (the
public code has none for this family).  The caller sets
``jax.default_matmul_precision("highest")``.

``params`` is the program's own tree (``layers`` = {"lead", "period",
"tail"}, a period's weights stacked over the periods), so the same
seeded weights go through both; a layer's kind is read off its weights.
"""

import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

# The mean loss's largest relative difference.  Two readings on the chip
# at the published widths, one sequence of 8,192 (PERF.md section 6,
# PR 31, ``tools/lfm2_precision.py``): the product, bfloat16 as the
# configuration states, differs by 1.3e-5 .. 8.3e-5 over thirteen seeds
# (mean 4.1e-5); this reference with every matmul operand outside the
# router rounded to float8 (e4m3), the nearest precision below, by
# 3.9e-4 .. 1.03e-3 on eleven of twelve seeds (and by 1.8e-4 on one: the
# mean over one sequence's 8,191 losses is a zero-mean draw, and a
# coarser precision only widens it, as in ``olmoe1b7b``).  2e-4 is 2.4
# times the former's largest and half the latter's smallest but one; a
# dropped layer or a wrong tap moves the loss by percents.
TOLERANCE = 2e-4
# The least share of (token, choice) pairs on which the program's router
# and this file's, given the same inputs and the same ``expert_bias``,
# must choose the same expert.  Both are float32 at the highest
# precision, so only exact ties may differ (1.0 on all thirteen seeds
# measured: 0 of 32,768 pairs a layer); a router in bfloat16 agrees on
# 0.99966 .. 0.99979 of the pairs, 7 to 11 a layer, and fails it (same
# chip runs: the sigmoid's scores and a bias drawn at 0.1 lie further
# apart than a softmax's over 64, so bfloat16 flips few).
SAME_INPUT_ROUTING_FLOOR = 0.9999
MICROBATCH = 1
EMBED_SCALE = 25.0
BIAS_SCALE = 0.1
QUERY_BLOCK = 1024


def shape_of(config):
    """What ``loss`` needs of the configuration's file."""
    held = config["num_experts"]
    return dict(heads=config["num_attention_heads"],
                kv_heads=config["num_key_value_heads"],
                top_k=config["num_experts_per_tok"],
                eps=config["norm_eps"],
                theta=float(config["rope_parameters"]["rope_theta"]),
                norm_topk=config["norm_topk_prob"],
                scale=float(config["routed_scaling_factor"]),
                first=config.get("share_index", 0) * held)


def case(config, params, rng, key):
    """See benchmark/lib/compare.py.  The tied embedding is drawn 25
    times wider than the product's 0.02, so that the logits are not all
    near zero and the loss is not ln(V) whatever the network computes;
    every ``expert_bias`` (zeros in the job, as the public code starts
    it) is drawn at 0.1, for both sides, so that the biased choice and
    the unbiased weights are compared too.  Also checks the routing
    (stderr; raises under the floor)."""
    tokens = jnp.asarray(rng.integers(
        0, config["vocab_size"], (MICROBATCH, config["seq_len"])), jnp.int32)
    params["embed"] = params["embed"] * EMBED_SCALE
    for group in params["layers"].values():
        for w in group.values():
            if "expert_bias" in w:
                w["expert_bias"] = jnp.asarray(
                    BIAS_SCALE * rng.standard_normal(w["expert_bias"].shape),
                    jnp.float32)
    shape = shape_of(config)
    check_routing(config, params, tokens, shape)
    return params, tokens, tokens, lambda p: loss(p, tokens, **shape)[0]


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
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def rope(x, theta):
    """x: [B, T, H, D]; rotate the two halves of D by position."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def route(h, w_router, bias, top_k):
    """(scores [B, T, X], chosen [B, T, X] bool) of float32 inputs: the
    ``top_k`` largest of score + bias."""
    scores = jax.nn.sigmoid(h @ w_router)
    biased = scores + bias
    kth = jnp.sort(biased, axis=-1)[..., -top_k]
    return scores, biased >= kth[..., None]


def attention(h, w, heads, kv_heads, eps, theta, r):
    """Causal grouped-query attention of the normed input, a block of
    queries at a time."""
    B, T, E = h.shape
    D = E // heads
    q = (r(h) @ r(w["wq"])).reshape(B, T, heads, D)
    k = (r(h) @ r(w["wk"])).reshape(B, T, kv_heads, D)
    v = (r(h) @ r(w["wv"])).reshape(B, T, kv_heads, D)
    q = rope(rmsnorm(q, w["q_norm"], eps), theta)
    k = rope(rmsnorm(k, w["k_norm"], eps), theta)
    # query head i reads K/V head i // (heads / kv_heads)
    k = jnp.repeat(k, heads // kv_heads, axis=2)
    v = jnp.repeat(v, heads // kv_heads, axis=2)
    out = []
    for start in range(0, T, QUERY_BLOCK):
        stop = min(start + QUERY_BLOCK, T)
        scores = jnp.einsum("bqhd,bkhd->bhqk", r(q[:, start:stop]),
                            r(k[:, :stop])) / np.sqrt(D)
        causal = (jnp.arange(start, stop)[:, None]
                  >= jnp.arange(stop)[None, :])
        scores = jnp.where(causal, scores, -jnp.inf)
        out.append(jnp.einsum("bhqk,bkhd->bqhd",
                              r(jax.nn.softmax(scores, -1)), r(v[:, :stop])))
    return r(jnp.concatenate(out, axis=1).reshape(B, T, E)) @ r(w["wo"])


def short_conv(h, w, r):
    """The gated short convolution of the normed input."""
    b, c, u = jnp.split(r(h) @ r(w["w_in"]), 3, axis=-1)
    g = b * u
    taps = w["conv_w"].shape[1]
    T = g.shape[1]
    padded = jnp.pad(g, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(w["conv_w"][:, j] * padded[:, j:j + T] for j in range(taps))
    return r(c * conv) @ r(w["w_out"])


def swiglu(h, gate, up, down, r):
    return r(jax.nn.silu(r(h) @ r(gate)) * (r(h) @ r(up))) @ r(down)


def experts(h, w, top_k, norm_topk, scale, first, r=lambda a: a):
    """(the held experts' part of the layer's result [B, T, E], chosen
    [B, T, X]) of the normed input: every held expert's SwiGLU on every
    token, weighted by the routing over all X experts."""
    scores, chosen = route(h, w["w_router"], w["expert_bias"], top_k)
    weights = jnp.where(chosen, scores, 0.0)
    if norm_topk:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-6)
    weights = weights * scale
    y = jnp.zeros_like(h)
    for e in range(w["w_gate"].shape[0]):     # the held experts
        y = y + weights[..., first + e, None] * swiglu(
            h, w["w_gate"][e], w["w_up"][e], w["w_down"][e], r)
    return y, chosen


def loss(params, tokens, heads, kv_heads, top_k, eps, theta, norm_topk,
         scale, first, rounded=None):
    """(per-sequence loss [B], [(chosen [B, T, X], router input [B, T,
    E], layer weights) of each layer with experts]); tokens [B, T]
    int32.  ``rounded`` is a dtype through which every matmul operand
    outside the router is rounded first: what this model would give
    computed in that precision (PERF.md's second reading)."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    r = (lambda a: a) if rounded is None else (
        lambda a: a.astype(rounded).astype(jnp.float32))
    embed = f32(params["embed"])
    x = embed[tokens]
    choices = []
    for w in layers_of(params):
        w = {k: f32(v) for k, v in w.items()}
        h = rmsnorm(x, w["ln1"], eps)
        if "w_in" in w:
            x = x + short_conv(h, w, r)
        else:
            x = x + attention(h, w, heads, kv_heads, eps, theta, r)
        h = rmsnorm(x, w["ln2"], eps)
        if "w_router" not in w:
            x = x + swiglu(h, w["w_gate"], w["w_up"], w["w_down"], r)
            continue
        y, chosen = experts(h, w, top_k, norm_topk, scale, first, r)
        choices.append((chosen, h, w))
        x = x + y
    logits = r(rmsnorm(x, f32(params["ln_f"]), eps)) @ r(embed.T)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -picked.mean(axis=-1), choices


def check_routing(config, params, tokens, shape):
    """The program's choice of experts against this file's, layer by
    layer: (a) both routers on the program's own inputs, which has to
    reach SAME_INPUT_ROUTING_FLOOR; (b) the program's whole forward
    against the reference's, where the inputs differ by the compute
    dtype's rounding, for the record.  One JSON line on stderr."""
    from benchmark.lib.runner import params_string
    from elasticdl_tpu.models import transformer as tfm
    from elasticdl_tpu.models.spec import load_model_spec

    cfg = load_model_spec(
        config["cli"]["model_zoo"],
        model_params=params_string(config["cli"]["model_params"])).config
    top_k = shape["top_k"]

    @jax.jit
    def program(params):
        dtype = jnp.dtype(cfg.dtype)
        x = params["embed"].astype(dtype)[tokens]
        positions = jnp.arange(tokens.shape[1])
        seen = []
        for kind, w in zip(cfg.kinds, layers_of(params)):
            if kind.op == "c":
                x = tfm._short_conv(x, w, cfg)
            else:
                x, _ = tfm._attention(x, w, cfg, None, positions)
            if not kind.dense:
                h = tfm._rmsnorm(x, w["ln2"].astype(dtype), cfg.norm_eps)
                experts = tfm.moe_route(h, w["w_router"], cfg,
                                        w["expert_bias"])[2]
                theirs = jax.nn.one_hot(experts, cfg.moe_experts).sum(-2) > 0
                with jax.default_matmul_precision("highest"):
                    ours = route(h.astype(jnp.float32),
                                 w["w_router"].astype(jnp.float32),
                                 w["expert_bias"], top_k)[1]
                seen.append((theirs, ours))
            x = tfm._ffn(x, w, cfg, None, dense=kind.dense)[0]
        return seen

    with jax.default_matmul_precision("highest"):
        reference = jax.jit(lambda p: [c for c, _, _ in loss(
            p, tokens, **shape)[1]])(params)
    same = lambda a, b: float((a & b).sum() / a.sum())
    seen = program(params)
    same_input = min(same(theirs, ours) for theirs, ours in seen)
    end_to_end = min(same(theirs, ref)
                     for (theirs, _), ref in zip(seen, reference))
    print(json.dumps({"routing_same_input": same_input,
                      "routing_end_to_end": end_to_end,
                      "floor": SAME_INPUT_ROUTING_FLOOR}),
          file=sys.stderr, flush=True)
    if same_input < SAME_INPUT_ROUTING_FLOOR:
        raise SystemExit(
            "the program's router chose other experts than a float32 "
            "router on the same inputs: %.5f of the pairs agree, under "
            "%.4f" % (same_input, SAME_INPUT_ROUTING_FLOOR))
