"""Plain reference of the ``smallthinker-21b-a3b`` configuration's loss.

SmallThinker-21BA3B-Instruct's layer equations as its public config
gives them, in straightforward ``jax.numpy`` and float32, with no
kernel, no scan, no sort, no remat.  For a block with input ``x``:

 - ``h = RMSNorm_1(x)``; ``r = h W_r``, one logit an expert: the router
   reads what attention reads, before attention runs.
 - ``q = h W_q`` of 28 heads of 128, ``k = h W_k`` and ``v = h W_v`` of
   4 (heads x head size is 3,584, not the hidden 2,560); no bias, no QK
   norm; query head i reads K/V head i // 7; scores scaled by 128^-1/2.
   A layer whose ``sliding_window_layout`` and ``rope_layout`` are 0:
   causal attention over the whole sequence and no positional encoding
   at all.  The others: RoPE (rotate-half, theta 1.5e6) on q and k,
   causal and ``q_pos - k_pos < sliding_window_size``.  Computed in
   blocks of ``QUERY_BLOCK`` queries against the keys a block can see:
   all [28, T, T] float32 scores of one sequence of 16,384 are 30 GB.
   ``x' = x + Attn W_o``.
 - the K largest of ``r`` are chosen, their weights the softmax over
   those K logits; expert e is ``W_down,e (relu(W_gate,e u) * (W_up,e
   u))`` of ``u = RMSNorm_2(x')``: ReGLU.  Every HELD expert is applied
   to every token and masked by the routing; ``y = x' + sum_e w_e
   Expert_e(u)``.  No shared expert, no dense layer.
 - one RMSNorm after the last layer, an untied head, the mean
   next-token cross entropy, its logits taken ``HEAD_BLOCK`` rows at a
   time.  RMSNorm (eps 1e-6) has a learned scale.

The share: the weights hold experts ``first .. first + held`` of the
router's ``published.moe_num_primary_experts`` and a slice of the
vocabulary; what the absent experts would add is left out, here as in
the program, and that partial result goes on to the next layer.  No
balance loss (the config gives none: ``assumed``).  The caller sets
``jax.default_matmul_precision("highest")``.

``params`` is the program's own tree (``layers`` = {"lead", "period",
"tail"}, a period's weights stacked over the periods), so the same
seeded weights go through both; which layers have a window and RoPE is
the configuration's to say, not the weights'.
"""

import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

# The mean loss's largest relative difference.  Two readings on the chip
# at the published widths, one sequence of 16,384 (PERF.md section 6,
# PR 33, ``tools/smallthinker_precision.py``, twelve seeds): the
# product, bfloat16 as the configuration states, differs by 7.3e-6 ..
# 4.5e-5 (mean 2.3e-5); this reference with every matmul operand
# outside the router rounded to float8 (e4m3), the nearest precision
# below, by 3.1e-4 .. 9.9e-4 on all twelve.  1.2e-4 is 2.7 times the
# former's largest and 0.38 of the latter's smallest.  (With the
# embedding drawn at 0.02, where every token's stream is mostly one
# shared vector and a token's error does not average out over the
# sequence, the two readings met: 9.2e-6 .. 1.64e-4 against 1.6e-4 ..
# 2.2e-3 over thirteen seeds.)  A dropped layer, a window left off or
# RoPE on the NoPE layer moves the loss by tenths of a percent to
# percents (tests/test_banded_stack.py does each at a small size).
TOLERANCE = 1.2e-4
# The least share of (token, choice) pairs on which the program's router
# (``models/transformer.moe_route``) and this file's, given the same
# inputs, must choose the same expert.  Both are float32 at the highest
# precision, so only exact ties may differ (1.0 on all twenty-six seeds
# measured); a router computed in bfloat16 agrees on 0.99910 .. 0.99936
# of the pairs, 63 to 88 of a layer's 98,304, and fails it (same chip
# runs).
SAME_INPUT_ROUTING_FLOOR = 0.9998
MICROBATCH = 1
HEAD_SCALE = 5.0
QUERY_BLOCK = 1024
HEAD_BLOCK = 2048


def shape_of(config):
    """What ``loss`` needs of the configuration's file."""
    held = config["moe_num_primary_experts"]
    kept = config["layers_kept"]
    return dict(
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        top_k=config["moe_num_active_primary_experts"],
        eps=config["rms_norm_eps"], theta=float(config["rope_theta"]),
        window=config["sliding_window_size"],
        windowed=tuple(config["sliding_window_layout"][i] for i in kept),
        roped=tuple(config["rope_layout"][i] for i in kept),
        first=config.get("share_index", 0) * held)


def inputs(config, params, rng):
    """(params, tokens [MICROBATCH, seq_len]) as both sides shall use
    them.  The head is drawn 5 times wider than the product's 0.02, so
    that the logits are not all near zero and the loss is not ln(V)
    whatever the network computes (the configuration draws the
    embedding at unit scale itself)."""
    tokens = jnp.asarray(rng.integers(
        0, config["vocab_size"], (MICROBATCH, config["seq_len"])), jnp.int32)
    params["lm_head"] = params["lm_head"] * HEAD_SCALE
    return params, tokens


def case(config, params, rng, key):
    """See benchmark/lib/compare.py.  The reference runs once, here, at
    the highest matmul precision (its program is a few thousand
    unrolled operations, and the comparison has a time limit): its loss
    is what the returned function hands back, its router inputs what
    the routing check reads (stderr; raises under the floor)."""
    params, tokens = inputs(config, params, rng)
    shape = shape_of(config)
    with jax.default_matmul_precision("highest"):
        per_record, seen = jax.jit(
            lambda p: loss(p, tokens, **shape))(params)
    check_routing(config, seen, shape["top_k"])
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


def route(h, w_router, top_k):
    """(weights [B, T, X], chosen [B, T, X] bool) of float32 inputs:
    the ``top_k`` largest logits, weighed by the softmax over them
    alone."""
    logits = h @ w_router
    kth = jnp.sort(logits, axis=-1)[..., -top_k]
    chosen = logits >= kth[..., None]
    return jax.nn.softmax(jnp.where(chosen, logits, -jnp.inf), -1), chosen


def attention(h, w, heads, kv_heads, head_dim, theta, window, r):
    """Causal grouped-query attention of the normed input, a block of
    queries at a time; ``theta`` None: no positional encoding;
    ``window`` 0: the whole sequence."""
    B, T, _ = h.shape
    group = heads // kv_heads
    q = (r(h) @ r(w["wq"])).reshape(B, T, heads, head_dim)
    k = (r(h) @ r(w["wk"])).reshape(B, T, kv_heads, head_dim)
    v = (r(h) @ r(w["wv"])).reshape(B, T, kv_heads, head_dim)
    if theta is not None:
        q, k = rope(q, theta), rope(k, theta)
    # query head i reads K/V head i // group
    q = q.reshape(B, T, kv_heads, group, head_dim)
    out = []
    for start in range(0, T, QUERY_BLOCK):
        stop = min(start + QUERY_BLOCK, T)
        low = max(0, start - window + 1) if window else 0
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", r(q[:, start:stop]),
                            r(k[:, low:stop])) / np.sqrt(head_dim)
        ahead = (jnp.arange(start, stop)[:, None]
                 - jnp.arange(low, stop)[None, :])
        seen = ahead >= 0
        if window:
            seen &= ahead < window
        scores = jnp.where(seen, scores, -jnp.inf)
        out.append(jnp.einsum("bgrqk,bkgd->bqgrd",
                              r(jax.nn.softmax(scores, -1)),
                              r(v[:, low:stop])))
    out = jnp.concatenate(out, axis=1).reshape(B, T, heads * head_dim)
    return r(out) @ r(w["wo"])


def reglu(u, gate, up, down, r):
    return r(jax.nn.relu(r(u) @ r(gate)) * (r(u) @ r(up))) @ r(down)


def experts(u, weights, w, first, r=lambda a: a):
    """The held experts' part of the layer's result [B, T, E]: every
    held expert's ReGLU on every token, weighted by the routing over
    all X experts (``weights`` [B, T, X], zero where not chosen)."""
    y = jnp.zeros_like(u)
    for e in range(w["w_gate"].shape[0]):     # the held experts
        y = y + weights[..., first + e, None] * reglu(
            u, w["w_gate"][e], w["w_up"][e], w["w_down"][e], r)
    return y


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


def loss(params, tokens, heads, kv_heads, head_dim, top_k, eps, theta,
         window, windowed, roped, first, rounded=None):
    """(per-sequence loss [B], [(chosen [B, T, X], router input [B, T,
    E], w_router) of each layer]); tokens [B, T] int32.  ``rounded`` is
    a dtype through which every matmul operand outside the router is
    rounded first: what this model would give computed in that precision
    (PERF.md's second reading)."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    r = (lambda a: a) if rounded is None else (
        lambda a: a.astype(rounded).astype(jnp.float32))
    x = f32(params["embed"])[tokens]
    choices = []
    for i, w in enumerate(layers_of(params)):
        w = {k: f32(v) for k, v in w.items()}
        h = rmsnorm(x, w["ln1"], eps)
        weights, chosen = route(h, w["w_router"], top_k)
        choices.append((chosen, h, w["w_router"]))
        x = x + attention(h, w, heads, kv_heads, head_dim,
                          theta if roped[i] else None,
                          window if windowed[i] else 0, r)
        x = x + experts(rmsnorm(x, w["ln2"], eps), weights, w, first, r)
    x = rmsnorm(x, f32(params["ln_f"]), eps)
    return head_loss(x, f32(params["lm_head"]), tokens, r), choices


def check_routing(config, seen, top_k):
    """The program's router against this file's on the same inputs: the
    reference's own router inputs of each layer (``seen``, ``loss``'s
    second result), rounded to the program's compute dtype as the
    program's are.  One JSON line on stderr; raises under
    SAME_INPUT_ROUTING_FLOOR."""
    from benchmark.lib.runner import params_string
    from elasticdl_tpu.models import transformer as tfm
    from elasticdl_tpu.models.spec import load_model_spec

    cfg = load_model_spec(
        config["cli"]["model_zoo"],
        model_params=params_string(config["cli"]["model_params"])).config

    @jax.jit
    def both(h, w_router):
        h = h.astype(jnp.dtype(cfg.dtype))
        theirs = jax.nn.one_hot(tfm.moe_route(h, w_router, cfg)[2],
                                cfg.moe_experts).sum(-2) > 0
        ours = route(h.astype(jnp.float32), w_router, top_k)[1]
        return (theirs & ours).sum() / theirs.sum()

    with jax.default_matmul_precision("highest"):
        same_input = min(float(both(h, w)) for _, h, w in seen)
    print(json.dumps({"routing_same_input": same_input,
                      "floor": SAME_INPUT_ROUTING_FLOOR}),
          file=sys.stderr, flush=True)
    if same_input < SAME_INPUT_ROUTING_FLOOR:
        raise SystemExit(
            "the program's router chose other experts than a float32 "
            "router on the same inputs: %.5f of the pairs agree, under "
            "%.4f" % (same_input, SAME_INPUT_ROUTING_FLOOR))
