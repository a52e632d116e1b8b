"""Plain reference of the ``trinity-mini`` configuration's loss.

Trinity-Mini's layer equations (``model_type: afmoe``) as its public
config gives them and, where the config has no key, as the
configuration's ``assumed`` lists them, in straightforward ``jax.numpy``
and float32, with no kernel, no scan, no sort, no remat.  Written from
those equations, not from ``models/transformer.py``.

 - ``x = E[token] * sqrt(2048)`` (``mup_enabled``); nothing divides the
   logits.
 - a block, every layer, four RMSNorms of 2048 (``n1..n4``; in the
   program's tree ``ln1``, ``ln1_post``, ``ln2``, ``ln2_post``):
   ``x = x + n2(Attn(n1(x)))`` then ``x = x + n4(FFN(n3(x)))``: the norm
   after a sublayer is on its OUTPUT, before the add.
 - attention: ``h = n1(x)``; ``q = h W_q`` of 32 heads of 128, ``k = h
   W_k`` and ``v = h W_v`` of 4, ``g = h W_g`` of 4,096.  q and k take an
   RMSNorm over each head's 128 values, one learned scale of 128 each.
   A ``sliding_attention`` layer: RoPE (rotate-half, theta 10,000) on q
   and k, causal softmax over the keys ``j`` with ``i - 2048 < j <= i``.
   A ``full_attention`` layer: no positional encoding at all, causal
   softmax over the whole sequence.  K and V are repeated to the 32
   query heads the long way (query head i reads K/V head i // 8);
   scores at ``128^-1/2``, explicit, a block of ``QUERY_BLOCK`` queries
   at a time (all [32, T, T] float32 scores of one sequence of 16,384
   are 34 GB).  ``o = (concat_heads(P v) * sigmoid(g)) W_o``: the gate
   is a value's own, after the softmax and before ``W_o``.
 - the kept dense layer (``num_dense_layers``): a SwiGLU of 6,144.
 - the others: ``u = n3(x)``; ``s = sigmoid(u W_r)`` over all 128
   experts; the 8 largest of ``s + expert_bias`` chosen (one group: no
   group limit), their weights the unbiased ``s`` over their sum
   (``route_norm``) times ``route_scale`` 2.826; SwiGLU experts of
   1,024; PLUS one shared SwiGLU of ``num_shared_experts`` x 1,024 on
   the same ``u``.
 - one RMSNorm after the last layer, an untied head, the mean
   next-token cross entropy, its logits taken ``HEAD_BLOCK`` rows at a
   time.  RMSNorm (eps 1e-5) is ``w * x / rms(x)``; no bias anywhere.

Departures from the published model, each the configuration's
(``configs/trinity-mini.json``: ``reduced``, ``assumed``):

 - the share: the weights hold experts ``first .. first + held`` of the
   router's 128 and a slice of the vocabulary; every HELD expert is
   applied to every token and masked by the routing, what the absent
   ones would add is left out, here as in the program, and that partial
   result goes on to the next layer; the shared expert is whole;
 - layers 1-5 of the 32, so one dense layer and not two;
 - the division by the chosen scores' sum adds 1e-6 (the program's; the
   afmoe code as recalled adds 1e-20: a sum of eight sigmoids is of
   order 1);
 - no update of ``expert_bias`` (``load_balance_coeff`` is that rule's
   step) and no balance loss.

``params`` is the program's own tree (``layers`` = {"lead", "period",
"tail"}, a period's weights stacked over the periods), so the same
seeded weights go through both; which layers have a window and RoPE is
the configuration's ``layer_types`` to say, not the weights'.  The
caller sets ``jax.default_matmul_precision("highest")``.
"""

import collections
import functools
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

# The mean loss's largest relative difference.  On the chip at the
# published widths and T = 16,384 (``tools/trinity_precision.py``, my
# chip run, PR 40, call ``c1``) the bfloat16 product reads 3.6e-5 ..
# 1.62e-4 from this file over eight seeds and 1.07e-4 on the traced run
# (a loss of ~19.6 under the comparison's five-times-wider head, so
# 1e-4 of it is 2e-3 nats), and this limit is three times the largest.
# It stands on the product's readings alone: this file with every matmul
# operand outside the router rounded to float8 (e4m3), the nearest
# precision below the bfloat16 the configuration states, reads 1.2e-5 ..
# 3.3e-3, inside the limit on two seeds of eight, because one mean over
# 16,383 positions is too blunt a scalar (PERF.md section 7 (8)).  What
# fails float8 on every seed is SAME_INPUT_LAYER_CEILING below.
TOLERANCE = 5e-4
# The least share of (token, choice) pairs on which the program's router
# (``models/transformer.moe_route``) and this file's, given the same
# inputs and the same bias, must choose the same expert.  Both are
# float32 at the highest precision, so only exact ties may differ: 1.0
# on every seed of ``c1``, where a bfloat16 router on the same inputs
# agrees on 0.99748-0.99786 of the pairs.
SAME_INPUT_ROUTING_FLOOR = 0.9998
# The largest relative distance (``layer_errors``: norms over a layer's
# whole [T, 2048] result, so no mean over the sequence cancels anything)
# of the program's gated attention, shared expert and held experts from
# this file's float32 math on the same inputs, the worst of the four
# expert layers.  The same call, eight seeds: the bfloat16 program with
# its kernels reads 3.88-3.98e-3 / 4.24-4.26e-3 / 4.85-4.86e-3
# (attention / shared expert / held experts), this file in float8
# 0.770-0.800 / 4.68-4.72e-2 / 7.09-7.12e-2: the ceiling is 3.1 times
# the former's largest and 0.32 of the latter's smallest, and float8 is
# past it in every part on eight seeds of eight.
SAME_INPUT_LAYER_CEILING = 1.5e-2
# what ``loss`` can round apart, and what ``layer_errors`` compares
PARTS = ("attention", "dense", "experts", "shared", "head")
LAYER_PARTS = ("attention", "shared_expert", "routed_experts")
# what ``loss`` can leave out of the block (``without``): what a test
# shows the tolerance to see
PIECES = ("gate", "post_norms", "multiplier", "qk_norm")
# what ``loss`` saw of a layer with experts: the router's choice
# [B, T, X] bool, attention's and the FFN's normed inputs [B, T, E]
Seen = collections.namedtuple("Seen", "chosen h u")
MICROBATCH = 1
HEAD_SCALE = 5.0
BIAS_SCALE = 0.1
NORM_SPREAD = 0.25
QUERY_BLOCK = 1024
HEAD_BLOCK = 2048
ROUTE_EPS = 1e-6


def shape_of(config):
    """What ``loss`` needs of the configuration's file."""
    return dict(
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        top_k=config["num_experts_per_tok"],
        eps=config["rms_norm_eps"], theta=float(config["rope_theta"]),
        window=config["sliding_window"],
        kinds=tuple(config["layer_types"][i] for i in config["layers_kept"]),
        norm_topk=config["route_norm"], scale=float(config["route_scale"]),
        multiplier=(float(np.sqrt(config["hidden_size"]))
                    if config["mup_enabled"] else 1.0),
        first=config.get("share_index", 0) * config["num_experts"])


def inputs(config, params, rng):
    """(params, tokens [MICROBATCH, seq_len]) as both sides shall use
    them.  The head is drawn 5 times wider than the product's 0.02, so
    that the logits are not all near zero and the loss is not ln(V)
    whatever the network computes; every ``expert_bias`` (zeros in the
    job) is drawn at 0.1, so that the biased choice and the unbiased
    weights are compared too; and the scales of the norms this block
    adds (the two on the sublayers' outputs, q's and k's), all ones in
    the job, are drawn within 1 +- 0.25, so that a scale in the wrong
    place shows."""
    tokens = jnp.asarray(rng.integers(
        0, config["vocab_size"], (MICROBATCH, config["seq_len"])), jnp.int32)
    params["lm_head"] = params["lm_head"] * HEAD_SCALE
    for group in params["layers"].values():
        for w in group.values():
            if "expert_bias" in w:
                w["expert_bias"] = jnp.asarray(
                    BIAS_SCALE * rng.standard_normal(w["expert_bias"].shape),
                    jnp.float32)
            for name in ("ln1_post", "ln2_post", "q_norm", "k_norm"):
                w[name] = jnp.asarray(1.0 + NORM_SPREAD * rng.uniform(
                    -1.0, 1.0, w[name].shape), jnp.float32)
    return params, tokens


def case(config, params, rng, key):
    """See benchmark/lib/compare.py.  The reference runs once, here, at
    the highest matmul precision: its loss is what the returned function
    hands back, its expert layers' normed inputs what the routing check
    and the layer check read (stderr; each raises past its limit)."""
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


def rope(x, theta):
    """x: [B, T, H, D]; rotate the two halves of D by position."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def route(u, w_router, bias, top_k):
    """(scores [B, T, X], chosen [B, T, X] bool) of float32 inputs: the
    ``top_k`` largest of sigmoid + bias."""
    scores = jax.nn.sigmoid(u @ w_router)
    biased = scores + bias
    kth = jnp.sort(biased, axis=-1)[..., -top_k]
    return scores, biased >= kth[..., None]


def attention(h, w, heads, kv_heads, head_dim, eps, theta, window, r,
              without=()):
    """Gated causal attention of the normed input, a block of queries at
    a time; ``theta`` None: no positional encoding; ``window`` 0: the
    whole sequence, else the keys j with i - window < j <= i."""
    B, T, _ = h.shape
    q = (r(h) @ r(w["wq"])).reshape(B, T, heads, head_dim)
    k = (r(h) @ r(w["wk"])).reshape(B, T, kv_heads, head_dim)
    v = (r(h) @ r(w["wv"])).reshape(B, T, kv_heads, head_dim)
    if "qk_norm" not in without:       # over each head's values
        q = rmsnorm(q, w["q_norm"], eps)
        k = rmsnorm(k, w["k_norm"], eps)
    if theta is not None:
        q, k = rope(q, theta), rope(k, theta)
    # the long way: every query head its own copy of its K/V head
    k = jnp.repeat(k, heads // kv_heads, axis=2)
    v = jnp.repeat(v, heads // kv_heads, axis=2)
    out = []
    for start in range(0, T, QUERY_BLOCK):
        stop = min(start + QUERY_BLOCK, T)
        # the keys any query of the block may see; the inequality below
        # decides, on the positions themselves
        low = max(0, start - window + 1) if window else 0
        scores = jnp.einsum("bqhd,bkhd->bhqk", r(q[:, start:stop]),
                            r(k[:, low:stop])) / np.sqrt(head_dim)
        i = jnp.arange(start, stop)[:, None]
        j = jnp.arange(low, stop)[None, :]
        seen = j <= i
        if window:
            seen &= i - window < j
        scores = jnp.where(seen, scores, -jnp.inf)
        out.append(jnp.einsum("bhqk,bkhd->bqhd",
                              r(jax.nn.softmax(scores, -1)),
                              r(v[:, low:stop])))
    out = jnp.concatenate(out, axis=1).reshape(B, T, heads * head_dim)
    if "gate" not in without:          # a value's own gate
        out = out * jax.nn.sigmoid(r(h) @ r(w["w_attn_gate"]))
    return r(out) @ r(w["wo"])


def swiglu(u, gate, up, down, r):
    return r(jax.nn.silu(r(u) @ r(gate)) * (r(u) @ r(up))) @ r(down)


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
    """a -> a through dtype ``rounded`` and back; the identity for None."""
    if rounded is None:
        return lambda a: a
    return lambda a: a.astype(rounded).astype(jnp.float32)


def loss(params, tokens, heads, kv_heads, head_dim, top_k, eps, theta,
         window, kinds, norm_topk, scale, multiplier, first, rounded=None,
         parts=PARTS, without=()):
    """(per-sequence loss [B], [Seen of each layer with experts]);
    tokens [B, T] int32.  ``rounded`` is a dtype through which every
    matmul operand outside the router is rounded first, in the ``parts``
    named (all of PARTS: what this model would give computed in that
    precision, PERF.md's second reading).  ``without`` names the PIECES
    of the block to leave out (what a test tells apart)."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    r = {part: rounding(rounded if part in parts else None)
         for part in PARTS}
    post = (lambda y, scale: y) if "post_norms" in without else (
        lambda y, scale: rmsnorm(y, scale, eps))
    x = f32(params["embed"])[tokens]
    if "multiplier" not in without:
        x = x * multiplier
    seen = []
    for kind, w in zip(kinds, layers_of(params), strict=True):
        w = {k: f32(v) for k, v in w.items()}
        sliding = {"sliding_attention": True, "full_attention": False}[kind]
        h = rmsnorm(x, w["ln1"], eps)
        x = x + post(attention(
            h, w, heads, kv_heads, head_dim, eps,
            theta if sliding else None, window if sliding else 0,
            r["attention"], without), w["ln1_post"])
        u = rmsnorm(x, w["ln2"], eps)
        if "w_router" not in w:       # the dense layer
            y = swiglu(u, w["w_gate"], w["w_up"], w["w_down"], r["dense"])
        else:
            y, chosen = experts(u, w, top_k, norm_topk, scale, first,
                                r["experts"])
            seen.append(Seen(chosen, h, u))
            y = y + swiglu(u, w["ws_gate"], w["ws_up"], w["ws_down"],
                           r["shared"])
        x = x + post(y, w["ln2_post"])
    x = rmsnorm(x, f32(params["ln_f"]), eps)
    return head_loss(x, f32(params["lm_head"]), tokens, r["head"]), seen


def expert_layers(params, kinds):
    """[(weights in float32, ``layer_types`` entry)] of each layer with
    experts, as ``loss`` lists what it saw of them."""
    return [({k: jnp.asarray(v, jnp.float32) for k, v in w.items()}, kind)
            for w, kind in zip(layers_of(params), kinds, strict=True)
            if "w_router" in w]


def program_config(config):
    """The program's ``TransformerConfig`` of the configuration's file."""
    from benchmark.lib.runner import params_string
    from elasticdl_tpu.models.spec import load_model_spec

    return load_model_spec(
        config["cli"]["model_zoo"],
        model_params=params_string(config["cli"]["model_params"])).config


def check_routing(config, params, seen, top_k):
    """The program's router against this file's on the same inputs: the
    reference's own router inputs of each layer (``seen``, ``loss``'s
    second result), rounded to the program's compute dtype as the
    program's are.  One JSON line on stderr; raises under
    SAME_INPUT_ROUTING_FLOOR."""
    from elasticdl_tpu.models import transformer as tfm

    cfg = program_config(config)

    @jax.jit
    def both(u, w_router, bias):
        u = u.astype(jnp.dtype(cfg.dtype))
        theirs = jax.nn.one_hot(tfm.moe_route(u, w_router, cfg, bias)[2],
                                cfg.moe_experts).sum(-2) > 0
        ours = route(u.astype(jnp.float32), w_router, bias, top_k)[1]
        return (theirs & ours).sum() / theirs.sum()

    kinds = shape_of(config)["kinds"]
    with jax.default_matmul_precision("highest"):
        same_input = min(
            float(both(s.u, w["w_router"], w["expert_bias"]))
            for s, (w, _) in zip(seen, expert_layers(params, kinds)))
    print(json.dumps({"routing_same_input": same_input,
                      "floor": SAME_INPUT_ROUTING_FLOOR}),
          file=sys.stderr, flush=True)
    if same_input < SAME_INPUT_ROUTING_FLOOR:
        raise SystemExit(
            "the program's router chose other experts than a float32 "
            "router on the same inputs: %.5f of the pairs agree, under "
            "%.4f" % (same_input, SAME_INPUT_ROUTING_FLOOR))


def layer_errors(config, rounded=None):
    """A function of (params, seen) that gives {part: the largest over
    the layers with experts of |got - want| / |want|, the norms over a
    layer's whole [B, T, E] result, which no mean over the sequence can
    cancel} of LAYER_PARTS on the same inputs: the reference's own
    normed inputs of each such layer (``seen``), rounded to the
    program's compute dtype as the program's are.  ``want`` is this
    file's float32 math; ``got`` the program's own functions
    (``models/transformer._attention_mix`` with its QK norm, kernels
    and gate, ``_shared_expert``, ``_moe_ffn``) or, with ``rounded``,
    this file's with every matmul operand rounded through that dtype.
    The routed part takes the program's route on both sides
    (``check_routing`` holds the route itself)."""
    from elasticdl_tpu.models import transformer as tfm

    cfg = program_config(config)
    shape = shape_of(config)
    dtype = jnp.dtype(cfg.dtype)
    r = rounding(rounded)

    def attend(h, w, sliding, r):
        return attention(
            h, w, shape["heads"], shape["kv_heads"], shape["head_dim"],
            shape["eps"], shape["theta"] if sliding else None,
            shape["window"] if sliding else 0, r)

    share = lambda u, w, r: swiglu(u, w["ws_gate"], w["ws_up"],
                                   w["ws_down"], r)

    @functools.partial(jax.jit, static_argnums=(3, 4))
    def program(h, u, w, kind, sliding):
        h, u = h.astype(dtype), u.astype(dtype)
        route = tfm.moe_route(u, w["w_router"], cfg, w["expert_bias"])
        weights = (jax.nn.one_hot(route[2], cfg.moe_experts)
                   * route[1][..., None]).sum(-2)
        if rounded is not None:
            f32 = lambda a: a.astype(jnp.float32)
            return weights, (
                attend(f32(h), w, sliding, r), share(f32(u), w, r),
                held_experts(f32(u), w, weights, shape["first"], r))
        positions = jnp.arange(h.shape[1])
        return weights, (
            tfm._attention_mix(h, w, cfg, None, positions, kind)[0],
            tfm._shared_expert(u, w, cfg),
            tfm._moe_ffn(u, w, cfg, None, route)[0])

    @functools.partial(jax.jit, static_argnums=5)
    def apart(h, u, w, weights, got, sliding):
        h, u = (a.astype(dtype).astype(jnp.float32) for a in (h, u))
        want = (attend(h, w, sliding, rounding(None)),
                share(u, w, rounding(None)),
                held_experts(u, w, weights, shape["first"]))
        norm = lambda a: jnp.sqrt(jnp.sum(jnp.square(a)))
        return [norm(g.astype(jnp.float32) - w_) / norm(w_)
                for g, w_ in zip(got, want)]

    def errors(params, seen):
        # the program's Kind of each layer with experts, beside the
        # configuration's word for it: each is the other side's to read
        kinds = [kind for kind, w in zip(cfg.kinds, layers_of(params))
                 if "w_router" in w]
        worst = dict.fromkeys(LAYER_PARTS, 0.0)
        for s, (w, said), kind in zip(
                seen, expert_layers(params, shape["kinds"]), kinds):
            sliding = said == "sliding_attention"
            # the program's side as lib/compare.py runs the product: at
            # the default precision; this file's math at the highest
            with jax.default_matmul_precision(
                    "highest" if rounded is not None else "default"):
                weights, got = program(s.h, s.u, w, kind, sliding)
            with jax.default_matmul_precision("highest"):
                found = apart(s.h, s.u, w, weights, got, sliding)
            for part, error in zip(LAYER_PARTS, found):
                worst[part] = max(worst[part], float(error))
        return worst

    return errors


def check_layers(config, params, seen):
    """The program's gated attention, shared expert and held experts
    against this file's on the same inputs (``layer_errors``).  One JSON
    line on stderr; raises over SAME_INPUT_LAYER_CEILING."""
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
