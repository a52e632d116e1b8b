"""Plain reference of the ``kanana-2-30b-a3b`` configuration's loss.

kanana-2-30b-a3b-instruct-2601's layer equations (``model_type:
deepseek_v3``) as its public config gives them, in straightforward
``jax.numpy`` and float32, with no kernel, no scan, no sort, no remat.
For a block with input ``x``:

 - ``h = RMSNorm_1(x)``.  ``q = h W_q``: 32 heads of 192, each split
   ``q_nope`` 128 | ``q_rope`` 64 (no query latent: ``q_lora_rank``
   null).  ``c = h W_kv_a``: 512 + 64; ``c_kv = RMSNorm(c[:512])``
   (``kv_a_layernorm``, a learned scale), ``k_rope = c[512:]``, ONE key
   of 64 for all the heads.  ``c_kv W_kv_b``: 32 heads of 128 + 128,
   split ``k_nope`` | ``v``.
 - RoPE (theta 1e6, no scaling) turns ``q_rope`` and ``k_rope`` alone,
   and pairs NEIGHBOURS, ``(2i, 2i + 1)`` (``rope_interleave``).
 - a head's key is built the long way, ``k = concat(k_nope,
   broadcast(k_rope))`` of 192; its score ``q . k * 192^-1/2``, causal
   softmax over the whole sequence, in blocks of ``QUERY_BLOCK``
   queries against the keys a block can see (all [32, T, T] float32
   scores of one sequence of 16,384 are 34 GB); its output ``P v`` of
   128; ``x' = x + concat(heads) W_o``, ``W_o`` 4096 x 2048.
 - layer 0 (``first_k_dense_replace`` 1): ``y = x' + SwiGLU_6144(u)``,
   ``u = RMSNorm_2(x')``.
 - layers 1..: ``s = sigmoid(u W_r)`` over all 128 experts in float32;
   the 6 largest of ``s + e_score_correction_bias`` chosen (``noaux_tc``
   with ``n_group`` 1, ``topk_group`` 1: no group limit), their weights
   the unbiased ``s`` over their sum (``norm_topk_prob``) times
   ``routed_scaling_factor`` 2.448; experts are SwiGLUs of 768; PLUS one
   SwiGLU of ``n_shared_experts`` x 768 = 1,536 that every token passes
   through: ``y = x' + sum_e w_e Expert_e(u) + Shared(u)``.
 - one RMSNorm after the last layer, an untied head, the mean
   next-token cross entropy, its logits taken ``HEAD_BLOCK`` rows at a
   time.  RMSNorm (eps 1e-6) has a learned scale; no bias anywhere.

Departures from the published model, each the configuration's
(``configs/kanana-2-30b-a3b.json``: ``reduced``, ``assumed``):

 - the share: the weights hold experts ``first .. first + held`` of the
   router's 128 and a slice of the vocabulary; every HELD expert is
   applied to every token and masked by the routing, what the absent
   ones would add is left out, here as in the program, and that partial
   result goes on to the next layer; the shared expert is whole;
 - the division by the chosen scores' sum adds 1e-6 (the program's; the
   config gives none);
 - no balance loss (the config gives no coefficient).

``params`` is the program's own tree (``layers`` = {"lead", "period",
"tail"}, a period's weights stacked over the periods), so the same
seeded weights go through both.  The program turns the two HALVES of a
RoPE part and so holds the RoPE columns of ``wq`` and ``w_kv_a`` evens
first, then odds: ``published_order`` maps them back to the published
neighbours' order before this file's RoPE pairs neighbours, so that the
two layouts are held equal and not both drawn at random.  The caller
sets ``jax.default_matmul_precision("highest")``.
"""

import collections
import functools
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

# The mean loss's largest relative difference, set from the product's
# readings alone.  On the chip at the published widths, one sequence of
# 16,384 (PERF.md section 6, PR 37: ``tools/kanana_precision.py`` and the
# cell's traced runs, fifteen seeds): the product, bfloat16 as the
# configuration states, differs by 1.9e-6 .. 1.64e-4 (in 1e-5: 0.2, 0.6,
# 1.3, 2.1, 2.2, 3.9, 4.0, 6.3, 7.3, 8.4, 9.0, 11.3, 11.6, 12.0, 16.4;
# root mean square 8.0).  3e-4 is 1.8 times the largest and 3.7 times the
# root mean square: fresh seeds read higher (the twelfth reading was the
# largest).  The readings are wider than ``smallthinker-21b-a3b``'s
# 0.1-5.3e-5 because of the two gated MLPs every token passes through,
# not because of the latent: this reference with its matmul operands
# rounded to bfloat16 reads 5.8e-5 (root mean square, ten seeds; the
# product 7.4e-5 on the same ten), and rounded in one part alone 3.7e-5
# in the leading dense layer's MLP, 3.2e-5 in the shared expert, 1.4e-5
# in attention, 1.0e-5 in the held experts, 0.5e-5 in the head;
# ``smallthinker-21b-a3b``'s reference rounded whole reads 1.1e-5 (eight
# of the same seeds), and it has neither a dense layer nor a shared
# expert.  THIS LIMIT DOES NOT TELL A LOWER PRECISION ON EVERY SEED: the
# mean over 16,383 losses is a zero-mean draw, and this reference with
# every matmul operand outside the router rounded to float8 (e4m3), the
# nearest precision below, reads 3.4e-4 .. 9.0e-4 on ten of twelve seeds
# and 1.1e-5, 1.3e-4 on two.  SAME_INPUT_LAYER_CEILING below is the limit
# float8 fails on every seed.  A dropped shared expert or the RoPE
# columns taken in the wrong order moves the loss by tenths of a percent
# to percents (tests/test_latent_attention.py does each at a small
# size).
TOLERANCE = 3e-4
# The least share of (token, choice) pairs on which the program's router
# (``models/transformer.moe_route``) and this file's, given the same
# inputs and the same bias, must choose the same expert.  Both are
# float32 at the highest precision, so only exact ties may differ (1.0
# on all ten seeds measured); a router computed in bfloat16 agrees on
# 0.99737 .. 0.99769 of the pairs, 227 to 259 of a layer's 98,304, and
# fails it (same chip runs).
SAME_INPUT_ROUTING_FLOOR = 0.9998
# The largest relative distance (``layer_errors``: norms over a layer's
# whole [T, 2048] result, so no mean over the sequence cancels anything)
# of the program's latent attention, shared expert and held experts from
# this file's float32 math on the same inputs, the worst of the four
# expert layers.  Two readings on the chip, ten seeds (PERF.md section
# 6, PR 37, call ``r1``): the program, bfloat16 and the kernels, reads
# 5.04-5.11e-3 in attention, 4.24e-3 in the shared expert, 4.85e-3 in
# the held experts (a seed moves the third digit); this reference with
# its matmul operands rounded to float8 0.59-0.63, 4.67e-2 and 7.07e-2.
# 1.5e-2 is 2.9 times the former's largest and 0.32 of the latter's
# smallest: float8 fails it in every part on every seed.
SAME_INPUT_LAYER_CEILING = 1.5e-2
# what ``loss`` can round apart, and what ``layer_errors`` compares
PARTS = ("attention", "dense", "experts", "shared", "head")
LAYER_PARTS = ("attention", "shared_expert", "routed_experts")
# what ``loss`` saw of a layer with experts: the router's choice
# [B, T, X] bool, attention's and the FFN's normed inputs [B, T, E]
Seen = collections.namedtuple("Seen", "chosen h u")
MICROBATCH = 1
HEAD_SCALE = 5.0
BIAS_SCALE = 0.1
QUERY_BLOCK = 1024
HEAD_BLOCK = 2048
ROUTE_EPS = 1e-6


def shape_of(config):
    """What ``loss`` needs of the configuration's file."""
    return dict(
        heads=config["num_attention_heads"],
        rank=config["kv_lora_rank"],
        d_nope=config["qk_nope_head_dim"],
        d_rope=config["qk_rope_head_dim"],
        d_v=config["v_head_dim"],
        top_k=config["num_experts_per_tok"],
        eps=config["rms_norm_eps"], theta=float(config["rope_theta"]),
        norm_topk=config["norm_topk_prob"],
        scale=float(config["routed_scaling_factor"]),
        first=config.get("share_index", 0) * config["n_routed_experts"])


def inputs(config, params, rng):
    """(params, tokens [MICROBATCH, seq_len]) as both sides shall use
    them.  The head is drawn 5 times wider than the product's 0.02, so
    that the logits are not all near zero and the loss is not ln(V)
    whatever the network computes (the configuration draws the
    embedding at unit scale itself); every ``expert_bias`` (zeros in
    the job, as the public code starts ``e_score_correction_bias``) is
    drawn at 0.1, so that the biased choice and the unbiased weights
    are compared too."""
    tokens = jnp.asarray(rng.integers(
        0, config["vocab_size"], (MICROBATCH, config["seq_len"])), jnp.int32)
    params["lm_head"] = params["lm_head"] * HEAD_SCALE
    for group in params["layers"].values():
        for w in group.values():
            if "expert_bias" in w:
                w["expert_bias"] = jnp.asarray(
                    BIAS_SCALE * rng.standard_normal(w["expert_bias"].shape),
                    jnp.float32)
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


def published_order(columns):
    """[.., D_rope] RoPE columns as the program holds them (evens first,
    then odds) -> the published order, neighbours (2i, 2i + 1) a pair."""
    half = columns.shape[-1] // 2
    return jnp.stack([columns[..., :half], columns[..., half:]],
                     axis=-1).reshape(columns.shape)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def rope_pairs(x, theta):
    """x: [B, T, H, D]; rotate each pair of neighbours (2i, 2i + 1) of D
    by position, pair i at theta^(-2i / D)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def route(u, w_router, bias, top_k):
    """(scores [B, T, X], chosen [B, T, X] bool) of float32 inputs: the
    ``top_k`` largest of sigmoid + bias."""
    scores = jax.nn.sigmoid(u @ w_router)
    biased = scores + bias
    kth = jnp.sort(biased, axis=-1)[..., -top_k]
    return scores, biased >= kth[..., None]


def attention(h, w, heads, rank, d_nope, d_rope, d_v, eps, theta, r):
    """Causal latent attention of the normed input, a block of queries
    at a time."""
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
    k_rope = rope_pairs(c[..., None, rank:], theta)    # one head
    q = jnp.concatenate([q[..., :d_nope], q_rope], axis=-1)
    # every head's key the long way: its own k_nope, the shared k_rope
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (B, T, heads, d_rope))], axis=-1)
    out = []
    for start in range(0, T, QUERY_BLOCK):
        stop = min(start + QUERY_BLOCK, T)
        scores = jnp.einsum("bqhd,bkhd->bhqk", r(q[:, start:stop]),
                            r(k[:, :stop])) / np.sqrt(d_nope + d_rope)
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


def loss(params, tokens, heads, rank, d_nope, d_rope, d_v, top_k, eps,
         theta, norm_topk, scale, first, rounded=None, parts=PARTS,
         shared=True):
    """(per-sequence loss [B], [Seen of each layer with experts]);
    tokens [B, T] int32.  ``rounded`` is a dtype through which every
    matmul operand outside the router is rounded first, in the ``parts``
    named (all of PARTS: what this model would give computed in that
    precision, PERF.md's second reading; some: where a precision's
    distance comes from).  ``shared`` False leaves the shared expert out
    (what a test tells apart)."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    r = {part: rounding(rounded if part in parts else None)
         for part in PARTS}
    x = f32(params["embed"])[tokens]
    seen = []
    for w in layers_of(params):
        w = {k: f32(v) for k, v in w.items()}
        h = rmsnorm(x, w["ln1"], eps)
        x = x + attention(h, w, heads, rank, d_nope, d_rope, d_v, eps,
                          theta, r["attention"])
        u = rmsnorm(x, w["ln2"], eps)
        if "w_router" not in w:       # a leading dense layer
            x = x + swiglu(u, w["w_gate"], w["w_up"], w["w_down"],
                           r["dense"])
            continue
        y, chosen = experts(u, w, top_k, norm_topk, scale, first,
                            r["experts"])
        seen.append(Seen(chosen, h, u))
        if shared:
            y = y + swiglu(u, w["ws_gate"], w["ws_up"], w["ws_down"],
                           r["shared"])
        x = x + y
    x = rmsnorm(x, f32(params["ln_f"]), eps)
    return head_loss(x, f32(params["lm_head"]), tokens, r["head"]), seen


def expert_layers(params):
    """The weights of each layer with experts, in float32, as ``loss``
    lists what it saw of them."""
    return [{k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
            for w in layers_of(params) if "w_router" in w]


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


def layer_errors(config, rounded=None):
    """A function of (params, seen) that gives {part: the largest over
    the layers with experts of |got - want| / |want|, the norms over a
    layer's whole [B, T, E] result, which no mean over the sequence can
    cancel} of LAYER_PARTS (compiled once, whatever the seeds) on the same
    inputs: the reference's own normed inputs of each such layer
    (``seen``), rounded to the program's compute dtype as the program's
    are.  ``want`` is this file's float32 math; ``got`` the program's own
    functions (``models/transformer._latent_mix``, ``_shared_expert``,
    ``_moe_ffn``: the kernels where kernels run) or, with ``rounded``,
    this file's with every matmul operand rounded through that dtype.
    The routed part takes the program's route on both sides
    (``check_routing`` holds the route itself)."""
    from elasticdl_tpu.models import transformer as tfm

    cfg = program_config(config)
    shape = shape_of(config)
    dtype = jnp.dtype(cfg.dtype)
    r = rounding(rounded)
    attend = lambda h, w, r: attention(
        h, w, shape["heads"], shape["rank"], shape["d_nope"],
        shape["d_rope"], shape["d_v"], shape["eps"], shape["theta"], r)
    share = lambda u, w, r: swiglu(u, w["ws_gate"], w["ws_up"],
                                   w["ws_down"], r)

    @functools.partial(jax.jit, static_argnums=3)
    def program(h, u, w, kind):
        h, u = h.astype(dtype), u.astype(dtype)
        route = tfm.moe_route(u, w["w_router"], cfg, w["expert_bias"])
        weights = (jax.nn.one_hot(route[2], cfg.moe_experts)
                   * route[1][..., None]).sum(-2)
        if rounded is not None:
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
        h, u = (a.astype(dtype).astype(jnp.float32) for a in (h, u))
        want = (attend(h, w, rounding(None)), share(u, w, rounding(None)),
                held_experts(u, w, weights, shape["first"]))
        norm = lambda a: jnp.sqrt(jnp.sum(jnp.square(a)))
        return [norm(g.astype(jnp.float32) - w_) / norm(w_)
                for g, w_ in zip(got, want)]

    def errors(params, seen):
        # a layer's Kind is the static argument: equal kinds compile once
        kinds = [kind for kind, w in zip(cfg.kinds, layers_of(params))
                 if "w_router" in w]
        worst = dict.fromkeys(LAYER_PARTS, 0.0)
        for s, w, kind in zip(seen, expert_layers(params), kinds):
            # the program's side as lib/compare.py runs the product: at
            # the default precision; this file's math at the highest
            with jax.default_matmul_precision(
                    "highest" if rounded is not None else "default"):
                weights, got = program(s.h, s.u, w, kind)
            with jax.default_matmul_precision("highest"):
                found = apart(s.h, s.u, w, weights, got)
            for part, error in zip(LAYER_PARTS, found):
                worst[part] = max(worst[part], float(error))
        return worst

    return errors


def check_layers(config, params, seen):
    """The program's latent attention, shared expert and held experts
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
