"""Plain reference of the ``nemotron-3-nano-30b-a3b`` configuration's loss.

NVIDIA-Nemotron-3-Nano-30B-A3B's layer equations (``model_type:
nemotron_h``) as its public config gives them and, where the config has
no key, as the configuration's ``assumed`` lists them, in straightforward
``jax.numpy`` and float32, with no kernel, no chunk, no sort-and-gather
dispatch, no remat.  Written from those equations (Mamba-2's recurrence,
arXiv:2405.21060; DeepSeek-V3's sigmoid router), not from
``models/transformer.py``, and it imports nothing from ``ops/``.

 - ``x = E[token]``; EVERY layer is ONE sublayer, pre-norm: ``x = x +
   F(RMSNorm(x))`` with one norm (eps 1e-5); no bias but the
   convolution's.  Layer ``i``'s ``F`` is the letter
   ``hybrid_override_pattern[i]`` (``layers_kept`` names the published
   layers that run here): ``M`` a Mamba-2 mixer, ``E`` an expert layer,
   ``*`` attention.
 - a Mamba-2 mixer, ``H`` = 64 heads of ``P`` = 64 over a state of ``N``
   = 128, B and C in ``G`` = 8 groups that 8 heads share, on ``h =
   RMSNorm(x)``:
   1. ``[z | x | B | C | dt] = h W_in`` (4096 | 4096 | 8 x 128 | 8 x 128
      | 64);
   2. each channel of ``x | B | C`` passes a causal convolution of 4
      taps (zeros before the sequence's start) WITH a bias, then SiLU;
   3. ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``, float32 a
      head;
   4. the state ``S`` [P, N] a head, zero before the first token, TOKEN
      BY TOKEN (``lax.scan`` over T, no chunks): ``S = exp(dt_t A) S +
      dt_t x_t B_t^T``; ``y_t = S C_t + D x_t``;
   5. ``RMSNorm(y * SiLU(z))`` over each group's 512 values (the gate
      BEFORE the norm) times one learned scale of 4,096; ``W_out``.
 - an attention layer: 32 query heads on 2 key/value heads of 128, NO
   positional encoding, causal softmax over the whole sequence at
   ``128^-1/2`` in blocks of ``QUERY_BLOCK`` queries; ``concat(heads)
   W_o``.
 - an expert layer, on ``u = RMSNorm(x)``: ``s = sigmoid(u W_r)`` over
   all 128 experts in float32; the 6 largest of ``s + bias`` chosen
   (explicit sort); weights the unbiased ``s`` of the chosen over their
   sum, times ``routed_scaling_factor`` 2.5; experts MLPs of TWO
   matrices, ``relu(u W_up)^2 W_down`` at 1,856 (a loop over the held
   ones), PLUS one shared MLP of the same form at 3,712.
 - one RMSNorm after the last layer, an untied head, the mean
   next-token cross entropy.

Departures from the published model, each the configuration's
(``configs/nemotron-3-nano-30b-a3b.json``: ``reduced``, ``deployment``,
``assumed``):

 - the shares: the weights hold experts ``first .. first + held`` of the
   router's 128 and a slice of the vocabulary; what the absent experts
   would add is left out, here as in the program, and that partial
   result goes on;
 - published layers 0-8 of the 52;
 - the division by the chosen scores' sum adds 1e-6 (the program's; the
   published form adds 1e-20: 1e-6 / sum of a sum of ~3);
 - no update of the bias and no balance loss.
"""

import collections
import contextlib
import functools
import json
import sys
import time

import jax
import jax.numpy as jnp

# |product - reference| / |reference| of the mean loss, at the published
# widths and T = 16,384 on the chip (``tools/nemotron3_precision.py``,
# seeds 11, 3000000019, 77, and two comparisons of the cell's; my chip
# runs, PR 61, calls ``prec``, ``t3``; PERF.md section 6): the product,
# bfloat16 as the configuration states, reads 7.9e-5, 1.7e-5, 1.7e-5 and
# 2.2e-5, 1.7e-5 (a loss of ~21.6 under the comparison's five-times-wider
# head); this file with every matmul operand outside the router rounded
# to float8 (e4m3), the nearest precision below, 4.2e-4, 5.9e-4 and
# 9.1e-4.  The limit is their geometric middle: 2.3 times over the
# product's largest and 2.3 times under float8's smallest.  Four of the
# layers' ceilings below refuse float8 too, on every seed.
TOLERANCE = 1.8e-4
# Both routers float32 at the highest precision: only exact ties may
# differ (``solar-open2-250b``'s).
SAME_INPUT_ROUTING_FLOOR = 0.9998
# The largest relative distance (norms over a part's whole result, so no
# mean over the sequence cancels anything) of the program's Mamba-2
# mixer, shared expert and held experts (and, by the tool, attention)
# from this file's float32 math on the same inputs, the worst of the
# layers.  Readings (``prec``, three seeds, every layer): the program
# mamba 5.66-5.72e-3 against float8's 2.68e-2, attention 3.3-3.4e-3
# against 0.86, the shared expert 3.96-3.97e-3 against 2.67-2.68e-2, the
# held experts 5.44e-3 against 6.4e-2: one limit for the four, their
# geometric middle, 2.1 times over the product's largest and 2.2 times
# under float8's smallest.
SAME_INPUT_LAYER_CEILING = 1.2e-2
# The same of the scan ALONE on a probe that remembers: the first
# Mamba-2 layer's own x, B, C and steps with its log decays times
# ``PROBE_DECAY``, so that a state lives thousands of tokens, crosses
# dozens of chunk boundaries and what a rounding of it leaves adds up
# (at the layer's own decays a state forgets a rounding within a chunk
# and a bfloat16 state reads under the bfloat16 program's own distance).
# Over the last quarter of the sequence.  Readings (``prec``): the
# program's kernels (float32 state and cumulative decays, bfloat16 x, B,
# C) 4.2e-3, 5.1e-3, 5.0e-3; this file with the state in bfloat16 0.85,
# 0.88, 0.66: the limit 9.8 times over the one and 13 times under the
# other (fresh seeds read higher: the room is above the product).  A
# chunk's cumulative log decays in bfloat16 read 0.8-1.0e-3 HERE, under
# the program's own: slowed a hundred times they round to nothing; the
# probe below is theirs.
SAME_INPUT_STATE_CEILING = 5e-2
PROBE_DECAY = 0.01
# The same of the scan alone at the layer's OWN decays, where a chunk's
# cumulative log decay runs to dozens: a bfloat16 running sum is then
# coarse by up to 0.25 where two near tokens' difference decides their
# score (a state rounded to bfloat16 is forgotten within the chunk and
# reads little here: the probe above is its).  Readings (``prec``): the
# program's kernels 2.72e-3, 2.76e-3, 2.64e-3; this file with the chunks'
# cumulative log decays in bfloat16 4.9e-2, 7.0e-2, 5.3e-2: the limit
# their geometric middle, 4.3 times over the one and 4.1 times under
# the other (a bfloat16 state reads 1.0-2.1e-2 here, on either side).
SAME_INPUT_DECAYS_CEILING = 1.2e-2
# part -> what the probe multiplies the layer's log decays by
PROBES = {"ssm_state": PROBE_DECAY, "ssm_decays": 1.0}
# what ``loss`` can round apart, and what ``layer_errors`` compares
PARTS = ("mamba", "attention", "experts", "shared", "head")
LAYER_PARTS = ("mamba", "attention", "shared_expert", "routed_experts",
               "ssm_state", "ssm_decays")
# what ``loss`` can leave out or put in (``without``): what a test shows
# the limits to see.  "skip": no ``D x``; "conv_bias": no bias on the
# taps; "gate": no ``SiLU(z)`` before the norm; "route_scale": the
# weights not times 2.5; "shared": no shared expert; "gate_product": a
# gate product put back, ``relu(a)^2 * a`` of the one product a.
PIECES = ("skip", "conv_bias", "gate", "route_scale", "shared",
          "gate_product")
# what ``loss`` saw of a layer: the router's choice [B, T, X] bool (None
# for a layer without one) and the sublayer's normed input
Seen = collections.namedtuple("Seen", "chosen h")
MICROBATCH = 1
HEAD_SCALE = 5.0
BIAS_SCALE = 0.1
NORM_SPREAD = 0.25
QUERY_BLOCK = 512
HEAD_BLOCK = 2048
ROUTE_EPS = 1e-6
CHUNK = 128      # the published chunk_size: the probe's bfloat16 sums'


def shape_of(config):
    """What ``loss`` needs of the configuration's file."""
    return dict(
        kinds=tuple({"M": "mamba", "E": "experts", "*": "attention"}[
            config["hybrid_override_pattern"][i]]
            for i in config["layers_kept"]),
        heads=config["mamba_num_heads"], width=config["mamba_head_dim"],
        d_state=config["ssm_state_size"], groups=config["n_groups"],
        q_heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        eps=config["norm_eps"], top_k=config["num_experts_per_tok"],
        norm_topk=config["norm_topk_prob"],
        scale=float(config["routed_scaling_factor"]),
        first=config.get("share_index", 0) * config["n_routed_experts"])


def inputs(config, params, rng):
    """(params, tokens [MICROBATCH, seq_len]) as both sides shall use
    them.  The head is drawn 5 times wider than the product's 0.02, so
    that the loss is not ln(V) whatever the network computes; every
    ``expert_bias`` (zeros in the job) at 0.1, so that the biased choice
    and the unbiased weights are compared too; the gated norm's scale
    and the skip ``D`` (ones in the job) within 1 +- 0.25."""
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
            elif name in ("ssm_norm", "ssm_D"):
                w[name] = jnp.asarray(1.0 + NORM_SPREAD * rng.uniform(
                    -1.0, 1.0, w[name].shape), jnp.float32)

    redraw(params)
    return params, tokens


def case(config, params, rng, key):
    """See benchmark/lib/compare.py.  The reference runs once, here, at
    the highest matmul precision: its loss is what the returned function
    hands back; what it saw of its layers is what the routing check and
    the layer check read (stderr; each raises past its limit)."""
    params, tokens = inputs(config, params, rng)
    took = {}
    with _timed(took, "reference_loss"), jax.default_matmul_precision(
            "highest"):
        per_record, seen = loss(params, tokens, **shape_of(config))
        mean = float(per_record.mean())
    with _timed(took, "routing"):
        check_routing(config, params, seen)
    with _timed(took, "layers"):
        check_layers(config, params, seen)
    # the seconds of the 300 the harness gives the comparison that this
    # file's part took (the product's own forward comes after it)
    print(json.dumps({"loss": mean, "seconds": took}), file=sys.stderr,
          flush=True)
    return params, tokens, tokens, lambda p: per_record


@contextlib.contextmanager
def _timed(took, name):
    start = time.time()
    yield
    took[name] = round(time.time() - start, 1)


def layers_of(params):
    """The weights of each layer in order, one dict a layer, float32."""
    groups = params["layers"]
    take = lambda group: [group[str(i)] for i in range(len(group))]
    out = take(groups["lead"])
    period = take(groups["period"])
    periods = jax.tree_util.tree_leaves(period)[0].shape[0] if period else 0
    for p in range(periods):
        out += [{k: v[p] for k, v in w.items()} for w in period]
    out += take(groups["tail"])
    return [{k: v.astype(jnp.float32) for k, v in w.items()} for w in out]


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def causal_conv(x, taps, bias=None):
    """x [B, T, C], taps [C, K] -> y_t = sum_k taps[:, k] x_(t - (K - 1 -
    k)) (+ bias), zeros before the sequence's start."""
    T, K = x.shape[1], taps.shape[1]
    y = jnp.zeros_like(x) if bias is None else jnp.zeros_like(x) + bias
    for k in range(K):
        back = K - 1 - k
        moved = x if not back else jnp.concatenate(
            [jnp.zeros_like(x[:, :back]), x[:, :T - back]], axis=1)
        y = y + taps[:, k] * moved
    return y


def recurrence(x, b, c, alpha, dt, r=lambda a: a):
    """y [B, T, H, P] of the state-space recurrence token by token; x
    [B, T, H, P], b, c [B, T, G, N], alpha (the decay) and dt [B, T, H];
    the state [B, G, H / G, P, N] passes ``r`` after every token (a
    lower precision's state)."""
    B, T, H, P = x.shape
    G, N = b.shape[2:]
    grouped = lambda a: a.reshape(B, T, G, H // G, *a.shape[3:])

    def token(S, now):
        x, b, c, alpha, dt = now
        write = (dt[..., None] * x)[..., None] * b[:, :, None, None, :]
        S = r(alpha[..., None, None] * S + write)
        return S, jnp.einsum("bghpn,bgn->bghp", S, c)

    first = lambda a: jnp.moveaxis(a, 1, 0)
    _, y = jax.lax.scan(
        token, jnp.zeros((B, G, H // G, P, N), x.dtype),
        tuple(map(first, (grouped(x), b, c, grouped(alpha), grouped(dt)))),
        unroll=4)
    return jnp.moveaxis(y, 0, 1).reshape(B, T, H, P)


def mamba_operands(h, w, heads, width, state, groups, r=lambda a: a,
                   without=()):
    """(z [B, T, H P], x [B, T, H, P], b, c [B, T, G, N], the log decay
    and dt [B, T, H]) of the normed input."""
    B, T, _ = h.shape
    inner, shared = heads * width, groups * state
    z, xbc, dt = jnp.split(r(h) @ r(w["ssm_in"]),
                           [inner, 2 * inner + 2 * shared], axis=-1)
    xbc = jax.nn.silu(causal_conv(
        xbc, w["ssm_conv"],
        None if "conv_bias" in without else w["ssm_conv_bias"]))
    x, b, c = jnp.split(xbc, [inner, inner + shared], axis=-1)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    return (z, x.reshape(B, T, heads, width),
            b.reshape(B, T, groups, state), c.reshape(B, T, groups, state),
            -jnp.exp(w["A_log"]) * dt, dt)


def mamba(h, w, heads, width, state, groups, eps, r=lambda a: a, without=(),
          rstate=lambda a: a):
    B, T, _ = h.shape
    z, x, b, c, g, dt = mamba_operands(h, w, heads, width, state, groups, r,
                                       without)
    y = recurrence(x, b, c, jnp.exp(g), dt, rstate)
    if "skip" not in without:
        y = y + w["ssm_D"][:, None] * x
    y = y.reshape(B, T, -1)
    if "gate" not in without:
        y = y * jax.nn.silu(z)
    y = rmsnorm(y.reshape(B, T, groups, -1), w["ssm_norm"].reshape(
        groups, -1), eps).reshape(B, T, -1)
    return r(y) @ r(w["ssm_out"])


def attention(h, w, q_heads, kv_heads, head_dim, r=lambda a: a):
    """Causal softmax attention over grouped K/V heads, no positional
    encoding, a block of ``QUERY_BLOCK`` queries at a time."""
    B, T, _ = h.shape
    per = q_heads // kv_heads
    q = (r(h) @ r(w["wq"])).reshape(B, T, kv_heads, per, head_dim)
    k = (r(h) @ r(w["wk"])).reshape(B, T, kv_heads, head_dim)
    v = (r(h) @ r(w["wv"])).reshape(B, T, kv_heads, head_dim)
    size = min(QUERY_BLOCK, T)

    def block(start):
        rows = jax.lax.dynamic_slice_in_dim(q, start, size, axis=1)
        s = jnp.einsum("bqgpd,bkgd->bgpqk", r(rows), r(k)) * head_dim ** -0.5
        seen = (start + jnp.arange(size))[:, None] >= jnp.arange(T)[None, :]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bgpqk,bkgd->bqgpd", r(p), r(v))

    o = jax.lax.map(block, jnp.arange(0, T, size))       # [n, B, size, ..]
    o = jnp.moveaxis(o, 0, 1).reshape(B, T, q_heads * head_dim)
    return r(o) @ r(w["wo"])


def mlp(u, up, down, r, without=()):
    """``relu(u W_up)^2 W_down``: two matrices, no gate product."""
    a = r(u) @ r(up)
    act = jnp.square(jax.nn.relu(a))
    if "gate_product" in without:
        act = act * a
    return r(act) @ r(down)


def route(u, w_router, bias, top_k):
    """(scores [B, T, X] float32, chosen [B, T, X] bool): a sigmoid of
    each expert's logit, the ``top_k`` largest of score + bias chosen,
    by an explicit sort."""
    s = jax.nn.sigmoid(u @ w_router)
    biased = s + bias
    kth = jnp.sort(biased, axis=-1)[..., -top_k][..., None]
    return s, biased >= kth


def route_weights(u, w_router, bias, top_k, norm_topk, scale):
    """(weights [B, T, X] float32: 0 where not chosen, chosen)."""
    s, chosen = route(u, w_router, bias, top_k)
    weights = jnp.where(chosen, s, 0.0)
    if norm_topk:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + ROUTE_EPS)
    return weights * scale, chosen


def held_experts(u, w, weights, first, r=lambda a: a, without=()):
    """The held experts' part of the routed result: a loop over them,
    each on every token, weighted by its column of ``weights``."""
    out = jnp.zeros_like(u)
    for j in range(w["w_up"].shape[0]):
        out = out + weights[..., first + j, None] * mlp(
            u, w["w_up"][j], w["w_down"][j], r, without)
    return out


def head_loss(x, head, tokens, r):
    """Per-sequence mean next-token cross entropy of the normed stream
    ``x`` [B, T, E], the logits a block of rows at a time."""
    B, T, _ = x.shape
    size = min(HEAD_BLOCK, T)
    targets = jnp.roll(tokens, -1, axis=1)
    counted = (jnp.arange(T) < T - 1).astype(jnp.float32)

    def block_sum(start):
        rows = jax.lax.dynamic_slice_in_dim(x, start, size, axis=1)
        logp = jax.nn.log_softmax(r(rows) @ r(head), axis=-1)
        picked = jnp.take_along_axis(logp, jax.lax.dynamic_slice_in_dim(
            targets, start, size, axis=1)[..., None], axis=-1)[..., 0]
        return -(picked * jax.lax.dynamic_slice_in_dim(
            counted, start, size)).sum(axis=-1)

    return jax.lax.map(block_sum, jnp.arange(0, T, size)).sum(axis=0) / (
        T - 1)


def rounding(rounded):
    """a -> a through dtype ``rounded`` and back; the identity for None.
    bfloat16 by ``reduce_precision``: a convert to bfloat16 and back is
    a pair XLA's TPU backend may drop as excess precision."""
    if rounded is None:
        return lambda a: a
    if jnp.dtype(rounded) == jnp.bfloat16:
        return lambda a: jax.lax.reduce_precision(a, 8, 7)
    return lambda a: a.astype(rounded).astype(jnp.float32)


Pieces = collections.namedtuple(
    "Pieces", "mamba attention shared held weights head")


@functools.lru_cache(maxsize=None)
def pieces(key, rounded=None, parts=PARTS, without=(), state=None):
    """This file's jitted programs for a shape (``key``: its sorted
    items), one a layer kind, so that the layers of a kind compile once:
    matmul operands of the ``parts`` named rounded to ``rounded``, the
    recurrent state to ``state``, the PIECES ``without`` left out."""
    shape = dict(key)
    r = {part: rounding(rounded if part in parts else None)
         for part in PARTS}
    scale = 1.0 if "route_scale" in without else shape["scale"]
    return Pieces(
        mamba=jax.jit(lambda h, w: mamba(
            h, w, shape["heads"], shape["width"], shape["d_state"],
            shape["groups"], shape["eps"], r["mamba"], without,
            rounding(state))),
        attention=jax.jit(lambda h, w: attention(
            h, w, shape["q_heads"], shape["kv_heads"], shape["head_dim"],
            r["attention"])),
        shared=jax.jit(lambda u, w: mlp(u, w["ws_up"], w["ws_down"],
                                        r["shared"], without)),
        held=jax.jit(lambda u, w, weights: held_experts(
            u, w, weights, shape["first"], r["experts"], without)),
        weights=jax.jit(lambda u, w: route_weights(
            u, w["w_router"], w["expert_bias"], shape["top_k"],
            shape["norm_topk"], scale)),
        head=jax.jit(lambda x, scale, head, tokens: head_loss(
            rmsnorm(x, scale, shape["eps"]), head, tokens, r["head"])))


def loss(params, tokens, rounded=None, parts=PARTS, without=(), state=None,
         **shape):
    """(per-sequence mean cross entropy [B], what each layer saw): the
    whole model in float32, a layer at a time."""
    run = pieces(tuple(sorted(shape.items())), rounded, tuple(parts),
                 tuple(without), state)
    norm = jax.jit(lambda x, scale: rmsnorm(x, scale, shape["eps"]))
    x = params["embed"].astype(jnp.float32)[tokens]
    seen = []
    for kind, w in zip(shape["kinds"], layers_of(params), strict=True):
        h = norm(x, w["ln2" if kind == "experts" else "ln1"])
        chosen = None
        if kind == "mamba":
            out = run.mamba(h, w)
        elif kind == "attention":
            out = run.attention(h, w)
        else:
            weights, chosen = run.weights(h, w)
            out = run.held(h, w, weights)
            if "shared" not in without:
                out = out + run.shared(h, w)
        seen.append(Seen(chosen, h))
        x = x + out
    return run.head(x, params["ln_f"].astype(jnp.float32),
                    params["lm_head"].astype(jnp.float32), tokens), seen


def program_config(config):
    """The program's ``TransformerConfig`` of the configuration's file."""
    from benchmark.lib.runner import params_string
    from elasticdl_tpu.models.spec import load_model_spec

    return load_model_spec(
        config["cli"]["model_zoo"],
        model_params=params_string(config["cli"]["model_params"])).config


def check_routing(config, params, seen):
    """The program's router against this file's on the same inputs: the
    reference's own router inputs of each expert layer (``seen``),
    rounded to the program's compute dtype as the program's are.  One
    JSON line on stderr; raises under SAME_INPUT_ROUTING_FLOOR."""
    from elasticdl_tpu.models import transformer as tfm

    cfg = program_config(config)
    shape = shape_of(config)

    @jax.jit
    def both(u, w_router, bias):
        u = u.astype(jnp.dtype(cfg.dtype))
        theirs = jax.nn.one_hot(tfm.moe_route(u, w_router, cfg, bias)[2],
                                cfg.moe_experts).sum(-2) > 0
        ours = route(u.astype(jnp.float32), w_router, bias,
                     shape["top_k"])[1]
        return (theirs & ours).sum() / theirs.sum()

    with jax.default_matmul_precision("highest"):
        same_input = min(
            float(both(s.h, w["w_router"], w["expert_bias"]))
            for s, w in zip(seen, layers_of(params)) if "w_router" in w)
    print(json.dumps({"routing_same_input": same_input,
                      "floor": SAME_INPUT_ROUTING_FLOOR}),
          file=sys.stderr, flush=True)
    if same_input < SAME_INPUT_ROUTING_FLOOR:
        raise SystemExit(
            "the program's router chose other experts than a float32 "
            "router on the same inputs: %.5f of the pairs agree, under "
            "%.4f" % (same_input, SAME_INPUT_ROUTING_FLOOR))
    return same_input


def coarse_decays(g, dtype):
    """The log decays a token that a scan with its chunks' cumulative
    sums in ``dtype`` would see: the differences of each chunk's rounded
    running sum."""
    B, T, H = g.shape
    size = min(CHUNK, T)
    cum = jnp.cumsum(g.reshape(B, T // size, size, H), axis=2)
    cum = rounding(dtype)(cum)
    before = jnp.concatenate([jnp.zeros_like(cum[:, :, :1]), cum[:, :, :-1]],
                             axis=2)
    return (cum - before).reshape(B, T, H)


def layer_errors(config, rounded=None, state=None, decays=None):
    """A function of (params, seen, every=False) that gives {part: |got
    - want| / |want|, the norms over a part's whole result} of
    LAYER_PARTS (the first Mamba-2 layer, the first expert layer and the
    two probes; with ``every`` the largest over all the layers, attention
    among them) on the same inputs: the reference's own (``seen``),
    rounded to the program's compute dtype as the program's are.
    ``want`` is this file's float32 math; ``got`` the program's own
    functions (``models/transformer._ssm_mix`` with its convolution and
    scan kernels, ``_attention_mix`` with its flash kernels,
    ``_shared_expert``, ``_moe_ffn``, ``ops/ssd.ssd`` on the probe: the
    kernels where kernels run) or, with ``rounded`` (matmul operands),
    ``state`` (the recurrent state) or ``decays`` (the chunks'
    cumulative log decays), this file's in that precision.  The routed
    part takes the program's route on both sides (``check_routing``
    holds the route itself)."""
    from elasticdl_tpu.models import transformer as tfm
    from elasticdl_tpu.ops import ssd

    cfg = program_config(config)
    shape = shape_of(config)
    key = tuple(sorted(shape.items()))
    dtype = jnp.dtype(cfg.dtype)
    lower = rounded is not None or state is not None or decays is not None
    exact = pieces(key)
    lowered = pieces(key, rounded, PARTS, (), state)
    r, rstate = rounding(rounded), rounding(state)
    cast = jax.jit(lambda a: a.astype(dtype).astype(jnp.float32))
    sizes = (shape["heads"], shape["width"], shape["d_state"],
             shape["groups"])

    @jax.jit
    def distance(got, want):
        norm = lambda a: jnp.sqrt(jnp.sum(jnp.square(a)))
        return norm(got.astype(jnp.float32) - want) / norm(want)

    @functools.partial(jax.jit, static_argnums=2)
    def mixed(h, w, kind):
        h = h.astype(dtype)
        if kind.op == "m":
            return tfm._ssm_mix(h, w, cfg)[0]
        return tfm._attention_mix(h, w, cfg, None, jnp.arange(h.shape[1]),
                                  kind)[0]

    @jax.jit
    def fed(u, w):
        """(the program's route as weights [B, T, X], its shared expert,
        its held experts)."""
        u = u.astype(dtype)
        route_ = tfm.moe_route(u, w["w_router"], cfg, w["expert_bias"])
        weights = (jax.nn.one_hot(route_[2], cfg.moe_experts)
                   * route_[1][..., None]).sum(-2)
        if lower:
            return weights, ()
        return weights, (tfm._shared_expert(u, w, cfg),
                         tfm._moe_ffn(u, w, cfg, None, route_)[0])

    @functools.partial(jax.jit, static_argnums=2)
    def remembered(h, w, slowed):
        """(what the program's scan (or this file's, lowered) and this
        file's float32 recurrence remember of the probe whose log decays
        are the layer's times ``slowed``, the last quarter of the
        sequence)."""
        with jax.default_matmul_precision("highest"):
            _, x, b, c, g, dt = mamba_operands(
                h.astype(dtype).astype(jnp.float32), w, *sizes)
            g = slowed * g
            want = recurrence(x, b, c, jnp.exp(g), dt)
            if lower:
                seen = g if decays is None else coarse_decays(g, decays)
                got = recurrence(r(x), r(b), r(c), jnp.exp(seen), dt, rstate)
        if not lower:
            got = ssd.ssd(*(a.astype(dtype) for a in (x, b, c)), g, dt)
        late = 3 * want.shape[1] // 4
        return got[:, late:], want[:, late:]

    def errors(params, seen, every=False):
        worst = dict.fromkeys(LAYER_PARTS, 0.0)
        at = lambda precision: jax.default_matmul_precision(precision)
        program = "highest" if lower else "default"
        layers = layers_of(params)
        done = set()
        for s, w, said, kind in zip(seen, layers, shape["kinds"], cfg.kinds,
                                    strict=True):
            if said in done and not every:
                continue      # one layer a kind: each is a program more
            done.add(said)
            if said == "attention" and not every:
                continue      # the tool's: the harness has 300 s
            h = cast(s.h)
            if said != "experts":
                with at(program):
                    got = getattr(lowered, said)(h, w) if lower else mixed(
                        s.h, w, kind)
                with at("highest"):
                    worst[said] = max(worst[said], float(distance(
                        got, getattr(exact, said)(h, w))))
                continue
            with at(program):
                weights, got = fed(s.h, w)
                if lower:
                    got = (lowered.shared(h, w), lowered.held(h, w, weights))
            with at("highest"):
                want = (exact.shared(h, w), exact.held(h, w, weights))
                for name, g, w_ in zip(("shared_expert", "routed_experts"),
                                       got, want):
                    worst[name] = max(worst[name], float(distance(g, w_)))
        if not every:
            del worst["attention"]
        first = shape["kinds"].index("mamba")
        for part, slowed in PROBES.items():
            with at(program):
                got, want = remembered(seen[first].h, layers[first], slowed)
            worst[part] = float(distance(got, want))
        return worst

    return errors


def ceilings():
    own = {"ssm_state": SAME_INPUT_STATE_CEILING,
           "ssm_decays": SAME_INPUT_DECAYS_CEILING}
    return {part: own.get(part, SAME_INPUT_LAYER_CEILING)
            for part in LAYER_PARTS}


def check_layers(config, params, seen):
    """The program's Mamba-2 mixer, shared expert and held experts (the
    first layer of each kind) and the scan on the probe that remembers
    against this file's on the same inputs (``layer_errors``; every
    layer and attention: ``tools/nemotron3_precision.py``).  One JSON
    line on stderr; raises over a part's ceiling."""
    errors = layer_errors(config)(params, seen)
    limits = ceilings()
    print(json.dumps({"layers_same_input": errors, "ceilings": {
        part: limits[part] for part in errors}}), file=sys.stderr,
        flush=True)
    over = {part: error for part, error in errors.items()
            if not error <= limits[part]}
    if over:
        raise SystemExit(
            "the program's layers lie further from float32 math on the "
            "same inputs than the stated precision allows: %s, over %s"
            % (", ".join("%s %.2e" % item for item in sorted(over.items())),
               limits))
