"""Plain reference of the ``olmo-hybrid-7b`` configuration's loss.

Olmo-Hybrid-7B's layer equations (``model_type: olmo_hybrid``) as its
public config gives them and, where the config has no key, as the
configuration's ``assumed`` lists them, in straightforward ``jax.numpy``
and float32, with no kernel, no chunk, no remat.  Written from those
equations (the Gated DeltaNet paper's recurrence, arXiv:2412.06464), not
from ``models/transformer.py``, and it imports nothing from
``ops/gated_delta.py``.

 - ``x = E[token]``.
 - a block, every layer, norms on the sublayers' OUTPUTS alone (the
   OLMo 2 / 3 family's reordered norm; in the program's tree
   ``ln1_post``, ``ln2_post``): ``x = x + n1(Mix(x))`` then ``x = x +
   n2(MLP(x))``: a sublayer reads the stream itself.
 - a ``linear_attention`` layer's mixer (Gated DeltaNet), ``H`` heads of
   ``d_k`` = 96 keys and ``d_v`` = 192 values, on the block's input
   ``h``:
   1. ``q~ = h W_q``, ``k~ = h W_k``, ``v~ = h W_v`` (the program keeps
      the three side by side, ``w_qkv`` = [q heads | k heads | v
      heads]); each channel passes a causal convolution of
      ``linear_conv_kernel_dim`` = 4 taps (zeros before the sequence's
      start, no bias) and then SiLU;
   2. a head at a time ``q <- q / |q|_2 * d_k^-1/2``, ``k <- k / |k|_2``;
   3. ``beta = 2 sigmoid(h W_b)`` a head (``linear_allow_neg_eigval``);
   4. ``g = -exp(A_log) softplus(h W_a + dt_bias)`` a head, ``alpha =
      exp(g)``;
   5. the state ``S`` [d_v, d_k] a head, zero before the first token,
      TOKEN BY TOKEN (``lax.scan`` over T, no chunks): ``S' = alpha_t
      S``; ``u_t = beta_t (v_t - S' k_t)``; ``S = S' + u_t k_t^T``;
      ``o_t = S q_t``;
   6. ``y = concat_heads(RMSNorm_192(o) * silu(h W_g)) W_o``: one
      learned scale of 192 that the heads share.
 - a ``full_attention`` layer's mixer: q, k, v of ``H`` heads of 128; q
   and k take an RMSNorm over the WHOLE projection (all the held heads'
   values, a scale of its width), no positional encoding at all
   (``rope_theta`` null), causal softmax over the whole sequence at
   ``128^-1/2``, explicit scores a block of ``QUERY_BLOCK`` queries at a
   time, ``W_o``.
 - the MLP: a SwiGLU of 11,008.
 - one RMSNorm after the last layer, an untied head, the mean next-token
   cross entropy, its logits ``HEAD_BLOCK`` rows at a time.  RMSNorm
   (eps 1e-6) is ``w * x / rms(x)``; no bias anywhere.

Departures from the published model, each the configuration's
(``configs/olmo-hybrid-7b.json``: ``reduced``, ``deployment``,
``assumed``):

 - the head share: the weights hold ``H`` = 15 of the 30 heads of both
   mixer kinds (their columns of every projection, their rows of
   ``W_o``) and a slice of the vocabulary; a mixer's result is the held
   heads' part of the ``W_o`` product, what the absent heads would add
   is left out, here as in the program, and the norm on the sublayer's
   output is taken of that partial result, which goes on to the next
   layer;
 - the full layer's QK norm takes its mean square over the held heads'
   1,920 values, what a chip has before the pair would exchange their
   sums of squares (``qk_stat`` hands a caller's in: the test that adds
   the shares up);
 - layers 0-3 of the 32; the MLP whole.

``params`` is the program's own tree (``layers`` = {"lead", "period",
"tail"}, a period's weights stacked over the periods), so the same
seeded weights go through both; which layer is of which kind is the
configuration's ``layer_types`` to say, not the weights'.  The caller
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
# published widths and T = 16,384 (``tools/olmo_hybrid_precision.py``, my
# chip runs, PR 44, calls ``c2`` and ``c3``) the bfloat16 product reads
# 1.4e-5 .. 1.16e-4 from this file over nine seeds and 6.9e-5 on the
# traced run (a loss of ~24.9 under the comparison's five-times-wider
# head, so 1e-4 of it is 2.5e-3 nats), and this limit is 3.4 times the
# largest.  It stands on the product's readings alone: this file with
# every matmul operand rounded to float8 (e4m3), the nearest precision
# below the bfloat16 the configuration states, reads 4.9e-5 .. 1.23e-3,
# inside the limit on three seeds of nine, because one mean over 16,383
# positions is too blunt a scalar (PERF.md section 7 (8)).  What fails
# float8 on every seed is SAME_INPUT_LAYER_CEILING below.
TOLERANCE = 4e-4
# The largest relative distance (``layer_errors``: norms over a layer's
# whole [T, 3840] result, so no mean over the sequence cancels anything)
# of the program's delta mixer, full-attention mixer and MLP from this
# file's float32 math on the same inputs, the worst of the layers of a
# kind.  The same calls, nine seeds: the bfloat16 program with its
# kernels reads 7.47-7.99e-3 / 3.47-3.55e-3 / 4.22e-3 (delta / attention
# / mlp: the delta mixer is three bfloat16 matmuls deep in a chunk
# where the others are one or two), this file in float8 5.75-6.09e-2 /
# 0.82 / 4.66e-2: the ceiling is 2.5 times the former's largest and 0.43
# of the latter's smallest, and float8 is past it in every part on nine
# seeds of nine.  NOT held by it: the delta rule's state alone in
# bfloat16 (every operand float32) reads 4.4-7.3e-3 in the delta part,
# under the bfloat16 program's own reading: at the decays these weights
# draw (a median alpha of 0.90-0.97 a token) a state's rounding is
# forgotten within a few dozen tokens and never accumulates (PERF.md
# section 7).
SAME_INPUT_LAYER_CEILING = 2e-2
# what ``loss`` can round apart, and what ``layer_errors`` compares
PARTS = ("delta", "attention", "mlp", "head")
LAYER_PARTS = ("delta", "attention", "mlp")
# what ``loss`` can leave out (``without``): what a test shows the
# tolerance to see
PIECES = ("conv", "silu", "l2norm", "beta2", "decay", "out_norm", "gate",
          "post_norms", "qk_norm")
# what ``loss`` saw of a layer: the mixer's input and the MLP's [B, T, E]
Seen = collections.namedtuple("Seen", "h u")
MICROBATCH = 1
HEAD_SCALE = 5.0
NORM_SPREAD = 0.25
QUERY_BLOCK = 1024
HEAD_BLOCK = 2048
L2_EPS = 1e-6
NORM_SCALES = ("ln1_post", "ln2_post", "q_norm", "k_norm", "o_norm")


def shape_of(config):
    """What ``loss`` needs of the configuration's file."""
    return dict(
        heads=config["num_attention_heads"],
        head_dim=config["head_dim"],
        delta_heads=config["linear_num_value_heads"],
        d_k=config["linear_key_head_dim"],
        d_v=config["linear_value_head_dim"],
        neg_eigval=config["linear_allow_neg_eigval"],
        eps=config["rms_norm_eps"],
        kinds=tuple(config["layer_types"][i] for i in config["layers_kept"]))


def inputs(config, params, rng):
    """(params, tokens [MICROBATCH, seq_len]) as both sides shall use
    them.  The head is drawn 5 times wider than the product's 0.02, so
    that the logits are not all near zero and the loss is not ln(V)
    whatever the network computes; and the scales of the block's norms
    (the two on the sublayers' outputs, q's and k's, the delta output's),
    all ones in the job, are drawn within 1 +- 0.25, so that a scale in
    the wrong place shows."""
    tokens = jnp.asarray(rng.integers(
        0, config["vocab_size"], (MICROBATCH, config["seq_len"])), jnp.int32)
    params["lm_head"] = params["lm_head"] * HEAD_SCALE
    for group in params["layers"].values():
        for w in group.values():
            for name in NORM_SCALES:
                if name in w:
                    w[name] = jnp.asarray(1.0 + NORM_SPREAD * rng.uniform(
                        -1.0, 1.0, w[name].shape), jnp.float32)
    return params, tokens


def case(config, params, rng, key):
    """See benchmark/lib/compare.py.  The reference runs once, here, at
    the highest matmul precision: its loss is what the returned function
    hands back, its layers' inputs what the layer check reads (stderr;
    it raises past its limit)."""
    params, tokens = inputs(config, params, rng)
    shape = shape_of(config)
    with jax.default_matmul_precision("highest"):
        per_record, seen = jax.jit(
            lambda p: loss(p, tokens, **shape))(params)
    check_layers(config, params, seen)
    return params, tokens, tokens, lambda p: per_record


def layers_of(params):
    """The weights of each layer in order, one dict a layer."""
    groups = params["layers"]
    take = lambda group: [group[str(i)] for i in range(len(group))]
    out = take(groups["lead"])
    period = take(groups["period"])
    periods = period[0]["ln1_post"].shape[0] if period else 0
    for p in range(periods):
        out += [{k: v[p] for k, v in w.items()} for w in period]
    return out + take(groups["tail"])


def rmsnorm(x, scale, eps, mean_square=None):
    if mean_square is None:
        mean_square = jnp.mean(x * x, axis=-1, keepdims=True)
    return scale * x / jnp.sqrt(mean_square + eps)


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
    """o [B, T, H, d_v] of the delta rule token by token; q, k [B, T, H,
    d_k], v [B, T, H, d_v], alpha, beta [B, T, H]; the state [B, H, d_v,
    d_k] passes ``r`` after every token (a lower precision's state).
    Also the state after the last token."""
    B, T, H, d_k = q.shape

    def token(S, x):
        q, k, v, alpha, beta = x
        S = alpha[..., None, None] * S
        u = beta[..., None] * (v - jnp.einsum("bhvk,bhk->bhv", S, k))
        S = r(S + u[..., :, None] * k[..., None, :])
        return S, jnp.einsum("bhvk,bhk->bhv", S, q)

    first = lambda a: jnp.moveaxis(a, 1, 0)
    S, o = jax.lax.scan(token, jnp.zeros((B, H, v.shape[-1], d_k), q.dtype),
                        tuple(map(first, (q, k, v, alpha, beta))))
    return jnp.moveaxis(o, 0, 1), S


def delta_gates(h, w, neg_eigval, without=()):
    """(alpha, beta) [B, T, H] of the block's input."""
    beta = jax.nn.sigmoid(h @ w["w_b"])
    if neg_eigval and "beta2" not in without:
        beta = 2.0 * beta
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(h @ w["w_a"] + w["dt_bias"])
    alpha = jnp.ones_like(g) if "decay" in without else jnp.exp(g)
    return alpha, beta


def delta_operands(h, w, heads, d_k, d_v, r, without=()):
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


def delta_mixer(h, w, heads, d_k, d_v, eps, neg_eigval, r=lambda a: a,
                without=(), state=lambda a: a):
    """The Gated DeltaNet mixer of the block's input [B, T, E] ->
    [B, T, E]: steps 1-6 of the module's text.  ``r`` rounds every
    matmul operand, ``state`` the recurrence's state after every token."""
    B, T, _ = h.shape
    q, k, v = delta_operands(h, w, heads, d_k, d_v, r, without)
    alpha, beta = delta_gates(h, w, neg_eigval, without)
    o, _ = recurrence(r(q), r(k), r(v), alpha, beta, state)
    if "out_norm" not in without:
        o = rmsnorm(o, w["o_norm"], eps)
    o = o.reshape(B, T, heads * d_v)
    if "gate" not in without:
        o = o * jax.nn.silu(r(h) @ r(w["w_out_gate"]))
    # departure (the head share): ``wo`` holds the held heads' rows, so
    # this is their part of the W_o product; the absent heads' part is
    # not added, and nothing stands in for the pair's all-reduce
    return r(o) @ r(w["wo"])


def attention(h, w, heads, head_dim, eps, r=lambda a: a, without=(),
              qk_stat=None):
    """Full causal attention of the block's input, no positional
    encoding, a block of queries at a time.  ``qk_stat``: (q's, k's)
    mean squares [B, T, 1] for the QK norm where the caller has them
    from more heads than are held here (the pair's exchange); else over
    the held heads' values."""
    B, T, _ = h.shape
    q, k, v = (r(h) @ r(w[name]) for name in ("wq", "wk", "wv"))
    # departure (the head share): the published norm's mean square runs
    # over all 30 heads' values; here over the held heads' (``qk_stat``
    # None), what a chip has before the pair would exchange its sums
    if "qk_norm" not in without:     # over the whole projection
        q = rmsnorm(q, w["q_norm"], eps, qk_stat and qk_stat[0])
        k = rmsnorm(k, w["k_norm"], eps, qk_stat and qk_stat[1])
    q, k, v = (a.reshape(B, T, heads, head_dim) for a in (q, k, v))
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
    # departure (the head share): the held heads' part of the W_o product
    return r(out) @ r(w["wo"])


def swiglu(u, w, r=lambda a: a):
    return r(jax.nn.silu(r(u) @ r(w["w_gate"]))
             * (r(u) @ r(w["w_up"]))) @ r(w["w_down"])


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
    a pair XLA's TPU backend may drop as excess precision (it did: the
    state "held in bfloat16" read 0.0 from the float32 one on the
    chip)."""
    if rounded is None:
        return lambda a: a
    if jnp.dtype(rounded) == jnp.bfloat16:
        return lambda a: jax.lax.reduce_precision(a, 8, 7)
    return lambda a: a.astype(rounded).astype(jnp.float32)


def mixer(kind, h, w, heads, head_dim, delta_heads, d_k, d_v, neg_eigval,
          eps, r=lambda a: a, without=(), state=lambda a: a):
    """The mixer of a layer of ``kind`` (its ``layer_types`` entry)."""
    if kind == "linear_attention":
        return delta_mixer(h, w, delta_heads, d_k, d_v, eps, neg_eigval, r,
                           without, state)
    assert kind == "full_attention", kind
    return attention(h, w, heads, head_dim, eps, r, without)


def loss(params, tokens, heads, head_dim, delta_heads, d_k, d_v, neg_eigval,
         eps, kinds, rounded=None, parts=PARTS, without=(), state=None):
    """(per-sequence loss [B], [Seen of each layer]); tokens [B, T]
    int32.  ``rounded`` is a dtype through which every matmul operand is
    rounded first, in the ``parts`` named (all of PARTS: what this model
    would give computed in that precision, PERF.md's second reading);
    ``state`` a dtype through which the delta rule's state passes after
    every token.  ``without`` names the PIECES to leave out (what a test
    tells apart)."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    r = {part: rounding(rounded if part in parts else None)
         for part in PARTS}
    post = (lambda y, scale: y) if "post_norms" in without else (
        lambda y, scale: rmsnorm(y, scale, eps))
    # departures (the cut): the ids, the logits and the loss are over a
    # slice of the vocabulary; ``kinds`` are published layers 0-3 alone
    x = f32(params["embed"])[tokens]
    seen = []
    for kind, w in zip(kinds, layers_of(params), strict=True):
        w = {k: f32(v) for k, v in w.items()}
        part = "delta" if kind == "linear_attention" else "attention"
        h = x
        x = x + post(mixer(kind, h, w, heads, head_dim, delta_heads, d_k,
                           d_v, neg_eigval, eps, r[part], without,
                           rounding(state)), w["ln1_post"])
        seen.append(Seen(h, x))
        # (the norm above was taken of the PARTIAL mixer result: the
        # deployment's would be taken after the pair's all-reduce)
        x = x + post(swiglu(x, w, r["mlp"]), w["ln2_post"])
    x = rmsnorm(x, f32(params["ln_f"]), eps)
    return head_loss(x, f32(params["lm_head"]), tokens, r["head"]), seen


def scan_statistics(config, params, seen):
    """The quartiles over (token, head) of ``alpha`` and ``beta`` and,
    over the heads, of the Frobenius norm of the state after the last
    token, in each ``linear_attention`` layer at the weights as drawn:
    what says whether the scan the comparison holds is a trivial one
    (every decay ~0 or ~1)."""
    shape = shape_of(config)
    quartiles = lambda a: [float(x) for x in np.quantile(
        np.asarray(a, np.float64).ravel(), (0.25, 0.5, 0.75))]
    out = []
    for (h, _), w, kind in zip(seen, layers_of(params), shape["kinds"]):
        if kind != "linear_attention":
            continue
        w = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}

        @jax.jit
        def stats(h, w):
            alpha, beta = delta_gates(h, w, shape["neg_eigval"])
            q, k, v = delta_operands(h, w, shape["delta_heads"],
                                     shape["d_k"], shape["d_v"],
                                     lambda a: a)
            _, S = recurrence(q, k, v, alpha, beta)
            return alpha, beta, jnp.sqrt(jnp.sum(S * S, axis=(-1, -2)))

        with jax.default_matmul_precision("highest"):
            alpha, beta, norms = stats(h, w)
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


def layer_errors(config, rounded=None, state=None):
    """A function of (params, seen) that gives {part: the largest over
    the layers of |got - want| / |want|, the norms over a layer's whole
    [B, T, E] result, which no mean over the sequence can cancel} of
    LAYER_PARTS on the same inputs: the reference's own inputs of each
    layer's mixer and MLP (``seen``, ``loss``'s second result), rounded
    to the program's compute dtype as the program's are.  ``want`` is
    this file's float32 math; ``got`` the program's own functions
    (``models/transformer._delta_mix`` with its convolution and scan
    kernels, ``_attention_mix`` with its QK norm and flash kernels,
    ``_gated_mlp``) or, with ``rounded`` or ``state``, this file's with
    every matmul operand rounded through the one dtype and the delta
    rule's state through the other."""
    from elasticdl_tpu.models import remat_keep
    from elasticdl_tpu.models import transformer as tfm

    cfg = program_config(config)
    shape = {k: v for k, v in shape_of(config).items() if k != "kinds"}
    dtype = jnp.dtype(cfg.dtype)
    lower = rounded is not None or state is not None

    @functools.partial(jax.jit, static_argnums=(3, 4))
    def program(h, u, w, said, kind):
        h, u = h.astype(dtype), u.astype(dtype)
        if lower:
            f32 = lambda a: a.astype(jnp.float32)
            r = rounding(rounded)
            return (mixer(said, f32(h), w, r=r, state=rounding(state),
                          **shape), swiglu(f32(u), w, r))
        mlp = tfm._gated_mlp(u, w, cfg, ("w_gate", "w_up", "w_down"),
                             (remat_keep.KEEP_GATE, remat_keep.KEEP_UP))
        if kind.op == "d":
            return tfm._delta_mix(h, w, cfg), mlp
        positions = jnp.arange(h.shape[1])
        return tfm._attention_mix(h, w, cfg, None, positions, kind)[0], mlp

    @functools.partial(jax.jit, static_argnums=4)
    def apart(h, u, w, got, said):
        h, u = (a.astype(dtype).astype(jnp.float32) for a in (h, u))
        want = (mixer(said, h, w, **shape), swiglu(u, w))
        norm = lambda a: jnp.sqrt(jnp.sum(jnp.square(a)))
        return [norm(g.astype(jnp.float32) - w_) / norm(w_)
                for g, w_ in zip(got, want)]

    def errors(params, seen):
        worst = dict.fromkeys(LAYER_PARTS, 0.0)
        for (h, u), w, said, kind in zip(
                seen, layers_of(params), shape_of(config)["kinds"],
                cfg.kinds, strict=True):
            w = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
            # the program's side as lib/compare.py runs the product: at
            # the default precision; this file's math at the highest
            with jax.default_matmul_precision(
                    "highest" if lower else "default"):
                got = program(h, u, w, said, kind)
            with jax.default_matmul_precision("highest"):
                found = apart(h, u, w, got, said)
            part = "delta" if said == "linear_attention" else "attention"
            for name, error in zip((part, "mlp"), found):
                worst[name] = max(worst[name], float(error))
        return worst

    return errors


def check_layers(config, params, seen):
    """The program's delta mixer, full-attention mixer and MLP against
    this file's on the same inputs (``layer_errors``).  One JSON line on
    stderr; raises over SAME_INPUT_LAYER_CEILING."""
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
