"""Plain reference of the ``ouro-2.6b`` configuration's loss.

Ouro-2.6B (ByteDance; "Scaling Latent Reasoning via Looped Language
Models", arXiv:2510.25741) as this repository reads it, in
straightforward ``jax.numpy`` and float32, with no kernel, no scan, no
remat and no cache; the turns are a Python loop over ONE set of weights:

 - ``h_0 = Emb[tokens]``;
 - a layer: ``a = x + n2(Attn(n1(x)))``, ``x' = a + n4(MLP(n3(a)))``,
   every ``n`` an RMSNorm with a learned scale; ``Attn``: q, k, v as 16
   heads of 128 without bias, RoPE (theta 1e6) on the whole head turned
   by halves, causal softmax at ``head_dim ** -0.5``, ``W_o``; ``MLP``:
   ``(SiLU(h W_gate) * (h W_up)) W_down``;
 - turn t = 1 .. R: ``u_t = Layer_N(.. Layer_1(h_(t-1)))`` on the SAME
   weights, ``h_t = RMSNorm_f(u_t)`` with the model's one final scale,
   and the next turn reads ``h_t``;
 - the exit gate a token: ``lambda_t = sigmoid(h_t . w_g + b_g)`` for t <
   R; ``p_1 = lambda_1``, ``p_t = lambda_t prod_(j<t) (1 - lambda_j)``,
   ``p_R = prod_(j<R) (1 - lambda_j)``;
 - a turn's loss a token: ``l_t[i] = CE(h_t[i] W_head, token[i + 1])``,
   the one untied head for every turn;
 - the job's loss: ``mean_i [sum_t p_t[i] l_t[i] - beta H(p[i])]`` over
   the T - 1 positions with a target, ``H(p) = - sum_t p_t log p_t``.

Departures, each noted in configs/ouro-2.6b.json: the depth (the first
layers of the 48; what ``assumed`` says of the norms, the carry, the
gate's form and ``beta``).  The logits are taken a block of rows at a
time and the scores a block of queries at a time, so that four [8192,
49152] float32 planes never stand at once; a turn and a head are a
``jax.jit`` each, compiled once for the four.

``params`` is the program's own tree (layer weights stacked on a leading
axis), so the same seeded weights go through both.  The caller sets
``jax.default_matmul_precision("highest")``.
"""

import collections
import functools
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

# The mean loss's largest relative difference.  On the chip at the
# published widths and T = 8,192 (``tools/ouro_precision.py`` and the
# cell's traced runs, my chip runs, PR 66; PERF.md section 6 has every
# reading) the bfloat16 product reads 5.8e-6 .. 6.0e-4 from this file
# over ten seeds and three traced runs (a loss of ~20.7 under the
# comparison's five-times-wider head) and the float32 product 9e-8 ..
# 3.3e-6; this limit is 3.3 times the largest.  It stands on the
# product's readings alone, as ``olmo-hybrid-7b``'s does: this file with
# every matmul operand rounded to float8 reads 8.6e-4 .. 1.3e-2, inside
# it on four seeds of ten, because one mean over 8,191 positions is a
# blunt scalar.  What refuses float8 on every seed is
# TURN_STATE_CEILING below; what this limit does refuse on every seed is
# a dropped entropy term (4.1e-3 .. 5.8e-3 at the gate ``inputs`` draws).
TOLERANCE = 2e-3
# The largest relative distance, over the turns, of the product's
# final-normed state ``h_t`` [T, E] from this file's on the same tokens
# and weights (norms over the whole plane: no mean over the sequence
# cancels anything, where one mean over 8,191 positions is a blunt
# scalar).  The same runs: the bfloat16 product reads 1.2e-2 at the
# first turn and 2.3e-2 .. 2.7e-2 at the fourth (the turns add up), the
# float32 product 2e-5, and this file with every matmul operand rounded
# to float8 (e4m3), the nearest precision below the bfloat16 the
# configuration states, 0.64 .. 0.73 at every turn: the ceiling is 3.0
# times the former's largest and an eighth of the latter's smallest.  An
# un-normed carry and dropped output norms read 1.2.
TURN_STATE_CEILING = 8e-2
# The largest difference of a turn's mean exit probability (the
# product's ``ut_exit`` a turn against this file's): a gate that fell
# out of the step reads (1/2, 1/4, 1/8, 1/8) whatever the weights, tenths
# from what the drawn gate gives, where the expectation's loss can hide
# it (the turns' losses lie within a percent of each other at random
# weights, and what the exit distribution moves of the expectation the
# entropy term can move back: a gate left at zero moved the loss by
# 6.5e-4 on one seed).  The bfloat16 product reads 1e-5 .. 4.1e-3 a turn
# on the chip, a gate left at zero 0.08 .. 0.32: five times the former's
# largest, a quarter of the latter's smallest.
EXIT_CEILING = 2e-2
MICROBATCH = 1
EMBED_SCALE = 25.0
HEAD_SCALE = 5.0
NORM_SPREAD = 0.25
GATE_LOGIT_SPREAD = 1.0
GATE_BIAS = 0.5
QUERY_BLOCK = 1024
HEAD_BLOCK = 2048
NORM_SCALES = ("ln1_post", "ln2_post")
# what ``loss`` can leave out (``without``): what a test and the tool
# show the limits to see
PIECES = ("gate", "entropy", "last_turn", "carry_norm", "post_norms")
# what ``loss`` saw beside the loss: each turn's mean cross entropy [R],
# the mean exit distribution [R], its mean entropy, every ``h_t``
Seen = collections.namedtuple("Seen", "turn_losses exit entropy states")


def shape_of(config):
    """What ``loss`` needs of the configuration's file."""
    return dict(heads=config["num_attention_heads"],
                head_dim=config["head_dim"], eps=config["rms_norm_eps"],
                theta=float(config["rope_theta"]),
                turns=config["total_ut_steps"],
                beta=config["cli"]["model_params"]["ut_entropy_weight"])


def inputs(config, params, rng):
    """(params, tokens [MICROBATCH, seq_len]) as both sides shall use
    them.  The embedding is drawn 25 times and the head 5 times wider
    than the product's 0.02, so that a token's own row is not lost under
    the first block's result and the loss is not ln(V) whatever the
    network computes; the scales of the norms on the sublayers' outputs
    and of the final norm, all ones in the job, are drawn within 1 +-
    0.25, so that a scale in the wrong place (or a turn's norm left out)
    shows; and the exit gate, drawn at ZERO in the job, gets weights
    that make its logits ~1 wide and a bias of +0.5 or -0.5: at zero
    every ``p_t`` is a constant and a swapped or dropped turn would
    hide, and a gate much wider than that leaves the exit distribution
    on one or two turns, its entropy under half a nat and the entropy
    term under the loss's tolerance (a first draw, 1.5 wide with a bias
    of 1, read 2.5e-3 .. 5.4e-3 for the dropped term over six seeds)."""
    tokens = jnp.asarray(rng.integers(
        0, config["vocab_size"], (MICROBATCH, config["seq_len"])), jnp.int32)
    params["embed"] = params["embed"] * EMBED_SCALE
    params["lm_head"] = params["lm_head"] * HEAD_SCALE
    spread = lambda shape: jnp.asarray(
        1.0 + NORM_SPREAD * rng.uniform(-1.0, 1.0, shape), jnp.float32)
    for name in NORM_SCALES:
        params["layers"][name] = spread(params["layers"][name].shape)
    params["ln_f"] = spread(params["ln_f"].shape)
    width = params["ut_gate_w"].shape[0]
    params["ut_gate_w"] = jnp.asarray(
        rng.normal(0.0, GATE_LOGIT_SPREAD / np.sqrt(width), (width,)),
        jnp.float32)
    params["ut_gate_b"] = jnp.asarray(rng.choice([-GATE_BIAS, GATE_BIAS]),
                                      jnp.float32)
    return params, tokens


def case(config, params, rng, key):
    """See benchmark/lib/compare.py.  The reference runs once, here, at
    the highest matmul precision: its loss is what the returned function
    hands back, its turns' states what the state check reads (stderr; it
    raises past its limit)."""
    params, tokens = inputs(config, params, rng)
    with jax.default_matmul_precision("highest"):
        per_record, seen = loss(params, tokens, **shape_of(config))
    check_turns(config, params, tokens, seen)
    return params, tokens, tokens, lambda p: per_record


def rounding(rounded):
    """a -> a through dtype ``rounded`` and back; the identity for None
    (bfloat16 by ``reduce_precision``: a convert there and back is a
    pair XLA's TPU backend may drop as excess precision)."""
    if rounded is None:
        return lambda a: a
    if jnp.dtype(rounded) == jnp.bfloat16:
        return lambda a: jax.lax.reduce_precision(a, 8, 7)
    return lambda a: a.astype(rounded).astype(jnp.float32)


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


def attention(h, w, heads, head_dim, theta, r):
    """Causal softmax attention over all of ``h`` [B, T, E], the scores
    a block of queries at a time."""
    B, T, _ = h.shape
    split = lambda a: a.reshape(B, T, heads, head_dim)
    q = rope(split(r(h) @ r(w["wq"])), theta)
    k = rope(split(r(h) @ r(w["wk"])), theta)
    v = split(r(h) @ r(w["wv"]))
    out = []
    for start in range(0, T, QUERY_BLOCK):
        stop = min(start + QUERY_BLOCK, T)
        scores = jnp.einsum("bqhd,bkhd->bhqk", r(q[:, start:stop]),
                            r(k[:, :stop])) * head_dim ** -0.5
        causal = (jnp.arange(start, stop)[:, None]
                  >= jnp.arange(stop)[None, :])
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", r(probs), r(v[:, :stop])))
    return r(jnp.concatenate(out, axis=1).reshape(B, T, -1)) @ r(w["wo"])


def swiglu(h, w, r):
    return r(jax.nn.silu(r(h) @ r(w["w_gate"]))
             * (r(h) @ r(w["w_up"]))) @ r(w["w_down"])


@functools.partial(jax.jit, static_argnames=(
    "heads", "head_dim", "eps", "theta", "rounded", "post_norms"))
def stack(x, layers, heads, head_dim, eps, theta, rounded=None,
          post_norms=True):
    """The N layers once: ``layers``' leaves are stacked on a leading
    axis."""
    r = rounding(rounded)
    post = (lambda y, scale: rmsnorm(y, scale, eps)) if post_norms else (
        lambda y, scale: y)
    for i in range(layers["wq"].shape[0]):
        w = {name: leaf[i] for name, leaf in layers.items()}
        x = x + post(attention(rmsnorm(x, w["ln1"], eps), w, heads,
                               head_dim, theta, r), w["ln1_post"])
        x = x + post(swiglu(rmsnorm(x, w["ln2"], eps), w, r),
                     w["ln2_post"])
    return x


@functools.partial(jax.jit, static_argnames=("rounded",))
def token_losses(h, head, tokens, rounded=None):
    """Each position's next-token cross entropy [B, T - 1] of the
    normed state ``h`` [B, T, E], the logits a block of rows at a
    time."""
    r = rounding(rounded)
    T, out = h.shape[1], []
    for start in range(0, T - 1, HEAD_BLOCK):
        stop = min(start + HEAD_BLOCK, T - 1)
        logp = jax.nn.log_softmax(r(h[:, start:stop]) @ r(head), axis=-1)
        out.append(-jnp.take_along_axis(
            logp, tokens[:, start + 1:stop + 1, None], axis=-1)[..., 0])
    return jnp.concatenate(out, axis=1)


def exit_distribution(gates):
    """[p_1 .. p_R] from [lambda_1 .. lambda_(R-1)], as written above."""
    p, stayed = [], 1.0
    for lam in gates:
        p.append(lam * stayed)
        stayed = stayed * (1.0 - lam)
    return p + [stayed]


def loss(params, tokens, heads, head_dim, eps, theta, turns, beta,
         rounded=None, without=()):
    """(per-sequence loss [B], Seen); tokens [B, T] int32.  ``rounded``
    is a dtype through which every matmul operand is rounded first (what
    this model would give computed in that precision, PERF.md's second
    reading); ``without`` names the PIECES to leave out (what a test
    tells apart): "gate" leaves the gate at zero (every lambda 1/2),
    "entropy" drops the entropy term, "last_turn" runs R - 1 turns,
    "carry_norm" hands the next turn the state BEFORE the final norm,
    "post_norms" drops the norms on the sublayers' outputs."""
    f32 = lambda tree: jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), tree)
    params = f32(params)
    if "last_turn" in without:
        turns -= 1
    # departure (the cut): ``layers`` are the first N of the published 48
    h = params["embed"][tokens]
    states = []
    for _ in range(turns):      # one set of weights, every turn
        u = stack(h, params["layers"], heads, head_dim, eps, theta,
                  rounded, "post_norms" not in without)
        states.append(rmsnorm(u, params["ln_f"], eps))
        h = u if "carry_norm" in without else states[-1]
    w_g, b_g = params["ut_gate_w"], params["ut_gate_b"]
    if "gate" in without:
        w_g, b_g = jnp.zeros_like(w_g), jnp.zeros_like(b_g)
    p = jnp.stack(exit_distribution(
        [jax.nn.sigmoid(state[:, :-1] @ w_g + b_g)
         for state in states[:-1]]))                        # [R, B, T - 1]
    losses = jnp.stack([
        token_losses(state, params["lm_head"], tokens, rounded)
        for state in states])                               # [R, B, T - 1]
    entropy = -jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)),
                         0.0).sum(axis=0)
    weight = 0.0 if "entropy" in without else beta
    per_token = (p * losses).sum(axis=0) - weight * entropy
    return per_token.mean(axis=-1), Seen(
        losses.mean(axis=(1, 2)), p.mean(axis=(1, 2)), entropy.mean(),
        jnp.stack(states))


def turn_errors(got, want):
    """The relative distance of each turn's state ``got`` [R, B, T, E]
    from ``want``."""
    norm = lambda a: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32)), axis=(1, 2, 3)))
    return [float(e) for e in norm(got.astype(jnp.float32) - want)
            / norm(want)]


def product_turns(config):
    """params, tokens -> (the program's own ``h_t`` [R, B, T, E], what
    its step hands out beside the loss: ``ut_loss``, ``ut_exit``,
    ``ut_exit_entropy``), as the training step makes them (bfloat16 and
    kernels where the configuration's ``cli`` says so)."""
    from benchmark.lib.runner import params_string
    from elasticdl_tpu.models.spec import load_model_spec

    cli = config["cli"]
    spec = load_model_spec(cli["model_zoo"],
                           model_params=params_string(cli["model_params"]))

    def run(params, tokens):
        out = spec.apply_fn(params, tokens, True)
        spec.loss_fn(out, tokens)
        return out["turns"], spec.step_stats_fn(out)

    return jax.jit(run)


def check_turns(config, params, tokens, seen):
    """The program's turns' states and its mean exit distribution
    against the reference's, on stderr; raises over TURN_STATE_CEILING
    or EXIT_CEILING."""
    turns, stats = product_turns(config)(params, tokens)
    errors = turn_errors(turns, seen.states)
    exits = [abs(float(got) - float(want)) for got, want in zip(
        stats["ut_exit"], seen.exit, strict=True)]
    print(json.dumps({"turn_state_errors": errors,
                      "ceiling": TURN_STATE_CEILING, "exit_errors": exits,
                      "exit_ceiling": EXIT_CEILING}),
          file=sys.stderr, flush=True)
    if not max(errors) <= TURN_STATE_CEILING:
        raise AssertionError(
            "the program's final-normed state of a turn lies %s from the "
            "reference's (a turn each), over %g"
            % (errors, TURN_STATE_CEILING))
    if not max(exits) <= EXIT_CEILING:
        raise AssertionError(
            "the program's mean exit distribution lies %s from the "
            "reference's (a turn each), over %g" % (exits, EXIT_CEILING))
