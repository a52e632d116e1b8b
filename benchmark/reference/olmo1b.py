"""Plain reference of the ``olmo1b`` configuration's loss.

A dense decoder LM as OLMo-1B describes it (pre-norm blocks, rotary
position embedding on q and k, causal softmax attention, SwiGLU MLP, tied
embedding as the output head, mean next-token cross entropy), in
straightforward ``jax.numpy`` and float32, with no kernel, no scan, no
remat and no cache.  Departure kept from the program (configs/olmo1b.json,
``assumed``): RMSNorm with a learned scale where OLMo-1B has a LayerNorm
without parameters.  The caller sets
``jax.default_matmul_precision("highest")``.

``params`` is the program's own tree (layer weights stacked on a leading
axis), so the same seeded weights go through both.
"""

import jax
import jax.numpy as jnp
import numpy as np

# bfloat16 keeps 8 bits of mantissa (relative step 2**-8 = 3.9e-3).  The
# rounding errors of the thousands of terms behind one loss value largely
# cancel: 3e-3 relative on the mean loss is under one bfloat16 step, so
# anything coarser than the configuration states, or a dropped term, fails
# it.  PERF.md holds what the chip measured.
TOLERANCE = 3e-3
MICROBATCH = 2


def case(config, params, rng, key):
    """See benchmark/lib/compare.py.  The embedding is drawn 25 times
    wider than the product's 0.02, so that the logits are not all near
    zero and the loss is not ln(V) whatever the network computes."""
    tokens = jnp.asarray(rng.integers(
        0, config["vocab_size"], (MICROBATCH, config["seq_len"])), jnp.int32)
    params["embed"] = params["embed"] * 25.0
    heads = config["num_attention_heads"]
    return params, tokens, tokens, lambda p: loss(p, tokens, heads)


def rmsnorm(x, scale, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def rope(x, theta=10000.0):
    """x: [B, T, H, D]; rotate the two halves of D by position."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def loss(params, tokens, num_heads):
    """Per-sequence mean next-token cross entropy; tokens [B, T] int32."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    embed = f32(params["embed"])
    x = embed[tokens]
    B, T, E = x.shape
    D = E // num_heads
    layers = params["layers"]
    causal = jnp.tril(jnp.ones((T, T), bool))
    for i in range(layers["wq"].shape[0]):
        w = {k: f32(v[i]) for k, v in layers.items()}
        h = rmsnorm(x, w["ln1"])
        q = rope((h @ w["wq"]).reshape(B, T, num_heads, D))
        k = rope((h @ w["wk"]).reshape(B, T, num_heads, D))
        v = (h @ w["wv"]).reshape(B, T, num_heads, D)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
        scores = jnp.where(causal, scores, -jnp.inf)
        attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
        x = x + attn.reshape(B, T, E) @ w["wo"]
        h = rmsnorm(x, w["ln2"])
        x = x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]
    logits = rmsnorm(x, f32(params["ln_f"])) @ embed.T
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -picked.mean(axis=-1)
