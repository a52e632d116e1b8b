"""Ulysses-style all-to-all sequence parallelism.

The second long-context strategy beside ring attention
(parallel/ring_attention.py; the reference has neither — SURVEY.md
§5.7).  Where the ring streams K/V blocks around the ``sp`` axis with
``ppermute``, Ulysses re-shards the activations themselves: an
all-to-all swaps the sequence sharding for a head sharding, every
device then runs ordinary (flash) attention over the FULL sequence for
its head subset, and a second all-to-all swaps back.

    [B, T/sp, H, D]  --a2a(seq<->heads)-->  [B, T, H/sp, D]
        -> attention_local (full causal context per head)
    [B, T, H/sp, D]  --a2a(heads<->seq)-->  [B, T/sp, H, D]

Trade-off vs the ring: two all-to-alls of the Q/K/V/O activations
(4·B·T·H·D/sp words each way on ICI) instead of (sp-1) K/V hops, and
NO cross-device softmax folding — the local kernel sees the whole
sequence, so the causal step-skipping and stats plumbing of the ring
are unnecessary.  Ulysses wins when heads are plentiful and the
sequence shard is long (a2a volume is independent of sp); the ring
wins when sp exceeds the head count (Ulysses requires
``(H / tp) % sp == 0``) or when overlap of K/V hops with compute
matters more.  Both compose with dp/tp the same way.

Autodiff passes straight through (the transpose of an all-to-all is
the reverse all-to-all), so the backward is the flash kernel's own
(its Pallas pair) unchanged.

Layout convention matches ring attention: [batch, seq, heads,
head_dim]; batch shards over ``dp``, sequence over ``sp``, heads over
``tp``.
"""

import functools

import jax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.ops.flash_attention import _check_window
from elasticdl_tpu.parallel.ring_attention import attention_local


def _ulysses_local(q, k, v, sp_axis, causal, scale, window):
    """Per-device body: shards are [B, T/sp, H_local, D]."""

    def a2a_to_heads(x):
        # gather sequence, scatter heads: [B,T/sp,H,D] -> [B,T,H/sp,D]
        return jax.lax.all_to_all(
            x, sp_axis, split_axis=2, concat_axis=1, tiled=True
        )

    def a2a_to_seq(x):
        return jax.lax.all_to_all(
            x, sp_axis, split_axis=1, concat_axis=2, tiled=True
        )

    q, k, v = a2a_to_heads(q), a2a_to_heads(k), a2a_to_heads(v)
    out = attention_local(q, k, v, causal=causal, scale=scale,
                          window=window)
    return a2a_to_seq(out)


def ulysses_attention(q, k, v, mesh, causal=True, scale=None,
                      dp_axis="dp", sp_axis="sp", tp_axis="tp", window=0):
    """All-to-all sequence-parallel attention over mesh axis ``sp``.

    q, k, v: [batch, seq, heads, head_dim] global (or sharded) arrays.
    Requires the per-tp-shard head count to be divisible by the sp
    extent.  Falls back to local attention when there is no sp extent.
    """
    _check_window(window, causal)
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if mesh is None or mesh.shape.get(sp_axis, 1) == 1:
        return attention_local(q, k, v, causal=causal, scale=scale,
                               window=window)
    sp = mesh.shape[sp_axis]
    tp = mesh.shape.get(tp_axis, 1)
    heads_local = q.shape[2] // tp
    if heads_local % sp:
        raise ValueError(
            "ulysses needs (heads/tp) %% sp == 0, got %d heads / tp=%d"
            " over sp=%d" % (q.shape[2], tp, sp)
        )
    spec = P(dp_axis, sp_axis, tp_axis, None)
    fn = shard_map(
        functools.partial(
            _ulysses_local, sp_axis=sp_axis, causal=causal, scale=scale,
            window=window,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
