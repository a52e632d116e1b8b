"""The LM head and the next-token loss as one op.

``head_loss(x, head, tokens)`` is ``next_token_loss(x @ head, tokens)``
with its own derivative, so that the ``[tokens, vocab]`` logits exist
once, in the dtype the head's matmul rounds them to (bfloat16 operands
give a bfloat16 ``dot_general``), where JAX's derivative of the two
functions has the compiler write a float32 copy beside them (3.3 GB at
16,384 tokens x 50,304) for the label's gather alone.  Every reduction
upcasts in registers and accumulates in float32, as the two functions
do; the backward pass forms the cotangent ``softmax - onehot`` from the
saved logits and their log-sum-exp, rounds it to the logits' dtype and
feeds the two matmuls JAX would emit.

All T positions are computed and the last one weighs zero: the matmuls
then run on whole tiles (4 x 4096 rows, not 4 x 4095).  ``shift`` = k
makes the target the k-th token on, the last k positions weigh zero and
the mean is over T - k (a multi-token-prediction module's loss,
``models/transformer.py``: a second call on another hidden state shares
the head, whose gradient is then the sum of both calls').

``token_loss`` is the same forward and the same backward without the
mean: each position's cross entropy ``[B, T]`` (zero where there is no
target) and a cotangent a position, for a caller that weighs the
tokens itself (a looped stack's exit distribution,
``models/transformer.looped_loss``: R calls on one head, whose gradient
is the sum of the R).  ``head_loss`` keeps the program it had, letter
for letter, in every model that calls it.

The op owns how its three matmuls are emitted, and holds them apart
from their neighbours with ``optimization_barrier``: left alone, XLA
computes the final norm again in the operands of both matmuls that read
``x`` and folds the optimizer's update into the weight gradient's
epilogue, and the matmuls lose more than the passes saved (PERF.md
section 6, PR 27: -1.4% ``records_per_s`` in ``olmo1b.seq2048`` without
the barriers, +0.5% with them; +1.3% and +3.5% in ``olmoe1b7b.seq4096``).
What the last barrier does for ``dhead`` the layer stack does for each
of its matrices (``models/transformer._updates_apart``, PR 46); the head
and the embedding stay this op's: held apart there as well they cost
``trinity-mini``'s step 1.1 GB (docs/training_pipeline.md).
"""

import functools

import jax
import jax.numpy as jnp

from elasticdl_tpu.ops import batch_shard, flash_attention


@functools.lru_cache(maxsize=None)
def announce_head_loss(rows, vocab, dtype, calls=1):
    """Once per compiled shape, by the logger ``announce_tiles`` uses:
    the one [tokens, vocab] buffer (of one shard of the trainer's data
    axis, where there is one) of a call, and how many calls of a step
    share the head where it is more than one (a looped stack's)."""
    flash_attention.logger.info(
        "head loss: tokens=%d vocab=%d logits=%s bytes=%d%s", rows, vocab,
        dtype, rows * vocab * jnp.dtype(dtype).itemsize,
        " calls=%d" % calls if calls > 1 else "")


def _targets(tokens, shift):
    """The tokens ``shift`` on, and which positions have one: all but
    the last ``shift``."""
    t = tokens.shape[1]
    has_target = (jnp.arange(t) < t - shift).astype(jnp.float32)
    return jnp.roll(tokens, -shift, axis=1), has_target


def _forward(x, head, tokens, tied, shift,
             reduce=lambda per_token: per_token):
    """(``reduce`` of each position's cross entropy [B, T] float32,
    zero where there is no target; the residuals): the one forward of
    both entries."""
    x = jax.lax.optimization_barrier(x)     # read, not recomputed
    # no preferred_element_type: the result takes the operands' dtype,
    # as ``x @ head`` does
    logits = jnp.einsum("bte,ve->btv" if tied else "bte,ev->btv", x, head)
    targets, has_target = _targets(tokens, shift)
    # optax.softmax_cross_entropy_with_integer_labels' arithmetic, on
    # values upcast where they are read
    top = logits.max(axis=-1).astype(jnp.float32)
    label = jnp.take_along_axis(
        logits, targets[..., None], axis=-1)[..., 0].astype(jnp.float32) - top
    log_z = jnp.log(jnp.exp(
        logits.astype(jnp.float32) - top[..., None]).sum(axis=-1))
    loss = reduce((log_z - label) * has_target)
    return loss, (logits, log_z + top, x, head, tokens)


def _backward(tied, residuals, targets, scale):
    """(dx, dhead, None) for ``scale`` [B, T] float32, each position's
    cotangent (zero where there is no target): the one backward of both
    entries."""
    logits, lse, x, head, _ = residuals
    vocab = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
    softmax = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
    cot = ((softmax - (vocab == targets[..., None])) * scale[..., None]
           ).astype(logits.dtype)
    if tied:
        # The embedding's gradient [V, E] contracts the cotangent
        # transposed: computed in that matmul's operands it costs more
        # than the one pass that writes it.  An untied head's [E, V]
        # does not, and its cotangent never reaches memory.
        cot = jax.lax.optimization_barrier(cot)
        dx = jnp.einsum("btv,ve->bte", cot, head)
        dhead = jnp.einsum("btv,bte->ve", cot, x)
    else:
        dx = jnp.einsum("btv,ev->bte", cot, head)
        dhead = jnp.einsum("bte,btv->ev", x, cot)
    dhead = jax.lax.optimization_barrier(dhead)  # the update: a pass apart
    return dx.astype(x.dtype), dhead.astype(head.dtype), None


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _head_loss(x, head, tokens, tied, shift):
    return _head_loss_fwd(x, head, tokens, tied, shift)[0]


def _head_loss_fwd(x, head, tokens, tied, shift):
    return _forward(x, head, tokens, tied, shift, lambda per_token: (
        per_token.sum(axis=-1) / (x.shape[1] - shift)))


def _head_loss_bwd(tied, shift, residuals, g):
    x, tokens = residuals[2], residuals[4]
    targets, has_target = _targets(tokens, shift)
    scale = (g.astype(jnp.float32)[:, None] * has_target
             / (x.shape[1] - shift))                          # [B, T]
    return _backward(tied, residuals, targets, scale)


_head_loss.defvjp(_head_loss_fwd, _head_loss_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _token_loss(x, head, tokens, tied, shift):
    return _forward(x, head, tokens, tied, shift)[0]


def _token_loss_bwd(tied, shift, residuals, g):
    targets, has_target = _targets(residuals[4], shift)
    return _backward(tied, residuals, targets,
                     g.astype(jnp.float32) * has_target)


_token_loss.defvjp(_forward, _token_loss_bwd)


def head_loss(x, head, tokens, tied=False, shift=1):
    """Per-example mean cross entropy ``[B]`` (float32) of the token
    ``shift`` on (1: the next).

    ``x`` [B, T, E]: the final norm's output; ``head`` [E, V], or with
    ``tied`` the embedding [V, E]; ``tokens`` [B, T].  Both operands in
    the compute dtype, which is the dtype the logits are held in.
    """
    _announce(x, head, tied, 1)
    return _head_loss(x, head, tokens, tied, shift)


def token_loss(x, head, tokens, tied=False, shift=1, calls=1,
               logits_kept=True):
    """Each position's cross entropy ``[B, T]`` (float32), zero at the
    last ``shift`` positions, which have no target; its backward takes a
    cotangent a position.  ``head_loss`` is its sum over ``T - shift``:
    the same forward and backward, the weights a caller's own (a looped
    stack's exit distribution, ``models/transformer.py``).  ``calls``:
    how many calls of a step share the head, for the once-per-shape
    line; ``logits_kept`` False makes the backward compute the logits
    again from ``x`` (``jax.checkpoint``) where it would read them."""
    _announce(x, head, tied, calls)
    op = _token_loss if logits_kept else jax.checkpoint(
        _token_loss, static_argnums=(3, 4))
    return op(x, head, tokens, tied, shift)


def _announce(x, head, tied, calls):
    b, t, _ = x.shape
    announce_head_loss(b * t // batch_shard.shards(),
                       head.shape[0 if tied else 1],
                       jnp.result_type(x, head).name, calls)
