"""Flagship decoder-only Transformer LM with 4-axis parallelism.

Pure-JAX (explicit param pytree + PartitionSpec tree) so every sharding
decision is visible:

 - ``dp``: batch data parallelism (gradient psum inserted by XLA)
 - ``tp``: Megatron-style tensor parallelism — attention heads and MLP
   hidden are column/row sharded; XLA places the reduce-scatter/all-reduce
 - ``sp``: sequence parallelism — activations carry a seq-dim sharding and
   attention runs as ring attention over the ICI ring
   (elasticdl_tpu/parallel/ring_attention.py)
 - ``pp``: layer-stage sharding — the scanned layer stack's leading axis is
   sharded over ``pp`` so each stage group holds only its layers' weights
   (memory-parallel; microbatch pipelining can layer on top)

The reference has no model parallelism at all beyond PS-sharded embeddings
(SURVEY.md §2.12); this module is the deliberate TPU-native design for it.
RoPE positions, pre-norm RMSNorm, SwiGLU MLP.
"""

import collections
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import NamedSharding, PartitionSpec as P

from elasticdl_tpu.models import remat_keep
from elasticdl_tpu.models.spec import ModelSpec
from elasticdl_tpu.ops import (batch_shard, gated_delta, hyper_mix,
                               short_conv, ssd)
from elasticdl_tpu.ops.embed_rows import embed_rows
from elasticdl_tpu.ops.flash_attention import (flash_attention, flash_mode,
                                               latent_attention,
                                               latent_mode, logger)
from elasticdl_tpu.ops.mode import kernels_off
from elasticdl_tpu.ops.moe_dispatch import (ACTIVATIONS, GATELESS, gated,
                                            moe_experts)
from elasticdl_tpu.utils import metrics


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    dim: int = 512
    num_heads: int = 8
    num_layers: int = 4
    mlp_ratio: int = 4
    max_seq_len: int = 2048
    dtype: str = "bfloat16"
    tied_embeddings: bool = True
    # The standard deviation the embedding is drawn at.  0.02 leaves a
    # token's own row a tenth of what the first block adds to the
    # stream (at random weights attention's output is mostly the mean
    # of its values, one vector every token shares), so every later
    # norm, and every router behind one, sees nearly the same input
    # for every token; 1.0 keeps the stream a token's own, as trained
    # weights do.
    embed_scale: float = 0.02
    # Width of the MLP (of one expert, in an MoE): 0 = dim * mlp_ratio.
    ffn_dim: int = 0
    # RMSNorm's epsilon.
    norm_eps: float = 1e-6
    # A learned RMSNorm of q and k before RoPE, in one of two forms:
    # True = over the whole projection, before the split into heads,
    # with a scale of its width (OLMoE); "head" = over each head's
    # ``head_dim`` values, with one scale of ``head_dim`` that the heads
    # share (LFM2).
    qk_norm: bool | str = False
    # A block whose sublayers' OUTPUTS are normed too: ``x + n2(Op(n1(x)))``
    # then ``x + n4(FFN(n3(x)))``, the two extra RMSNorms (``ln1_post``,
    # ``ln2_post``: learned scales drawn at 1 that AdamW does not decay)
    # on what the operator and the FFN return, before the residual add,
    # whatever the operator and whatever the FFN (``_residual``).
    post_norms: bool = False
    # False: a block with no norm on a sublayer's INPUT (no ``ln1``, no
    # ``ln2``): the operator and the FFN read the stream itself, ``x +
    # n(Op(x))`` then ``x + n(FFN(x))`` with ``post_norms``, which it
    # needs (OLMo 2's reordered norm).
    pre_norms: bool = True
    # A gate on attention's output, read from the normed input that q, k
    # and v read and applied to the kernel's output in the compute
    # dtype, before ``wo``.  True = a gate a VALUE: ``w_attn_gate``
    # [dim, heads * head_dim], every value multiplied by the sigmoid of
    # its own gate (attention with wk and wv alone: latent attention
    # refuses it at construction); "head" = a gate a HEAD:
    # ``w_attn_gate`` [dim, heads], a head's values by the sigmoid of
    # its one gate (attention with wk and wv, and latent attention).  A
    # stack with a short-convolution layer refuses either.
    attn_gate: bool | str = False
    # What a token's row is multiplied by where the table is read as the
    # model's input (muP: sqrt(dim)), in the compute dtype; never where
    # a tied head reads the table.  Not ``embed_scale``, which is the
    # standard deviation the table is drawn at.  1.0 = no multiply.
    embed_multiplier: float = 1.0
    # RoPE's base, and the attention kinds of ``layer_pattern`` whose q
    # and k it turns: a kind that is not named has no positional
    # encoding at all ("w": windowed layers alone, full ones NoPE).
    rope_theta: float = 10000.0
    rope_kinds: str = "aw"
    # Values a head: 0 = dim // num_heads; a size of its own makes the
    # q and output projections num_heads * head_dim wide, whatever
    # ``dim`` (resolved at construction: ``dataclasses.replace`` of
    # ``dim`` or ``num_heads`` keeps it).
    head_dim: int = 0
    # Multi-head latent attention: ``kv_latent_rank`` > 0 replaces wk
    # and wv by ``w_kv_a`` [dim, rank + qk_rope_dim], whose first
    # ``rank`` outputs are the latent (RMSNorm ``kv_norm`` of its own)
    # and whose last ``qk_rope_dim`` are ONE RoPE key for all the
    # heads, and ``w_kv_b`` [rank, heads * (qk_nope_dim + v_head_dim)],
    # which makes each head's no-position key and its value from the
    # latent.  A q/k head is ``qk_nope_dim`` values RoPE leaves alone
    # then ``qk_rope_dim`` that it turns (``wq`` [dim, heads * their
    # sum], no query latent); scores run over both at ``(qk_nope_dim +
    # qk_rope_dim) ** -0.5``, values and ``wo``'s inputs are
    # ``v_head_dim`` a head (``ops/flash_attention.latent_attention``);
    # ``head_dim``, ``num_kv_heads`` and ``qk_norm`` are not read.  RoPE
    # turns the halves of the RoPE part (``_rope``): a checkpoint whose
    # rotation pairs neighbours (2i, 2i + 1) loads the RoPE columns of
    # ``wq`` and ``w_kv_a`` evens first, then odds, and every score is
    # the same.  0 = attention as above, all four.
    kv_latent_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # Latent attention's query latent: R > 0 replaces ``wq`` by ``w_q_a``
    # [dim, R], an RMSNorm ``q_norm`` [R] of its own and ``w_q_b`` [R,
    # heads * (qk_nope_dim + qk_rope_dim)]: ``q = RMSNorm(h W_qa) W_qb``.
    q_latent_rank: int = 0
    # YaRN (arXiv:2309.00071) on RoPE's frequencies: "factor,original,
    # beta_fast,beta_slow", e.g. "64,4096,32,1".  Each frequency is a
    # blend of itself and itself / factor by a linear ramp between the
    # two correction dimensions (where ``original`` positions make
    # beta_fast and beta_slow turns), cos and sin are unscaled, and
    # latent attention's softmax scale is multiplied by ``(0.1 ln factor
    # + 1) ** 2`` (``mscale = mscale_all_dim = 1``).  "" = none.
    rope_scaling: str = ""
    # Manifold-constrained hyper-connections (arXiv:2512.24880): n > 0
    # makes the residual stream n wide, [B, T, n * dim].  Each sublayer
    # reads the streams mixed by a learned map and writes back through
    # two more, one of them (streams onto streams) made doubly
    # stochastic by ``hyper_sinkhorn_iters`` Sinkhorn rounds
    # (``ops/hyper_mix.py``: the equations; ``_read``, ``_residual``),
    # and the model reads the last layer's streams through one map
    # more before its final norm.  0 = ``x + F(norm(x))``, as ever.
    hyper_streams: int = 0
    hyper_sinkhorn_iters: int = 20
    # Multi-token prediction (DeepSeek-V3's form): N modules, module k
    # one block of the model's last kind on ``[RMSNorm(h) ; RMSNorm(E[
    # token_(t+k)])] W_proj`` (h: the hidden state before the final norm,
    # of the model for k = 1, of module k - 1 after), the model's own
    # final norm and head, and a loss on ``token_(t+k+1)``; training's
    # loss is ``main + mtp_weight * mean of the modules'``.  Training
    # alone: no caller reads a module's logits.
    mtp_modules: int = 0
    mtp_weight: float = 0.1
    # A looped stack (arXiv:2510.25741): R > 1 runs the whole layer
    # stack R times on ONE set of weights, ``h_t = RMSNorm_f(Stack(
    # h_(t-1)))`` with the model's one final norm between the turns; the
    # head reads every ``h_t``, an exit gate ``lambda_t = sigmoid(h_t .
    # ut_gate_w + ut_gate_b)`` (float32; turns 1 .. R - 1) makes the exit
    # distribution ``p_t = lambda_t prod_(j<t) (1 - lambda_j)``, ``p_R``
    # the rest, and training's loss a token is ``sum_t p_t CE_t -
    # ut_entropy_weight H(p)``; evaluation reads turn R's logits.  The
    # plain scanned stack alone (docs/designs/looped_stack.md).  1 = one
    # pass, one head, one loss, as ever.
    ut_steps: int = 1
    ut_entropy_weight: float = 0.1
    # A stack whose layers differ.  ``layer_pattern``: one letter a
    # layer, "a" causal attention over the whole sequence, "w" causal
    # attention over the last ``window`` positions, "c" gated short
    # convolution of ``conv_kernel`` taps (ops/short_conv.py), "d" the
    # gated delta rule (below), "m" a Mamba-2 state-space mixer (below),
    # "e" NO operator: a layer that is its FFN alone, ``x + FFN(norm(
    # x))`` with one norm (``ln2``) and no mixer weights; "" = one
    # attention kind in every layer ("w" if ``window``, else "a").
    # ``dense_layers``: how many leading layers have a dense MLP
    # of ``dense_ffn_dim`` in an MoE model.  With either, the stack is
    # the leading layers, then whole periods of the rest's pattern under
    # one scan (a period's layers unrolled in its body, weights stacked
    # over periods), then a remainder (:func:`stack_plan`), and
    # ``params["layers"]`` is {"lead", "period", "tail"}.  Without,
    # one layer scanned ``num_layers`` times, as ever.
    layer_pattern: str = ""
    dense_layers: int = 0
    dense_ffn_dim: int = 0
    conv_kernel: int = 3
    # False: the layers behind the leading ones are ONE period, however
    # short the pattern they repeat: no loop over stacked weights, each
    # layer's weights a tree of its own (``params["layers"]["period"]``
    # has a key a layer, each leaf led by 1).  A loop of several turns
    # fills one stacked gradient that stands whole until the loop has
    # ended and AdamW reads it; unrolled, each matrix goes to AdamW
    # behind its own layer's backward (``_updates_apart``) and the
    # stack's gradients never stand at once: 4 B a parameter of the
    # stack that a model whose state fills the chip does not have.
    scan_periods: bool = True
    # A "d" layer of ``layer_pattern``: a Gated DeltaNet mixer
    # (``_delta_mix``; ops/gated_delta.py) of ``num_heads`` heads, each
    # with keys and queries of ``delta_key_dim`` and values of
    # ``delta_value_dim`` and a float32 state of their product carried
    # through the sequence; q, k and v pass a causal convolution of
    # ``conv_kernel`` taps and a SiLU.  ``delta_neg_eigval`` doubles the
    # write strength, sigmoid -> (0, 2), so that a token can flip the
    # state along its key and not only erase it.
    delta_key_dim: int = 0
    delta_value_dim: int = 0
    delta_neg_eigval: bool = False
    # Which delta layer a "d" is: "gdn" (Gated DeltaNet: one decay a
    # head, ``w_a`` [dim, heads]; the output gate a SiLU of ``w_out_gate``
    # [dim, heads * value_dim]) | "kda" (Kimi Delta Attention,
    # arXiv:2510.26692: a decay a CHANNEL of the key, projected through
    # the low-rank pair ``w_a_down`` [dim, delta_rank] / ``w_a_up``
    # [delta_rank, heads * key_dim] with a ``dt_bias`` a channel; the
    # output gate a sigmoid of the pair ``w_g_down`` / ``w_g_up`` + ``b_g``
    # of the same rank).  ``delta_rank`` is the pairs' and kda's alone;
    # a kda layer with ``delta_rank`` 0 has FULL projections in the
    # pairs' place, ``w_a`` [dim, heads * key_dim] and ``w_out_gate``
    # [dim, heads * value_dim], and no ``b_g``.
    delta_kind: str = "gdn"
    delta_rank: int = 0
    # A kda layer's decay gate: 0 = ``g = -exp(A_log) softplus(a +
    # dt_bias)``, unbounded below; F < 0 = the bounded gate ``g = F
    # sigmoid(exp(A_log) (a + dt_bias))``, a log decay a channel in (F,
    # 0) (``a`` the decay's projection a channel, ``A_log`` a head's).
    delta_gate_floor: float = 0.0
    # An "m" layer of ``layer_pattern``: a Mamba-2 mixer (``_ssm_mix``;
    # ops/ssd.py, arXiv:2405.21060) of ``ssm_heads`` heads of
    # ``ssm_head_dim`` values, each with a float32 state [head_dim,
    # ``ssm_state``] carried through the sequence under one decay a
    # head, ``S_t = exp(dt_t A) S_(t-1) + dt_t x_t B_t^T``, ``y_t = S_t
    # C_t + D x_t``; B and C lie in ``ssm_groups`` groups that ``ssm_heads
    # / ssm_groups`` heads share; x, B and C pass a causal convolution of
    # ``conv_kernel`` taps (with a bias a channel under ``conv_bias``)
    # and a SiLU; the output is ``RMSNorm(y * SiLU(z))`` over each
    # group's ``ssm_heads * ssm_head_dim / ssm_groups`` values with one
    # learned scale, the gate BEFORE the norm.
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    conv_bias: bool = False
    # False: a layer is ONE sublayer.  A layer whose letter names an
    # operator (a, w, c, d, m) has no FFN, ``x + Op(norm(x))`` with one
    # norm (``ln1``) and no FFN weights; the FFNs of the stack are its
    # "e" layers (dense or with experts as ``dense_layers`` and
    # ``moe_experts`` say of their positions).  True = every layer with
    # an operator has an FFN behind it, as ever.
    mixer_ffn: bool = True
    # How many chips share a layer's heads in the deployment this model
    # is one chip of (tensor parallel over heads): ``num_heads`` and
    # ``num_kv_heads`` are the heads held HERE, every mixer's result is
    # their part of the ``wo`` product, and nothing stands in for the
    # exchange.  Changes no arithmetic: the ``layer stack:`` line states
    # it (``heads_held=15/30``).
    head_shares: int = 1
    # Mixture-of-experts: 0 = dense FFN; >0 = dropless top-k routing
    # (every chosen expert computes every token that chose it, whatever
    # the routing) with experts sharded over the ``ep`` mesh axis and an
    # auxiliary load-balance loss over all k choices (weight
    # ``moe_aux_weight``) to stop router collapse.  ``moe_norm_topk``
    # renormalizes the k > 1 chosen gates to sum to 1 (GShard); False
    # keeps the softmax's own values (OLMoE's ``norm_topk_prob``).
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_norm_topk: bool = True
    moe_aux_weight: float = 0.01
    # The router's scores (float32 at the highest precision either way):
    # "softmax" over the experts, the K largest chosen; "sigmoid_bias" =
    # a sigmoid of each expert's logit, the K largest of score +
    # ``expert_bias`` chosen (a float32 vector a layer that no gradient
    # reaches and AdamW's decay is masked from: ``model_spec``), their
    # weights the unbiased scores, with ``moe_norm_topk`` divided by
    # their sum + 1e-6, times ``moe_route_scale``.
    moe_router: str = "softmax"
    moe_route_scale: float = 1.0
    # A choice limited to groups (DeepSeek-V3's ``noaux_tc``; the
    # "sigmoid_bias" router's alone): the experts lie in ``moe_groups``
    # equal groups of neighbours, a group's score is the sum of its two
    # largest score + bias, and the K experts are chosen inside the
    # ``moe_top_groups`` best groups alone.  0 and 0 = no limit.
    moe_groups: int = 0
    moe_top_groups: int = 0
    # What the router reads: False = the FFN's input, RMSNorm_2 of the
    # stream after the operator; True = the operator's input,
    # RMSNorm_1 of the block's input, so the route is taken before the
    # operator runs and carried past it to the experts.
    moe_route_before_op: bool = False
    # The gate's activation, of the experts and of a dense FFN alike
    # (``ops/moe_dispatch.ACTIVATIONS``): "silu" (SwiGLU) | "relu"
    # (ReGLU) | "relu2": NO gate product, an MLP of TWO matrices,
    # ``relu(h W_up) ** 2 W_down``: the experts, the shared expert and a
    # dense FFN hold ``w_up`` and ``w_down`` alone (no ``w_gate``, no
    # ``ws_gate`` in ``init_params``) and the grouped matmul runs two
    # products a layer where it ran three.
    ffn_activation: str = "silu"
    # A clamp on a gated MLP's two products that is a LAYER's: "L0,L1,
    # .." one limit a layer of ``num_layers``; where a layer's L > 0 its
    # routed experts (``ffn_limits``) or its shared expert
    # (``shared_limits``) compute ``(act(min(a, L)) * clip(b, -L, L))
    # W_down`` of the gate product a and the up product b; 0 = no clamp
    # in that layer, "" = none in any.  A dense layer's MLP and a
    # multi-token-prediction module have none.
    ffn_limits: str = ""
    shared_limits: str = ""
    # One chip's share of the experts: ``moe_experts`` stays the
    # router's width, every token is routed over all of them, and this
    # model holds (and multiplies) experts ``moe_share_index *
    # moe_experts_held .. + moe_experts_held`` alone, so an expert
    # layer's result is the held experts' part, weighted as if all were
    # here; what the absent ones would add is left out.  0 = all held.
    moe_experts_held: int = 0
    moe_share_index: int = 0
    # Always-on shared experts: N > 0 adds to every expert layer's
    # routed result one SwiGLU of N x the expert width on the same
    # normed input, which every token passes through; a dense layer has
    # none.  Under a share it is whole here: every chip of the group
    # computes it alike for its own tokens.
    moe_shared_experts: int = 0
    # Rematerialize each scanned layer in the backward pass instead of
    # saving all its activations (24 layers x T=2048 save ~20 GB of
    # them un-remat'ed on one chip).  True = recompute what does not
    # fit: of the values the backward reads, the ops name the dear ones,
    # and the stack keeps the names whose bytes fit what the trainer
    # says a device has left (models/remat_keep.py; the worker's
    # ``remat keep:`` line says which).  Where no memory is stated (the
    # CPU, a model-parallel mesh, the pipelined forward) nothing is
    # kept and the backward runs every layer's forward again, ~1/3 more
    # FLOPs.
    remat: bool = False
    # Sequence-parallel strategy over the ``sp`` mesh axis: "ring"
    # (ppermute K/V streaming, parallel/ring_attention.py) or "ulysses"
    # (all-to-all head/sequence re-sharding, parallel/ulysses.py;
    # requires (heads/tp) % sp == 0).
    attention_impl: str = "ring"
    # The window of the windowed attention kind: its layers keep only
    # the last W positions (O(T·W) attention compute — out-of-band
    # blocks skip matmuls and DMA in the flash kernel, and whole ring
    # steps skip when the shard lies past the band).  Which layers are
    # of that kind is ``layer_pattern``'s to say ("w"); without a
    # pattern W > 0 makes every layer one.  0 = no such kind.
    window: int = 0
    # Grouped-query attention: 0 = MHA (kv heads == num_heads); G > 0
    # projects K/V to G heads and each group of num_heads/G query heads
    # shares one — smaller wk/wv params + projection FLOPs, and the
    # G-head KV cache is the standard serving memory win.  Q heads are
    # grouped consecutively (head i attends kv head i // (H/G)).
    num_kv_heads: int = 0

    def __post_init__(self):
        if not self.head_dim:
            object.__setattr__(self, "head_dim",
                               self.dim // self.num_heads)
        self._validate()

    def _validate(self):
        """Everything that makes a configuration invalid, refused where
        it is built; what reads the fields afterwards only computes."""
        for field in dataclasses.fields(self):
            words = _WORDS.get(field.name,
                               (False, True) if field.type is bool else ())
            value = getattr(self, field.name)
            if words and value not in words:
                raise ValueError("unknown %s %r (want one of %s)%s" % (
                    field.name, value,
                    ", ".join(str(word).lower() for word in words),
                    _WORDS_GONE.get(field.name, "")))
        if self.attn_gate and ("c" in self.layer_pattern or (
                self.kv_latent_rank and self.attn_gate != "head")):
            raise ValueError(
                "attn_gate=%s: a gate a value (true) is on the output of "
                "attention with wk and wv, a gate a head (head) on theirs "
                "or latent attention's: latent attention (kv_latent_rank="
                "%d) takes head alone and a short convolution "
                "(layer_pattern=%r) has no w_attn_gate"
                % (self.attn_gate, self.kv_latent_rank, self.layer_pattern))
        if not self.pre_norms and (not self.post_norms
                                   or self.moe_route_before_op):
            raise ValueError(
                "pre_norms=false needs post_norms (a block with no norm at "
                "all is not trained here) and a router that reads the "
                "FFN's input (moe_route_before_op reads ln1's result)")
        if self.kv_heads <= 0 or self.num_heads % self.kv_heads:
            raise ValueError(
                "num_heads (%d) must be a positive multiple of "
                "num_kv_heads (%d)" % (self.num_heads, self.kv_heads))
        held = self.experts_held[1]
        if held and (self.moe_experts % held
                     or not 0 <= self.moe_share_index
                     < self.moe_experts // held):
            raise ValueError(
                "moe_experts_held (%d) must divide moe_experts (%d) and "
                "moe_share_index (%d) name one of the shares"
                % (held, self.moe_experts, self.moe_share_index))
        sizes = self.latent
        if sizes and (min(sizes) <= 0 or self.qk_rope_dim % 2):
            raise ValueError(
                "latent attention needs kv_latent_rank, qk_nope_dim, an "
                "even qk_rope_dim and v_head_dim, all > 0; got %s"
                % (sizes,))
        if self.q_latent_rank and not sizes:
            raise ValueError(
                "q_latent_rank=%d is latent attention's query latent: it "
                "needs kv_latent_rank" % self.q_latent_rank)
        if self.rope_scaling and not (sizes and yarn_of(self.rope_scaling)):
            raise ValueError(
                "rope_scaling=%r is latent attention's (its softmax scale "
                "takes YaRN's mscale): it needs kv_latent_rank"
                % self.rope_scaling)
        if self.hyper_streams and (
                self.hyper_streams < 2 or self.hyper_sinkhorn_iters < 1
                or self.moe_route_before_op or self.post_norms):
            raise ValueError(
                "hyper_streams=%d: want at least 2 streams and 1 Sinkhorn "
                "round, a router that reads the FFN's input and a block "
                "without norms on its sublayers' outputs"
                % self.hyper_streams)
        if self.mtp_modules and (self.mtp_modules < 0
                                 or self.moe_route_before_op):
            raise ValueError(
                "mtp_modules=%d: want a count >= 0 and a router that reads "
                "the FFN's input" % self.mtp_modules)
        groups, top_groups = self.moe_groups, self.moe_top_groups
        if (groups or top_groups) and (
                self.moe_router != "sigmoid_bias"
                or not 0 < top_groups <= groups
                or self.moe_experts % groups
                or self.moe_top_k > top_groups * (self.moe_experts // groups)
                or self.moe_experts // groups < 2):
            raise ValueError(
                "moe_groups=%d, moe_top_groups=%d: the sigmoid_bias router's "
                "(moe_router=%s), 0 < moe_top_groups <= moe_groups, groups of "
                "moe_experts / moe_groups >= 2 experts (moe_experts=%d) and "
                "moe_top_k (%d) experts inside the chosen groups"
                % (groups, top_groups, self.moe_router, self.moe_experts,
                   self.moe_top_k))
        for name in ("ffn_limits", "shared_limits"):
            limits = limits_of(getattr(self, name))
            if limits and (len(limits) != self.num_layers or min(limits) < 0
                           or not self.moe_experts):
                raise ValueError(
                    "%s=%r: want one limit >= 0 a layer of num_layers=%d, of "
                    "a model with experts (a dense MLP has no clamp)"
                    % (name, getattr(self, name), self.num_layers))
        if self.conv_bias and "m" not in self.layer_pattern:
            raise ValueError(
                "conv_bias=true is the m layer's (a Mamba-2 mixer's "
                "convolution): layer_pattern=%r has none"
                % self.layer_pattern)
        if not self.mixer_ffn and "e" not in self.layer_pattern:
            raise ValueError(
                "mixer_ffn=false: a layer with an operator has no FFN, the "
                "stack's FFNs are the e layers of layer_pattern=%r"
                % self.layer_pattern)
        if self.plain_only and (
                self.attn_gate or self.post_norms or not self.pre_norms
                or self.hyper_streams or self.mtp_modules
                or self.moe_route_before_op):
            raise ValueError(
                "layer_pattern=%r, mixer_ffn=%s: a stack with a Mamba-2 "
                "layer (m) or a layer of one sublayer (e, mixer_ffn=false) "
                "is not held to attn_gate (%s), post_norms (%s), pre_norms="
                "false, hyper_streams (%d), mtp_modules (%d) or "
                "moe_route_before_op (%s): none of them runs with it"
                % (self.layer_pattern, self.mixer_ffn, self.attn_gate,
                   self.post_norms, self.hyper_streams, self.mtp_modules,
                   self.moe_route_before_op))
        if not self.gated_mlp and (self.ffn_limits or self.shared_limits):
            raise ValueError(
                "ffn_activation=%s has no gate product: the clamps "
                "ffn_limits=%r, shared_limits=%r are a gated MLP's"
                % (self.ffn_activation, self.ffn_limits, self.shared_limits))
        if self.ut_steps != 1 and (
                self.ut_steps < 1 or _pattern(self) is not None
                or self.moe_experts or self.hyper_streams
                or self.mtp_modules):
            raise ValueError(
                "ut_steps=%d: want a count >= 1, and a looped stack is the "
                "plain scanned one: no layer_pattern (%r) or dense_layers "
                "(%d), no experts (moe_experts=%d), no wide stream "
                "(hyper_streams=%d), no mtp_modules (%d); no configuration "
                "needs the pair yet"
                % (self.ut_steps, self.layer_pattern, self.dense_layers,
                   self.moe_experts, self.hyper_streams, self.mtp_modules))
        if set(self.rope_kinds) - set("aw"):
            raise ValueError(
                "rope_kinds %r: want letters of a (full attention) and w "
                "(windowed attention)" % (self.rope_kinds,))
        pattern = _pattern(self)
        if pattern is None or len(pattern) == self.num_layers:
            kinds, plan = self.kinds, stack_plan(self)
            first, size, turns = (0, 1, self.num_layers) if plan is None \
                else (len(plan.lead), len(plan.period), plan.periods)
            if any(kind.dense and (kind.limit or kind.shared_limit)
                   for kind in kinds) or any(
                       kinds[first + i] != kinds[first + i % size]
                       for i in range(size * turns)):
                raise ValueError(
                    "ffn_limits=%r, shared_limits=%r: a clamp is an expert "
                    "layer's (a dense layer's limit is 0), and the turns of "
                    "a scan (%d of a period of %d) cannot differ in a "
                    "constant; scan_periods=false unrolls them"
                    % (self.ffn_limits, self.shared_limits, turns, size))
        if pattern is None:
            return
        if len(pattern) != self.num_layers or set(pattern) - set("awcdme"):
            raise ValueError(
                "layer_pattern %r: want %d letters, each a (attention), w "
                "(attention over the last `window` positions), c (short "
                "convolution), d (gated delta rule), m (Mamba-2 mixer) or "
                "e (no operator: an FFN alone)"
                % (pattern, self.num_layers))
        if "m" in pattern and (
                min(self.ssm_heads, self.ssm_head_dim, self.ssm_state,
                    self.ssm_groups) <= 0
                or self.ssm_heads % self.ssm_groups
                or not 1 <= self.conv_kernel <= 8 - self.conv_bias):
            raise ValueError(
                "an m layer needs ssm_heads, ssm_head_dim and ssm_state > 0, "
                "ssm_groups that divide the heads and 1 <= conv_kernel <= %d "
                "(conv_bias=%s); got %d, %d, %d, %d and %d"
                % (8 - self.conv_bias, self.conv_bias, self.ssm_heads,
                   self.ssm_head_dim, self.ssm_state, self.ssm_groups,
                   self.conv_kernel))
        if "d" in pattern and (
                min(self.delta_key_dim, self.delta_value_dim) <= 0
                or not 1 <= self.conv_kernel <= short_conv.HALO + 1):
            raise ValueError(
                "a d layer needs delta_key_dim and delta_value_dim > 0 "
                "and 1 <= conv_kernel <= %d; got %d, %d and %d"
                % (short_conv.HALO + 1, self.delta_key_dim,
                   self.delta_value_dim, self.conv_kernel))
        if "d" in pattern and self.delta_kind != "kda" and (
                self.delta_rank or self.delta_gate_floor):
            raise ValueError(
                "delta_kind=%s, delta_rank=%d, delta_gate_floor=%g: the "
                "rank is the kda layer's low-rank pairs' (decay, output "
                "gate; 0 = full projections) and the floor its decay "
                "gate's: both need delta_kind=kda"
                % (self.delta_kind, self.delta_rank, self.delta_gate_floor))
        if self.delta_rank < 0 or self.delta_gate_floor > 0:
            raise ValueError(
                "delta_rank=%d, delta_gate_floor=%g: want a rank >= 0 and a "
                "floor <= 0 (a log decay's)"
                % (self.delta_rank, self.delta_gate_floor))
        if ("w" in pattern) != bool(self.window):
            raise ValueError(
                "layer_pattern %r and window=%d: the window is the w "
                "layers' and theirs alone, so each needs the other"
                % (pattern, self.window))
        if self.dense_layers and not (self.moe_experts
                                      and self.dense_ffn_dim):
            raise ValueError(
                "dense_layers=%d needs moe_experts and dense_ffn_dim: "
                "without experts every layer's FFN is dense"
                % self.dense_layers)

    @property
    def kv_heads(self):
        """Effective K/V head count (num_kv_heads=0 -> MHA)."""
        return self.num_kv_heads or self.num_heads

    @property
    def mlp_dim(self):
        return self.ffn_dim or self.dim * self.mlp_ratio

    @property
    def shared_dim(self):
        """Width of an expert layer's shared expert (0: none)."""
        return self.moe_shared_experts * self.mlp_dim if (
            self.moe_experts) else 0

    @property
    def latent(self):
        """(rank, qk_nope_dim, qk_rope_dim, v_head_dim) of latent
        attention, or None for attention with wk and wv."""
        sizes = (self.kv_latent_rank, self.qk_nope_dim, self.qk_rope_dim,
                 self.v_head_dim)
        return sizes if any(sizes) else None

    @property
    def kinds(self):
        """The Kind of every layer, in order."""
        pattern = _pattern(self) or ("w" if self.window else "a") * (
            self.num_layers)
        routed, shared = (limits_of(text) or (0.0,) * len(pattern)
                          for text in (self.ffn_limits, self.shared_limits))
        return tuple(
            _kind(self, letter, i < self.dense_layers or not self.moe_experts,
                  routed[i], shared[i])
            for i, letter in enumerate(pattern))

    @property
    def plain_only(self):
        """Whether the stack has what this repo holds to training its
        plain block alone: a Mamba-2 layer, or a layer of one
        sublayer."""
        return bool(set("me") & set(self.layer_pattern)
                    or not self.mixer_ffn)

    @property
    def gated_mlp(self):
        """Whether an MLP has a gate product (three matrices) or is
        ``act(h W_up) W_down`` (two: ``GATELESS``)."""
        return self.ffn_activation not in GATELESS

    @property
    def mtp_kind(self):
        """The Kind of a multi-token-prediction module's block: the
        model's last layer's, without a clamp."""
        return self.kinds[-1]._replace(limit=0.0, shared_limit=0.0)

    @property
    def stream_width(self):
        """Values a token of the residual stream holds."""
        return self.dim * max(1, self.hyper_streams)

    @property
    def experts_held(self):
        """(first held expert, how many): all of them without a share."""
        held = self.moe_experts_held or self.moe_experts
        return self.moe_share_index * held, held


# The fields that take one of a few words (one declared ``bool`` takes
# true | false and has no row), and what the refusal says besides of a
# field that took more words once.
_WORDS = {
    "qk_norm": (False, True, "head"),
    "ffn_activation": tuple(sorted(ACTIVATIONS)),
    "attention_impl": ("ring", "ulysses"),
    "moe_router": ("softmax", "sigmoid_bias"),
    "delta_kind": ("gdn", "kda"),
    "attn_gate": (False, True, "head"),
}
_WORDS_GONE = {
    "remat": ': "attn" and "dots" are gone; remat=true keeps the flash '
             "kernel's output and row statistics, and more, when they fit",
}


# One layer's kind: its operator ("a" attention | "c" short
# convolution | "d" gated delta rule | "m" Mamba-2 mixer | "e" none),
# whether its FFN is dense (in an
# MoE model, a leading
# layer's) and, of an attention layer, the window it attends over (0:
# the whole sequence) and whether RoPE turns its q and k; of a layer
# with experts, the clamps of its routed and of its shared experts'
# products (``ffn_limits``, ``shared_limits``; 0: none); whether it has
# an FFN at all (``mixer_ffn``: a layer without one counts as dense, it
# has no experts).
Kind = collections.namedtuple(
    "Kind", "op dense window rope limit shared_limit ffn",
    defaults=(0, True, 0.0, 0.0, True))
# ``lead`` and ``tail``: the kinds of the layers before and after the
# scan; ``period``: the kinds of one period; ``periods``: how many the
# scan runs.
StackPlan = collections.namedtuple("StackPlan", "lead period periods tail")


def _kind(cfg, letter, dense, limit=0.0, shared_limit=0.0):
    """The Kind a letter of ``layer_pattern`` names."""
    ffn = cfg.mixer_ffn or letter == "e"
    if not ffn:
        dense, limit, shared_limit = True, 0.0, 0.0
    if letter in "cdme":
        return Kind(letter, dense, limit=limit, shared_limit=shared_limit,
                    ffn=ffn)
    return Kind("a", dense, cfg.window if letter == "w" else 0,
                letter in cfg.rope_kinds, limit, shared_limit, ffn)


def limits_of(text):
    """The floats of an ``ffn_limits`` / ``shared_limits`` string, one a
    layer; () for ""."""
    try:
        return tuple(float(x) for x in text.split(",")) if text else ()
    except ValueError:
        raise ValueError(
            "%r: want limits as \"L0,L1,..\", one number a layer, e.g. "
            "\"0,4,4\"" % (text,)) from None


def _letter(kind):
    return "w" if kind.window else kind.op


def _pattern(cfg):
    """A letter a layer of a stack whose layers differ, or None for a
    model of one layer kind (no pattern, no leading dense layers)."""
    if not cfg.layer_pattern and not cfg.dense_layers:
        return None
    return cfg.layer_pattern or ("w" if cfg.window else "a") * (
        cfg.num_layers)


def stack_plan(cfg):
    """How a stack whose layers differ is run, or None for a model of
    one layer kind.  The period is the shortest that the layers after
    the leading ones repeat."""
    pattern = _pattern(cfg)
    if pattern is None:
        return None
    kinds = cfg.kinds
    lead = cfg.dense_layers
    rest = pattern[lead:]
    size = next((p for p in range(1, len(rest) + 1)
                 if all(rest[i] == rest[i % p] for i in range(len(rest)))),
                1) if cfg.scan_periods else max(len(rest), 1)
    periods = len(rest) // size
    return StackPlan(kinds[:lead], kinds[lead:lead + size], periods,
                     kinds[lead + periods * size:])


def _one_kind(cfg):
    """The kind of a model's layers where no caller names one: the
    first attention kind of its stack."""
    return next(kind for kind in cfg.kinds if kind.op == "a")


def yarn_of(text):
    """(factor, original positions, beta_fast, beta_slow) of a
    ``rope_scaling`` string."""
    try:
        factor, original, fast, slow = (float(x) for x in text.split(","))
    except ValueError:
        raise ValueError(
            "rope_scaling %r: want \"factor,original,beta_fast,beta_slow\" "
            "(YaRN), e.g. \"64,4096,32,1\"" % (text,)) from None
    return factor, original, fast, slow


# What some callers cannot run yet (docs/training_pipeline.md, "What
# runs where"): feature -> (whether a model has it, how the refusal
# names it, its fields and its weights, why).
_CANNOT = {
    "latent": (
        lambda cfg: cfg.latent,
        "latent attention (kv_latent_rank={cfg.kv_latent_rank})",
        "decoding needs a cache of the latent and the one RoPE key (rank "
        "+ qk_rope_dim values a position, not heads x head size) and the "
        "up-projection absorbed into q and the output; a mesh has no "
        "spec for w_kv_a, kv_norm and w_kv_b, its ring_attention and "
        "ulysses_attention take q, k, v of one width, and the pipeline's "
        "weights lie on a mesh"),
    "block": (
        lambda cfg: cfg.post_norms or cfg.attn_gate or not cfg.pre_norms,
        "a block with norms on its sublayers' outputs (post_norms="
        "{cfg.post_norms}: ln1_post, ln2_post; pre_norms={cfg.pre_norms}"
        ": ln1, ln2) or a gate on attention's "
        "output (attn_gate={cfg.attn_gate}: w_attn_gate, a value's or a "
        "head's)",
        "decoding restates the block for one position (_decode_layer) "
        "without them; the pipeline's stages run the block itself, but "
        "their weights lie on a mesh, which has no spec for the three"),
    "stack": (
        lambda cfg: _pattern(cfg) is not None,
        "a stack whose layers differ (layer_pattern={cfg.layer_pattern!r}"
        ", dense_layers={cfg.dense_layers}, delta_kind={cfg.delta_kind}, "
        "mixer_ffn={cfg.mixer_ffn})",
        "a short-convolution layer needs a state cache of its own, a "
        "gated-delta layer (d; gdn or kda) a recurrent state [heads, "
        "value_dim, key_dim] and the last conv_kernel - 1 rows of its "
        "convolution's input and no K/V cache, a Mamba-2 layer (m) a "
        "state [ssm_heads, ssm_head_dim, ssm_state] and its "
        "convolution's tail, a layer of one sublayer (e, or any layer "
        "under mixer_ffn=false) a block of its own in _decode_layer, a "
        "windowed layer (w) beside full ones a K/V cache that keeps its "
        "last `window` positions, a mesh specs for the weights of lead, "
        "period and tail, and the pipeline a split of them into stages"),
    "route": (
        lambda cfg: cfg.moe_groups or cfg.ffn_limits or cfg.shared_limits,
        "a router limited to groups (moe_groups={cfg.moe_groups}, "
        "moe_top_groups={cfg.moe_top_groups}) or a clamp a layer on the "
        "experts' products (ffn_limits={cfg.ffn_limits!r}, shared_limits="
        "{cfg.shared_limits!r})",
        "decoding restates the block for one position (_decode_layer) "
        "with one FFN for every layer; a mesh's ragged_dot path and the "
        "pipeline's stages scan one layer body, which has no limit a "
        "layer, and neither was held to the group choice"),
    "hyper": (
        lambda cfg: cfg.hyper_streams,
        "a residual stream {cfg.hyper_streams} wide (hyper_streams: hc1_*, "
        "hc2_*, hc_out_*)",
        "decoding restates the block for one position (_decode_layer) on "
        "a stream one wide; a mesh has no spec for the maps' weights and "
        "its activations' specs are [batch, seq, dim]; the pipeline's "
        "stages pass a stream one wide"),
    "mtp": (
        lambda cfg: cfg.mtp_modules,
        "multi-token prediction (mtp_modules={cfg.mtp_modules}: "
        "params[\"mtp\"])",
        "the modules are training's second loss: decoding has no draft "
        "path that reads them, a mesh no spec for their weights, and the "
        "pipeline's stages end at the model's own hidden state"),
    "mlp": (
        lambda cfg: not cfg.gated_mlp,
        "an MLP of two matrices (ffn_activation={cfg.ffn_activation}: no "
        "w_gate, no ws_gate)",
        "decoding and the pipeline's stages were not held to an FFN "
        "without a gate product, and a mesh's specs name w_gate and "
        "ws_gate"),
    "loop": (
        lambda cfg: cfg.ut_steps > 1,
        "a looped stack (ut_steps={cfg.ut_steps}: ut_gate_w, ut_gate_b)",
        "decoding needs a K/V cache a turn a layer (ut_steps x num_layers "
        "of them) and, for an early exit, a step whose cost is decided a "
        "token (generate's loop runs one stack a token); a mesh has no "
        "spec for the gate's weights and the pipeline's stages run their "
        "layers once, with no final norm between turns"),
    "share": (
        lambda cfg: cfg.moe_experts_held,
        "one chip's share of the experts (moe_experts_held="
        "{cfg.moe_experts_held})",
        "a model-parallel mesh shards all the experts over ep"),
}
# what decoding and the pipelined forward cannot run
_TRAINS_ONLY = ("latent", "block", "stack", "route", "hyper", "mtp", "mlp",
                "loop")


def _refuse(cfg, what, *features):
    """Raise, naming each of ``features`` that ``cfg`` has and the
    caller ``what`` cannot run."""
    found = ["%s does not run %s: %s" % (what, named.format(cfg=cfg), why)
             for has, named, why in map(_CANNOT.get, features) if has(cfg)]
    if found:
        raise NotImplementedError("\n".join(found))


# -- parameters --------------------------------------------------------------


def _norm_init(*shape):
    return jnp.ones(shape, jnp.float32)


def _dense_init(key, *shape, scale=None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[0]
    scale = scale or (1.0 / np.sqrt(fan_in))
    return jax.random.normal(key, shape, jnp.float32) * scale


def _init_layers(k_attn, k_mlp, cfg, kind, stack):
    """The weights of layers of one Kind, every leaf led by ``stack``
    (the scanned axis, or () for one layer)."""
    E, H, D, G = cfg.dim, cfg.num_heads, cfg.head_dim, cfg.kv_heads
    keys = jax.random.split(k_attn, 6)
    layers = {}
    if cfg.pre_norms:
        # one norm a sublayer: a layer without an operator ("e") has no
        # ln1, a layer without an FFN (``Kind.ffn``) no ln2
        layers.update(
            **({"ln1": _norm_init(*stack, E)} if kind.op != "e" else {}),
            **({"ln2": _norm_init(*stack, E)} if kind.ffn else {}))
    if cfg.post_norms:
        layers.update(ln1_post=_norm_init(*stack, E),
                      ln2_post=_norm_init(*stack, E))
    if kind.op == "a" and cfg.attn_gate:   # a gate a value | a head
        layers["w_attn_gate"] = _dense_init(
            jax.random.fold_in(k_attn, 6), *stack, E,
            H if cfg.attn_gate == "head" else H * D)
    if kind.op == "a" and cfg.latent:
        rank, dn, dr, dv = cfg.latent
        if cfg.q_latent_rank:
            q_keys = jax.random.split(keys[0])
            layers.update(
                w_q_a=_dense_init(q_keys[0], *stack, E, cfg.q_latent_rank),
                q_norm=_norm_init(*stack, cfg.q_latent_rank),
                w_q_b=_dense_init(q_keys[1], *stack, cfg.q_latent_rank,
                                  H * (dn + dr)))
        else:
            layers["wq"] = _dense_init(keys[0], *stack, E, H * (dn + dr))
        layers.update(
            w_kv_a=_dense_init(keys[1], *stack, E, rank + dr),
            kv_norm=_norm_init(*stack, rank),
            w_kv_b=_dense_init(keys[2], *stack, rank, H * (dn + dv)),
            wo=_dense_init(keys[3], *stack, H * dv, E))
    elif kind.op == "a":
        layers.update(
            wq=_dense_init(keys[0], *stack, E, H * D),
            wk=_dense_init(keys[1], *stack, E, G * D),
            wv=_dense_init(keys[2], *stack, E, G * D),
            wo=_dense_init(keys[3], *stack, H * D, E))
        if cfg.qk_norm:
            whole = cfg.qk_norm != "head"
            layers["q_norm"] = _norm_init(*stack, H * D if whole else D)
            layers["k_norm"] = _norm_init(*stack, G * D if whole else D)
    elif kind.op == "d":
        layers.update(_init_delta(k_attn, cfg, stack))
    elif kind.op == "m":
        layers.update(_init_ssm(k_attn, cfg, stack))
    elif kind.op == "c":
        layers.update(
            w_in=_dense_init(keys[0], *stack, E, 3 * E),
            conv_w=_dense_init(keys[1], *stack, E, cfg.conv_kernel,
                               scale=cfg.conv_kernel ** -0.5),
            w_out=_dense_init(keys[3], *stack, E, E))
    gate = cfg.gated_mlp    # an MLP of two matrices draws none
    if not kind.ffn:
        pass
    elif kind.dense:
        F = cfg.dense_ffn_dim if cfg.moe_experts else cfg.mlp_dim
        if gate:
            layers["w_gate"] = _dense_init(keys[4], *stack, E, F)
        layers["w_up"] = _dense_init(keys[5], *stack, E, F)
        layers["w_down"] = _dense_init(jax.random.fold_in(k_mlp, 1),
                                       *stack, F, E)
    else:
        X, F, held = cfg.moe_experts, cfg.mlp_dim, cfg.experts_held[1]
        layers["w_router"] = _dense_init(keys[4], *stack, E, X, scale=0.02)
        if cfg.moe_router == "sigmoid_bias":
            layers["expert_bias"] = jnp.zeros((*stack, X), jnp.float32)
        if gate:
            layers["w_gate"] = _dense_init(keys[5], *stack, held, E, F)
        layers["w_up"] = _dense_init(jax.random.fold_in(k_mlp, 0),
                                     *stack, held, E, F)
        layers["w_down"] = _dense_init(jax.random.fold_in(k_mlp, 1),
                                       *stack, held, F, E)
        S = cfg.shared_dim
        if S:
            shared = jax.random.split(jax.random.fold_in(k_mlp, 2), 3)
            if gate:
                layers["ws_gate"] = _dense_init(shared[0], *stack, E, S)
            layers["ws_up"] = _dense_init(shared[1], *stack, E, S)
            layers["ws_down"] = _dense_init(shared[2], *stack, S, E)
    if cfg.hyper_streams:
        for which in (1, 2):
            layers.update(_init_hyper(
                jax.random.fold_in(k_attn, 10 + which), cfg, stack,
                "hc%d" % which))
    return layers


# ``hc_eps``: what a Sinkhorn round adds to a sum it divides by.
HYPER_SINKHORN_EPS = 1e-6
# What a hyper-connection's ``alpha`` starts at: the dynamic part of a
# map is a hundredth of its bias's.
HYPER_ALPHA = 0.01
# The diagonal of ``b_res``: ``exp(8)`` to 1 off it, so that the
# Sinkhorn rounds start from the identity to 1e-3.
HYPER_RES_DIAGONAL = 8.0


def _init_hyper(key, cfg, stack, name, read_only=False):
    """A sublayer's three maps (``ops/hyper_mix.py``), or with
    ``read_only`` the first alone: ``<name>_phi`` [n dim, 2n + n^2]
    drawn as a projection, ``<name>_alpha`` [3] at HYPER_ALPHA and
    ``<name>_bias`` such that a new model is ``x + F(norm(x))`` on
    streams that stay what they were: H_pre = 1 / n a stream (the mean:
    the streams start as n copies of the embedding), H_post = 1, H_res
    the identity to 1e-3."""
    n = cfg.hyper_streams
    bias = [jnp.full((n,), -np.log(n - 1.0), jnp.float32)]
    if not read_only:
        bias += [jnp.zeros((n,), jnp.float32),
                 HYPER_RES_DIAGONAL * jnp.eye(n, dtype=jnp.float32).ravel()]
    bias = jnp.concatenate(bias)
    return {
        name + "_phi": _dense_init(key, *stack, n * cfg.dim, bias.shape[0]),
        name + "_alpha": jnp.full((*stack, 1 if read_only else 3),
                                  HYPER_ALPHA, jnp.float32),
        name + "_bias": jnp.broadcast_to(bias, (*stack, bias.shape[0])),
    }


def _init_delta(key, cfg, stack):
    """A "d" layer's mixer (``_delta_mix``, which states the forms).
    ``A_log`` and ``dt_bias`` as the Gated DeltaNet layer of the
    flash-linear-attention library draws them: a decay rate A uniform
    in (0, 16) a head, a step dt log-uniform in (0.001, 0.1) behind an
    inverse softplus, so that at a zero projection a head's decay
    ``exp(-A dt)`` lies in (0.2, 1).  A kda layer draws A in (1, 16) and
    a step a CHANNEL of the key; its decay and its output gate are
    projected by the low-rank pairs ``w_a_down`` / ``w_a_up`` and
    ``w_g_down`` / ``w_g_up`` + ``b_g`` (the second of a pair drawn at
    ``rank ** -0.5``, so that the pair's result is of the order the one
    projection's is) or, with ``cfg.delta_rank`` 0, by the full ``w_a``
    [dim, heads * key_dim] and ``w_out_gate`` alone.  Under the floored
    gate (``cfg.delta_gate_floor`` F) ``dt_bias`` is the b at which a
    zero projection decays as the softplus gate does at the same draw,
    ``F sigmoid(A b) = -A dt``."""
    E, H = cfg.dim, cfg.num_heads
    dk, dv = cfg.delta_key_dim, cfg.delta_value_dim
    kda, rank = cfg.delta_kind == "kda", cfg.delta_rank
    keys = jax.random.split(jax.random.fold_in(key, 7), 8)
    width = H * (2 * dk + dv)
    dt = jnp.exp(jax.random.uniform(
        keys[5], (*stack, H * dk if kda else H), jnp.float32,
        np.log(1e-3), np.log(1e-1)))
    A = jax.random.uniform(keys[4], (*stack, H), jnp.float32,
                           1.0 if kda else 1e-3, 16.0)
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))
    if cfg.delta_gate_floor:
        rate = jnp.repeat(A, dk, axis=-1)      # a channel its head's
        share = rate * dt / -cfg.delta_gate_floor
        dt_bias = (jnp.log(share) - jnp.log1p(-share)) / rate
    layers = dict(
        # q, k and v of every head side by side: one product, one
        # convolution
        w_qkv=_dense_init(keys[0], *stack, E, width),
        delta_conv=_dense_init(keys[1], *stack, width, cfg.conv_kernel,
                               scale=cfg.conv_kernel ** -0.5),
        w_b=_dense_init(keys[3], *stack, E, H),
        A_log=jnp.log(A),
        dt_bias=dt_bias,
        o_norm=_norm_init(*stack, dv),
        wo=_dense_init(keys[7], *stack, H * dv, E))
    if not (kda and rank):
        layers.update(
            w_a=_dense_init(keys[2], *stack, E, H * dk if kda else H),
            w_out_gate=_dense_init(keys[6], *stack, E, H * dv))
        return layers
    down, up = jax.random.split(keys[2]), jax.random.split(keys[6])
    layers.update(
        w_a_down=_dense_init(down[0], *stack, E, rank),
        w_a_up=_dense_init(down[1], *stack, rank, H * dk),
        w_g_down=_dense_init(up[0], *stack, E, rank),
        w_g_up=_dense_init(up[1], *stack, rank, H * dv),
        b_g=jnp.zeros((*stack, H * dv), jnp.float32))
    return layers


# The least step a Mamba-2 layer's ``dt_bias`` is drawn for
# (``time_step_floor``).
SSM_STEP_FLOOR = 1e-4


def _init_ssm(key, cfg, stack):
    """An "m" layer's mixer (``_ssm_mix``, which states the forms), as
    the Mamba-2 reference layer draws it: ``ssm_in`` [dim, z | x | B | C
    | dt] one projection without a bias (z and x ``ssm_heads *
    ssm_head_dim`` wide, B and C ``ssm_groups * ssm_state``, dt a
    head); the taps ``ssm_conv`` over the channels of x | B | C and,
    with ``cfg.conv_bias``, ``ssm_conv_bias`` uniform in +- taps^-1/2 (a
    depthwise convolution's default); a step dt log-uniform in (0.001,
    0.1) a head, floored at ``SSM_STEP_FLOOR``, ``dt_bias`` its inverse
    softplus; a decay rate A uniform in (1, 16) a head, ``A_log`` its
    log; the skip ``ssm_D`` ones; the gated norm's scale ``ssm_norm``
    ones; ``ssm_out`` back to dim."""
    E, H, P = cfg.dim, cfg.ssm_heads, cfg.ssm_head_dim
    inner, both = H * P, 2 * cfg.ssm_groups * cfg.ssm_state
    taps = cfg.conv_kernel
    keys = jax.random.split(jax.random.fold_in(key, 8), 6)
    dt = jnp.maximum(SSM_STEP_FLOOR, jnp.exp(jax.random.uniform(
        keys[2], (*stack, H), jnp.float32, np.log(1e-3), np.log(1e-1))))
    A = jax.random.uniform(keys[3], (*stack, H), jnp.float32, 1.0, 16.0)
    layers = dict(
        ssm_in=_dense_init(keys[0], *stack, E, 2 * inner + both + H),
        ssm_conv=_dense_init(keys[1], *stack, inner + both, taps,
                             scale=taps ** -0.5),
        A_log=jnp.log(A),
        dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
        ssm_D=_norm_init(*stack, H),
        ssm_norm=_norm_init(*stack, inner),
        ssm_out=_dense_init(keys[4], *stack, inner, E))
    if cfg.conv_bias:
        layers["ssm_conv_bias"] = jax.random.uniform(
            keys[5], (*stack, inner + both), jnp.float32, -taps ** -0.5,
            taps ** -0.5)
    return layers


def init_params(rng, cfg):
    """Layer weights are stacked on a leading [num_layers] axis
    (scanned); for a stack whose layers differ (:func:`stack_plan`),
    ``layers`` is {"lead": {"0": layer, ..}, "period": {"0": layers
    stacked over the periods, ..}, "tail": {..}}."""
    k_embed, k_attn, k_mlp, k_out = jax.random.split(rng, 4)
    E = cfg.dim
    plan = stack_plan(cfg)
    if plan is None:
        layers = _init_layers(k_attn, k_mlp, cfg, cfg.kinds[0],
                              (cfg.num_layers,))
    else:
        def group(name, kinds, stack):
            return {str(i): _init_layers(
                jax.random.fold_in(k_attn, 1000 * name + i),
                jax.random.fold_in(k_mlp, 2 + 1000 * name + i),
                cfg, kind, stack) for i, kind in enumerate(kinds)}

        layers = {"lead": group(0, plan.lead, ()),
                  "period": group(1, plan.period, (plan.periods,)),
                  "tail": group(2, plan.tail, ())}
    params = {
        "embed": _dense_init(k_embed, cfg.vocab_size, E,
                             scale=cfg.embed_scale),
        "layers": layers,
        "ln_f": _norm_init(E),
    }
    if not cfg.tied_embeddings:
        params["lm_head"] = _dense_init(k_out, E, cfg.vocab_size, scale=0.02)
    if cfg.ut_steps > 1:
        # the exit gate, drawn at zero: every lambda 1/2 at the start
        params["ut_gate_w"] = jnp.zeros((E,), jnp.float32)
        params["ut_gate_b"] = jnp.zeros((), jnp.float32)
    if cfg.hyper_streams:
        params.update(_init_hyper(jax.random.fold_in(k_out, 1), cfg, (),
                                  "hc_out", read_only=True))
    if cfg.mtp_modules:
        params["mtp"] = {str(k): _init_mtp(jax.random.fold_in(k_out, 2 + k),
                                           cfg)
                         for k in range(cfg.mtp_modules)}
    return params


def _init_mtp(key, cfg):
    """One multi-token-prediction module: the norms of its two inputs,
    the projection of their concatenation, one block of ``cfg.mtp_kind``
    and, on a wide stream, the map its result is read through."""
    E = cfg.dim
    k_proj, k_attn, k_mlp, k_out = jax.random.split(key, 4)
    module = {
        "norm_h": _norm_init(E), "norm_e": _norm_init(E),
        "proj": _dense_init(k_proj, 2 * E, E),
        "layer": _init_layers(k_attn, k_mlp, cfg, cfg.mtp_kind, ()),
    }
    if cfg.hyper_streams:
        module.update(_init_hyper(k_out, cfg, (), "hc_out", read_only=True))
    return module


def param_specs(cfg):
    """PartitionSpec tree matching init_params' structure."""
    _refuse(cfg, "a model-parallel mesh", *_TRAINS_ONLY, "share")
    layers = {
        "ln1": P("pp", None),
        "wq": P("pp", None, "tp"),
        "wk": P("pp", None, "tp"),
        "wv": P("pp", None, "tp"),
        "wo": P("pp", "tp", None),
        "ln2": P("pp", None),
    }
    if cfg.qk_norm:
        layers["q_norm"] = P("pp", "tp")
        layers["k_norm"] = P("pp", "tp")
    if cfg.moe_experts:
        layers["w_router"] = P("pp", None, None)
        layers["w_gate"] = P("pp", "ep", None, "tp")
        layers["w_up"] = P("pp", "ep", None, "tp")
        layers["w_down"] = P("pp", "ep", "tp", None)
        if cfg.shared_dim:      # as a dense FFN's
            layers["ws_gate"] = P("pp", None, "tp")
            layers["ws_up"] = P("pp", None, "tp")
            layers["ws_down"] = P("pp", "tp", None)
    else:
        layers["w_gate"] = P("pp", None, "tp")
        layers["w_up"] = P("pp", None, "tp")
        layers["w_down"] = P("pp", "tp", None)
    specs = {
        "embed": P(None, "tp"),
        "layers": layers,
        "ln_f": P(None),
    }
    if not cfg.tied_embeddings:
        specs["lm_head"] = P(None, "tp")
    return specs


def shard_params(params, mesh, cfg):
    specs = param_specs(cfg)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, specs,
        is_leaf=lambda x: isinstance(x, P),
    )


# -- forward ------------------------------------------------------------------


def _rmsnorm(x, scale, eps=1e-6, axis=-1):
    """RMSNorm over ``axis`` (the last; the head and width axes of a
    head-major projection normed whole), ``scale`` broadcast to x."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=axis,
                   keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def _group_rmsnorm(x, scale, groups, eps=1e-6):
    """RMSNorm of each of ``groups`` equal runs of lanes of the last
    axis by its own mean square (a Mamba-2 mixer's gated norm), on x as
    it stands: ``_rmsnorm`` of the ``[.., groups, -1]`` view puts a
    small axis on the sublanes, another tiling, and the compiler moves
    float32 planes round it.  A group's sum is a product with the
    ``[lanes, groups]`` 0 / 1 membership matrix and its ``rsqrt`` is
    spread back by the transpose (exact: one term a sum), on the MXU, as
    ``ops/ssd.py``'s ``_head_sums`` and ``_columns``; ``_rmsnorm``'s
    roundings in ``_rmsnorm``'s places.  ``scale`` [lanes]."""
    lanes = x.shape[-1]
    member = (jnp.arange(lanes)[:, None] // (lanes // groups)
              == jnp.arange(groups)).astype(jnp.float32)
    dot = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    var = dot(jnp.square(x.astype(jnp.float32)), member) / (lanes // groups)
    rstd = dot(jax.lax.rsqrt(var + eps), member.T)
    return (x * rstd).astype(x.dtype) * scale


def yarn_ramp(d, theta, scaling):
    """[d / 2] float64: how far each of RoPE's frequencies for heads of
    ``d`` goes from itself (0) to itself / factor (1) under YaRN: a
    linear ramp between the two correction dimensions, those whose
    wavelength fits ``original`` positions beta_fast and beta_slow
    times."""
    _, original, fast, slow = yarn_of(scaling)
    turns = lambda n: d * np.log(original / (n * 2 * np.pi)) / (
        2 * np.log(float(theta)))
    low = max(np.floor(turns(fast)), 0)
    high = min(np.ceil(turns(slow)), d - 1)
    return np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)


def yarn_scale(scaling):
    """What YaRN multiplies latent attention's softmax scale by: the
    square of ``0.1 ln factor + 1`` (1 without scaling)."""
    factor = yarn_of(scaling)[0] if scaling else 1.0
    return (0.1 * np.log(factor) + 1.0) ** 2 if factor > 1 else 1.0


def _rope_tables(d, positions, theta, scaling=""):
    """(cos, sin) [T, d / 2] float32 of RoPE's angles at base ``theta``
    for heads of ``d``, the frequencies blended by YaRN where
    ``scaling`` says so (``TransformerConfig.rope_scaling``)."""
    half = d // 2
    freqs = jnp.exp(
        -np.log(float(theta)) * jnp.arange(0, half, dtype=jnp.float32)
        / half
    )
    if scaling:
        # f (1 - ramp) + (f / factor) ramp, written so that factor 1
        # is f itself
        freqs = freqs * jnp.asarray(
            1.0 - yarn_ramp(d, theta, scaling) * (
                1.0 - 1.0 / yarn_of(scaling)[0]), jnp.float32)
    angles = positions[:, None].astype(jnp.float32) * freqs[None, :]
    return jnp.cos(angles), jnp.sin(angles)


def _rope(x, positions, theta=10000.0, scaling=""):
    """Rotary embeddings (rotate-half) at base ``theta``; x: [B, T, H,
    D]."""
    half = x.shape[-1] // 2
    cos, sin = (table[None, :, None, :] for table in _rope_tables(
        x.shape[-1], positions, theta, scaling))
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return rotated.astype(x.dtype)


def _rope_heads_first(x, positions, theta, scaling=""):
    """:func:`_rope` of x [B, H, T, D], positions along T, in the
    layout it has: the same arithmetic written ``x * [cos, cos] + (x P)
    * [-sin, sin]``, P the D x D permutation that swaps the halves (a
    product of 0 / 1 entries summed in float32: exact).  A slice or
    concatenate at D / 2 = 32 lanes XLA's TPU backend writes out as
    arrays of their own, each padded to 128 lanes in HBM (five ops and
    3.7 ms a layer at 32 heads x 16,384, forward and backward); the
    product it fuses with the elementwise work into one pass
    (docs/designs/attention.md)."""
    d = x.shape[-1]
    cos, sin = _rope_tables(d, positions, theta, scaling)
    swap = jnp.asarray(np.roll(np.eye(d), d // 2, axis=1), x.dtype)
    swapped = jnp.einsum("bhtd,de->bhte", x, swap,
                         preferred_element_type=jnp.float32)
    rotated = (x * jnp.concatenate([cos, cos], axis=-1)
               + swapped * jnp.concatenate([-sin, sin], axis=-1))
    return rotated.astype(x.dtype)


def chosen_groups(biased, cfg):
    """[B, T, groups] bool of the biased scores [B, T, X]: the
    ``cfg.moe_top_groups`` groups a token may choose its experts in, a
    group's score the sum of its two largest members'."""
    groups = biased.reshape(*biased.shape[:-1], cfg.moe_groups, -1)
    score = jax.lax.top_k(groups, 2)[0].sum(axis=-1)
    best = jax.lax.top_k(score, cfg.moe_top_groups)[1]
    return jax.nn.one_hot(best, cfg.moe_groups, dtype=jnp.bool_).any(
        axis=-2)


def moe_route(h, w_router, cfg, expert_bias=None):
    """(probs [B, T, X] float32, gates [B, T, K] float32, experts
    [B, T, K] int32).  The router's matmul and scores run in float32
    at the highest precision whatever the compute dtype (X*E
    multiply-adds a token), so that near-ties alone can change which
    experts a token gets.  ``cfg.moe_router`` "sigmoid_bias":
    ``probs`` are the sigmoid scores, ``expert_bias`` [X] moves the
    choice and not the weights, and no gradient reaches it; with
    ``cfg.moe_groups`` the K are the largest inside the token's
    ``chosen_groups`` (the others' scores count as -inf)."""
    logits = jnp.einsum(
        "bte,ex->btx", h.astype(jnp.float32),
        w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST)
    top_k = min(cfg.moe_top_k, cfg.moe_experts)
    if cfg.moe_router == "sigmoid_bias":
        probs = checkpoint_name(jax.nn.sigmoid(logits),
                                remat_keep.KEEP_ROUTE)
        biased = probs + jax.lax.stop_gradient(expert_bias)
        if cfg.moe_groups:
            allowed = jnp.repeat(chosen_groups(biased, cfg),
                                 cfg.moe_experts // cfg.moe_groups, axis=-1)
            biased = jnp.where(allowed, biased, -jnp.inf)
        experts = checkpoint_name(jax.lax.top_k(biased, top_k)[1],
                                  remat_keep.KEEP_ROUTE)
        gates = checkpoint_name(
            jnp.take_along_axis(probs, experts, axis=-1),
            remat_keep.KEEP_ROUTE)
        if cfg.moe_norm_topk:
            gates = gates / (gates.sum(axis=-1, keepdims=True) + 1e-6)
        return probs, gates * cfg.moe_route_scale, experts
    probs = checkpoint_name(jax.nn.softmax(logits, axis=-1),
                            remat_keep.KEEP_ROUTE)
    gates, experts = (
        checkpoint_name(a, remat_keep.KEEP_ROUTE)
        for a in jax.lax.top_k(probs, top_k))
    if cfg.moe_norm_topk and gates.shape[-1] > 1:
        # GShard-style renormalization over the chosen experts.  Top-1
        # keeps the raw p_top1 gate (Switch): renormalizing would make
        # it identically 1.0 and cut the router out of the task loss.
        gates = gates / jnp.maximum(
            gates.sum(axis=-1, keepdims=True), 1e-9)
    return probs, gates, experts


def _moe_ffn(h, w, cfg, mesh, route=None, limit=0.0):
    """Dropless top-k MoE FFN (expert weights sharded over ``ep``).
    ``route``: :func:`moe_route`'s three results where the block took
    them before its operator (``cfg.moe_route_before_op``); else the
    router reads ``h``.  ``limit``: the layer's clamp on the experts'
    gate and up products (``Kind.limit``; 0: none).

    One dispatch (``ops/moe_dispatch.moe_experts``) with two ways to
    multiply, chosen by where the code runs: the Pallas grouped matmul,
    per shard of the trainer's data axis, where no mesh is given and
    ``ops/mode.py`` allows a kernel; ``lax.ragged_dot`` under a
    model-parallel mesh and everywhere else.

    Returns (out, aux, stats, load).  ``aux`` is the load-balance loss
    over all K choices, X * sum_x assigned(x) * mean_prob(x) with
    ``assigned(x)`` the assignments to x over the tokens: K at perfect
    balance, approaching X under router collapse.  ``stats`` [2, X] are
    its LINEAR sufficient statistics (assigned, mean_prob): callers
    that accumulate across microbatches (the pipeline) combine them at
    the end for the exact full-batch aux.  ``load`` [X + 1] float32:
    assignments per expert, and the grouped matmul's padded rows; with
    a share of the experts (``cfg.moe_experts_held``) [held + 3]: the
    held experts' assignments alone, the padded rows, then what the
    dispatch measured of itself (``ops/moe_dispatch._moe_experts``):
    the rows its blocks moved, and how many of its shards ran more
    than one block, and where the row kernel moves the rows
    [held + 5], the slots its sums walked and those of tokens x K a
    call; under a router limited to groups
    (``cfg.moe_groups``) one more, last the share of the tokens among
    whose chosen groups is one with an expert held here.
    """
    B, T = h.shape[:2]
    X = cfg.moe_experts
    first, held = cfg.experts_held
    probs, gates, experts = route or moe_route(
        h, w["w_router"], cfg, w.get("expert_bias"))
    weights = tuple(w[name].astype(h.dtype)
                    for name in ("w_gate", "w_up", "w_down") if name in w)
    with kernels_off(mesh is not None):
        out, load = moe_experts(h, gates, experts, *weights,
                                total=X, first=first,
                                activation=cfg.ffn_activation, limit=limit)
    load = load.sum(axis=0).astype(jnp.float32)
    stats = jnp.stack([load[:X] / (B * T), probs.mean(axis=(0, 1))])
    aux = X * jnp.sum(stats[0] * stats[1])
    if held != X:
        load = jnp.concatenate([load[first:first + held], load[X:]])
        if cfg.moe_groups:
            # the group choice once more, the compiler's to share with
            # the router's: which tokens could choose an expert held here
            size = X // cfg.moe_groups
            ours = slice(first // size, (first + held - 1) // size + 1)
            hit = chosen_groups(probs + w["expert_bias"], cfg)[..., ours]
            load = jnp.concatenate(
                [load, hit.any(axis=-1).mean(dtype=jnp.float32)[None]])
    return out, aux, stats, load


def _constrain(x, mesh, spec):
    if mesh is not None:
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, spec)
        )
    return x


def _heads_first(x, weight):
    """x [B, T, dim] times a per-head view [dim, heads, width] of a
    weight, written [B, heads, T, width] by the matmul itself: the
    layout the flash kernels take, so no [T, heads, width] product is
    transposed on its way to them, nor a cotangent on its way back."""
    return jnp.einsum("btd,dhk->bhtk", x, weight)


def _project_qkv(h, w, cfg, positions, rope=True):
    """q [B, H, T, D], k and v [B, G, T, D] of the normed input, as
    ``ops/flash_attention.flash_attention`` takes them (head-major
    planes, K and V at their own head count: ``_heads_first``), RoPE
    applied to q and k (after the QK norm where the model has one)
    unless the layer's kind has none (``rope`` False).  A caller that
    wants them token-major (ring / Ulysses attention, the decode cache)
    transposes at its own edge."""
    compute_dtype = jnp.dtype(cfg.dtype)
    H, D, G = cfg.num_heads, cfg.head_dim, cfg.kv_heads

    def project(name, heads, norm=None, turn=False):
        x = _heads_first(
            h, w[name].astype(compute_dtype).reshape(-1, heads, D))
        if norm:
            scale = w[norm].astype(compute_dtype)
            if cfg.qk_norm == "head":        # over each head's D values
                x = _rmsnorm(x, scale, cfg.norm_eps)
            else:                            # over the whole projection
                x = _rmsnorm(x, scale.reshape(heads, 1, D), cfg.norm_eps,
                             axis=(1, 3))
        if turn:
            x = _rope_heads_first(x, positions, cfg.rope_theta)
        return x

    # as the attention takes them: what its backward reads
    return (checkpoint_name(
                project("wq", H, cfg.qk_norm and "q_norm", rope),
                remat_keep.KEEP_Q),
            checkpoint_name(
                project("wk", G, cfg.qk_norm and "k_norm", rope),
                remat_keep.KEEP_K),
            checkpoint_name(project("wv", G), remat_keep.KEEP_V))


def _project_latent(h, w, cfg, positions, rope=True):
    """Latent attention's five operands of the normed input, as
    ``ops/flash_attention.latent_attention`` takes them: q_nope
    [B, H, T, Dn], q_rope [B, H, T, Dr], k_nope [B, H, T, Dn], k_rope
    [B, T, Dr] (one key for all the heads), v [B, H, T, Dv].  RoPE turns
    q_rope and k_rope alone; the latent has an RMSNorm of its own.

    Each head-major operand is its own product of the input with a
    per-head view of the one weight (``wq`` as [dim, H, Dn + Dr] cut at
    Dn, ``w_kv_b`` as [rank, H, Dn + Dv] cut at Dn: weight-sized
    slices), written [B, H, T, .] by the matmul itself, so no [T, H,
    width] product is sliced, padded or transposed on its way to the
    kernels, nor a cotangent on its way back; the two views' gradients
    are joined weight-sized.  Splitting a product by output columns
    changes no sum."""
    compute_dtype = jnp.dtype(cfg.dtype)
    H = cfg.num_heads
    rank, dn, dr, dv = cfg.latent
    q_in = h
    if cfg.q_latent_rank:
        # the query latent, normed: what the two per-head products read
        q_in = _rmsnorm(
            checkpoint_name(h @ w["w_q_a"].astype(compute_dtype),
                            remat_keep.KEEP_Q_LATENT),
            w["q_norm"].astype(compute_dtype), cfg.norm_eps)
    wq = w["w_q_b" if cfg.q_latent_rank else "wq"].astype(
        compute_dtype).reshape(-1, H, dn + dr)
    q_nope = _heads_first(q_in, wq[..., :dn])
    q_rope = _heads_first(q_in, wq[..., dn:])
    # the latent and the RoPE key as the projection gives them: [T,
    # rank + Dr] a layer, what two matmuls make k_nope and v from
    c = checkpoint_name(h @ w["w_kv_a"].astype(compute_dtype),
                        remat_keep.KEEP_LATENT)
    latent = _rmsnorm(c[..., :rank], w["kv_norm"].astype(compute_dtype),
                      cfg.norm_eps)
    w_kv_b = w["w_kv_b"].astype(compute_dtype).reshape(rank, H, dn + dv)
    k_rope = c[..., None, rank:]
    if rope:
        q_rope = _rope_heads_first(q_rope, positions, cfg.rope_theta,
                                   cfg.rope_scaling)
        k_rope = _rope(k_rope, positions, cfg.rope_theta, cfg.rope_scaling)
    name = checkpoint_name
    return (name(q_nope, remat_keep.KEEP_Q),
            name(q_rope, remat_keep.KEEP_Q),
            name(_heads_first(latent, w_kv_b[..., :dn]), remat_keep.KEEP_KV),
            k_rope[:, :, 0],
            name(_heads_first(latent, w_kv_b[..., dn:]), remat_keep.KEEP_KV))


@functools.lru_cache(maxsize=None)
def announce_latent(heads, seq, latent, mode, tile, why, gate=False):
    """Once per compiled shape and mode, by the logger ``announce_tiles``
    uses: what latent attention runs as (``latent_mode``'s answer), and
    the gate on its output where it has one (``gate=head``)."""
    logger.info(
        "latent attention: heads=%d t=%d rank=%d qk_nope=%d qk_rope=%d "
        "v=%d rope_key=shared%s tile=%d %s%s", heads, seq, *latent,
        " gate=%s" % gate if gate else "", tile,
        {"tpu": "kernel", "interpret": "interpreter",
         "off": "reference"}[mode], " (%s)" % why if why else "")


def _latent_mix(h, w, cfg, positions, kind):
    """LatentAttention(h) of the normed input, [B, T, dim]: the five
    operands, the op, with ``cfg.attn_gate`` (a gate a head: the one
    form it takes) each head's output times the sigmoid of its gate, a
    projection of ``h``, then ``W_o``."""
    compute_dtype = jnp.dtype(cfg.dtype)
    T = h.shape[1]
    announce_latent(cfg.num_heads, T, cfg.latent, *latent_mode(
        T, *cfg.latent[1:], compute_dtype.itemsize), gate=cfg.attn_gate)
    scale = None
    if cfg.rope_scaling:
        scale = float((cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
                      * yarn_scale(cfg.rope_scaling))
    attn = latent_attention(
        *_project_latent(h, w, cfg, positions, kind.rope), causal=True,
        scale=scale, window=kind.window)
    if cfg.attn_gate:
        attn = attn * jax.nn.sigmoid(_head_gate(h, w, cfg))
    # W_o contracts the kernels' [B, H, T, Dv] output over (head, width)
    # as it stands: no token-major copy of it is made first.  Reshaped
    # before the cast, so that the gradient stays [H, Dv, dim] through
    # its convert and XLA does not merge (head, width) in the product,
    # which costs a [H, Dv, T] copy of the output
    wo = w["wo"].reshape(cfg.num_heads, -1, cfg.dim).astype(compute_dtype)
    return jnp.einsum("bhtk,hkd->btd", attn, wo)


def _head_gate(h, w, cfg):
    """A gate a head on attention's output (``cfg.attn_gate`` "head"),
    before its sigmoid: [B, H, T, 1], a projection of the normed input
    in the layout of the kernels' output."""
    gate = jnp.einsum("btd,dh->bht", h,
                      w["w_attn_gate"].astype(jnp.dtype(cfg.dtype)))
    return checkpoint_name(gate, remat_keep.KEEP_ATTN_GATE)[..., None]


def _gated_mlp(h, w, cfg, weights, keep, limit=0.0):
    """``(act(h W_gate) * (h W_up)) W_down`` with the three ``weights``
    named, the gate and up products named ``keep`` for a remat policy
    and clamped where the layer has a ``limit``
    (``ops/moe_dispatch.gated``); under an activation without a gate
    product (``GATELESS``) ``act(h W_up) W_down``."""
    compute_dtype = jnp.dtype(cfg.dtype)
    if not cfg.gated_mlp:
        # an MLP of two matrices: the first name has no weight
        up, down = (w[name].astype(compute_dtype) for name in weights[1:])
        up = checkpoint_name(h @ up, keep[1])
        return gated(cfg.ffn_activation, None, up) @ down
    gate, up, down = (w[name].astype(compute_dtype) for name in weights)
    gate = checkpoint_name(h @ gate, keep[0])
    up = checkpoint_name(h @ up, keep[1])
    return gated(cfg.ffn_activation, gate, up, limit) @ down


def _shared_expert(h, w, cfg, limit=0.0):
    """The always-on shared expert of an expert layer: one gated MLP of
    ``cfg.shared_dim`` on the FFN's normed input (``limit``:
    ``Kind.shared_limit``)."""
    return _gated_mlp(h, w, cfg, ("ws_gate", "ws_up", "ws_down"),
                      (remat_keep.KEEP_SHARED_GATE,
                       remat_keep.KEEP_SHARED_UP), limit)


# A wide stream's write that the sublayer behind it makes with its own
# read (``_operator`` -> ``_ffn``): the stream with its error, the
# operator's result, the maps that write.
_Write = collections.namedtuple("_Write", "x out maps")


def _read(x, w, cfg, name):
    """(what a sublayer computes on, the stream as ``_residual`` takes
    it back, how ``_residual`` writes to it): the stream itself, and
    None; of a stream ``cfg.hyper_streams`` wide, [B, T, n dim], its
    streams mixed by the sublayer's first map, [B, T, dim], and the two
    maps that write (``ops/hyper_mix.pre``, whose Sinkhorn error joins
    the running maximum the stream carries beside it).  Handed a
    ``_Write``, it makes that write and reads what it wrote in one call
    (``ops/hyper_mix.post_pre``)."""
    if not cfg.hyper_streams:
        return x, x, None
    mix = (*(w["%s_%s" % (name, part)] for part in ("phi", "alpha", "bias")),
           cfg.hyper_streams, cfg.hyper_sinkhorn_iters, cfg.norm_eps,
           HYPER_SINKHORN_EPS)
    if isinstance(x, _Write):
        (x, err), out, maps = x
        # under the stream's name, as it is wherever X' is named
        err = checkpoint_name(err, remat_keep.KEEP_STREAM)
        u, x, maps, off = hyper_mix.post_pre(x, out, maps, *mix,
                                             remat_keep.KEEP_STREAM)
    else:
        x, err = x
        u, x, maps, off = hyper_mix.pre(x, *mix)
    return u, (x, jnp.maximum(err, off)), maps


def _residual(x, out, w, cfg, mesh, post, maps=None):
    """``x + post(out)``: where a sublayer's result ``out`` joins the
    stream, the operator's and the FFN's alike; ``post`` names the
    RMSNorm it passes first in a block with ``cfg.post_norms``.  On a
    wide stream (``maps``: ``_read``'s) ``H_res x + H_post out``."""
    if maps is not None:
        x, err = x
        return hyper_mix.post(x, out, maps, cfg.hyper_streams), err
    if cfg.post_norms:
        out = _rmsnorm(out, w[post].astype(jnp.dtype(cfg.dtype)),
                       cfg.norm_eps)
    return x + _constrain(out, mesh, P("dp", "sp", None))


def _pre(x, w, cfg, name):
    """What a sublayer reads: the RMSNorm ``name`` of the stream, or the
    stream itself in a block without norms on its sublayers' inputs
    (``cfg.pre_norms`` False)."""
    if not cfg.pre_norms:
        return x
    return _rmsnorm(x, w[name].astype(jnp.dtype(cfg.dtype)), cfg.norm_eps)


def _ffn(x, w, cfg, mesh, dense=False, route=None, limits=(0.0, 0.0)):
    """x + post(FFN(norm(x))) -> (x, aux, stats, load); the last three
    are the MoE's (:func:`_moe_ffn`, which ``route`` is for), zeros and
    None for a dense FFN (a model without experts, or a ``dense`` layer
    of one with).  ``limits``: the layer's clamps, of its routed and of
    its shared experts."""
    u, x, maps = _read(x, w, cfg, "hc2")
    h = _pre(u, w, cfg, "ln2")
    if cfg.moe_experts and not dense:
        out, aux, stats, load = _moe_ffn(h, w, cfg, mesh, route, limits[0])
        if cfg.shared_dim:
            out = out + _shared_expert(h, w, cfg, limits[1])
    else:
        out = _gated_mlp(h, w, cfg, ("w_gate", "w_up", "w_down"),
                         (remat_keep.KEEP_GATE, remat_keep.KEEP_UP))
        aux, stats, load = jnp.float32(0.0), None, None
    return (_residual(x, out, w, cfg, mesh, "ln2_post", maps), aux, stats,
            load)


@functools.lru_cache(maxsize=None)
def announce_attention(cfg, rows, repeated):
    """Once per compiled shape, by the logger ``announce_tiles`` uses:
    the attention block one shard of the data axis runs on ``rows``
    tokens, and the bytes a layer's step moves beyond what its K/V
    heads hold where K and V are ``repeated`` to the query heads
    (``kv_repeat_bytes``: K, V and their two gradients, heads -
    kv_heads more of each; ``kv_repeat_again_bytes``: K and V once
    more, in the backward of a rematerialized layer, whose kept K and V
    are the ones before the repeat).  The jnp reference repeats; the
    kernels read K/V head ``head // group`` and the line says 0 and 0,
    as it does without GQA."""
    more = (cfg.num_heads - cfg.kv_heads) * repeated
    repeat = 2 * more * rows * cfg.head_dim * jnp.dtype(cfg.dtype).itemsize
    logger.info(
        "attention block: rows=%d heads=%d kv_heads=%d head_dim=%d "
        "qk_norm=%s gate=%s out_norms=%d embed_multiplier=%g layers=%d "
        "kv_repeat_bytes=%d kv_repeat_again_bytes=%d", rows,
        cfg.num_heads, cfg.kv_heads, cfg.head_dim, cfg.qk_norm,
        cfg.attn_gate if cfg.attn_gate == "head" else int(cfg.attn_gate),
        cfg.post_norms, cfg.embed_multiplier,
        sum(kind.op == "a" for kind in cfg.kinds), 2 * repeat,
        repeat if cfg.remat else 0)


def _attention_mix(h, w, cfg, mesh, positions, kind):
    """Attention(h) of the normed input -> ([B, T, dim], (k, v)): k, v
    post-RoPE at their own head count, [B, G, T, D].  Nothing
    activation-sized is made between the projections and the op:
    ``_project_qkv`` writes the op's operands (q at H heads, k and v at
    G, head-major), the gate is projected in the output's layout, and
    ``wo`` contracts the op's [B, H, T, D] output where it stands.
    Without a mesh the attention is the op itself
    (``ops/flash_attention.py``, which picks kernel or reference, and
    reads K/V head ``head // (H // G)``); ``parallel/`` serves a mesh,
    token-major and at the query heads, so its edge transposes and
    repeats.  With ``cfg.attn_gate`` each value of the kernel's output
    is multiplied by the sigmoid of its own gate, a projection of
    ``h``, before ``wo`` (``"head"``: a head's values by its one
    gate's)."""
    compute_dtype = jnp.dtype(cfg.dtype)
    B, T = h.shape[0], h.shape[1]
    H, D = cfg.num_heads, cfg.head_dim
    q, k, v = _project_qkv(h, w, cfg, positions, kind.rope)
    kv_out = (k, v)
    if mesh is None:
        announce_attention(cfg, B * T // batch_shard.shards(),
                           flash_mode(T, D)[0] == "off")
        attn = flash_attention(q, k, v, causal=True, window=kind.window)
    else:
        if cfg.attention_impl == "ulysses":
            from elasticdl_tpu.parallel.ulysses import (
                ulysses_attention as sharded)
        else:
            from elasticdl_tpu.parallel.ring_attention import (
                ring_attention as sharded)
        # [B, T, H, D] each: K/V (G heads wide) repeated, group order
        # consecutive (head i -> K/V head i // (H / G))
        q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
        if cfg.kv_heads != H:
            k, v = (jnp.repeat(x, H // cfg.kv_heads, axis=2)
                    for x in (k, v))
        attn = sharded(q, k, v, mesh, causal=True,
                       window=kind.window).transpose(0, 2, 1, 3)
    if cfg.attn_gate == "head":
        attn = attn * jax.nn.sigmoid(_head_gate(h, w, cfg))
    elif cfg.attn_gate:
        gate = checkpoint_name(
            _heads_first(h, w["w_attn_gate"].astype(compute_dtype).reshape(
                -1, H, D)), remat_keep.KEEP_ATTN_GATE)
        attn = attn * jax.nn.sigmoid(gate)
    # as ``_latent_mix``: reshaped before the cast
    wo = w["wo"].reshape(H, D, cfg.dim).astype(compute_dtype)
    return jnp.einsum("bhtk,hkd->btd", attn, wo), kv_out


def _conv_mix(h, w, cfg):
    """W_out(short_conv(W_in h)) of the normed input: the operator of a
    "c" layer (``ops/short_conv.py``, which picks kernel or
    reference)."""
    compute_dtype = jnp.dtype(cfg.dtype)
    bcu = checkpoint_name(h @ w["w_in"].astype(compute_dtype),
                          short_conv.KEEP_IN)
    mixed = short_conv.short_conv(bcu, w["conv_w"])
    return mixed @ w["w_out"].astype(compute_dtype)


@functools.lru_cache(maxsize=None)
def announce_delta(cfg, rows, chunk, kept, inverse, mode, why):
    """Once per compiled shape, by the logger ``announce_tiles`` uses:
    the gated delta rule one shard of the data axis scans over ``rows``
    tokens, whether the chunk-start states its backward reads are
    ``kept`` from the forward or made again by the second forward of a
    rematerialized layer, and how the backward gets a chunk's inverse:
    from the forward kernel, ``inverse`` bytes a layer, or by
    differentiating the jnp twin.  ``decay=`` names a kda layer's
    projections (``rank=0``: full), its gate (``gate=softplus`` |
    ``gate=floor<F>``) and what the scan makes of that floor
    (``pairs=block`` | ``pairs=columns``: ``gated_delta.pairs_of``)."""
    logger.info(
        "delta scan: rows=%d heads=%d key_dim=%d value_dim=%d chunk=%d "
        "conv_taps=%d neg_eigval=%d decay=%s states=%s inverse=%s %s%s",
        rows, cfg.num_heads, cfg.delta_key_dim, cfg.delta_value_dim, chunk,
        cfg.conv_kernel, cfg.delta_neg_eigval,
        "channel rank=%d gate=%s pairs=%s" % (
            cfg.delta_rank, "floor%g" % cfg.delta_gate_floor
            if cfg.delta_gate_floor else "softplus",
            gated_delta.pairs_of(cfg.delta_gate_floor))
        if cfg.delta_kind == "kda" else "head",
        "kept" if kept else "recomputed",
        "twin" if mode == "off" else "forward inverse_mb=%.1f" % (
            inverse / 1e6),
        {"tpu": "kernel", "interpret": "interpreter",
         "off": "reference"}[mode], " (%s)" % why if why else "")


def _l2norm(x, eps=1e-6):
    """x over the L2 norm of its last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _delta_mix(h, w, cfg, with_excess=False):
    """GatedDeltaNet(h) of the block's input, [B, T, dim] (with
    ``with_excess`` also how far under ``cfg.delta_gate_floor`` the
    lowest log decay lies): the operator of a "d" layer.  q, k and v of
    every head are one projection, pass one causal convolution and a
    SiLU (``ops/short_conv.conv_silu``), then q and k an L2 norm a head
    (q also ``key_dim ** -0.5``); the write strength ``beta`` is a
    sigmoid a head (times 2 with ``cfg.delta_neg_eigval``) and the log
    decay ``g = -exp(A_log) * softplus(h W_a + dt_bias)``, both float32;
    the scan is ``ops/gated_delta.py``'s, which picks kernel or
    reference; its output takes an RMSNorm over each head's values (one
    scale the heads share) times the SiLU of a gate projected from
    ``h``, and ``wo`` contracts (head, width) where the output stands.

    A kda layer (``cfg.delta_kind``): the log decay a CHANNEL of the
    key, [B, H, T, key_dim], and the gate a sigmoid.  Its two
    projections are low-rank pairs, ``a = (h W_a_down) W_a_up`` and
    ``(h W_g_down) W_g_up + b_g`` (``cfg.delta_rank`` > 0) | full, ``a
    = h W_a`` and ``h W_out_gate`` with no bias (rank 0); its decay
    gate ``g = -exp(A_log[head]) * softplus(a + dt_bias)`` | floored,
    ``g = F * sigmoid(exp(A_log[head]) * (a + dt_bias))`` in (F, 0)
    (``cfg.delta_gate_floor`` F < 0)."""
    compute_dtype = jnp.dtype(cfg.dtype)
    B, T, _ = h.shape
    H, dk, dv = cfg.num_heads, cfg.delta_key_dim, cfg.delta_value_dim
    kda = cfg.delta_kind == "kda"
    mode, why = gated_delta.delta_mode(T, dk, dv, vector=kda)
    rows = B * T // batch_shard.shards()
    announce_delta(cfg, rows, gated_delta.CHUNK,
                   not cfg.remat or remat_keep.keeps(
                       gated_delta.KEEP_STATES),
                   gated_delta.inverse_bytes(
                       rows, H, compute_dtype.itemsize,
                       gated_delta.pack_of(T)), mode, why)
    qkv = checkpoint_name(h @ w["w_qkv"].astype(compute_dtype),
                          remat_keep.KEEP_DELTA_IN)
    qkv = checkpoint_name(short_conv.conv_silu(qkv, w["delta_conv"]),
                          remat_keep.KEEP_DELTA_QKV)

    def heads(x, width, norm=None):
        x = x.reshape(B, T, H, width).transpose(0, 2, 1, 3)
        return x if norm is None else (_l2norm(x) * norm).astype(x.dtype)

    q = heads(qkv[..., :H * dk], dk, dk ** -0.5)
    k = heads(qkv[..., H * dk:2 * H * dk], dk, 1.0)
    v = heads(qkv[..., 2 * H * dk:], dv)
    low_rank = lambda name, width: _heads_first(
        checkpoint_name(h @ w[name + "_down"].astype(compute_dtype),
                        remat_keep.KEEP_DELTA_RANK),
        w[name + "_up"].astype(compute_dtype).reshape(-1, H, width))
    full = lambda name, width: _heads_first(
        h, w[name].astype(compute_dtype).reshape(-1, H, width))
    pairs = kda and cfg.delta_rank
    # [B, H, T] float32 each, from the compute dtype's products
    a, b = (jnp.einsum("btd,dh->bht", h, w[name].astype(
        compute_dtype)).astype(jnp.float32)
        if name in w and not (kda and name == "w_a") else None
        for name in ("w_a", "w_b"))
    per_head = lambda x: x.astype(jnp.float32)[None, :, None]
    beta = jax.nn.sigmoid(b) * (2.0 if cfg.delta_neg_eigval else 1.0)
    if kda:     # [B, H, T, key_dim]: a channel its own step and bias
        # (called where they stand: the softplus gate traces its
        # operations in the order it always did, and lowers to the text
        # it did)
        rate = lambda: jnp.exp(per_head(w["A_log"]))[..., None]
        step = lambda: (low_rank if pairs else full)("w_a", dk).astype(
            jnp.float32) + w["dt_bias"].astype(jnp.float32).reshape(H, 1, dk)
        floor = cfg.delta_gate_floor
        if floor:
            g = floor * jax.nn.sigmoid(rate() * step())
        else:
            g = -rate() * jax.nn.softplus(step())
    else:
        g = -jnp.exp(per_head(w["A_log"])) * jax.nn.softplus(
            a + per_head(w["dt_bias"]))
    if with_excess:
        excess = jnp.maximum(0.0, cfg.delta_gate_floor - g.min())
    g, beta = (checkpoint_name(x, remat_keep.KEEP_DELTA_DECAY)
               for x in (g, beta))
    o = gated_delta.gated_delta(q, k, v, g, beta,
                                floor=cfg.delta_gate_floor)
    if pairs:
        gate = checkpoint_name(low_rank("w_g", dv),
                               remat_keep.KEEP_DELTA_GATE)
        gate = gate + w["b_g"].astype(compute_dtype).reshape(H, 1, dv)
    else:
        gate = checkpoint_name(full("w_out_gate", dv),
                               remat_keep.KEEP_DELTA_GATE)
    o = _rmsnorm(o, w["o_norm"].astype(compute_dtype), cfg.norm_eps) * (
        jax.nn.sigmoid(gate) if kda else jax.nn.silu(gate))
    # as ``_latent_mix``: reshaped before the cast
    wo = w["wo"].reshape(H, dv, cfg.dim).astype(compute_dtype)
    out = jnp.einsum("bhtk,hkd->btd", o, wo)
    return (out, excess) if with_excess else out


# The published ``chunk_size`` of the Mamba-2 models: what ``chunk_keep``
# is taken over, whatever chunk the kernel walks.
SSM_PUBLISHED_CHUNK = 128


@functools.lru_cache(maxsize=None)
def announce_ssm(cfg, rows, chunk, kept, mode, why):
    """Once per compiled shape, by the logger ``announce_tiles`` uses:
    the state-space scan one shard of the data axis runs over ``rows``
    tokens, and whether the chunk-start states its backward reads are
    ``kept`` from the forward or made again by the second forward of a
    rematerialized layer."""
    logger.info(
        "ssm scan: rows=%d heads=%d groups=%d head_dim=%d state=%d "
        "chunk=%d conv_taps=%d conv_bias=%d states=%s %s%s", rows,
        cfg.ssm_heads, cfg.ssm_groups, cfg.ssm_head_dim, cfg.ssm_state,
        chunk, cfg.conv_kernel, cfg.conv_bias,
        "kept" if kept else "recomputed",
        {"tpu": "kernel", "interpret": "interpreter",
         "off": "reference"}[mode], " (%s)" % why if why else "")


def _ssm_mix(h, w, cfg):
    """Mamba2(h) of the normed input -> ([B, T, dim], ``chunk_keep``):
    the operator of an "m" layer.  One projection ``ssm_in`` gives z | x
    | B | C | dt (three products of its column blocks: splitting a
    product by output columns changes no sum); x | B | C pass one causal
    convolution with its bias and a SiLU (``ops/short_conv.conv_silu``);
    ``dt = softplus(dt + dt_bias)`` and the log decay ``g = -exp(A_log)
    dt``, float32 a head; the scan is ``ops/ssd.py``'s, which picks
    kernel or reference, on the token-major operands as they stand; ``y
    + D x``; ``RMSNorm(y * SiLU(z))`` over each group's values with the
    learned scale ``ssm_norm``; ``ssm_out``.  ``chunk_keep``: the mean
    over heads and chunks of ``SSM_PUBLISHED_CHUNK`` tokens of ``exp(sum
    of the chunk's log decays)``, the share of a state that outlives a
    chunk."""
    compute_dtype = jnp.dtype(cfg.dtype)
    B, T, _ = h.shape
    H, P, N, G = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups
    inner, group = H * P, G * N
    mode, why = ssd.ssd_mode(T, H // G, P, N)
    announce_ssm(cfg, B * T // batch_shard.shards(), ssd.CHUNK,
                 not cfg.remat or remat_keep.keeps(ssd.KEEP_STATES), mode,
                 why)
    w_in = w["ssm_in"].astype(compute_dtype)
    z = checkpoint_name(h @ w_in[:, :inner], remat_keep.KEEP_SSM_GATE)
    xbc = checkpoint_name(h @ w_in[:, inner:2 * inner + 2 * group],
                          remat_keep.KEEP_SSM_IN)
    dt = (h @ w_in[:, 2 * inner + 2 * group:]).astype(jnp.float32)
    xbc = checkpoint_name(
        short_conv.conv_silu(xbc, w["ssm_conv"],
                             bias=w.get("ssm_conv_bias")),
        remat_keep.KEEP_SSM_XBC)
    x = xbc[..., :inner].reshape(B, T, H, P)
    b = xbc[..., inner:inner + group].reshape(B, T, G, N)
    c = xbc[..., inner + group:].reshape(B, T, G, N)
    dt = jax.nn.softplus(dt + w["dt_bias"].astype(jnp.float32))
    g = -jnp.exp(w["A_log"].astype(jnp.float32)) * dt
    g, dt = (checkpoint_name(a, remat_keep.KEEP_SSM_DECAY) for a in (g, dt))
    size = min(SSM_PUBLISHED_CHUNK, T)
    keep = jnp.exp(g[:, :T // size * size].reshape(
        B, T // size, size, H).sum(axis=2)).mean()
    y = ssd.ssd(x, b, c, g, dt)
    y = y + x * w["ssm_D"].astype(compute_dtype)[:, None]
    y = _group_rmsnorm(y.reshape(B, T, inner) * jax.nn.silu(z),
                       w["ssm_norm"].astype(compute_dtype), G, cfg.norm_eps)
    out = y @ w["ssm_out"].astype(compute_dtype)
    return out, jax.lax.stop_gradient(keep)


def _operator(x, w, cfg, mesh, positions, kind):
    """x + post(Op(norm(x))) -> (x, what the operator hands on beside
    it: attention's (k, v); a floored kda layer's gate excess
    (``_delta_mix``); a Mamba-2 layer's ``chunk_keep`` (``_ssm_mix``);
    else None), ``Op`` the operator of ``kind``:
    attention, latent attention (nothing cached: decoding refuses it),
    the short convolution, the gated delta rule or the Mamba-2 mixer.
    On a wide stream with an FFN behind the operator x is a ``_Write``:
    the write not yet made."""
    u, x, maps = _read(x, w, cfg, "hc1")
    h = _pre(u, w, cfg, "ln1")
    kv_out = None
    if kind.op == "c":
        out = _conv_mix(h, w, cfg)
    elif kind.op == "d":
        out = _delta_mix(h, w, cfg, bool(cfg.delta_gate_floor))
        if cfg.delta_gate_floor:
            out, kv_out = out
    elif kind.op == "m":
        out, kv_out = _ssm_mix(h, w, cfg)
    elif cfg.latent:
        if mesh is not None:
            _refuse(cfg, "a model-parallel mesh", "latent")
        out = _latent_mix(h, w, cfg, positions, kind)
    else:
        out, kv_out = _attention_mix(h, w, cfg, mesh, positions, kind)
    if maps is not None and kind.ffn:
        # a wide stream's write is the FFN's read's to make (``_read``)
        return _Write(x, out, maps), kv_out
    x = _residual(x, out, w, cfg, mesh, "ln1_post", maps)
    if not kind.ffn:    # no FFN behind it: the stream is the layer's result
        return x, kv_out
    return checkpoint_name(x, remat_keep.KEEP_STREAM), kv_out


def _attention(x, w, cfg, mesh, positions, kind=None):
    """:func:`_operator` of an attention layer (``kind`` None: the
    model's one attention kind)."""
    return _operator(x, w, cfg, mesh, positions, kind or _one_kind(cfg))


def _short_conv(x, w, cfg):
    """:func:`_operator` of a "c" layer, the stream alone."""
    return _operator(x, w, cfg, None, None, Kind("c", False))[0]


def _layer_body(x, w, cfg, mesh, positions, moe_stats=False,
                return_kv=False, moe_load=False, kind=None):
    """One block, ``x + post(Op(norm(x)))`` then ``x + post(FFN(norm(
    x)))``, of ``kind`` (None: attention, and the model's one FFN; a
    kind without an operator, "e", or without an FFN, ``Kind.ffn``
    False, is the other sublayer alone);
    ``post`` is the identity unless ``cfg.post_norms`` (``_residual``,
    the one place either is written).  Shared by the scanned stack
    (forward) and the per-stage slice scan (forward_pipelined).
    ``moe_stats`` swaps
    the scalar aux for the linear [2, X] router statistics (pipeline
    accumulation); ``moe_load`` returns (aux, load [X + 1]) for an MoE
    (the step statistics).  ``return_kv`` additionally returns this
    layer's (k, v) — the decode prefill captures them into the KV
    cache.  In a model with ``cfg.delta_gate_floor`` what it returns
    beside x is (that, the layer's gate excess).  Which kernels run is
    not its business: the ops ask
    ``ops/mode.py``, and a caller that traces it where none can run
    says so with ``kernels_off()``."""
    kind = kind or _one_kind(cfg)
    route = None
    if cfg.moe_route_before_op and not kind.dense:
        # the router reads what the operator reads (the operator takes
        # the same norm again: one value, the compiler's to share)
        route = moe_route(
            _rmsnorm(x, w["ln1"].astype(jnp.dtype(cfg.dtype)),
                     cfg.norm_eps),
            w["w_router"], cfg, w.get("expert_bias"))
    kv_out, aux, stats, load = None, jnp.float32(0.0), None, None
    if kind.op != "e":      # "e": no operator, the FFN alone
        x, kv_out = _operator(x, w, cfg, mesh, positions, kind)
    if kind.ffn:            # one sublayer (``mixer_ffn`` False): none
        x, aux, stats, load = _ffn(x, w, cfg, mesh, dense=kind.dense,
                                   route=route,
                                   limits=(kind.limit, kind.shared_limit))
    if moe_stats and not kind.dense:
        aux = stats
    elif moe_load and not kind.dense:
        aux = (aux, load)
    if return_kv:
        return x, (aux, kv_out)
    if cfg.delta_gate_floor:
        # every layer of a model with a floored gate hands on how far
        # under the floor its decays went: 0 for a layer that has none
        aux = (aux, kv_out if kind.op == "d" else jnp.float32(0.0))
    if "m" in cfg.layer_pattern:
        # and every layer of a model with a Mamba-2 layer its
        # ``chunk_keep``: 0 for a layer that has no such state
        aux = (aux, kv_out if kind.op == "m" else jnp.float32(0.0))
    return x, aux


def _embed(params, tokens, cfg, mesh=None):
    """The stream's first value: the tokens' rows of the table in the
    compute dtype, times ``cfg.embed_multiplier`` where the model has
    one (``ops/embed_rows.py``: the lookup, the table's gradient its
    own).  ``mesh``: a model-parallel mesh."""
    compute_dtype = jnp.dtype(cfg.dtype)
    x = embed_rows(params["embed"], tokens, compute_dtype, mesh)
    if cfg.embed_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embed_multiplier, compute_dtype)
    return x


def _head(params, x, cfg):
    compute_dtype = jnp.dtype(cfg.dtype)
    x = _rmsnorm(x, params["ln_f"].astype(compute_dtype), cfg.norm_eps)
    head = (
        params["embed"].T if cfg.tied_embeddings else params["lm_head"]
    ).astype(compute_dtype)
    return (x @ head).astype(jnp.float32)


def head_loss(params, hidden, tokens, cfg, shift=1):
    """``next_token_loss(_head(params, hidden, cfg), tokens)`` for the
    training path: the head and the loss as one op
    (:mod:`elasticdl_tpu.ops.head_loss`), whose one [B, T, V] tensor is
    the logits in the compute dtype.  ``shift``: the target is the
    token that many on (a multi-token-prediction module's is 2 and
    more)."""
    from elasticdl_tpu.ops import head_loss as op

    compute_dtype = jnp.dtype(cfg.dtype)
    x = _rmsnorm(hidden, params["ln_f"].astype(compute_dtype), cfg.norm_eps)
    head = params["embed" if cfg.tied_embeddings else "lm_head"]
    return op.head_loss(x, head.astype(compute_dtype), tokens,
                        tied=cfg.tied_embeddings, shift=shift)


@jax.custom_vjp
def _update_apart(w):
    """``w``, its gradient handed on through an ``optimization_barrier``
    of its own: the optimizer's update of ``w`` cannot become the
    epilogue of the matmul that makes the gradient."""
    return w


_update_apart.defvjp(
    lambda w: (w, None),
    lambda _, g: (jax.lax.optimization_barrier(g),))


@functools.lru_cache(maxsize=None)
def announce_updates_apart(leaves, nbytes):
    """Once per model, by the logger ``announce_tiles`` uses: the
    matrices of the layer stack whose gradients stand behind a barrier
    each, and their float32 bytes."""
    logger.info("update apart: leaves=%d bytes=%d", leaves, nbytes)


def _updates_apart(layers, plan, mtp=None):
    """The stack's weights, each MATRIX (a leaf of rank 2 once the
    scanned axis of ``layers`` or of ``layers["period"]`` is taken off:
    projections, FFN weights, routers, a convolution's taps; not a
    norm's vector, not the experts' ``[X, ., .]``, whose gradients
    Mosaic calls make) through :func:`_update_apart`, a leaf at a time
    and before the stack reads it.  Left alone, XLA unrolls a scan of
    one turn and runs a layer's AdamW update (seven float32 streams) as
    the epilogue of its weight-gradient matmul, which then reads 34-54%
    of the MXU's peak where the same product alone reads 80-90 (PERF.md
    section 6, PR 46; ``ops/head_loss.py`` holds the head's apart the
    same way, which is why ``embed`` and ``lm_head`` are not in this
    rule: held apart here as well, ``lm_head`` costs ``trinity-mini``
    1.1 GB).  A scan of several turns is left as it is: its update
    already runs after the loop on the stacked gradient, and a barrier
    there buys nothing and moves the program (``olmo1b`` on four chips:
    the compiler's bytes +0.18 GB, the loop's all-reduces combined
    otherwise).  With ``mtp`` (the multi-token-prediction modules, each
    a layer outside any loop) -> (layers, mtp)."""
    held = []

    def apart(scanned):
        def leaf(w):
            if w.ndim - scanned != 2 or (scanned and w.shape[0] > 1):
                return w
            held.append(w.size)
            return _update_apart(w)

        return functools.partial(jax.tree_util.tree_map, leaf)

    if plan is None:
        layers = apart(1)(layers)
    else:
        layers = {name: apart(int(name == "period"))(group)
                  for name, group in layers.items()}
    if mtp is not None:     # the modules' blocks and projections too
        mtp = apart(0)(mtp)
    announce_updates_apart(len(held), 4 * sum(held))
    return layers if mtp is None else (layers, mtp)


def _widen(x, cfg):
    """The stream's first value of a model with ``cfg.hyper_streams``:
    x [B, T, dim] n times side by side, beside the running maximum of
    the Sinkhorn error that the blocks' reads raise (``_read``)."""
    if not cfg.hyper_streams:
        return x
    return jnp.tile(x, (1, 1, cfg.hyper_streams)), jnp.float32(0.0)


def _narrow(x, w, cfg):
    """(hidden state [B, T, dim], Sinkhorn error or None) of the stream
    behind the last block: on a wide stream the streams read through
    the map ``hc_out_*`` of ``w``."""
    if not cfg.hyper_streams:
        return x, None
    x, err = x
    return hyper_mix.narrow(
        x, w["hc_out_phi"], w["hc_out_alpha"], w["hc_out_bias"],
        cfg.hyper_streams, cfg.norm_eps), err


def _mtp_module(w, hidden, embedded, k, cfg, block):
    """Multi-token-prediction module ``k`` (0-based) -> (its hidden
    state [B, T, dim] before the final norm, what its block returned
    beside the stream, its Sinkhorn error or None).  ``hidden``: the
    hidden state it continues; ``embedded``: the tokens' embeddings, of
    which position t takes token t + k + 1's (the last k + 1 positions
    take the sequence's first: no loss reads them, and causal attention
    lets no other position see them)."""
    dtype = jnp.dtype(cfg.dtype)
    norm = lambda x, name: _rmsnorm(x, w[name].astype(dtype), cfg.norm_eps)
    proj = w["proj"].astype(dtype)
    # [RMSNorm(h) ; RMSNorm(E)] W_proj, the product split by W_proj's
    # rows: no [B, T, 2 dim] copy
    x = (norm(hidden, "norm_h") @ proj[:cfg.dim]
         + norm(jnp.roll(embedded, -(k + 1), axis=1), "norm_e")
         @ proj[cfg.dim:])
    x, out = block(cfg.mtp_kind)(_widen(x, cfg), w["layer"])
    return (*_narrow(x, w, cfg), out)


def _forward_stack(params, tokens, cfg, mesh=None, with_load=False,
                   with_mtp=False):
    """tokens [B, T] -> {"hidden": [B, T, dim] before ln_f and the head;
    "aux": each layer's MoE aux [L_moe] (a zero where none has experts);
    "load": each such layer's load (``_moe_ffn``) with ``with_load``;
    "hc_err": on a wide stream the largest Sinkhorn error of the step;
    "gate_excess": under ``cfg.delta_gate_floor`` how far under it the
    step's lowest log decay lies (``_delta_mix``; 0 by construction);
    "chunk_keep": the Mamba-2 layers' mean share of a state that
    outlives a chunk (``_ssm_mix``; None without such a layer);
    "mtp_hidden": with ``with_mtp`` each multi-token-prediction
    module's hidden state, its block's aux and load joined to the
    stack's; of a looped stack (``cfg.ut_steps`` > 1) "hidden" is the
    LAST turn's, "aux" a zero a layer a turn, "turns" every turn's
    final-normed state [R, B, T, dim] and "logits_kept" whether the
    heads' backward finds their logits kept (``remat_keep``'s choice)}."""
    embedded = _constrain(_embed(params, tokens, cfg, mesh), mesh,
                          P("dp", "sp", None))
    x = _widen(embedded, cfg)
    positions = jnp.arange(tokens.shape[1])

    remat = lambda fn: fn
    if cfg.remat:
        # the names that fit the room declared around this trace, none
        # without one or under a model-parallel mesh (whose activations
        # are not whole on a device)
        names = () if mesh is not None else remat_keep.names_for(
            cfg, params, tokens.shape)
        remat = functools.partial(
            jax.checkpoint,
            policy=(jax.checkpoint_policies.save_only_these_names(*names)
                    if names else None))

    def block(kind=None):
        def layer(x, w):
            return _layer_body(x, w, cfg, mesh, positions,
                               moe_load=with_load, kind=kind)

        return remat(layer)

    plan = stack_plan(cfg)
    modules = params.get("mtp") if with_mtp else None
    layers = _updates_apart(params["layers"], plan, modules)
    if modules:
        layers, modules = layers
    out = {"mtp_hidden": []}
    with remat_keep.keeping(names if cfg.remat and plan is not None
                            else ()):
        excess = keep = None
        if cfg.ut_steps > 1:
            x, seen, out["turns"] = _looped_stack(x, layers, params, cfg,
                                                  block())
            out["logits_kept"] = remat_keep.KEEP_LOGITS in names if (
                cfg.remat) else True
            announce_loop(cfg.ut_steps, cfg.num_layers,
                          tokens.size // batch_shard.shards(),
                          out["logits_kept"])
        elif plan is None:
            x, seen = jax.lax.scan(block(), x, layers)
        else:
            x, seen, excess, keep = _mixed_stack(x, layers, cfg, plan, block)
        hidden, err = _narrow(x, params, cfg)
        out["hidden"] = hidden
        for k in range(len(modules or ())):
            hidden, off, more = _mtp_module(
                modules[str(k)], hidden, embedded, k, cfg, block)
            out["mtp_hidden"].append(hidden)
            if off is not None:
                err = jnp.maximum(err, off)
            if excess is not None:
                more, under = more
                excess = jnp.maximum(excess, under)
            if not cfg.mtp_kind.dense:
                seen = jax.tree_util.tree_map(
                    lambda a, b: jnp.concatenate([a, b[None]]), seen, more)
    out["aux"], out["load"] = seen if with_load else (seen, None)
    out["hc_err"], out["gate_excess"] = err, excess
    out["chunk_keep"] = keep
    return out


@functools.lru_cache(maxsize=None)
def announce_loop(turns, layers, rows, logits_kept):
    """Once per compiled shape, by the logger ``announce_tiles`` uses:
    how a looped stack is run (``_looped_stack``: the turns one scan,
    the carry final-normed; ``looped_loss``: a linear gate, a head a
    turn, their logits kept or made again as ``remat_keep`` chose)."""
    logger.info(
        "loop stack: turns=%d layers=%d rows=%d carry=normed gate=linear "
        "heads=%d turns_as=scan logits=%s", turns, layers, rows, turns,
        "kept" if logits_kept else "recomputed")


def _looped_stack(x, layers, params, cfg, layer):
    """``cfg.ut_steps`` turns round the scanned stack on its one set of
    weights, the model's final norm behind each turn: ``h_t = RMSNorm_f(
    Stack(h_(t-1)))`` -> (the last turn's state BEFORE that norm, what
    the layers returned beside the stream [R * L], every ``h_t`` [R, B,
    T, dim]).  The turns are one ``lax.scan`` round the layers' scan
    (docs/designs/looped_stack.md: why, and where the stacked weights'
    gradient stands, ``remat_keep.grads_standing``)."""
    scale = params["ln_f"].astype(jnp.dtype(cfg.dtype))
    # its backward reads u alone: no float32 plane of the norm is saved
    norm = jax.checkpoint(lambda u: _rmsnorm(u, scale, cfg.norm_eps))

    def turn(carry, _):
        u, seen = jax.lax.scan(layer, carry[0], layers)
        h = norm(u)
        return (h, u), (h, seen)

    (_, last), (turns, seen) = jax.lax.scan(
        turn, (x, x), None, length=cfg.ut_steps)
    return last, seen.reshape(-1), turns


def exit_distribution(gate_logits):
    """The log of a looped stack's exit distribution [R, ..] (float32)
    from its gate's logits [R - 1, ..]: ``p_t = lambda_t prod_(j<t) (1 -
    lambda_j)`` with ``lambda = sigmoid(logit)``, and ``p_R`` what is
    left; sums to 1 over the turns."""
    leave = jax.nn.log_sigmoid(gate_logits)
    stay = jnp.cumsum(jax.nn.log_sigmoid(-gate_logits), axis=0)
    stayed = jnp.concatenate([jnp.zeros_like(stay[:1]), stay[:-1]])
    return jnp.concatenate([leave + stayed, stay[-1:]])


def looped_loss(outputs, tokens, cfg):
    """A looped stack's training loss [B] and what ``step_stats`` hands
    out of it (``outputs["ut"]``): the head (``ops/head_loss.
    token_loss``: one head, R calls, its gradient their sum) on every
    turn's state, the exit gate on turns 1 .. R - 1, and a token's loss
    the expectation of its R cross entropies under the exit
    distribution less ``cfg.ut_entropy_weight`` times that
    distribution's entropy, the mean over the T - 1 positions that have
    a target.  Everything behind the head in float32."""
    from elasticdl_tpu.ops import head_loss as op

    params, turns = outputs["params"], outputs["turns"]
    dtype = jnp.dtype(cfg.dtype)
    # the R calls' gradients of the one head are summed behind a barrier
    # of the sum's own: left alone, XLA makes the sum part of the
    # optimizer's update and the R addends stand through the stack's
    # backward (0.2 GB each at 2,048 x 49,152)
    head = _update_apart(
        params["embed" if cfg.tied_embeddings else "lm_head"].astype(dtype))
    R, _, T, _ = turns.shape
    # the last turn's logits are read by the backward that follows them
    losses = jnp.stack([
        op.token_loss(turns[t], head, tokens, tied=cfg.tied_embeddings,
                      calls=R,
                      logits_kept=outputs["logits_kept"] or t == R - 1)
        for t in range(R)])                                     # [R, B, T]
    gate = jnp.einsum(
        "rbte,e->rbt", turns[:-1].astype(jnp.float32),
        params["ut_gate_w"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST) + params["ut_gate_b"].astype(
            jnp.float32)
    log_p = exit_distribution(gate)
    p = jnp.exp(log_p)
    # a turn no token can leave at (p = 0) adds nothing: 0 log 0 = 0
    entropy = -(p * jnp.where(p > 0, log_p, 0.0)).sum(axis=0)   # [B, T]
    has_target = (jnp.arange(T) < T - 1).astype(jnp.float32)
    mean = lambda a: (a * has_target).sum(axis=-1) / (T - 1)
    outputs["ut"] = {
        "ut_loss": mean(losses).mean(axis=-1),
        "ut_exit": mean(p).mean(axis=-1),
        "ut_exit_entropy": mean(entropy).mean()}
    return mean((p * losses).sum(axis=0) - cfg.ut_entropy_weight * entropy)


def forward_hidden(params, tokens, cfg, mesh=None, return_load=False):
    """tokens: [B, T] int32 -> (final hidden [B, T, dim] BEFORE the
    ln_f/head, mean per-layer MoE aux); with ``return_load`` (an MoE)
    also each layer's load [L, X + 1] (:func:`_moe_ffn`).

    Pair with :func:`next_token_loss_chunked` to train without ever
    materializing the [B, T, V] logits tensor (at the flagship config
    that tensor is ~2 GB in f32 — a pure HBM-bandwidth tax the chunked
    loss removes).
    """
    with_load = bool(return_load
                     and not all(kind.dense for kind in cfg.kinds))
    out = _forward_stack(params, tokens, cfg, mesh, with_load)
    if with_load:
        return out["hidden"], out["aux"].mean(), out["load"]
    return out["hidden"], out["aux"].mean()


@functools.lru_cache(maxsize=None)
def announce_stack(pattern, plan, experts, shared=0, heads=None,
                   more=""):
    """Once per model, by the logger ``announce_tiles`` uses: how a
    stack whose layers differ is run (``shared``: the width of an
    expert layer's always-on shared expert, said where there is one;
    ``heads``: (held here, of how many) where chips share a layer's
    heads; ``more``: the fields of a wide stream, multi-token
    prediction, a query latent, a gate a head, a router's groups
    (chosen/all), a kda layer's full projections, its gate's floor and
    the clamps a layer, where the model has them)."""
    letters = lambda kinds: "".join(map(_letter, kinds)) or "-"
    # an attention kind once, whatever its layer's FFN and clamps
    kinds = sorted(set(k._replace(limit=0.0, shared_limit=0.0)
                       for k in plan.lead + plan.period + plan.tail
                       if k.op == "a"), key=_letter)
    logger.info(
        "layer stack: pattern=%s lead=%s period=%s periods=%d tail=%s "
        "dense_layers=%d experts_held=%d/%d%s%s%s%s", pattern,
        letters(plan.lead), letters(plan.period), plan.periods,
        letters(plan.tail), len(plan.lead), *experts,
        " shared_expert=%d" % shared if shared else "",
        " heads_held=%d/%d" % heads if heads else "", more,
        "".join(" %s:window=%d,rope=%d" % (_letter(k), k.window, k.rope)
                for k in kinds))


def _mixed_stack(x, layers, cfg, plan, block):
    """The leading layers, the whole periods under one scan (a period's
    layers unrolled in its body), the remainder -> (x, what the layers
    with experts returned beside it, stacked in layer order: aux [L_moe]
    or (aux [L_moe], load [L_moe, ..]); a zero where none has experts,
    the largest gate excess of a layer: None without
    ``cfg.delta_gate_floor``, the Mamba-2 layers' mean ``chunk_keep``:
    None without one)."""
    announce_stack("".join(map(_letter, cfg.kinds)), plan,
                   (cfg.experts_held[1], cfg.moe_experts), cfg.shared_dim,
                   (cfg.num_heads, cfg.num_heads * cfg.head_shares)
                   if cfg.head_shares > 1 else None,
                   "".join(" %s=%s" % (name, value) for name, value in (
                       ("hyper", cfg.hyper_streams),
                       ("sinkhorn", cfg.hyper_streams
                        and cfg.hyper_sinkhorn_iters),
                       ("mtp", cfg.mtp_modules),
                       ("q_latent", cfg.q_latent_rank),
                       ("attn_gate", cfg.attn_gate == "head" and "head"),
                       ("route_groups", cfg.moe_groups and "%d/%d" % (
                           cfg.moe_top_groups, cfg.moe_groups)),
                       ("kda_rank", "d" in cfg.layer_pattern
                        and cfg.delta_kind == "kda" and not cfg.delta_rank
                        and "full"),
                       ("gate_floor", cfg.delta_gate_floor),
                       # a digit a layer: 1 = a mixer or an FFN alone
                       ("sublayers", cfg.plain_only and "".join(
                           str((k.op != "e") + k.ffn) for k in cfg.kinds)),
                       ("mlp", not cfg.gated_mlp
                        and "two-matrix:" + cfg.ffn_activation),
                       ("ffn_limits", cfg.ffn_limits),
                       ("shared_limits", cfg.shared_limits)) if value))

    tree_map = jax.tree_util.tree_map
    floored = bool(cfg.delta_gate_floor)
    ssm = sum(kind.op == "m" for kind in cfg.kinds)

    def run(x, kinds, weights):
        """(x, (what the layers with experts returned, stacked; None
        where none has, the layers' largest gate excess or None, the sum
        of their ``chunk_keep`` or None))."""
        seen, off = [], jnp.float32(0.0) if floored else None
        kept = jnp.float32(0.0) if ssm else None
        for i, kind in enumerate(kinds):
            x, out = block(kind)(x, weights[str(i)])
            if ssm:
                out, keep = out
                kept = kept + keep
            if floored:
                out, excess = out
                off = jnp.maximum(off, excess)
            if not kind.dense:
                seen.append(out)
        return x, (tree_map(lambda *a: jnp.stack(a), *seen) if seen
                   else None, off, kept)

    x, (lead, off, kept) = run(x, plan.lead, layers["lead"])
    period = None
    if plan.periods:
        x, (period, offs, keeps) = jax.lax.scan(
            lambda x, w: run(x, plan.period, w), x, layers["period"])
        # [periods, positions, ..] -> [periods * positions, ..]
        period = tree_map(lambda a: a.reshape((-1,) + a.shape[2:]), period)
    x, (tail, last, more) = run(x, plan.tail, layers["tail"])
    if floored:
        off = jnp.maximum(jnp.maximum(off, last),
                          offs.max() if plan.periods else 0.0)
    if ssm:     # the mean over the Mamba-2 layers
        kept = (kept + more + (keeps.sum() if plan.periods else 0.0)) / ssm
    parts = [part for part in (lead, period, tail) if part is not None]
    if not parts:
        return x, jnp.zeros((1,), jnp.float32), off, kept
    return x, tree_map(lambda *a: jnp.concatenate(a), *parts), off, kept


def forward(params, tokens, cfg, mesh=None, return_aux=False):
    """tokens: [B, T] int32 -> logits [B, T, V].

    With ``return_aux`` (training an MoE), also returns the mean
    per-layer load-balance loss for the spec's loss_fn to add.
    """
    x, aux = forward_hidden(params, tokens, cfg, mesh=mesh)
    logits = _head(params, x, cfg)
    if return_aux:
        return logits, aux
    return logits


def forward_pipelined(params, tokens, cfg, mesh, num_microbatches,
                      remat=False, return_aux=False,
                      return_hidden=False):
    """Microbatch-pipelined forward over the ``pp`` mesh axis.

    The layer stack runs as a GPipe schedule (parallel/pipeline.py):
    S = mesh.shape['pp'] stages compute concurrently on different
    microbatches, activations hopping stages via ppermute.  Bubble
    fraction is (S-1)/(M+S-1) — S=2, M=8 -> 11.1%.  With ``return_aux``
    the MoE load-balance loss equals the EXACT full-batch statistic
    (all K choices counted): stages accumulate the linear per-expert
    (assigned, prob)
    sufficient statistics over real ticks (bubbles masked) and combine
    them after the loop, so the objective is identical to the scanned
    forward's and independent of the microbatch count.  Embedding
    lookup and
    the LM head run replicated over pp outside the pipeline (their FLOPs
    are small next to the stack).  Attention is per-shard local inside a
    stage, so this path requires sp=1; dp/tp compose as auto axes.
    """
    from elasticdl_tpu.parallel.pipeline import (
        merge_microbatches,
        pipeline_apply,
        split_microbatches,
    )

    _refuse(cfg, "forward_pipelined", *_TRAINS_ONLY)
    if mesh.shape.get("sp", 1) != 1:
        raise ValueError(
            "forward_pipelined requires sp=1 (stage-local attention); "
            "use ring attention (plain forward) for sequence parallelism"
        )
    x = _embed(params, tokens, cfg, mesh)
    positions = jnp.arange(tokens.shape[1])

    collect_aux = bool(return_aux and cfg.moe_experts)

    def stage_fn(w, x_mb):
        def body(x, w1):
            # Inside the pp-manual shard_map the dp/tp axes are auto,
            # and a pallas_call under auto axes would be all-gathered by
            # GSPMD; the jnp paths partition.
            with kernels_off():
                return _layer_body(x, w1, cfg, None, positions,
                                   moe_stats=collect_aux)

        x_mb, aux_per_layer = jax.lax.scan(body, x_mb, w)
        if collect_aux:
            return x_mb, aux_per_layer  # [L_stage, 2, X] router stats
        return x_mb

    def finalize(stats, num_mb):
        # stats: [L_stage, 2, X] SUMS of per-microbatch (frac, prob)
        # means.  /M gives the full-batch means (equal microbatch
        # sizes), so this stage's layers contribute their EXACT aux —
        # no dependence on M.
        f = stats[:, 0] / num_mb
        p = stats[:, 1] / num_mb
        return (cfg.moe_experts * (f * p).sum(-1)).sum()

    xm = split_microbatches(x, num_microbatches)
    if collect_aux:
        ym, aux_sum = pipeline_apply(
            stage_fn, params["layers"], xm, mesh=mesh,
            num_microbatches=num_microbatches, remat=remat,
            with_aux=True, aux_finalize=finalize,
        )
    else:
        ym = pipeline_apply(
            stage_fn, params["layers"], xm, mesh=mesh,
            num_microbatches=num_microbatches, remat=remat,
        )
    x = merge_microbatches(ym)
    # The head runs on the MERGED hidden states outside the pipeline,
    # so ``return_hidden`` composes with the chunked loss exactly like
    # the scanned forward's forward_hidden.
    out = x if return_hidden else _head(params, x, cfg)
    if return_aux:
        if not collect_aux:  # dense model asked for aux: trivially zero
            return out, jnp.float32(0.0)
        # aux_sum covers ALL layers (stages sum via psum); normalize to
        # mean-per-layer to match forward(return_aux=True).
        return out, aux_sum / cfg.num_layers
    return out


# -- autoregressive decoding ---------------------------------------------------

NEG_INF_DECODE = -1e30


def init_kv_cache(cfg, batch, max_len):
    """Zeroed per-layer K/V caches, each [L, B, max_len, G, D].

    G = cfg.kv_heads: with grouped-query attention the cache holds G
    heads, not num_heads — the standard serving memory win (e.g. G=2,
    H=16 caches 8x less KV).
    """
    shape = (cfg.num_layers, batch, max_len, cfg.kv_heads, cfg.head_dim)
    dtype = jnp.dtype(cfg.dtype)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def _decode_layer(x, w, cfg, ck, cv, pos):
    """One block for ONE position.  x: [B, 1, E]; ck/cv: [B, max, G, D]
    caches (updated at ``pos`` and returned).  Attention is the single
    query against the cache, computed grouped (no K/V head repeat)."""
    compute_dtype = jnp.dtype(cfg.dtype)
    B = x.shape[0]
    H, D, G = cfg.num_heads, cfg.head_dim, cfg.kv_heads
    R = H // G
    kind = _one_kind(cfg)     # a uniform stack's (``_refuse``)
    positions = jnp.reshape(pos, (1,))
    h = _rmsnorm(x, w["ln1"].astype(compute_dtype), cfg.norm_eps)
    route = None
    if cfg.moe_route_before_op and not kind.dense:
        # as ``_layer_body``: the router reads what attention reads
        route = moe_route(h, w["w_router"], cfg, w.get("expert_bias"))
    q, k, v = _project_qkv(h, w, cfg, positions, kind.rope)
    # head-major [B, ., 1, D]; the cache is token-major
    ck = jax.lax.dynamic_update_slice(
        ck, k.transpose(0, 2, 1, 3).astype(ck.dtype), (0, pos, 0, 0))
    cv = jax.lax.dynamic_update_slice(
        cv, v.transpose(0, 2, 1, 3).astype(cv.dtype), (0, pos, 0, 0))

    qg = q.reshape(B, G, R, D).astype(jnp.float32)
    s = jnp.einsum(
        "bgrd,btgd->bgrt", qg, ck.astype(jnp.float32),
    ) * (D ** -0.5)                                   # [B, G, R, max]
    idx = jnp.arange(ck.shape[1])
    valid = idx <= pos
    if kind.window:
        valid &= (pos - idx) < kind.window
    s = jnp.where(valid[None, None, None, :], s, NEG_INF_DECODE)
    p = jax.nn.softmax(s, axis=-1)
    attn = jnp.einsum(
        "bgrt,btgd->bgrd", p, cv.astype(jnp.float32)
    ).reshape(B, 1, H * D).astype(compute_dtype)
    x = x + attn @ w["wo"].astype(compute_dtype)

    x = _ffn(x, w, cfg, None, route=route)[0]
    return x, ck, cv


def prefill(params, cfg, prompt, max_len):
    """Batched prefill: ONE forward pass over the prompt computes every
    layer's K/V and writes them into fresh caches of length
    ``max_len``.  Returns (last-position logits [B, V], caches).  This
    is the time-to-first-token path — Tp sequential decode steps would
    be MXU-starved serialized work."""
    _refuse(cfg, "prefill", *_TRAINS_ONLY)
    b, tp = prompt.shape
    x = _embed(params, prompt, cfg)
    positions = jnp.arange(tp)

    def layer(x, w):
        x, (_aux, kv) = _layer_body(
            x, w, cfg, None, positions, return_kv=True
        )
        return x, kv

    x, (ks, vs) = jax.lax.scan(layer, x, params["layers"])
    ck, cv = init_kv_cache(cfg, b, max_len)  # [L, B, max, G, D]
    # the layers' K/V are head-major [L, B, G, Tp, D]; the cache is not
    ck = jax.lax.dynamic_update_slice(
        ck, ks.transpose(0, 1, 3, 2, 4).astype(ck.dtype), (0, 0, 0, 0, 0))
    cv = jax.lax.dynamic_update_slice(
        cv, vs.transpose(0, 1, 3, 2, 4).astype(cv.dtype), (0, 0, 0, 0, 0))
    logits = _head(params, x, cfg)[:, -1]
    return logits, (ck, cv)


def decode_step(params, cfg, caches, pos, tokens_1):
    """One decode step: tokens_1 [B] int32 at position ``pos`` ->
    (logits [B, V], updated caches).  ``caches`` from
    :func:`init_kv_cache`."""
    _refuse(cfg, "decode_step", *_TRAINS_ONLY)
    x = _embed(params, tokens_1, cfg)[:, None, :]

    def body(x, inputs):
        w, ck, cv = inputs
        x, ck, cv = _decode_layer(x, w, cfg, ck, cv, pos)
        return x, (ck, cv)

    x, new_caches = jax.lax.scan(body, x, (params["layers"],) + caches)
    logits = _head(params, x, cfg)[:, 0]
    return logits, new_caches


def generate(params, cfg, prompt, max_new_tokens, temperature=0.0,
             rng=None):
    """Autoregressive generation: batched prefill + KV-cache decode.

    prompt: [B, Tp] int32, Tp >= 1 (seed unconditional generation with
    a BOS token).  Returns [B, Tp + max_new_tokens]; greedy when
    ``temperature`` == 0, else softmax sampling at the given
    temperature.  Positions use RoPE, so sequences may run past
    cfg.max_seq_len (quality, not correctness, degrades).
    """
    _refuse(cfg, "generate", *_TRAINS_ONLY)
    prompt = jnp.asarray(prompt, jnp.int32)
    b, tp = prompt.shape
    if tp == 0:
        raise ValueError(
            "prompt must have at least one token (use a BOS token for "
            "unconditional generation)")
    # Accept numpy-loaded params (e.g. a servable export's npz):
    # indexing a numpy embed table with a traced token id would fail
    # inside the decode scan.
    params = jax.tree_util.tree_map(jnp.asarray, params)
    max_new_tokens = int(max_new_tokens)
    if max_new_tokens == 0:
        return prompt
    total = tp + max_new_tokens
    if rng is None:
        rng = jax.random.PRNGKey(0)
    greedy = not temperature

    def sample(logits, key):
        if greedy:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits / temperature, axis=-1
        ).astype(jnp.int32)

    logits0, caches = prefill(params, cfg, prompt, total)
    rng, sub = jax.random.split(rng)
    first = sample(logits0, sub)
    tokens = jnp.concatenate(
        [prompt, jnp.zeros((b, max_new_tokens), jnp.int32)], axis=1
    )
    tokens = jax.lax.dynamic_update_index_in_dim(
        tokens, first, tp, axis=1)

    def body(carry, t):
        tokens, caches, rng = carry
        tok_t = jax.lax.dynamic_index_in_dim(
            tokens, t, axis=1, keepdims=False)
        logits, caches = decode_step(params, cfg, caches, t, tok_t)
        rng, sub = jax.random.split(rng)
        tokens = jax.lax.dynamic_update_index_in_dim(
            tokens, sample(logits, sub), t + 1, axis=1)
        return (tokens, caches, rng), None

    (tokens, _, _), _ = jax.lax.scan(
        body, (tokens, caches, rng), jnp.arange(tp, total - 1)
    )
    return tokens


def next_token_loss(logits, tokens):
    """Per-example mean next-token cross entropy; tokens: [B, T]."""
    targets = tokens[:, 1:]
    logits = logits[:, :-1]
    per_tok = optax.softmax_cross_entropy_with_integer_labels(
        logits, targets
    )
    return per_tok.mean(axis=-1)


def next_token_loss_chunked(params, hidden, tokens, cfg, chunk=512):
    """Next-token xent from :func:`forward_hidden` output WITHOUT a
    [B, T, V] logits tensor: the ln_f + head matmul + softmax-xent run
    per T-chunk under ``jax.checkpoint`` inside a scan, so peak live
    logits are [B, chunk, V] in both directions (the backward
    recomputes each chunk's logits).  Numerically identical (f32
    accumulation) to ``next_token_loss(_head(hidden))``.  Returns the
    per-example mean, matching :func:`next_token_loss`.
    """
    b, t, _ = hidden.shape
    h = hidden[:, :-1]
    targets = tokens[:, 1:]
    n = t - 1
    pad = (-n) % chunk
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
    valid = jnp.arange(n + pad) < n
    nc = (n + pad) // chunk
    h = h.reshape(b, nc, chunk, h.shape[-1]).transpose(1, 0, 2, 3)
    tg = targets.reshape(b, nc, chunk).transpose(1, 0, 2)
    mk = valid.reshape(nc, chunk)

    @jax.checkpoint
    def chunk_sum(h_c, t_c, m_c):
        logits = _head(params, h_c, cfg)              # [B, chunk, V]
        per_tok = optax.softmax_cross_entropy_with_integer_labels(
            logits, t_c
        )
        return (per_tok * m_c[None, :]).sum(axis=-1)  # [B]

    def body(acc, xs):
        return acc + chunk_sum(*xs), None

    total, _ = jax.lax.scan(
        body, jnp.zeros((b,), jnp.float32), (h, tg, mk)
    )
    return total / n


# -- zoo contract -------------------------------------------------------------


def _flag(value):
    """CLI model_params arrive as strings: "false" is not falsy.  A word
    that is neither true nor false goes on as it is, for the
    configuration's check to take (``qk_norm=head``) or refuse."""
    if isinstance(value, str):
        word = value.strip().lower()
        return {"true": True, "false": False}.get(word, word)
    return bool(value)


def _decayed(params):
    """AdamW's weight-decay mask: everything but the routers'
    ``expert_bias``, the scales of the norms on a sublayer's output
    and, of a gated-delta layer, its decay rates, its step bias, its
    output norm's scale, its convolution's taps and (kda) its output
    gate's bias, of a Mamba-2 layer its decay rates, step bias, skip
    ``ssm_D``, gated norm's scale and convolution's bias, a looped
    stack's exit gate (``ut_gate_w``, ``ut_gate_b``), and a
    hyper-connection's ``alpha`` and bias (decayed,
    its maps would leave the identity they start from)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: getattr(path[-1], "key", None) not in (
            "expert_bias", "ln1_post", "ln2_post", "A_log", "dt_bias",
            "o_norm", "delta_conv", "b_g", "ssm_D", "ssm_norm",
            "ut_gate_w", "ut_gate_b")
        and not str(getattr(path[-1], "key", "")).endswith(
            ("_alpha", "_bias")), params)


def model_spec(seq_len=512, learning_rate=3e-4, warmup_steps=0, mesh=None,
               pipeline_microbatches=0, xent_chunk=0, **model_params):
    """Zoo entry for the flagship LM.

    ``model_params`` are :class:`TransformerConfig`'s fields, under
    their names and with their defaults: the class and its comments are
    the list of the model's options.  Each is coerced by its field's
    declared type (they arrive as strings from the command line) and the
    configuration is checked where it is built; a keyword that is no
    field is a ``TypeError``.  ``seq_len`` is ``max_seq_len``.

    ``xent_chunk`` > 0 computes the loss via
    :func:`next_token_loss_chunked` — no [B, T, V] logits tensor, the
    memory-lean path for large vocab x seq (numerically identical,
    tested).

    Training an MoE through the scanned stack, the spec also hands the
    trainer its step statistics (``step_stats_fn``): each layer's
    assignments per expert and padded rows, ``moe_load`` [L, X + 1];
    with a share of the experts, the held experts' alone,
    ``moe_moved`` [L], the rows each layer's dispatch moved (its bound
    times the blocks that ran), ``moe_spilled`` [L], the shards on
    which it ran more than one, and where the row kernel moves the rows
    ``moe_sum_terms`` and ``moe_sum_slots`` [L], the slots its sums
    walked and the tokens x K a call they would have; under a router
    limited to groups
    ``moe_group_hit`` [L], the share of the tokens whose chosen groups
    reach an expert held here; with a floored kda gate
    ``kda_gate_excess``, how far under the floor the step's lowest log
    decay lies (0 by construction); with a Mamba-2 layer
    ``ssm_chunk_keep``, the mean share of a state that outlives a chunk
    of 128 tokens.

    ``warmup_steps`` > 0 raises AdamW's rate from 0 to ``learning_rate``
    linearly over that many steps (0: constant, as ever).  Adam's steps
    do not scale with the gradient, so at the full rate a 2048-wide
    router's logits move by up to ~0.6 a step from the first step on;
    under a share of the experts, whose router gradient holds the held
    experts' terms alone, that drove every held expert out of the top K
    within 16-24 steps on the chip (PERF.md section 6, PR 31).

    A ``sigmoid_bias`` router's ``expert_bias`` is state and no weight:
    it lives in the parameter tree (so checkpoints and the reference
    carry it), no gradient reaches it, and AdamW's weight decay is
    masked from it here, so the optimizer leaves it as it is.  The
    decay is masked from ``post_norms``' two scales as well: they set
    how much of a sublayer's result joins the stream; and from a
    gated-delta layer's ``A_log``, ``dt_bias``, ``o_norm``, taps and
    ``b_g``.
    """
    types = {field.name: field.type
             for field in dataclasses.fields(TransformerConfig)}

    def coerced(key, value):
        # ``bool | str`` (a flag or a word) as ``bool``; a keyword that
        # is no field is left to the constructor's TypeError
        kind = types.get(key)
        return kind(value) if kind in (int, float, str) else _flag(value)

    cfg = TransformerConfig(max_seq_len=int(seq_len), **{
        key: coerced(key, value) for key, value in model_params.items()})
    if mesh is not None:
        param_specs(cfg)    # raises, naming what a mesh cannot run yet
    pipelined = (
        pipeline_microbatches > 0
        and mesh is not None
        and mesh.shape.get("pp", 1) > 1
        and mesh.shape.get("sp", 1) == 1
    )
    if pipeline_microbatches > 0 and not pipelined:
        # No mesh, pp=1, or sp>1 (ring attention needs the sequence
        # axis): say so instead of silently ignoring the knob.
        import warnings

        warnings.warn(
            "pipeline_microbatches ignored: pipelining requires a mesh "
            "with pp>1 and sp=1; using the scanned forward",
            stacklevel=2,
        )

    def init_fn(rng):
        params = init_params(rng, cfg)
        if mesh is not None:
            params = shard_params(params, mesh, cfg)
        return params

    moe = not all(kind.dense for kind in cfg.kinds)   # a layer has experts
    wide = bool(cfg.hyper_streams or cfg.mtp_modules
                or cfg.delta_gate_floor or "m" in cfg.layer_pattern
                or cfg.ut_steps > 1)
    if cfg.ut_steps > 1 and (xent_chunk or pipeline_microbatches):
        raise ValueError(
            "ut_steps=%d: a looped stack's loss is ops/head_loss.py's a "
            "token, weighed by the exit distribution; xent_chunk (%d) and "
            "a pipelined forward (pipeline_microbatches=%d) have none"
            % (cfg.ut_steps, xent_chunk, pipeline_microbatches))
    if cfg.mtp_modules and (xent_chunk or pipelined):
        raise ValueError(
            "mtp_modules=%d: the modules' loss is ops/head_loss.py's at a "
            "shift of its own; xent_chunk and a pipelined forward have "
            "none" % cfg.mtp_modules)

    def apply_fn(params, tokens, train):
        """Logits; training, a dict for ``loss_fn``, in which the head
        runs: ``hidden`` (before ``ln_f``), ``params``, ``aux`` and,
        from an MoE's scanned stack, ``moe_load``."""
        if not train:
            if pipelined:
                return forward_pipelined(
                    params, tokens, cfg, mesh, pipeline_microbatches,
                    remat=cfg.remat)
            return forward(params, tokens, cfg, mesh=mesh)
        out = {"params": params}
        if wide:
            # a wide stream's Sinkhorn error, the modules' hidden states
            # and a floored gate's excess leave the stack beside the
            # model's
            out.update(_forward_stack(params, tokens, cfg, mesh, moe,
                                      with_mtp=True))
            out["aux"] = out["aux"].mean()
            out["moe_load"] = out.pop("load")
        elif pipelined:
            out["hidden"], out["aux"] = forward_pipelined(
                params, tokens, cfg, mesh, pipeline_microbatches,
                remat=cfg.remat, return_aux=True,
                return_hidden=True)
        elif moe:
            out["hidden"], out["aux"], out["moe_load"] = forward_hidden(
                params, tokens, cfg, mesh=mesh, return_load=True)
        else:
            out["hidden"], out["aux"] = forward_hidden(
                params, tokens, cfg, mesh=mesh)
        return out

    def loss_fn(outputs, tokens):
        if not isinstance(outputs, dict):
            return next_token_loss(outputs, tokens)
        if cfg.ut_steps > 1:
            return looped_loss(outputs, tokens, cfg)
        if xent_chunk:
            loss = next_token_loss_chunked(
                outputs["params"], outputs["hidden"], tokens, cfg,
                chunk=xent_chunk)
        else:
            loss = head_loss(
                outputs["params"], outputs["hidden"], tokens, cfg)
        if outputs.get("mtp_hidden"):
            # left in ``outputs`` for ``step_stats``, before its weight
            outputs["mtp_loss"] = sum(
                head_loss(outputs["params"], hidden, tokens, cfg, shift=k + 2)
                for k, hidden in enumerate(outputs["mtp_hidden"])
            ) / cfg.mtp_modules
            loss = loss + cfg.mtp_weight * outputs["mtp_loss"]
        if moe:
            loss = loss + cfg.moe_aux_weight * outputs["aux"]
        return loss

    def step_stats(outputs):
        stats = dict(outputs.get("ut", ()))
        if outputs.get("hc_err") is not None:
            stats["hc_err"] = outputs["hc_err"]
        if "mtp_loss" in outputs:
            stats["mtp_loss"] = outputs["mtp_loss"].mean()
        if outputs.get("gate_excess") is not None:
            stats["kda_gate_excess"] = outputs["gate_excess"]
        if outputs.get("chunk_keep") is not None:
            stats["ssm_chunk_keep"] = outputs["chunk_keep"]
        if not moe:
            return stats
        load = outputs["moe_load"]
        if not cfg.moe_experts_held:
            return dict(stats, moe_load=load)
        # a share's dispatch counts the rows it moved and its spills,
        # and under a group limit its layer the tokens that could reach it
        if cfg.moe_groups:
            stats["moe_group_hit"], load = load[:, -1], load[:, :-1]
        if load.shape[1] > cfg.experts_held[1] + 3:
            # the row kernel moved the rows: what its sums walked
            stats["moe_sum_terms"], stats["moe_sum_slots"], load = (
                load[:, -2], load[:, -1], load[:, :-2])
        return dict(stats, moe_load=load[:, :-2], moe_moved=load[:, -2],
                    moe_spilled=load[:, -1])

    def feed(records):
        toks = np.stack(
            [np.asarray(r[0], dtype=np.int32) for r in records]
        )
        # causal LM: inputs are the labels (shifted inside the loss)
        return toks, toks

    spec = ModelSpec(
        name="transformer_lm",
        init_fn=init_fn,
        apply_fn=apply_fn,
        loss_fn=loss_fn,
        optimizer=optax.adamw(
            (optax.linear_schedule(0.0, learning_rate, int(warmup_steps))
             if warmup_steps else learning_rate), weight_decay=0.01,
            mask=(_decayed if cfg.moe_router == "sigmoid_bias"
                  or cfg.post_norms or set("dm") & set(cfg.layer_pattern)
                  or cfg.hyper_streams or cfg.ut_steps > 1 else None)),
        feed=feed,
        eval_metrics_fn=lambda: {
            "nll": metrics.Mean(lambda outputs, labels: outputs)
        },
        step_stats_fn=(None if pipelined or not (moe or wide)
                       else step_stats),
    )
    spec.config = cfg
    return spec


def export_generate(export_dir, params, cfg, max_new_tokens,
                    prompt_len, model_name="lm", temperature=0.0,
                    **export_kwargs):
    """Export GENERATION itself as a servable: the whole batched
    prefill + KV-cache decode loop compiles into the StableHLO
    artifact, so a plain servable host (``elasticdl-tpu serve``, or
    anything that deserializes StableHLO) serves token generation over
    ``:predict`` — prompt ids in, prompt+generated ids out — with no
    model code, no generation loop, no LoRA code (pass merged params)
    on the serving side.

    Static shapes rule the export: ``prompt_len`` and
    ``max_new_tokens`` are fixed per export (export several prompt
    lengths side by side if clients vary); the BATCH stays polymorphic
    like every servable.

    ``temperature`` > 0 exports a SAMPLING servable: the input becomes
    the dict {"prompt": [B, Tp] int32, "seed": [] int32} — the
    per-request seed folds into the PRNG key inside the artifact, so
    repeated requests with different seeds draw different
    continuations and equal seeds reproduce exactly.
    """
    from elasticdl_tpu.serving.export import export_servable

    _refuse(cfg, "export_generate", *_TRAINS_ONLY)
    if prompt_len + max_new_tokens > cfg.max_seq_len:
        raise ValueError(
            "prompt_len %d + max_new_tokens %d exceeds max_seq_len %d"
            % (prompt_len, max_new_tokens, cfg.max_seq_len))
    if temperature < 0:
        # A typo'd sign would silently ship a GREEDY artifact here
        # while generate() itself would sample an inverted
        # distribution — reject instead of exporting either surprise.
        raise ValueError("temperature must be >= 0, got %r"
                         % (temperature,))
    if temperature > 0:
        def serve_fn(p, inputs):
            rng = jax.random.PRNGKey(inputs["seed"].astype(jnp.uint32))
            return generate(
                p, cfg, inputs["prompt"],
                max_new_tokens=max_new_tokens,
                temperature=temperature, rng=rng)

        example = {"prompt": np.zeros((1, prompt_len), np.int32),
                   "seed": np.int32(0)}
    else:
        serve_fn = lambda p, prompt: generate(
            p, cfg, prompt, max_new_tokens=max_new_tokens)
        example = np.zeros((1, prompt_len), np.int32)
    return export_servable(
        export_dir,
        serve_fn,
        params,
        example,
        model_name=model_name,
        **export_kwargs,
    )
