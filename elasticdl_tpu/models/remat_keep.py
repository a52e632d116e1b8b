"""What ``remat=True`` keeps: the residuals of the scanned layer stack
that fit the memory a device has left, chosen from shapes.

``jax.checkpoint`` with no policy saves nothing of a layer, and the
backward runs every layer's forward a second time.  The ops name the
values their backward reads where they make them (``checkpoint_name``);
``choose`` walks ``table``, an order of what a byte buys, and takes each
entry whose bytes (one shard's rows, all the layers of the kind that
makes it: the scan stacks what a layer keeps) still fit the budget; ``models/transformer.forward_hidden``
turns the names into ``save_only_these_names``.  The kept values are the
ones the second forward would have produced, so the gradients are the
same bits' worth of arithmetic, done once.

An entry is taken when the peak predicted with it kept stays under the
caller's ``batch_shard.DeviceRoom`` less a reserve of ``RESERVE`` of the
limit: ``step_bytes`` is what this model's step needs beside the
caller's state *with the entries chosen so far kept*, since a layer's
backward does not make again the products it reads from the kept
stack.  No room stated (the CPU, a model-parallel mesh, the pipelined
forward): nothing kept, the program ``jax.checkpoint(layer)`` always
gave.

The step's need is the larger of two places: the head with the stack's
gradients standing, and a layer's backward with its second forward.
What a layer holds there is counted from shapes by its kind: a dense
layer's gate, up, product and a cotangent; an expert layer that XLA
unrolls by the inventory of its dispatch's pullback
(``dispatch_phases``) beside the operator's second forward
(``_expert_layer``); and of an unrolled stack's gradients one layer's
worth stands there, not the stack's (``grads_standing``).  A group
scanned over several turns keeps its whole stacked gradient.  The sum
is held to the chips' peaks cell by cell (the benchmark's
``remat.estimate_over_gb``; tests/test_remat_keep.py) and to the TPU
compiler's count of whole steps for a described v5e.
"""

import contextlib
import contextvars
import functools

import jax
import jax.numpy as jnp

from elasticdl_tpu.ops import (batch_shard, flash_attention, gated_delta,
                               hyper_mix, moe_dispatch, short_conv, ssd)

# Named in models/transformer.py: q, k, v as the attention takes them
# (after RoPE and the QK norm), the stream after the operator's
# residual add (attention or short convolution), the router's results,
# a dense FFN's two products.  A short convolution's are the op's:
# its input (the projection's output) and its result.
KEEP_Q, KEEP_K, KEEP_V = "attn_q", "attn_k", "attn_v"
# The projection behind a gate on attention's output (``cfg.attn_gate``),
# [rows, heads * head_dim] before its sigmoid; a gate a head's, [rows,
# heads].
KEEP_ATTN_GATE = "attn_gate"
# Latent attention's: the down-projection's result (the latent before
# its norm, and the one RoPE key before RoPE), and the k_nope and v a
# matmul makes from it for every head.
KEEP_LATENT, KEEP_KV = "attn_latent", "attn_kv"
# The query latent before its norm, [rows, q_latent_rank]: kept with the
# latent, as cheap and as dear.
KEEP_Q_LATENT = "attn_q_latent"
KEEP_STREAM = "attn_stream"
KEEP_ROUTE = "moe_route"
KEEP_GATE, KEEP_UP = "ffn_gate", "ffn_up"
KEEP_SHARED_GATE, KEEP_SHARED_UP = "shared_gate", "shared_up"
# A gated-delta layer's (``transformer._delta_mix``): the one projection
# of q, k and v before and after its convolution and SiLU (the first is
# what the convolution's backward reads, the second what the scan's
# does, behind an L2 norm), the log decays and write strengths, [rows,
# heads] float32 each, and the output gate's projection.  The scan's
# own three are ``gated_delta.KEEP_OUT``, ``KEEP_STATES`` and
# ``KEEP_INVERSE``.
KEEP_DELTA_IN, KEEP_DELTA_QKV = "delta_in", "delta_qkv"
KEEP_DELTA_DECAY, KEEP_DELTA_GATE = "delta_decay", "delta_gate"
# A kda layer's (``delta_kind``): the two [rows, delta_rank] products of
# the stream that its decay's and its gate's low-rank pairs start from
# (none under full projections, ``delta_rank`` 0); its ``delta_decay``
# is [rows, heads * key_dim] float32, a channel each.
KEEP_DELTA_RANK = "delta_rank"
# A Mamba-2 layer's (``transformer._ssm_mix``): the gate z's projection,
# the projection of x | B | C before and after its convolution and SiLU,
# the log decays and steps, [rows, heads] float32 each.  The scan's own
# two are ``ssd.KEEP_OUT`` and ``ssd.KEEP_STATES``.
KEEP_SSM_GATE, KEEP_SSM_IN, KEEP_SSM_XBC = "ssm_gate", "ssm_in", "ssm_xbc"
KEEP_SSM_DECAY = "ssm_decay"
# A looped stack's (``cfg.ut_steps`` > 1; ``transformer.looped_loss``):
# the logits of the heads on turns 1 .. R - 1, [rows, vocab] in the
# compute dtype each.  No ``checkpoint_name`` carries it: the name on
# the list is the loss's to read, which computes those logits again in
# its backward where it is absent (the last turn's are read at once).
KEEP_LOGITS = "head_logits"

# The share of the device's limit nothing is planned into: the
# allocator's fragmentation, the batches in flight, whatever
# ``step_bytes`` does not see.
RESERVE = 0.05

# What a kept GB of a kda layer's scan results (output, chunk-start
# states, inverses) saves of its second forward, ms: ``kda_fwd``'s 3.8
# ms a call over their 0.185 GB at the ``solar-open2-250b`` cell's
# shape (my chip run, PR 49; PERF.md section 5).  That cell keeps them
# since PR 60 counts its dispatch's backward from shapes.
KDA_SCAN = 21

# The ``flash`` entry's names: the kernel's output and row statistics.
ATTN_NAMES = (flash_attention.KEEP_OUT, flash_attention.KEEP_LSE)

# The entries of ``table`` that are products of a layer's own second
# forward and stand in ``step_bytes``'s one-layer term: a dense FFN's
# gate and up, an expert layer's gate, up and down products.
DENSE_PRODUCTS = ("ffn_gate", "ffn_up")
SHARED_PRODUCTS = (KEEP_SHARED_GATE, KEEP_SHARED_UP)
EXPERT_PRODUCTS = ("moe_out", "moe_gate", "moe_up")
# The entries of ``table`` that a layer's operator makes in its second
# forward (``Kind.op``; latent attention's flash, q and kv are
# ``_latent_layer``'s), and the two every expert layer makes beside
# them: what of them is not kept stands until the operator's backward
# has read it.
OPERATOR_ENTRIES = {
    "a": ("flash", "qkv", "latent", "gate"),
    "c": ("conv_in", "conv_out"),
    "d": ("delta_decay", "delta", "delta_gate", "delta_in", "delta_qkv",
          "delta_rank"),
    "m": ("ssm_decay", "ssm", "ssm_gate", "ssm_in", "ssm_xbc"),
    "e": (),
}
LAYER_ENTRIES = ("route", "hc_read")


def _entries(cfg, rows):
    """[(label, names, bytes a layer that makes the entry, how many
    layers do)]: ``table`` in full."""
    size = jnp.dtype(cfg.dtype).itemsize
    e, h, g, d = cfg.dim, cfg.num_heads, cfg.kv_heads, cfg.head_dim
    # a multi-token-prediction module's block is one layer more; a
    # looped stack saves what a layer keeps once a turn
    kinds = cfg.kinds * cfg.ut_steps + (cfg.mtp_kind,) * cfg.mtp_modules
    attention = sum(kind.op == "a" for kind in kinds)
    conv = sum(kind.op == "c" for kind in kinds)
    delta = sum(kind.op == "d" for kind in kinds)
    d_k, d_v = cfg.delta_key_dim, cfg.delta_value_dim
    ssm = sum(kind.op == "m" for kind in kinds)
    # a layer without an FFN (``Kind.ffn``) counts as dense and has none
    dense = sum(kind.dense and kind.ffn for kind in kinds)
    experts = sum(not kind.dense for kind in kinds)
    # an MLP of two matrices has no gate product to keep
    gateless = not cfg.gated_mlp
    latent = cfg.latent
    if latent:
        rank, d_nope, d_rope, d = latent    # d: a value head's, out's
    entries = [
        ("flash", ATTN_NAMES, rows * h * (d * size + 4), attention),
    ]
    if cfg.ut_steps > 1:
        # a head's forward again, rows x dim x vocab multiply-adds for
        # rows x vocab values: what a byte of q buys (~12 ms a GB), but
        # the bytes are gone where a layer's backward is the peak, so
        # they are tried while the head's place has the room: 28 ms a
        # step for 0.49 GB of peak at six layers (PERF.md section 6, PR
        # 66), behind the flash forward's 30 ms for 0.82
        entries.append(("logits", (KEEP_LOGITS,),
                        rows * cfg.vocab_size * size, cfg.ut_steps - 1))
    x = cfg.moe_experts
    k = min(cfg.moe_top_k, x)
    # probs f32 [rows, X]; gates f32, experts, order and (where all
    # experts are held) inverse int32 [rows, k]; sizes [X]
    sorts = 3 if cfg.moe_experts_held else 4
    entries.append(("route", (KEEP_ROUTE, moe_dispatch.KEEP_SORT),
                    4 * (rows * (x + sorts * k) + x), experts))
    if latent:
        # the latent first: [rows, rank + d_rope], a fourteenth of the
        # k_nope and v that one matmul makes from it again
        entries += [
            ("latent",
             (KEEP_LATENT,) + (KEEP_Q_LATENT,) * bool(cfg.q_latent_rank),
             rows * (rank + d_rope + cfg.q_latent_rank) * size, attention),
            # as the kernels take it: the RoPE part a plane of its own,
            # its minor dimension tiled to the 128 lanes in HBM
            ("q", (KEEP_Q,), rows * h * (d_nope + _lanes(d_rope)) * size,
             attention),
        ]
    else:
        entries.append(("qkv", (KEEP_Q, KEEP_K, KEEP_V),
                        rows * (h + 2 * g) * d * size, attention))
    if cfg.attn_gate:
        # a product of the hidden size as wide as q: a byte's worth the
        # same, ~13; a gate a head (latent attention's one form): the
        # whole stream read for a 128-lane tile of ``heads`` values a row
        entries.append(("gate", (KEEP_ATTN_GATE,), rows * size * (
            _lanes(h) if cfg.attn_gate == "head" else h * d), attention))
    # the stream BETWEEN the sublayers: a layer of one has none
    entries.append(("stream", (KEEP_STREAM,),
                    rows * cfg.stream_width * size,
                    sum(kind.ffn and kind.op != "e" for kind in kinds)))
    f, dense_f = cfg.mlp_dim, cfg.dense_ffn_dim if x else cfg.mlp_dim
    # What a kept GB is worth, ms (``table``).  The dispatch's buffers
    # have ``row_bound`` rows, of which a balanced router fills
    # ``live``: a byte of them buys that share of what a byte of full
    # rows buys.  The down product is the gate's and the up product's
    # matmul the other way round, [R, f] x [f, e]: the same operations
    # for e / f times the bytes, so its byte buys f / e of theirs.
    # Where every expert is held the kept array is that product moved
    # back to the tokens' order, a gather as the sorted rows' is, ~8
    # more (~14 at the widths of the trace it was read from, f = e / 2);
    # under a share it is the product alone (``moe_dispatch._block``:
    # the rows' sum follows it and is not kept).
    bound, live = _routed_rows(cfg, rows)
    share = bool(x) and cfg.experts_held[1] != x
    routed = [
        ((12 * f / e + 8 * (not share)) * live, "moe_out",
         (moe_dispatch.KEEP_OUT,), bound * e * size, experts),
        (12 * live, "moe_gate", (moe_dispatch.KEEP_GATE,),
         bound * f * size, experts * (not gateless)),
        (12 * live, "moe_up", (moe_dispatch.KEEP_UP,), bound * f * size,
         experts),
        (8 * live, "moe_rows", (moe_dispatch.KEEP_ROWS,), bound * e * size,
         experts),
    ]
    rest = [
        (12, "ffn_gate", (KEEP_GATE,), rows * dense_f * size,
         dense * (not gateless)),
        (12, "ffn_up", (KEEP_UP,), rows * dense_f * size, dense),
        (11, "conv_in", (short_conv.KEEP_IN,), rows * 3 * e * size, conv),
        (5, "conv_out", (short_conv.KEEP_OUT,), rows * e * size, conv),
    ]
    if delta:
        # the decays are [rows, heads] and save two products that read
        # the whole stream; the gate's projection is made as q is; the
        # scan's output with its chunk-start states (float32, a state's
        # rows 128-lane tiles in HBM) and its chunks' inverses (the
        # compute dtype, two side by side) saves ``gdn_fwd``, 5.4 ms of
        # a layer's second forward for 0.50 GB (PERF.md section 5, PR
        # 48; 6.7 for 0.47 until then: 14); the projection of q, k, v
        # as a convolution's input is; the convolved projection a pass
        # bound by memory
        states = rows // gated_delta.CHUNK * h * d_k * _lanes(d_v) * 4
        # a kda layer (``cfg.delta_kind``): the decays a channel, [rows,
        # heads * key_dim] float32, and the gate's projection are each
        # made from a [rows, rank] product by a contraction over the
        # rank: what a byte of them buys is what a byte of q buys times
        # their share of q's operations; the two [rows, rank] products
        # themselves read the whole stream for 2 * rank values a row
        # (the dearest byte of the layer).  Under full projections
        # (rank 0) both are products of the hidden size, as q is
        kda, rank = cfg.delta_kind == "kda", cfg.delta_rank
        cheap = rank * (1 / e + 1 / (h * d_v)) if kda and rank else 1.0
        rest += [
            (13 * cheap * size / 4 if kda else 100, "delta_decay",
             (KEEP_DELTA_DECAY,), rows * h * 4 * (d_k + 1 if kda else 2),
             delta),
            (KDA_SCAN if kda else 11, "delta",
             (gated_delta.KEEP_OUT, gated_delta.KEEP_STATES,
              gated_delta.KEEP_INVERSE),
             rows * h * d_v * size + states
             + gated_delta.inverse_bytes(rows, h, size), delta),
            (13 * cheap, "delta_gate", (KEEP_DELTA_GATE,),
             rows * h * d_v * size, delta),
            (11, "delta_in", (KEEP_DELTA_IN,),
             rows * h * (2 * d_k + d_v) * size, delta),
            (5, "delta_qkv", (KEEP_DELTA_QKV,),
             rows * h * (2 * d_k + d_v) * size, delta),
        ]
        if kda and rank:
            rest.append((13 * e / rank / 2, "delta_rank", (KEEP_DELTA_RANK,),
                         rows * 2 * rank * size, delta))
    if ssm:
        # as a gated-delta layer's: the decays and steps are [rows,
        # heads] and save a product that reads the whole stream; the
        # gate's projection is made as q is; the scan's output with its
        # chunk-start states saves ``ssd_fwd``; the projection of x | B |
        # C as a convolution's input is; the convolved projection a pass
        # bound by memory
        inner, xbc = _ssm_widths(cfg)
        rest += [
            (100, "ssm_decay", (KEEP_SSM_DECAY,),
             rows * cfg.ssm_heads * 4 * 2, ssm),
            (11, "ssm", (ssd.KEEP_OUT, ssd.KEEP_STATES),
             rows * inner * size + ssd.states_bytes(
                 rows, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), ssm),
            (13, "ssm_gate", (KEEP_SSM_GATE,), rows * inner * size, ssm),
            (11, "ssm_in", (KEEP_SSM_IN,), rows * xbc * size, ssm),
            (5, "ssm_xbc", (KEEP_SSM_XBC,), rows * xbc * size, ssm),
        ]
    if cfg.hyper_streams:
        # a sublayer's mixed input and its logits (a 128-lane float32
        # tile a row), two sublayers a layer: kept, the second forward
        # makes no ``hc_pre_fwd`` call, a read of the n-wide stream for
        # a one-wide result: n passes' worth of a pass bound by memory
        rest.append((5, "hc_read", (hyper_mix.KEEP_U, hyper_mix.KEEP_Z),
                     2 * rows * (e * size + hyper_mix.LANES * 4),
                     len(kinds)))
    if cfg.shared_dim:
        rest += [(12, name, (name,), rows * cfg.shared_dim * size, experts)
                 for name in SHARED_PRODUCTS[gateless:]]
    if latent:
        # a contraction over the rank, not the hidden size: rank / dim
        # of what a byte of q buys
        rest.append((13 * rank / e, "kv", (KEEP_KV,),
                     rows * h * (d_nope + d) * size, attention))
    # stable: at equal worth the dispatch's come first
    entries += [entry[1:] for entry in sorted(
        routed + rest, key=lambda entry: -entry[0])]
    return [entry for entry in entries if entry[3]]


def _ssm_widths(cfg):
    """(values a token of a Mamba-2 layer's z, x and y; of its x | B |
    C side by side)."""
    inner = cfg.ssm_heads * cfg.ssm_head_dim
    return inner, inner + 2 * cfg.ssm_groups * cfg.ssm_state


def _lanes(width):
    """A minor dimension as HBM tiles it: whole 128-lane tiles."""
    return -(-width // 128) * 128


def _latent_layer(cfg, rows, kept):
    """(bytes of a latent-attention layer's second forward that stay
    until its attention's backward has read them, with the entries
    ``kept`` read from the stack instead; bytes that backward makes):
    what ``ops/flash_attention.latent_attention`` takes and gives a
    head, [rows, heads, width] each (a RoPE part 128 lanes wide in
    HBM), and as much again for the two projections' results and
    cotangents.  32 heads x 16,384 rows make each of them 0.13 GB: 2.8
    GB a layer with nothing kept, where attention with wk and wv of the
    benchmark's other cells stays under their FFN's term.

    The ``flat_*`` widths were written for token-major copies of the
    projections' results and cotangents.  The program makes none any
    more (``models/transformer._project_latent`` writes the kernels'
    planes itself), and the chip's peak stands where it stood with
    them counted: 16.003 GB against 16.005 with the same eleven names,
    and over six kept lists of 2.9-4.4 GB this count reads +0.03 ..
    +0.39 GB over the chip where the count without them reads 0.6-1.0
    GB under (PERF.md section 6, PR 38; tests/test_remat_keep.py holds
    the six).  So they stay, as what the estimate needs to describe the
    chip and not as arrays anyone can name: where in the step that peak
    stands is an open question (PERF.md section 7)."""
    size = jnp.dtype(cfg.dtype).itemsize
    _, d_nope, d_rope, d_v = cfg.latent
    unit = rows * cfg.num_heads * size
    q, kv = d_nope + _lanes(d_rope), d_nope + d_v
    flat_q, flat_kv = d_nope + d_rope, d_nope + d_v
    made = {"flash": d_v, "q": q + flat_q, "kv": kv + flat_kv}
    # the output a second time: never kept
    residuals = unit * (d_v + sum(width for label, width in made.items()
                                  if label not in kept))
    # dO twice, dq, dk_nope and dv, each head's float32 part of the
    # RoPE key's gradient, the ``flat_*`` widths
    backward = unit * (2 * d_v + q + kv + flat_q + flat_kv) + (
        rows * cfg.num_heads * _lanes(d_rope) * 4)
    return residuals, backward


def _routed_rows(cfg, rows):
    """(rows of the dispatch's buffers for ``rows`` tokens,
    ``ops/moe_dispatch.row_bound``; the share of them that a balanced
    router makes a held expert's)."""
    x = cfg.moe_experts
    if not x:
        return 0, 1.0
    assigned = rows * min(cfg.moe_top_k, x)
    held = cfg.experts_held[1]
    bound = moe_dispatch.row_bound(assigned, held, x)
    return bound, assigned * held / x / bound


def table(cfg, rows):
    """[(label, names, bytes a layer that makes the entry)] for ``rows``
    tokens a device, in
    the order of what a kept byte saves of the second forward (ms a GB,
    from the traces of PERF.md section 5: the flash forward ~18; the
    down product, a grouped matmul and a gather, ~14 (under a share
    the matmul alone, f / e of the up product's by its shapes); q, k, v
    and the stream ~13; the FFN's products ~12, and by their shapes a
    short convolution's input ~11; the sorted rows, one gather, ~8; the
    convolution's result, a pass bound by memory, ~5; the flash
    forward's, the router's and attention's first as they stand (latent
    attention's: the latent with the RoPE key, [rows, rank + D_rope],
    then q; the k_nope and v that one matmul over the rank makes from
    the latent again are worth rank / dim of q's, ~3, and stand among
    the others; behind q, k, v the projection of a gate on attention's
    output, as wide as q and made as q is), the others by that worth, a
    share's dispatch buffers at the part of their rows a balanced
    router fills, a half).  Elementwise
    work (norms, RoPE's rotation, the
    activation, the weighted combine) is not here: it is cheap and its
    inputs are what is kept.  An entry is there if a layer of the model
    makes it: attention layers the flash kernel's and q, k, v, layers
    with experts the router's and the dispatch's, dense layers the
    FFN's, short-convolution layers the op's."""
    return [entry[:3] for entry in _entries(cfg, rows)]


def _nbytes(tree):
    """Bytes of a tree's leaves as they are stored."""
    return sum(a.size * jnp.dtype(a.dtype).itemsize
               for a in jax.tree_util.tree_leaves(tree))


def _stack(params):
    """The layer stack's weights, ``params["layers"]``, with each
    multi-token-prediction module among the layers outside any loop."""
    layers, mtp = params["layers"], params.get("mtp")
    if not mtp:
        return layers
    if "period" not in layers:
        layers = {"lead": {}, "period": {"0": layers}, "tail": {}}
    return dict(layers, tail={
        **layers["tail"], **{"mtp" + k: w for k, w in mtp.items()}})


def _unrolled(layers):
    """(the groups of the stack that a scan runs over several turns,
    stacked; the layers XLA unrolls, one tree each): a loop of one turn
    it unrolls, and a leading or trailing layer is outside any loop
    (``transformer.init_params``: a plan's ``lead`` / ``period`` /
    ``tail``, or one stacked tree)."""
    if "period" in layers:
        period = list(layers["period"].values())
        singles = list(layers["lead"].values()) + list(
            layers["tail"].values())
    else:
        period, singles = [layers], []
    turns = jax.tree_util.tree_leaves(period)[0].shape[0]
    return (period, singles) if turns > 1 else ([], singles + period)


def _weight_copies(layers, copy):
    """Bytes of the stack's compute-dtype weight copies that stand at
    once (``copy(tree)``: those of a tree's leaves).  A layer casts its
    weights inside its checkpoint, so no copy is a saved residual; XLA
    hoists the cast of a scan's stacked weights out of its loop, all
    turns' at once, but a loop of one turn it unrolls, and a layer
    outside a loop is cast where it is read: there the copies are the
    running layer's and the next one's, which XLA fetches ahead (the
    TPU compiler's buffer assignment of ``lfm2-24b-a2b``'s step: 0.3-0.5
    GB of the five layers' 0.9 at the peak)."""
    scanned, unrolled = _unrolled(layers)
    return copy(scanned) + sum(sorted(map(copy, unrolled))[-2:])


def _kinds_apart(cfg, layers):
    """``_unrolled`` for the layers' kinds (``cfg.kinds``, and
    ``cfg.mtp_kind`` for each module ``_stack`` put behind the tail):
    (the kinds of the groups scanned over several turns, the kinds of
    the layers XLA unrolls)."""
    kinds = cfg.kinds
    if "period" not in layers:
        lead, period, tail = (), kinds[:1], ()
    else:
        lead = kinds[:len(layers["lead"])]
        period = kinds[len(lead):len(lead) + len(layers["period"])]
        modules = sum(name.startswith("mtp") for name in layers["tail"])
        tail = kinds[len(kinds) - len(layers["tail"]) + modules:] + (
            cfg.mtp_kind,) * modules
    scanned, _ = _unrolled(layers)
    return (period, lead + tail) if scanned else ((), lead + tail + period)


def _unrolled_experts(cfg, layers):
    """The kinds of the expert layers that XLA unrolls: the layers whose
    backward ``_expert_layer`` counts from shapes."""
    return {kind for kind in _kinds_apart(cfg, layers)[1] if not kind.dense}


def _decay_channels(cfg, rows, kept_decays):
    """Bytes of a kda layer's decays a channel in its backward: the log
    decays, their cumulative sums and both's cotangents, [rows, heads *
    key_dim] float32 each (a scalar decay's are [rows, heads]:
    nothing), less the plane that ``kept_decays`` bytes of kept
    ``delta_decay`` take out."""
    channels = rows * cfg.num_heads * cfg.delta_key_dim * 4
    return 4 * channels - min(kept_decays, channels)


def dispatch_phases(cfg, rows, kept=()):
    """The inventory of an unrolled expert layer's dispatch
    (``ops/moe_dispatch.py``) while the layer is back-propagated, from
    shapes: ({phase of a block's pullback: bytes of the arrays that
    stand in it}, bytes that stand through all of them), the arrays by
    the names the op gives them, each in the dtype it makes it in, at
    ``row_bound``'s R rows for n tokens of width e over experts f wide,
    h of them held:

     - the second forward's results that the pullback reads: the
       sorted rows ``xs`` [R, e], ``gate`` and ``up`` [R, f], the down
       product ``ys`` [R, e] (the combine's pullback weighs a row's
       gate by it); ``act`` [R, f] is made again where it is read;
     - the cotangents: ``g`` of the float32 [n, e] sum, ``d_ys`` and
       ``d_xs`` [R, e] (the latter twice: a product each of ``d_gate``
       and ``d_up``, then their sum), ``d_act``, ``d_gate``, ``d_up``
       [R, f], a block's ``dx`` [n, e];
     - the [R, 1, 1, words] copies ``ops/row_moves.py`` packs a moved
       array into: of ``g`` (a float32 row its own words) and of
       ``d_xs`` (counted at the row's own bytes: a 16-bit row of an odd
       number of lane tiles is packed a tile wider, 1,408 words for
       2,688 columns, 4.8%); where a share's rows are no whole number
       of lanes, or too wide for the row kernel's slots
       (``moe_dispatch.rows_by_kernel`` on a chip: ``moe dispatch: ..
       rows=reference``; no cell of the benchmark since PR 67), the jnp
       moves' buffers instead:
       the float32 [R, e] rows that ``g`` is gathered into
       (``_rows_to_tokens_bwd``), and for ``dx`` the float32 copy of
       ``d_xs`` and the float32 [n, e] sum it is scattered into
       (``_tokens_to_rows_bwd``); with every expert held (the jnp
       gathers by the sort and its inverse) the [R, e] claims that
       ``g`` is gathered into;
     - the three weight gradients [h, e, f] in the compute dtype, as
       ``gmm_tn`` writes them before ``_updates_apart`` hands them on;
     - the gates' gradients: float32 [n, K] as the combine's pullback
       gives them, and float32 [n, X] once they reach the router's
       logits;
     - under a share, the further blocks' accumulators
       (``_further_blocks_bwd``: zeros like x, the gates and the three
       matrices).

    The phases are the pullback's, in its order: the combine's (``ys``
    and the packed ``g`` in, ``d_ys`` out), the down product's, the
    gate's, the two products' and the gather's; the term is the largest
    phase and what stands through them.  Every array is one the TPU
    compiler's buffer assignment of ``trinity-mini``'s step for a
    described v5e shows live in that layer (``rows_pack`` u32[32768, 1,
    1, 1024], ``select_add_fusion`` bf16[32768, 2048], ``gmm_tn`` x 3
    bf16[16, 2048, 1024], three ``broadcast`` of the same shape and one
    bf16[16384, 2048], a ``fusion`` f32[16384, 2048]: PERF.md section
    6, PR 60).

    A kept entry (``moe_rows``, ``moe_gate``, ``moe_up``, ``moe_out``)
    is read from the stack, so the second forward does not make its
    array, and the stack's copy is dead once its last phase has read
    it: a phase is less by the kept arrays read before it (``ys`` by
    the combine's; ``gate`` and ``up`` by the gate's; ``xs`` by the
    products').  That holds where every expert is held.  Under a share
    a kept entry changes nothing: the further blocks' loop, which runs
    before the first block's pullback, runs each block's forward again
    whatever the first block keeps, and XLA assigns the loop's buffers
    whether or not it turns.  A share's weight gradients wait for the
    loop's sums, all three; without one each leaves through its own
    barrier (``_updates_apart``) in the phase that makes it."""
    size = jnp.dtype(cfg.dtype).itemsize
    bound, _ = _routed_rows(cfg, rows)
    held = cfg.experts_held[1]
    share = held != cfg.moe_experts
    # the products on the input's side: gate and up, or (an MLP of two
    # matrices) up alone, and as many weight gradients beside the down
    # product's
    ins = 1 + cfg.gated_mlp
    # a room is stated on a chip alone: the kernel's own test of the
    # widths there decides which moves the program holds
    kernel = share and moe_dispatch.rows_by_kernel(
        rows, bound, cfg.dim, cfg.dtype,
        min(cfg.moe_top_k, cfg.moe_experts), "tpu")
    wide, narrow = bound * cfg.dim * size, bound * cfg.mlp_dim * size
    claims = bound * cfg.dim * 4
    tokens = rows * cfg.dim * size
    weight = held * cfg.dim * cfg.mlp_dim * size
    g = rows * cfg.dim * 4
    made = {"moe_rows": wide, "moe_gate": narrow * (ins - 1),
            "moe_up": narrow, "moe_out": wide}
    own = [label for label in made if label in kept and not share]
    read = lambda *labels: sum(
        made[label] for label in labels if label not in own)
    gone = lambda *labels: sum(
        made[label] for label in labels if label in own)
    phases = {
        "combine": read(*made) + wide + (
            g if kernel else claims if share else wide),
        "down": read("moe_rows", "moe_gate", "moe_up") + wide + 2 * narrow
        + weight - gone("moe_out"),
        "gate": read("moe_rows", "moe_gate", "moe_up") + (ins + 1) * narrow
        + weight - gone("moe_out"),
        "products": read("moe_rows") + ins * narrow + 2 * wide
        + (ins + 1 if share else ins) * weight
        - gone("moe_out", "moe_gate", "moe_up"),
        "gather": (2 * wide if kernel or not share else wide + claims + g)
        + tokens + (ins + 1) * share * weight - gone(*made),
    }
    choices = 4 * rows * min(cfg.moe_top_k, cfg.moe_experts)
    router = 4 * rows * cfg.moe_experts
    return phases, g + choices + router + share * (
        tokens + (ins + 1) * weight + choices)


def dispatch_bytes(cfg, rows, kept=()):
    """``dispatch_phases`` as one number: the largest phase and what
    stands through them; 0 for a model without experts."""
    if not cfg.moe_experts:
        return 0
    phases, through = dispatch_phases(cfg, rows, kept)
    return max(phases.values()) + through


def grads_standing(cfg, params, rows, kept=()):
    """Bytes of the layer stack's gradients, which the caller counted
    whole, that stand where a layer's backward is the step's peak, with
    the entries labelled ``kept`` kept.

     - A group the stack scans over several turns: its gradient is one
       stacked array that the loop fills and the update reads after it.
       Whole.
     - A looped stack (``cfg.ut_steps`` > 1: the layers' scan inside a
       scan over the turns): the turns' reverse loop carries the sum
       of the turns done, whole, and the layers' reverse loop fills the
       running turn's beside it before the two are added: TWICE, once
       more than the caller counted, whatever the number of turns (the
       chip's peaks of the ``ouro-2.6b`` step at 4, 6, 7 and 8 layers
       over eleven kept lists: PERF.md section 6, PR 66; with the turns
       written out the compiler places four such sums and counts 3.7
       GB more at six layers).
     - The layers XLA unrolls (``_unrolled``; the test
       ``transformer._updates_apart`` makes): each matrix goes to AdamW
       behind its own layer's backward through a barrier of its own, so
       the stack's never stand at once.  How much does is read from
       the TPU compiler's count and schedule of ``olmo-hybrid-7b``'s
       step for a described v5e over six kept lists (PERF.md section
       6, PR 50): in the first layer back-propagated, where every kept
       value still stands, no gradient of the stack exists that the
       layer's own term does not hold; in a later one the layer
       before's, whose update is in flight, stands where that layer's
       kept values stood (with nothing kept the count is 0.56 GB over
       an estimate that has none and 0.12 under one that has a
       layer's).  So: ONE layer's worth, the largest, less what a
       layer done gives back (the kept entries that every layer
       makes).
       That holds with expert layers or without: an unrolled expert
       layer's term counts what its dispatch's backward holds from
       shapes (``dispatch_phases``; until PR 60 the whole-gradient
       count stood in for those 1.4-2.3 GB of temporaries).  One
       unrolled layer alone has no layer before it: none.
     - A stack that scans a group of EXPERT layers keeps the whole
       count, for its leading and trailing layers too: no described
       compile tells where a scanned stack's peak stands
       (``kanana-2-30b-a3b``, PERF.md section 7), and there the chip
       reads 0.03 GB under the estimate as it is."""
    stack = _stack(params)
    if cfg.ut_steps > 1:
        return 2 * _nbytes(stack)
    scanned, unrolled = _unrolled(stack)
    each = sorted(map(_nbytes, unrolled))
    scanned_kinds, _ = _kinds_apart(cfg, stack)
    if not each or not all(kind.dense for kind in scanned_kinds):
        return _nbytes(scanned) + sum(each)
    if len(each) == 1 and not scanned:
        return 0
    given_back = sum(per_layer
                     for label, _, per_layer, layers in _entries(cfg, rows)
                     if label in kept
                     and layers == cfg.num_layers * cfg.ut_steps)
    return _nbytes(scanned) + max(0, each[-1] - given_back)


def _expert_layer(cfg, rows, kept, kind, sizes):
    """Bytes an unrolled expert layer of ``kind`` holds while it is
    back-propagated, beside what the stack keeps of it (``sizes``: the
    table's bytes a layer by label).

    While the FFN's pullback runs: the dispatch's inventory
    (``dispatch_bytes``), the shared expert's gate, up, product and a
    cotangent less its kept ones, the router's results and the wide
    stream's reads where they are not kept, and four [n, e] planes: the
    FFN's normed input (the dispatch's operand), the stream between
    the sublayers (read from the stack where it is kept: three), the
    cotangent that comes in and the one that leaves.

    The operator's second forward: the entries of the table that it
    makes and the stack does not keep (``OPERATOR_ENTRIES``; latent
    attention's by ``_latent_layer``, with its backward), and a kda
    layer's decays a channel (the log decays, their cumulative sums and
    both's cotangents, [rows, heads * key_dim] float32 each; the log
    decays are the ``delta_decay`` entry's plane, counted once: with
    the entry where it is not kept, in the stack where it is).  They
    stand beside the FFN's pullback, which needs the
    operator's result, unless the stream between the sublayers is kept:
    then the FFN's second forward starts from the kept stream, XLA runs
    the operator's where its backward reads it, behind the FFN's
    pullback (``solar-open2-250b``'s step with the stream kept holds
    none of a KDA layer's operands at its peak, 0.35 GB of a second
    forward where it holds 0.96 with nothing kept), and the term is the
    larger of the two."""
    size = jnp.dtype(cfg.dtype).itemsize
    own = lambda labels: sum(sizes[label] for label in labels
                             if label in kept)
    free = lambda labels: sum(sizes[label] for label in labels
                              if label in sizes and label not in kept)
    # a layer that is its FFN alone ("e") has no stream between
    # sublayers; an MLP of two matrices no gate product
    planes = 3 if kind.op == "e" else 4 - ("stream" in kept)
    ffn = (dispatch_bytes(cfg, rows, kept) + planes * rows * cfg.dim * size
           + rows * (3 + cfg.gated_mlp) * cfg.shared_dim * size
           - own(SHARED_PRODUCTS) + free(LAYER_ENTRIES))
    operator, backward = free(OPERATOR_ENTRIES[kind.op]), 0
    if kind.op == "a" and cfg.latent:
        residuals, backward = _latent_layer(cfg, rows, kept)
        operator += residuals
    if kind.op == "d" and cfg.delta_kind == "kda":
        # the log decays are the entry's plane: with ``free`` or kept
        operator += _decay_channels(cfg, rows, sizes["delta_decay"])
    if "stream" in kept:
        return max(ffn, operator + backward)
    return max(ffn, backward) + operator


def _ssm_layer(cfg, rows, kept, sizes):
    """Bytes an unrolled Mamba-2 layer that is its mixer alone holds
    while it is back-propagated, beside what the stack keeps of it: the
    entries its second forward makes and the stack does not keep, the
    gated product and its norm ([rows, heads * head_dim] each: the
    norm's and the output projection's operands), and the backward's
    cotangents: of the norm, the gate and the scan's output, of x | B |
    C behind and before the convolution, and the stream's three planes
    (the normed input, a cotangent in and one out)."""
    size = jnp.dtype(cfg.dtype).itemsize
    inner, xbc = _ssm_widths(cfg)
    made = sum(sizes[label] for label in OPERATOR_ENTRIES["m"]
               if label not in kept)
    return made + rows * size * (5 * inner + 2 * xbc + 3 * cfg.dim)


def step_bytes(cfg, params, rows, kept=()):
    """Bytes one device needs for a training step of this model on
    ``rows`` tokens with the entries labelled ``kept`` kept, beside
    those entries and the state its caller holds (parameters, optimizer
    state, gradients):

     - the compute-dtype copies of the parameters (``_weight_copies``:
       all of a scan's at once, two layers' where XLA unrolls);
     - the carries the scan saves, one stream a layer
       (``cfg.stream_width`` wide: ``hyper_streams`` times the hidden
       size), a multi-token-prediction module's block a layer more
       and the two normed [rows, dim] operands of its projection
       (``transformer._mtp_module``: outside the block's checkpoint,
       saved from the forward to the module's backward);
     - the larger of the two places the peak can be: the head
       (``ops/head_loss.py``: the logits, and their cotangent where the
       head is tied), while the stack's gradients, which the caller
       counted, do not exist yet; or one layer's backward with its
       second forward, of the layer kind that needs most (a leading
       dense layer's beside the expert layers'), beside what of those
       gradients stands there (``grads_standing``).  A kept product of
       that layer's own is read from the scan's stack and not made
       again, so it leaves the term: a dense layer's gate and up leave
       their product and a cotangent;
     - a Mamba-2 layer: ``_ssm_layer``, its second forward beside its
       backward's cotangents;
     - an expert layer XLA unrolls: ``_expert_layer``, the dispatch's
       inventory (``dispatch_phases``) with the shared expert's planes
       and four of the stream beside the operator's second forward;
       and what of the head stands into the first layer
       back-propagated.  An expert layer of a group scanned over
       several turns (``kanana-2-30b-a3b``) keeps the term it had,
       ``row_bound x (dim + 2 mlp_dim)`` and half of it with its
       products kept, beside the whole-gradient count;
     - where no expert layer is unrolled: for latent attention, whose
       operands are [rows, heads, width]
       each at 32 heads (``_latent_layer``): the attention residuals
       of the second forward that are not kept beside that backward,
       or attention's own backward where it is the larger; for a kda
       layer, its decays a channel (four float32 planes of
       [rows, heads * key_dim]);
     - for a wide stream, four streams more in a layer's backward (read
       against ``xing4.0-29b-a4b``'s described compile in PR 60: beside
       the seven carries its peak holds the second forward's
       ``hc_post_fwd``, ``hc_post_bwd``'s cotangent, a ``broadcast`` of
       zeros and the block's own input, bf16[8192, 14336] each);
     - less, at either place, what an untied embedding was counted
       for: its copy is read by the forward's first gather alone and
       its gradient is the last thing the backward makes;
     - a looped stack (``cfg.ut_steps`` = R > 1): a carry a layer a
       turn, and a turn's state before and behind the final norm (the
       norm's operand and the head's, the cotangent that comes back)
       and the gate's float32 operand; one call's logits at the head
       beside the calls' summed head gradient (the other calls' logits
       are the ``logits`` entry's, kept or made again one at a time),
       which are gone again where a layer's backward is the peak: the
       entry's kept bytes come off that place.  Held to the chip's
       peaks of the ``ouro-2.6b`` step over eleven kept lists at four
       depths: +0.03 / +0.32 GB (tests/test_remat_keep.py).

    Held to the chips' measured peaks for the cells of the benchmark
    (tests/test_remat_keep.py: -0.1 / +0.9 GB; -0.1 / +0.5 in the six
    cells whose unrolled stack has expert layers) and to the TPU
    compiler's own count of the share cells' whole steps and of the
    dense hybrid cell's (tests/test_step_compile_tpu.py,
    tests/test_delta_step_compile_tpu.py: over, by under 0.5 GB with
    the chosen list kept)."""
    dtype = jnp.dtype(cfg.dtype)
    size = dtype.itemsize
    copy = lambda tree: sum(a.size * size * (a.dtype != dtype)
                            for a in jax.tree_util.tree_leaves(tree))
    stack = _stack(params)
    copies = copy(params) - copy(stack) + _weight_copies(stack, copy)
    stack_grads = _nbytes(stack)
    stream = rows * cfg.stream_width * size
    turns = cfg.ut_steps
    carries = (cfg.num_layers * turns + cfg.mtp_modules + 1) * stream
    if turns > 1:
        # a turn's state before and behind the final norm and the
        # cotangent the heads and the gate hand back, [R, rows, dim]
        # each, and the gate's float32 operand, [R - 1, rows, dim]
        carries += 3 * turns * stream + (turns - 1) * rows * cfg.dim * 4
    # a module's projection runs outside its block's checkpoint: its two
    # normed operands stand from the forward to the module's backward
    carries += cfg.mtp_modules * 2 * rows * cfg.dim * size
    # a multi-token-prediction module's logits stand beside the model's
    head = rows * cfg.vocab_size * size * (
        2 if cfg.tied_embeddings else 1) * (1 + cfg.mtp_modules)
    if turns > 1:
        # the calls' summed head gradient beside the running call's own
        head += cfg.vocab_size * cfg.dim * size
    sizes = {label: per_layer
             for label, _, per_layer, _ in _entries(cfg, rows)}
    own = lambda labels: sum(sizes[label] for label in labels
                             if label in kept)
    experts = _unrolled_experts(cfg, stack)
    layer = 0
    if any(kind.dense and kind.ffn for kind in cfg.kinds):
        f = cfg.dense_ffn_dim if cfg.moe_experts else cfg.mlp_dim
        layer = rows * 4 * f * size - own(DENSE_PRODUCTS)
    if any(kind.op == "m" for kind in cfg.kinds):
        layer = max(layer, _ssm_layer(cfg, rows, kept, sizes))
    if experts:
        layer = max([layer] + [_expert_layer(cfg, rows, kept, kind, sizes)
                               for kind in experts])
        # what of the head stands into the first layer back-propagated:
        # an untied head's weight gradient in the compute dtype; with a
        # multi-token-prediction module the model's own logits, whose
        # backward the module's block does not wait for
        layer += size * (
            rows * cfg.vocab_size * bool(cfg.mtp_modules)
            + cfg.vocab_size * cfg.dim * (not cfg.tied_embeddings))
    else:
        if cfg.moe_experts:     # a scanned group's expert layers
            term = _routed_rows(cfg, rows)[0] * (
                cfg.dim + 2 * cfg.mlp_dim) * size
            # the shared expert's gate, up, product and a cotangent
            layer = max(layer, max(term - own(EXPERT_PRODUCTS), term // 2)
                        + rows * 4 * cfg.shared_dim * size
                        - own(SHARED_PRODUCTS))
        if cfg.latent and any(kind.op == "a" for kind in cfg.kinds):
            # attention's residuals stand through the FFN's backward,
            # and its own backward follows where the FFN's was
            residuals, backward = _latent_layer(cfg, rows, kept)
            layer = max(layer, backward) + residuals
        if cfg.delta_kind == "kda" and any(
                kind.op == "d" for kind in cfg.kinds):
            # beside the FFN's term, which the other cells' slack has
            # covered for the scan's own operands (the compiler's count
            # of the cell's step: tests/test_delta_step_compile_tpu.py)
            layer += _decay_channels(cfg, rows, own(("delta_decay",)))
    if cfg.hyper_streams:
        # a wide stream in a layer's backward: the stream the second
        # forward makes between the sublayers and the one it ends on,
        # a cotangent in and a cotangent out (``ops/hyper_mix.py``: the
        # kernels make no float32 copy of any)
        layer += 4 * stream
    embed = params["embed"]
    unread = 0 if cfg.tied_embeddings else copy(embed) + _nbytes(embed)
    absent = stack_grads - grads_standing(cfg, params, rows, kept)
    # a looped stack's kept logits, which its caller adds whole: the
    # heads' backward has read them before the first layer's starts
    gone = (turns - 1) * sizes["logits"] if "logits" in kept else 0
    return copies + carries + max(head - stack_grads,
                                  layer - absent - gone) - unread


def choose(cfg, params, rows, room):
    """(names kept, their bytes a device, the budget: what the room
    leaves for them once the step's need with them kept and the reserve
    are taken off, the peak predicted with them) for ``rows`` tokens a
    device under ``room``."""
    budget = lambda need: int(room.free - need - RESERVE * room.limit)
    need = step_bytes(cfg, params, rows)
    labels, names, kept = [], [], 0
    for label, entry, per_layer, layers in _entries(cfg, rows):
        nbytes = per_layer * layers
        with_it = step_bytes(cfg, params, rows, labels + [label])
        if kept + nbytes <= budget(with_it):
            labels.append(label)
            names += entry
            kept += nbytes
            need = with_it
    return (tuple(names), kept, budget(need),
            room.limit - room.free + need + kept)


@functools.lru_cache(maxsize=None)
def announce_keep(names, kept, budget, need, peak, standing, dispatch,
                  layers, rows, fallback, turns=1):
    """Once per compiled shape, by the logger ``announce_tiles`` uses:
    what the layer stack keeps for its backward, of one shard of the
    trainer's data axis, what of the stack's gradients the estimate
    took to stand at the peak (``grads_standing``) and what it took an
    expert layer's dispatch to hold there (``dispatch_bytes``);
    ``turns``: how often a looped stack runs its layers (what it keeps
    of a layer it keeps a turn), said where it is more than once."""
    flash_attention.logger.info(
        "remat keep: names=%s bytes=%d budget=%d need=%d "
        "predicted_peak=%d grads_standing=%d dispatch=%d layers=%d "
        "rows=%d fallback=%d%s", ",".join(names) or "-", kept, budget, need,
        peak, standing, dispatch, layers, rows, fallback,
        " turns=%d" % turns if turns > 1 else "")


_KEPT = contextvars.ContextVar("elasticdl_remat_kept", default=())


@contextlib.contextmanager
def keeping(names):
    """Declare that the layers traced inside this block are
    rematerialized with ``names`` saved (``forward_hidden``), for an op
    that says in its once-per-shape line what its backward finds
    kept."""
    token = _KEPT.set(tuple(names))
    try:
        yield
    finally:
        _KEPT.reset(token)


def keeps(name):
    """Whether ``name`` is among the names declared kept around the
    code traced here."""
    return name in _KEPT.get()


def names_for(cfg, params, tokens_shape):
    """The names ``remat=True`` saves for a [B, T] batch traced here:
    () where no ``DeviceRoom`` is declared."""
    room = batch_shard.device_room()
    if room is None:
        return ()
    rows = tokens_shape[0] * tokens_shape[1] // batch_shard.shards()
    names, kept, budget, peak = choose(cfg, params, rows, room)
    need = peak - kept - (room.limit - room.free)
    labels = [label for label, entry, _ in table(cfg, rows)
              if set(entry) <= set(names)]
    # the inventory, where it is the count that chose the list: 0 for a
    # stack that unrolls no expert layer
    counted = bool(_unrolled_experts(cfg, _stack(params)))
    announce_keep(names, kept, budget, need, peak,
                  grads_standing(cfg, params, rows, labels),
                  counted * dispatch_bytes(cfg, rows, labels),
                  cfg.num_layers, rows, int(not room.free), cfg.ut_steps)
    return names
