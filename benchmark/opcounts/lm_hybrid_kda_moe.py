"""Forward + backward operations of one record (one sequence) of a
decoder LM whose kept layers (``layers_kept``) are gated softmax
attention without positions where ``gqa_layers`` names them and Kimi
Delta Attention mixers elsewhere, every layer before a router over all
the ``published.n_routed_experts`` experts with ``n_routed_experts`` of
them held here beside ``n_shared_experts`` always-on ones, for
``trainer.mfu``.  The heads are the ones HELD here
(``num_attention_heads`` on ``num_key_value_heads``,
``linear_attn_config.num_heads``: a chip's share of a layer's).  A KDA
layer's scan is counted by the RECURRENCE, 3 x d_k x d_v multiply-adds
a token a head (``kernels/kda.py``: what any chunk form has to amount
to, whatever its sub-blocks multiply), the softmax layer's scores over
the query-key pairs a causal head sees.  Active operations only: the
held experts at the expectation of a balanced router, K * held / X
experts a token (the real rows are in the worker's ``moe load:``
lines), the shared expert whole.  One multiply-add is two operations;
recomputed operations and the elementwise work (the convolution's taps,
the norms, the gates' sigmoids) are not counted."""

from benchmark.lib import manifest

# multiply-adds a token a head of the delta rule, in units of d_k d_v
SCAN = manifest.load_named("kernels", "kda").MULTIPLY_ADDS["fwd"]
RANK = 128     # the low-rank pairs': the head size (configs' ``assumed``)


def softmax_layers(config):
    return sum(i in config["gqa_layers"] for i in config["layers_kept"])


def per_token(config):
    """{part: multiply-adds a token}."""
    E, V = config["hidden_size"], config["vocab_size"]
    H, D = config["num_attention_heads"], config["head_dim"]
    G = config["num_key_value_heads"]
    linear = config["linear_attn_config"]
    Hd, d = linear["num_heads"], linear["head_dim"]
    rank = min(RANK, d)
    held = config["n_routed_experts"]
    X = config.get("published", {}).get("n_routed_experts", held)
    K, F = config["num_experts_per_tok"], config["moe_intermediate_size"]
    layers = len(config["layers_kept"])
    full = softmax_layers(config)
    delta = layers - full
    return {
        # q, k, v; W_o; the two low-rank pairs; the write strength's
        "kda_projections": delta * (
            3 * E * Hd * d + Hd * d * E + 2 * (E * rank + rank * Hd * d)
            + E * Hd),
        "kda_scan": delta * SCAN * Hd * d * d,
        # q, o and the gate; k, v
        "attention_projections": full * (3 * E * H * D + 2 * E * G * D),
        "router": layers * E * X,
        "shared": layers * 3 * E * config["n_shared_experts"] * F,
        "experts": layers * (K * held / X) * 3 * E * F,
        "head": E * V,
    }


def scores_per_sequence(config):
    """Multiply-adds of the softmax layers' scores and weighted values
    of one sequence, forward: T (T + 1) / 2 pairs a head."""
    T = config["seq_len"]
    return softmax_layers(config) * (T * (T + 1) // 2) * config[
        "num_attention_heads"] * 2 * config["head_dim"]


def train_flops(config):
    """Matmul, scan and attention operations of one sequence: backward =
    2 x forward, the embedding lookup nothing, one untied head matmul."""
    T = config["seq_len"]
    return 3 * 2 * (T * sum(per_token(config).values())
                    + scores_per_sequence(config))
