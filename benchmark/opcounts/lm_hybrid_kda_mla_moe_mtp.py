"""Forward + backward operations of one record (one sequence) of a
decoder LM whose kept layers (``layers_kept``) are latent attention
(no query latent, a gate a head) where ``(i + 1) % layer_group_size ==
0`` and Kimi Delta Attention mixers with FULL decay and gate
projections elsewhere, ``first_k_dense_replace`` leading dense layers,
every other layer a router over all the ``published.num_experts``
experts with ``num_experts`` of them held here beside
``num_shared_experts`` always-on ones, and ``num_nextn_predict_layers``
multi-token-prediction modules, each one latent expert-layer block more
behind a [2 hidden, hidden] projection and a second pass of the head,
for ``trainer.mfu``.  The heads are the ones HELD here
(``num_attention_heads``: a chip's share of a layer's, of both mixers).
A KDA layer's scan is counted by the RECURRENCE, 3 x d_k x d_v
multiply-adds a token a head (``kernels/kda.py``), latent attention's
scores over the query-key pairs a causal head sees
(``kernels/latent_attention.py``).  Active operations only: the held
experts at the expectation of a balanced router, K * held / X experts a
token (the real rows are in the worker's ``moe load:`` lines), the
shared expert whole.  One multiply-add is two operations; recomputed
operations and the elementwise work (the convolution's taps, the norms,
the gates' sigmoids, the clamp, the group choice) are not counted."""

from benchmark.lib import manifest

# multiply-adds a token a head of the delta rule, in units of d_k d_v
SCAN = manifest.load_named("kernels", "kda").MULTIPLY_ADDS["fwd"]
# query-key pairs a causal head scores
pairs = manifest.load_named("kernels", "latent_attention").pairs


def latent_layers(config):
    """The kept layers that are latent attention."""
    return sum((i + 1) % config["layer_group_size"] == 0
               for i in config["layers_kept"])


def per_token(config):
    """{part: multiply-adds a token in the matmuls and the scans}."""
    E, V = config["hidden_size"], config["vocab_size"]
    H, d = config["num_attention_heads"], config["head_dim"]
    dn, dr = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv, rank = config["v_head_dim"], config["kv_lora_rank"]
    held = config["num_experts"]
    X = config.get("published", {}).get("num_experts", held)
    K, F = config["num_experts_per_tok"], config["moe_intermediate_size"]
    modules = config["num_nextn_predict_layers"]
    layers = len(config["layers_kept"])
    dense = config["first_k_dense_replace"]
    latent = latent_layers(config) + modules * (not config["mtp_use_kda"])
    delta = layers + modules - latent
    moe = layers - dense + modules       # a module's block has experts
    return {
        # q, k, v; the decay's and the gate's full projections; W_o; the
        # write strength's
        "kda_projections": delta * (6 * E * H * d + E * H),
        "kda_scan": delta * SCAN * H * d * d,
        # W_q, W_kva, W_kvb, the gate a head, W_o
        "latent_projections": latent * (
            E * H * (dn + dr) + E * (rank + dr) + rank * H * (dn + dv)
            + E * H + H * dv * E),
        "dense": dense * 3 * E * config["intermediate_size"],
        "router": moe * E * X,
        "shared": moe * 3 * E * config["num_shared_experts"] * config[
            "moe_shared_expert_intermediate_size"],
        "experts": moe * (K * held / X) * 3 * E * F,
        # the model's head and each module's pass of it
        "head": (1 + modules) * E * V,
        "mtp_projection": modules * 2 * E * E,
    }


def scores_per_sequence(config):
    """Multiply-adds of the latent blocks' scores and weighted values of
    one sequence, forward: every block's held heads over the causal
    pairs."""
    blocks = latent_layers(config) + config["num_nextn_predict_layers"] * (
        not config["mtp_use_kda"])
    widths = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
              + config["v_head_dim"])
    return (blocks * config["num_attention_heads"]
            * pairs(config["seq_len"]) * widths)


def train_flops(config):
    """Matmul, scan and attention operations of one sequence: backward =
    2 x forward, the embedding lookup nothing."""
    T = config["seq_len"]
    return 3 * 2 * (T * sum(per_token(config).values())
                    + scores_per_sequence(config))
