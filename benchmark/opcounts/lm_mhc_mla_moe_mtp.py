"""Forward + backward operations of one record (one sequence) of a
decoder LM with a residual stream ``hc_mult`` wide (manifold-constrained
hyper-connections: each sublayer's ``phi`` [hc_mult * hidden, 2 hc_mult
+ hc_mult^2], the narrowing maps' [hc_mult * hidden, hc_mult]), latent
attention with a query latent (``q_lora_rank``) beside the key-value
latent, ``first_k_dense_replace`` leading dense layers, a router over
all the ``published.n_routed_experts`` experts with ``n_routed_experts``
held here beside ``n_shared_experts`` always-on ones, and
``num_nextn_predict_layers`` multi-token-prediction modules, each one
expert-layer block more behind a [2 hidden, hidden] projection and a
second pass of the head, for ``trainer.mfu``.  Active operations only:
the held experts at the expectation of a balanced router, the shared
expert whole, the ``num_attention_heads`` held here.  One multiply-add
is two operations; recomputed operations and the elementwise work (the
streams' weighted sums, the Sinkhorn rounds) are not counted."""

from benchmark.lib import manifest

# query-key pairs a causal head scores: the count the kernel's roofline
# uses
pairs = manifest.load_named("kernels", "latent_attention").pairs


def per_token(config):
    """{part: multiply-adds a token in the matmuls}."""
    E, V = config["hidden_size"], config["vocab_size"]
    H = config["num_attention_heads"]
    dn, dr = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv, rank = config["v_head_dim"], config["kv_lora_rank"]
    q_rank = config["q_lora_rank"]
    held = config["n_routed_experts"]
    X = config.get("published", {}).get("n_routed_experts", held)
    K, F = config["num_experts_per_tok"], config["moe_intermediate_size"]
    n = config["hc_mult"]
    modules = config["num_nextn_predict_layers"]
    layers = config["num_hidden_layers"]
    dense = config["first_k_dense_replace"]
    moe = layers - dense + modules       # a module's block has experts
    blocks = layers + modules
    return {
        # W_qa, W_qb, W_kva, W_kvb, W_o
        "attention": blocks * (E * q_rank + q_rank * H * (dn + dr)
                               + E * (rank + dr) + rank * H * (dn + dv)
                               + H * dv * E),
        "dense": dense * 3 * E * config["intermediate_size"],
        "router": moe * E * X,
        "shared": moe * 3 * E * config["n_shared_experts"] * F,
        "experts": moe * (K * held / X) * 3 * E * F,
        # the model's head and each module's pass of it
        "head": (1 + modules) * E * V,
        "mtp_projection": modules * 2 * E * E,
        # two sublayers' logits a block, one narrowing map a stack
        "mixing": (blocks * 2 * n * E * (2 * n + n * n)
                   + (1 + modules) * n * E * n),
    }


def scores_per_sequence(config):
    """Multiply-adds of the scores and the weighted values of one
    sequence, forward: every block's held heads over the causal pairs."""
    blocks = (config["num_hidden_layers"]
              + config["num_nextn_predict_layers"])
    widths = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
              + config["v_head_dim"])
    return (blocks * config["num_attention_heads"]
            * pairs(config["seq_len"]) * widths)


def train_flops(config):
    """Matmul and attention operations of one sequence: backward = 2 x
    forward, the embedding lookup nothing."""
    T = config["seq_len"]
    return 3 * 2 * (T * sum(per_token(config).values())
                    + scores_per_sequence(config))
