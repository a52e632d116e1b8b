"""Forward + backward operations of one record (one sequence) of a
decoder LM with multi-head latent attention (a q of
``qk_nope_head_dim + qk_rope_head_dim`` a head, a latent of
``kv_lora_rank`` with one RoPE key, values of ``v_head_dim``),
``first_k_dense_replace`` leading dense layers and, in the others, a
router over all the ``published.n_routed_experts`` experts with
``n_routed_experts`` of them held here beside ``n_shared_experts``
always-on ones, for ``trainer.mfu``.  Active operations only: the held
experts at the expectation of a balanced router, K * held / X experts a
token (the real rows are in the worker's ``moe load:`` lines), the
shared expert whole.  One multiply-add is two operations; recomputed
operations and the elementwise work are not counted."""

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
    held = config["n_routed_experts"]
    X = config.get("published", {}).get("n_routed_experts", held)
    K, F = config["num_experts_per_tok"], config["moe_intermediate_size"]
    layers = config["num_hidden_layers"]
    dense = config["first_k_dense_replace"]
    moe = layers - dense
    return {
        # W_q, W_kv_a, W_kv_b, W_o
        "attention": layers * (E * H * (dn + dr) + E * (rank + dr)
                               + rank * H * (dn + dv) + H * dv * E),
        "dense": dense * 3 * E * config["intermediate_size"],
        "router": moe * E * X,
        "shared": moe * 3 * E * config["n_shared_experts"] * F,
        "experts": moe * (K * held / X) * 3 * E * F,
        "head": E * V,
    }


def scores_per_sequence(config):
    """Multiply-adds of the scores and the weighted values of one
    sequence, forward: every layer's heads over the causal pairs."""
    H = config["num_attention_heads"]
    widths = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
              + config["v_head_dim"])
    return (config["num_hidden_layers"] * H * pairs(config["seq_len"])
            * widths)


def train_flops(config):
    """Matmul and attention operations of one sequence: backward = 2 x
    forward, the embedding lookup nothing, one untied head matmul."""
    T = config["seq_len"]
    return 3 * 2 * (T * sum(per_token(config).values())
                    + scores_per_sequence(config))
