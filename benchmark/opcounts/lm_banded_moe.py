"""Forward + backward operations of one record (one sequence) of a
decoder LM whose attention layers differ in what a query sees (the whole
sequence, or the last ``sliding_window_size`` positions: the layers of
``layers_kept`` whose ``sliding_window_layout`` is 1), whose heads have a
size of their own (``num_attention_heads * head_dim`` need not be the
hidden size) and whose every FFN is a router over all the
``published.moe_num_primary_experts`` experts with
``moe_num_primary_experts`` of them held here, for ``trainer.mfu``.
Active operations only, and of the held experts the expectation at a
balanced router, K * held / X experts a token (the real rows are in the
worker's ``moe load:`` lines).  One multiply-add is two operations;
recomputed operations and the elementwise work are not counted."""

from benchmark.lib import manifest

# query-key pairs a head scores in one causal layer, whole or windowed:
# the count the kernel's roofline uses
pairs = manifest.load_named("kernels", "banded_attention").pairs


def per_token(config):
    """{part: multiply-adds a token in the matmuls}."""
    E, V = config["hidden_size"], config["vocab_size"]
    H, D = config["num_attention_heads"], config["head_dim"]
    G = config.get("num_key_value_heads", H)
    held = config["moe_num_primary_experts"]
    X = config.get("published", {}).get("moe_num_primary_experts", held)
    K = config["moe_num_active_primary_experts"]
    layers = len(config["layers_kept"])
    return {
        "attention": layers * (2 * E * H * D + 2 * E * G * D),  # q, o; k, v
        "router": layers * E * X,
        "experts": layers * (K * held / X) * 3 * E * config[
            "moe_ffn_hidden_size"],
        "head": E * V,
    }


def layer_pairs(config):
    """[pairs a head of each kept layer scores]."""
    T, W = config["seq_len"], config["sliding_window_size"]
    return [pairs(T, W if config["sliding_window_layout"][i] else 0)
            for i in config["layers_kept"]]


def train_flops(config):
    """Matmul and attention operations of one sequence: backward = 2 x
    forward, attention's two products (QK^T, PV) over the pairs a layer
    scores, the embedding lookup nothing, one untied head matmul."""
    T = config["seq_len"]
    H, D = config["num_attention_heads"], config["head_dim"]
    attention = sum(layer_pairs(config)) * 2 * 2 * H * D
    return 3 * (T * 2 * sum(per_token(config).values()) + attention)
