"""Forward + backward operations of one record (one sequence) of a
decoder LM whose layers differ, for ``trainer.mfu``: per layer of
``layer_types_run`` a gated short convolution's two projections or
grouped-query attention; a dense MLP in the first ``num_dense_layers``
layers and elsewhere a router over all the ``published.num_experts``
experts with ``num_experts`` of them held here.  Active operations only,
and of the held experts the expectation at a balanced router, K * held /
X experts a token (the real rows are in the worker's ``moe load:``
lines).  One multiply-add is two operations; recomputed operations and
the elementwise work (the convolution's taps and gates) are not
counted."""


def per_token(config):
    """{part: multiply-adds a token in the matmuls}."""
    E, V = config["hidden_size"], config["vocab_size"]
    H, D = config["num_attention_heads"], config["head_dim"]
    G = config.get("num_key_value_heads", H)
    X = config.get("published", {}).get("num_experts",
                                        config["num_experts"])
    held, K = config["num_experts"], config["num_experts_per_tok"]
    kinds = config["layer_types_run"]
    convs = sum(kind == "conv" for kind in kinds)
    dense = config["num_dense_layers"]
    routed = len(kinds) - dense
    return {
        "conv": convs * (E * 3 * E + E * E),             # W_in, W_out
        "attention": (len(kinds) - convs) * (
            E * H * D + 2 * E * G * D + H * D * E),      # wq, wk, wv, wo
        "dense": dense * 3 * E * config["intermediate_size"],
        "router": routed * E * X,
        "experts": routed * (K * held / X) * 3 * E * config[
            "moe_intermediate_size"],
        "head": E * V,
    }


def train_flops(config):
    """Matmul and attention operations of one sequence: backward = 2 x
    forward, the causal half of attention counted once, the embedding
    lookup nothing, one tied head matmul."""
    T = config["seq_len"]
    H, D = config["num_attention_heads"], config["head_dim"]
    attention_layers = sum(kind != "conv"
                           for kind in config["layer_types_run"])
    attention = attention_layers * 2 * T * T * H * D   # QK^T + PV, causal
    return 3 * (T * 2 * sum(per_token(config).values()) + attention)
