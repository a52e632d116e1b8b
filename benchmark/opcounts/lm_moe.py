"""Forward + backward operations of one record (one sequence) of a
decoder LM whose FFN is a top-k mixture of experts, from the
configuration's shapes, for ``trainer.mfu``.  Active operations only:
the experts a token is routed to, not the ones it is not.  One
multiply-add is two operations; recomputed operations are not
counted."""


def train_flops(config):
    """Matmul and attention operations of one sequence: backward = 2 x
    forward, the causal half of attention counted once, the embedding
    lookup nothing, one (untied or tied) head matmul."""
    E, L = config["hidden_size"], config["num_hidden_layers"]
    F, V = config["intermediate_size"], config["vocab_size"]
    X, K = config["num_experts"], config["num_experts_per_tok"]
    T = config["seq_len"]
    H, D = config["num_attention_heads"], config["head_dim"]
    G = config.get("num_key_value_heads", H)
    proj = E * H * D + 2 * E * G * D + H * D * E     # wq, wk, wv, wo
    experts = K * 3 * E * F                          # gate, up, down, K times
    per_token = 2 * (L * (proj + E * X + experts) + E * V)
    attention = L * 2 * T * T * H * D                # QK^T + PV, causal half
    return 3 * (T * per_token + attention)
