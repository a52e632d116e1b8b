"""Forward + backward operations of one record (one sequence) of a looped
dense decoder LM (``total_ut_steps`` turns round ONE layer stack, the
head and an exit gate after every turn), from the configuration's
shapes, for ``trainer.mfu``.  One multiply-add is two operations;
recomputed operations are not counted.  6 x parameters x tokens would
count the stack and the head once: wrong by the number of turns."""


def forward_macs(config):
    """{part: multiply-adds of one sequence's forward pass}: the layers'
    matmuls, causal attention (the causal half of the scores, T / 2
    keys a query as ``lm_dense.py`` counts it, QK^T and PV), the untied
    head and the gate's one vector, each a turn; the embedding lookup
    nothing.  At one turn the sum is ``lm_dense.py``'s."""
    E, L = config["hidden_size"], config["num_hidden_layers"]
    F, V = config["intermediate_size"], config["vocab_size"]
    T, R = config["seq_len"], config["total_ut_steps"]
    H, D = config["num_attention_heads"], config["head_dim"]
    G = config.get("num_key_value_heads", H)
    proj = E * H * D + 2 * E * G * D + H * D * E     # wq, wk, wv, wo
    mlp = 3 * E * F                                  # gate, up, down
    return {
        "matmuls": R * L * T * (proj + mlp),
        "attention": R * L * T * T * H * D,          # 2 x H x D x T / 2
        "head": R * T * E * V,
        "gate": (R - 1) * T * E,
    }


def train_flops(config):
    """Backward = 2 x forward."""
    return 3 * 2 * sum(forward_macs(config).values())
