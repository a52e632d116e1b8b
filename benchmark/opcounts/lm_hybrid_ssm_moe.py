"""Forward + backward operations of one record (one sequence) of a
decoder LM whose layers are ONE sublayer each, by the letters of
``hybrid_override_pattern`` at ``layers_kept``: ``M`` a Mamba-2 mixer,
``*`` causal attention over grouped K/V heads, ``E`` an expert layer of
two-matrix MLPs (a router over all the ``published.n_routed_experts``
experts with ``n_routed_experts`` of them held here, beside one
always-on shared MLP of ``moe_shared_expert_intermediate_size``), for
``trainer.mfu``.  A Mamba-2 layer's scan is counted by the RECURRENCE, 2
x d_state x d_head multiply-adds a token a head (``kernels/ssd.py``:
what any chunk form has to amount to), attention's scores over the
query-key pairs a causal head sees.  Active operations only: the held
experts at the expectation of a balanced router, K * held / X experts a
token (the real rows are in the worker's ``moe load:`` lines), the
shared expert whole.  One multiply-add is two operations; recomputed
operations and the elementwise work (the convolution's taps, the norms,
the gate, the skip, the squared ReLU) are not counted."""

from benchmark.lib import manifest

# multiply-adds a token a head of the recurrence, in units of d_state d_head
SCAN = manifest.load_named("kernels", "ssd").MULTIPLY_ADDS["fwd"]


def kinds(config):
    return [config["hybrid_override_pattern"][i]
            for i in config["layers_kept"]]


def per_token(config):
    """{part: multiply-adds a token in the matmuls and the scans}."""
    E, V = config["hidden_size"], config["vocab_size"]
    H, D = config["num_attention_heads"], config["head_dim"]
    G = config["num_key_value_heads"]
    heads, P = config["mamba_num_heads"], config["mamba_head_dim"]
    N, groups = config["ssm_state_size"], config["n_groups"]
    inner = heads * P
    held = config["n_routed_experts"]
    X = config.get("published", {}).get("n_routed_experts", held)
    K, F = config["num_experts_per_tok"], config["moe_intermediate_size"]
    mamba, attention, experts = (kinds(config).count(c) for c in "M*E")
    return {
        # z | x | B | C | dt in one projection, and the way back
        "mamba_projections": mamba * (
            E * (2 * inner + 2 * groups * N + heads) + inner * E),
        "mamba_scan": mamba * SCAN * heads * P * N,
        "attention_projections": attention * (2 * E * H * D + 2 * E * G * D),
        "router": experts * E * X,
        "shared": experts * 2 * E * config[
            "moe_shared_expert_intermediate_size"],
        "experts": experts * (K * held / X) * 2 * E * F,
        "head": E * V,
    }


def scores_per_sequence(config):
    """Multiply-adds of the attention layers' scores and weighted values
    of one sequence, forward: T (T + 1) / 2 pairs a head."""
    T = config["seq_len"]
    return kinds(config).count("*") * (T * (T + 1) // 2) * config[
        "num_attention_heads"] * 2 * config["head_dim"]


def train_flops(config):
    """Matmul, scan and attention operations of one sequence: backward =
    2 x forward, the embedding lookup nothing, one untied head matmul."""
    T = config["seq_len"]
    return 3 * 2 * (T * sum(per_token(config).values())
                    + scores_per_sequence(config))
