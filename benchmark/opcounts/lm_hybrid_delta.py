"""Forward + backward operations of one record (one sequence) of a dense
decoder LM whose kept layers (``layers_kept`` of ``layer_types``) are
Gated DeltaNet mixers (``linear_attention``) and full causal attention
(``full_attention``), each before a SwiGLU, for ``trainer.mfu``.  The
heads are the ones HELD here (``num_attention_heads``,
``linear_num_value_heads``: a chip's share of a layer's), the MLP whole.
A delta layer's scan is counted by the RECURRENCE, 3 x d_k x d_v
multiply-adds a token a head (``kernels/gated_delta.py``: what any chunk
form has to amount to), the full layer's scores over the query-key
pairs a causal head sees.  One multiply-add is two operations;
recomputed operations and the elementwise work (the convolution's taps,
the norms, the gates) are not counted."""

from benchmark.lib import manifest

# multiply-adds a token a head of the delta rule, in units of d_k d_v
SCAN = manifest.load_named("kernels", "gated_delta").MULTIPLY_ADDS["fwd"]


def kinds(config):
    return [config["layer_types"][i] for i in config["layers_kept"]]


def per_token(config):
    """{part: multiply-adds a token}."""
    E, V = config["hidden_size"], config["vocab_size"]
    H, D = config["num_attention_heads"], config["head_dim"]
    Hd = config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    delta = sum(kind == "linear_attention" for kind in kinds(config))
    full = len(kinds(config)) - delta
    return {
        "mlp": len(kinds(config)) * 3 * E * config["intermediate_size"],
        # q, k, v; the output gate; W_o; the decay's and the write
        # strength's projections
        "delta_projections": delta * (
            E * Hd * (2 * dk + dv) + 2 * E * Hd * dv + 2 * E * Hd),
        "delta_scan": delta * SCAN * Hd * dk * dv,
        "attention_projections": full * 4 * E * H * D,
        "head": E * V,
    }


def scores_per_sequence(config):
    """Multiply-adds of the full layers' scores and weighted values of
    one sequence, forward: T (T + 1) / 2 pairs a head."""
    T = config["seq_len"]
    full = sum(kind == "full_attention" for kind in kinds(config))
    return full * (T * (T + 1) // 2) * config["num_attention_heads"] * (
        2 * config["head_dim"])


def train_flops(config):
    """Matmul, scan and attention operations of one sequence: backward =
    2 x forward, the embedding lookup nothing, one untied head matmul."""
    T = config["seq_len"]
    return 3 * 2 * (T * sum(per_token(config).values())
                    + scores_per_sequence(config))
