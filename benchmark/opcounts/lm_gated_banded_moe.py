"""Forward + backward operations of one record (one sequence) of a
decoder LM whose attention layers differ in what a query sees (the
layers of ``layers_kept`` whose ``layer_types`` entry is
``sliding_attention`` the last ``sliding_window`` positions, the others
the whole sequence), whose attention output passes a gate projected
from the block's input (one more ``hidden x heads * head_dim`` product a
layer), with ``num_dense_layers`` leading dense layers and, in the
others, a router over all the ``published.num_experts`` experts with
``num_experts`` of them held here beside ``num_shared_experts``
always-on ones, for ``trainer.mfu``.  Active operations only: the held
experts at the expectation of a balanced router, K * held / X experts a
token (the real rows are in the worker's ``moe load:`` lines), the
shared expert whole.  One multiply-add is two operations; recomputed
operations and the elementwise work (the norms on the sublayers'
outputs, the gate's sigmoid and multiply) are not counted."""

from benchmark.lib import manifest

# query-key pairs a head scores in one causal layer, whole or windowed:
# the count the kernel's roofline uses
pairs = manifest.load_named("kernels", "banded_attention").pairs


def per_token(config):
    """{part: multiply-adds a token in the matmuls}."""
    E, V = config["hidden_size"], config["vocab_size"]
    H, D = config["num_attention_heads"], config["head_dim"]
    G = config["num_key_value_heads"]
    held = config["num_experts"]
    X = config.get("published", {}).get("num_experts", held)
    K, F = config["num_experts_per_tok"], config["moe_intermediate_size"]
    layers = len(config["layers_kept"])
    dense = config["num_dense_layers"]
    moe = layers - dense
    return {
        # q, o and the gate; k, v
        "attention": layers * (3 * E * H * D + 2 * E * G * D),
        "dense": dense * 3 * E * config["intermediate_size"],
        "router": moe * E * X,
        "shared": moe * 3 * E * config["num_shared_experts"] * F,
        "experts": moe * (K * held / X) * 3 * E * F,
        "head": E * V,
    }


def layer_pairs(config):
    """[pairs a head of each kept layer scores]."""
    T, W = config["seq_len"], config["sliding_window"]
    return [pairs(T, W if config["layer_types"][i] == "sliding_attention"
                  else 0) for i in config["layers_kept"]]


def scores_per_sequence(config):
    """Multiply-adds of the scores and the weighted values of one
    sequence, forward: every kept layer's heads over its pairs."""
    H, D = config["num_attention_heads"], config["head_dim"]
    return sum(layer_pairs(config)) * H * 2 * D


def train_flops(config):
    """Matmul and attention operations of one sequence: backward = 2 x
    forward, the embedding lookup nothing, one untied head matmul."""
    T = config["seq_len"]
    return 3 * 2 * (T * sum(per_token(config).values())
                    + scores_per_sequence(config))
