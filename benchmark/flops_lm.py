"""Operation counts of the hybrid language model's training step, from the
configuration's shapes and the step's own routing counter: what the
algorithm needs, forward and backward (3x forward), recomputation not
counted. 2 FLOPs a multiply-add; norms, activations, the conv's 2K a
channel and the softmaxes are left out (under 1% together).

`model` is the configuration file's `model` group: the counts of heads,
experts and vocabulary rows are what this chip holds.
"""

from __future__ import annotations

from benchmark.weights_lm import layer_kinds


def mamba_flops_per_token(model: dict) -> float:
    """In-projection, the chunked recurrence's products (the causal half of
    each chunk's C.B^T and of its product with x; building and applying the
    chunk's state), out-projection."""
    d, h, p = model["hidden_size"], model["mamba_num_heads"], model["mamba_head_dim"]
    g, n, q = model["n_groups"], model["ssm_state_size"], model["chunk_size"]
    di = h * p
    proj = 2.0 * d * (2 * di + 2 * g * n + h) + 2.0 * di * d
    half = (q + 1) / 2.0
    scan = 2.0 * n * half * g + 2.0 * p * half * h + 2 * (2.0 * p * n * h)
    return proj + scan


def attention_flops_per_token(model: dict, seq_len: int) -> float:
    """The four projections, and scores and values over the (T + 1) / 2 keys
    a position sees on average."""
    d, dh = model["hidden_size"], model["head_dim"]
    hq, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    proj = 2.0 * d * (hq + 2 * hkv) * dh + 2.0 * hq * dh * d
    return proj + 2 * (2.0 * dh * hq) * (seq_len + 1) / 2.0


def moe_dense_flops_per_token(model: dict) -> float:
    """What every token pays in an expert layer: router, latent down- and
    up-projection, shared expert."""
    d, lat = model["hidden_size"], model["moe_latent_size"]
    return (2.0 * d * model["n_routed_experts_total"] + 2 * (2.0 * d * lat)
            + 2 * (2.0 * d * model["moe_shared_expert_intermediate_size"]))


def expert_flops_per_pair(model: dict) -> float:
    """One token through one routed expert: two products in the latent."""
    return 2 * (2.0 * model["moe_latent_size"] * model["moe_intermediate_size"])


def head_flops_per_token(model: dict) -> float:
    return 2.0 * model["hidden_size"] * model["vocab_size"]


def forward_flops_per_step(model: dict, batch: int, seq_len: int,
                           pairs_here_per_layer: float) -> float:
    """`pairs_here_per_layer`: token-expert pairs routed to the experts held
    here in one expert layer of one step (the record's `moe_pairs_here`)."""
    kinds = layer_kinds(model)
    tokens = batch * seq_len
    per_token = (kinds.count("M") * mamba_flops_per_token(model)
                 + kinds.count("*") * attention_flops_per_token(model, seq_len)
                 + kinds.count("E") * moe_dense_flops_per_token(model))
    head = batch * (seq_len - 1) * head_flops_per_token(model)
    experts = kinds.count("E") * pairs_here_per_layer * expert_flops_per_pair(model)
    return tokens * per_token + head + experts


def train_flops_per_step(model: dict, batch: int, seq_len: int,
                         pairs_here_per_layer: float) -> float:
    return 3.0 * forward_flops_per_step(model, batch, seq_len, pairs_here_per_layer)
