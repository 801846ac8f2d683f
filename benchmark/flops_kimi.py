"""Operation counts of the Kimi Linear language model's training step, from
the configuration's shapes alone: what the algorithm needs, forward and
backward (3x forward), recomputation not counted. 2 FLOPs a multiply-add. The
latent attention's count is causality-aware: a query multiplies only the keys
at or before it, whatever tiles the program visits. The delta rule's count is
the recurrence's, a position at a time: three products of a head's D x D
state with a vector (S^T k, the rank-one update, S^T q), whatever the chunked
form spends on its in-chunk products and its solve. The routed experts'
count is of the pairs the step's own counter says were routed here. Norms,
activations, the short convolutions, the decays and the softmaxes are left
out (under 1% together).

`attention_kernel_ops_and_bytes` is the attention kernels' alone
(`attn_flash_fwd`, `attn_flash_bwd_onesweep` at D 192, Dv 128): the scores'
and values' products of the seen pairs, and the bytes a step's calls cannot
avoid moving.

`model` is the configuration file's `model` group.
"""

from __future__ import annotations

from benchmark.flops_sambay import keys_seen
from benchmark.reference.kimi_linear_ref import layer_kinds


def kda_projection_flops_per_token(model: dict) -> float:
    """q, k, v, the decay's and the gate's low-rank maps, beta, the out-projection."""
    d, h, dk = model["hidden_size"], model["linear_num_heads"], model["linear_head_dim"]
    w = h * dk
    return 2.0 * d * (3 * w + 2 * dk + h) + 2.0 * 2 * dk * w + 2.0 * w * d


def kda_state_flops_per_token(model: dict) -> float:
    """The recurrence's three products of a D x D state with a vector, a head."""
    return model["linear_num_heads"] * 3 * 2.0 * model["linear_head_dim"] ** 2


def latent_projection_flops_per_token(model: dict) -> float:
    """Queries, the latent with the shared key part, its expansion, the out-projection."""
    d, h, lat = model["hidden_size"], model["num_attention_heads"], model["kv_lora_rank"]
    nope, rope, dv = model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"]
    return (2.0 * d * (h * (nope + rope) + lat + rope) + 2.0 * lat * h * (nope + dv)
            + 2.0 * h * dv * d)


def latent_score_flops_per_sequence(model: dict, seq_len: int) -> float:
    """For every head and every key seen, scores over nope + rope and values over Dv."""
    qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    return model["num_attention_heads"] * keys_seen(seq_len) * 2.0 * (qk + model["v_head_dim"])


def dense_mlp_flops_per_token(model: dict) -> float:
    return 2.0 * model["hidden_size"] * 3 * model["intermediate_size"]


def expert_layer_flops(model: dict, tokens: int, pairs_here: float) -> float:
    """One `E` layer's MLP half over `tokens` tokens: the router's float32
    product over every expert it scores, the shared expert, and a SwiGLU of
    the experts' width for each pair routed to an expert held here."""
    d, f = model["hidden_size"], model["moe_intermediate_size"]
    per_token = 2.0 * d * model["num_experts_total"] + (
        2.0 * d * 3 * f * model["num_shared_experts"])
    return tokens * per_token + pairs_here * 2.0 * d * 3 * f


def head_flops_per_token(model: dict) -> float:
    return 2.0 * model["hidden_size"] * model["vocab_size"]


def forward_flops_by_part(model: dict, batch: int, seq_len: int, pairs_here: float) -> dict:
    """{part: FLOPs of one step's forward pass}; `pairs_here` is the mean
    over the expert layers of the pairs routed to the experts held, a step."""
    out = {"kda_projections": 0.0, "kda_state": 0.0, "latent_attention": 0.0, "dense_mlp": 0.0,
           "experts": 0.0}
    for mixer, mlp in layer_kinds(model):
        if mixer == "K":
            out["kda_projections"] += batch * seq_len * kda_projection_flops_per_token(model)
            out["kda_state"] += batch * seq_len * kda_state_flops_per_token(model)
        else:
            out["latent_attention"] += batch * (
                seq_len * latent_projection_flops_per_token(model)
                + latent_score_flops_per_sequence(model, seq_len))
        if mlp == "D":
            out["dense_mlp"] += batch * seq_len * dense_mlp_flops_per_token(model)
        else:
            out["experts"] += expert_layer_flops(model, batch * seq_len, pairs_here)
    out["head"] = batch * (seq_len - 1) * head_flops_per_token(model)
    return out


def step_flops(model: dict, batch: int, seq_len: int, pairs_here: float) -> float:
    return 3.0 * sum(forward_flops_by_part(model, batch, seq_len, pairs_here).values())


def attention_kernel_ops_and_bytes(model: dict, batch: int, seq_len: int) -> dict:
    """What a step asks of the attention kernels, over the latent layers
    held. Operations: for each seen pair of a head, the forward's two
    products (q k^T over D = nope + rope, p v over Dv) and the backward's four
    the algorithm needs (dv = p^T do and dp = do v^T over Dv, dk = ds^T q and
    dq = ds k over D; the kernel rebuilds the scores as a fifth: not needed,
    not counted): 6 (D + Dv) a pair. Bytes: forward q, k, v in and o out;
    backward q, k, v, o, do in and dq, dk, dv out, in the compute type's 2
    bytes, each once (the expanded keys and values are what the kernels are
    given: a latent they never see)."""
    h = model["num_attention_heads"]
    qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    dv = model["v_head_dim"]
    layers = sum(mixer == "A" for mixer, _ in layer_kinds(model))
    ops = layers * batch * h * keys_seen(seq_len) * 6.0 * (qk + dv)
    bytes_ = layers * batch * seq_len * h * 3 * (2 * qk + 2 * dv) * 2.0
    return {"ops": ops, "bytes": bytes_}
