"""Operation counts of the Laguna language model's training step, from the
configuration's shapes alone: what the algorithm needs, forward and backward
(3x forward), recomputation not counted. 2 FLOPs a multiply-add. The
attention count is window- and causality-aware: a query multiplies only the
keys its mask lets it see (at most `sliding_window` in an `S` layer, t + 1
in an `F` layer), whatever tiles the program visits. The routed experts'
count is of the pairs the step's own counter says were routed here. Norms,
activations, the rotation, the gate's product with the output and the
softmaxes are left out (under 1% together).

`attention_kernel_ops_and_bytes` is the attention kernels' alone (`attn_flash_fwd`,
`attn_flash_bwd_onesweep`): the scores' and values' products of the seen
pairs, and the bytes a step's calls cannot avoid moving.

`model` is the configuration file's `model` group.
"""

from __future__ import annotations

from benchmark.flops_sambay import keys_seen
from benchmark.reference.laguna_ref import layer_kinds, query_heads


def attention_projection_flops_per_token(model: dict, kind: str) -> float:
    """q, k, v, the gate and the out-projection."""
    d, dh = model["hidden_size"], model["head_dim"]
    heads, kv = query_heads(kind, model), model["num_key_value_heads"] * model["head_dim"]
    return 2.0 * d * (heads * dh + 2 * kv + heads) + 2.0 * heads * dh * d


def attention_score_flops_per_sequence(model: dict, seq_len: int, kind: str) -> float:
    """For every query head, scores over D and values over D for each key seen."""
    pairs = keys_seen(seq_len, model["sliding_window"] if kind == "S" else None)
    return query_heads(kind, model) * pairs * 4.0 * model["head_dim"]


def dense_mlp_flops_per_token(model: dict) -> float:
    return 2.0 * model["hidden_size"] * 3 * model["intermediate_size"]


def expert_layer_flops(model: dict, tokens: int, pairs_here: float) -> float:
    """One `E` layer's MLP half over `tokens` tokens: the router's float32
    product over every expert it scores, the shared expert, and a SwiGLU of
    the experts' width for each pair routed to an expert held here."""
    d = model["hidden_size"]
    per_token = 2.0 * d * model["num_experts_total"] + (
        2.0 * d * 3 * model["shared_expert_intermediate_size"])
    return tokens * per_token + pairs_here * 2.0 * d * 3 * model["moe_intermediate_size"]


def head_flops_per_token(model: dict) -> float:
    return 2.0 * model["hidden_size"] * model["vocab_size"]


def forward_flops_by_part(model: dict, batch: int, seq_len: int, pairs_here: float) -> dict:
    """{part: FLOPs of one step's forward pass}; `pairs_here` is the mean
    over the expert layers of the pairs routed to the experts held, a step."""
    out = {"window_attention": 0.0, "full_attention": 0.0, "dense_mlp": 0.0, "experts": 0.0}
    for attention, mlp in layer_kinds(model):
        part = "window_attention" if attention == "S" else "full_attention"
        out[part] += batch * (
            seq_len * attention_projection_flops_per_token(model, attention)
            + attention_score_flops_per_sequence(model, seq_len, attention))
        if mlp == "D":
            out["dense_mlp"] += batch * seq_len * dense_mlp_flops_per_token(model)
        else:
            out["experts"] += expert_layer_flops(model, batch * seq_len, pairs_here)
    out["head"] = batch * (seq_len - 1) * head_flops_per_token(model)
    return out


def train_flops_per_step(model: dict, batch: int, seq_len: int, pairs_here: float) -> float:
    return 3.0 * sum(forward_flops_by_part(model, batch, seq_len, pairs_here).values())


def attention_kernel_ops_and_bytes(model: dict, batch: int, seq_len: int) -> dict:
    """What a step asks of the attention kernels, over all the layers held.
    Operations: for each seen pair of a query head, the forward's two
    products (q k^T, p v) and the backward's four the algorithm needs (dv =
    p^T do, dp = do v^T, dk = ds^T q, dq = ds k; the kernel rebuilds the
    scores as a fifth, and the layer's recomputation runs the forward again:
    neither is needed, neither is counted): 12 D a pair. Bytes: forward q, k,
    v in and o out; backward q, k, v, o, do in and dq, dk, dv out, in the
    compute type's 2 bytes, each once (a kernel reads a key tile once a
    query tile that sees it; that is the kernel's choice, not the
    algorithm's need)."""
    dh, g = model["head_dim"], model["num_key_value_heads"]
    ops = bytes_ = 0.0
    for attention, _ in layer_kinds(model):
        heads = query_heads(attention, model)
        pairs = keys_seen(seq_len, model["sliding_window"] if attention == "S" else None)
        ops += batch * heads * pairs * 12.0 * dh
        q_like, kv_like = batch * seq_len * heads * dh * 2.0, batch * seq_len * g * dh * 2.0
        bytes_ += (2 * q_like + 2 * kv_like) + (4 * q_like + 4 * kv_like)
    return {"ops": ops, "bytes": bytes_}
