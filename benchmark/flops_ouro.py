"""Operation counts of the Ouro looped language model's training step, from
the configuration's shapes alone: what the algorithm needs, forward and
backward (3x forward), recomputation not counted. 2 FLOPs a multiply-add.
Counted by layer APPLICATION: a looped weight does `total_ut_steps` x 6 FLOPs
a token a parameter, so `6 N tokens` is wrong by the passes for everything but
the embedding; the head's product runs once a pass. The attention's count is
causal: a query multiplies the keys at or before it. Norms, activations, the
rotation, the softmaxes and the exit gate (one [d] product a position a pass)
are left out (under 1% together).

`attention_kernel_ops_and_bytes` is the attention kernels' alone
(`attn_flash_fwd`, `attn_flash_bwd_onesweep`): the scores' and values'
products of the seen pairs, and the bytes a step's calls cannot avoid moving.

`model` is the configuration file's `model` group.
"""

from __future__ import annotations


def applications(model: dict) -> int:
    """Layer applications a step: the layers held times the passes."""
    return model["num_hidden_layers"] * model["total_ut_steps"]


def pairs_seen(seq_len: int) -> int:
    """(query, key) pairs of one head over one row under the causal mask."""
    return seq_len * (seq_len + 1) // 2


def attention_flops_per_sequence(model: dict, seq_len: int) -> float:
    """One application: the projections (q and o over the query heads, k and
    v over the KV heads) and for every seen pair the scores over D and the
    values over D."""
    d, dh = model["hidden_size"], model["head_dim"]
    h, g = model["num_attention_heads"], model["num_key_value_heads"]
    return seq_len * 2.0 * d * dh * (2 * h + 2 * g) + h * pairs_seen(seq_len) * 2 * 2.0 * dh


def mlp_flops_per_token(model: dict) -> float:
    return 2.0 * model["hidden_size"] * 3 * model["intermediate_size"]


def head_flops_per_token(model: dict) -> float:
    return 2.0 * model["hidden_size"] * model["vocab_size"]


def forward_flops_by_part(model: dict, batch: int, seq_len: int) -> dict:
    n = applications(model)
    return {"attention": n * batch * attention_flops_per_sequence(model, seq_len),
            "dense_mlp": n * batch * seq_len * mlp_flops_per_token(model),
            "head": model["total_ut_steps"] * batch * seq_len * head_flops_per_token(model)}


def step_flops(model: dict, batch: int, seq_len: int) -> float:
    return 3.0 * sum(forward_flops_by_part(model, batch, seq_len).values())


def attention_kernel_ops_and_bytes(model: dict, batch: int, seq_len: int) -> dict:
    """What a step asks of the attention kernels, over the layer applications.
    Operations: for each seen pair of a head the forward's two products (q k^T
    and p v over D) and the backward's four the algorithm needs (dv, dp, dk,
    dq; the kernel rebuilds the scores as a fifth: not needed, not counted):
    12 D a pair. Bytes, in the compute type's 2: the forward reads q, k, v and
    writes o; the backward reads q, k, v, o, do and writes dq, dk, dv; each a
    [T, D] array a head, once (the KV heads' arrays are the query heads' here:
    one query head a KV head)."""
    h, g, dh = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"]
    n = applications(model) * batch
    return {"ops": n * h * pairs_seen(seq_len) * 12.0 * dh,
            "bytes": n * (6 * h + 6 * g) * seq_len * dh * 2.0}
