"""Operation counts of the SambaY language model's training step, from the
configuration's shapes alone: what the algorithm needs, forward and backward
(3x forward), recomputation not counted. 2 FLOPs a multiply-add. The
matrix-product count is window- and causality-aware: a query multiplies only
the keys its mask lets it see (at most `sliding_window` in a `W` layer, t + 1
in an `F` or `X` layer), whatever blocks the program slices. Norms,
activations, biases, the conv's 2K a channel, the softmaxes and the lambda
vectors are left out (under 1% together).

The selective scan is no matrix product: `scan_ops_and_bytes` counts its
elementwise operations and the bytes it has to move, for the day a kernel of
its own reports a roofline share.

`model` is the configuration file's `model` group.
"""

from __future__ import annotations

from benchmark.reference.sambay_ref import layer_kinds


def _sizes(model: dict):
    d = model["hidden_size"]
    dh = d // model["num_attention_heads"]
    return (d, model["mamba_expand"] * d, model["mamba_d_state"], -(-d // 16), dh,
            model["num_attention_heads"] * dh, model["num_key_value_heads"] * dh)


def mlp_flops_per_token(model: dict) -> float:
    return 2.0 * model["hidden_size"] * 3 * model["intermediate_size"]


def mamba_flops_per_token(model: dict) -> float:
    """In-projection, x_proj, dt_proj, out-projection."""
    d, di, n, rank, *_ = _sizes(model)
    return 2.0 * d * 2 * di + 2.0 * di * (rank + 2 * n) + 2.0 * rank * di + 2.0 * di * d


def gmu_flops_per_token(model: dict) -> float:
    d, di, *_ = _sizes(model)
    return 2 * (2.0 * d * di)


def keys_seen(seq_len: int, window=None) -> float:
    """Query-key pairs under the mask, summed over a sequence's queries."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) / 2.0
    return window * (window + 1) / 2.0 + (seq_len - window) * float(window)


def attention_flops_per_sequence(model: dict, seq_len: int, kind: str) -> float:
    """The projections (`X` has no keys and values of its own) and, for
    every query head, scores over D and values over the pair's 2 D for each
    key seen."""
    d, _, _, _, dh, q, kv = _sizes(model)
    proj = 2.0 * d * (q if kind == "X" else q + 2 * kv) + 2.0 * q * d
    pairs = keys_seen(seq_len, model["sliding_window"] if kind == "W" else None)
    return seq_len * proj + model["num_attention_heads"] * pairs * (2.0 * dh + 2.0 * 2 * dh)


def head_flops_per_token(model: dict) -> float:
    return 2.0 * model["hidden_size"] * model["vocab_size"]


def forward_flops_per_step(model: dict, batch: int, seq_len: int) -> float:
    kinds = layer_kinds(model)
    per_token = (len(kinds) * mlp_flops_per_token(model)
                 + kinds.count("M") * mamba_flops_per_token(model)
                 + kinds.count("G") * gmu_flops_per_token(model))
    attention = sum(attention_flops_per_sequence(model, seq_len, k) for k in kinds if k in "WFX")
    head = (seq_len - 1) * head_flops_per_token(model)
    return batch * (seq_len * per_token + attention + head)


def train_flops_per_step(model: dict, batch: int, seq_len: int) -> float:
    return 3.0 * forward_flops_per_step(model, batch, seq_len)


def scan_ops_and_bytes(model: dict, batch: int, seq_len: int) -> dict:
    """One Mamba layer's recurrence, forward: for every position, channel
    and state an exponential, the decay's product with dt, two
    multiply-adds (the update and the read-out) and the input's outer
    product: 7 operations; the bytes it cannot avoid are x, dt and y (a
    channel each, 2 + 4 + 2 bytes in bfloat16 compute) and B and C (a state
    each), read or written once. The [T, N, C] states themselves never need
    to leave the chip's fast memory."""
    _, di, n, *_ = _sizes(model)
    tokens = batch * seq_len
    return {"ops": 7.0 * tokens * di * n, "bytes": tokens * (8.0 * di + 2 * 2.0 * n)}
