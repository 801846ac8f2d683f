"""Operation counts of the EvaByte language model's training step, from the
configuration's shapes alone: what the algorithm needs, forward and backward
(3x forward), recomputation not counted. 2 FLOPs a multiply-add. The
attention's count is mask-aware: a query multiplies only the keys of L(t)
(its own window up to itself) and the summaries of C(t) (every chunk of the
windows before), whatever tiles the program visits. Norms, activations, the
rotation and the softmaxes are left out (under 1% together); the chunk
summariser's products (a key with `phi`, two weighted sums) are counted, and
are a thousandth of the step.

`attention_kernel_ops_and_bytes` is the attention kernels' alone
(`attn_flash_fwd`, `attn_flash_bwd_onesweep` over both key segments): the
scores' and values' products of the seen pairs, and the bytes a step's calls
cannot avoid moving. `needed_key_blocks` is what the mask asks of a schedule
in the program's own unit, for `eva_key_blocks_visited_pct.train`.

`model` is the configuration file's `model` group.
"""

from __future__ import annotations

QUERY_TILE = 512  # queries whose key blocks are counted together: the kernels' query tile at R = 1
KEY_BLOCK = 128   # the unit the program counts keys in (`hybrid_lm.ATTN_KEY_BLOCK`)


def pairs_seen(model: dict, seq_len: int):
    """(query, own key) pairs and (query, summary) pairs of one head over one
    row: sum over t of |L(t)| and of |C(t)|."""
    w, c = model["window_size"], model["chunk_size"]
    own = summary = 0
    for start in range(0, seq_len, w):
        n = min(w, seq_len - start)          # queries of this window
        own += n * (n + 1) // 2
        summary += n * (start // c)
    return own, summary


def needed_key_blocks(model: dict, seq_len: int, query_tile: int = QUERY_TILE,
                      block: int = KEY_BLOCK):
    """(blocks of own keys, blocks of summary keys) a head needs over one row:
    for every tile of `query_tile` queries, the blocks of `block` keys that
    hold a key some query of the tile sees."""
    w, c = model["window_size"], model["chunk_size"]
    own = summary = 0
    for first in range(0, seq_len, query_tile):
        last = min(seq_len, first + query_tile) - 1
        own += last // block - (first // w * w) // block + 1
        summary += -(-(last // w * w // c) // block)
    return own, summary


def attention_flops_per_sequence(model: dict, seq_len: int) -> float:
    """The held heads' projections (q, k, v, o), the summariser, and for every
    seen pair the scores over D and the values over D."""
    d, h, dh = model["hidden_size"], model["num_attention_heads"], model["head_dim"]
    chunked = seq_len // model["chunk_size"] * model["chunk_size"]
    return (seq_len * 4 * 2.0 * d * h * dh + chunked * h * 3 * 2.0 * dh
            + h * sum(pairs_seen(model, seq_len)) * 2 * 2.0 * dh)


def mlp_flops_per_token(model: dict) -> float:
    return 2.0 * model["hidden_size"] * 3 * model["intermediate_size"]


def head_flops_per_token(model: dict) -> float:
    return 2.0 * model["hidden_size"] * model["num_pred_heads"] * model["vocab_size"]


def forward_flops_by_part(model: dict, batch: int, seq_len: int) -> dict:
    layers = model["num_hidden_layers"]
    return {"attention": layers * batch * attention_flops_per_sequence(model, seq_len),
            "dense_mlp": layers * batch * seq_len * mlp_flops_per_token(model),
            "head": batch * seq_len * head_flops_per_token(model)}


def step_flops(model: dict, batch: int, seq_len: int) -> float:
    return 3.0 * sum(forward_flops_by_part(model, batch, seq_len).values())


def attention_kernel_ops_and_bytes(model: dict, batch: int, seq_len: int) -> dict:
    """What a step asks of the attention kernels, over the layers held.
    Operations: for each seen pair of a head, own key or summary alike, the
    forward's two products (q k^T and p v over D) and the backward's four the
    algorithm needs (dv, dp, dk, dq; the kernel rebuilds the scores as a
    fifth: not needed, not counted): 12 D a pair. Bytes, in the compute
    type's 2: the arrays of T rows (forward q in and o out; backward q, o, do
    in and dq out) and of T + T / c rows (forward k, v in; backward k, v in
    and dk, dv out), each once."""
    h, dh = model["num_attention_heads"], model["head_dim"]
    calls = model["num_hidden_layers"] * batch * h
    keys = seq_len + seq_len // model["chunk_size"]
    return {"ops": calls * sum(pairs_seen(model, seq_len)) * 12.0 * dh,
            "bytes": calls * dh * 6 * (seq_len + keys) * 2.0}
