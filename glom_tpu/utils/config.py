"""Configuration dataclasses.

The reference's entire "config system" is the six `Glom.__init__` kwargs
(glom_pytorch/glom_pytorch.py:76-83) plus two forward kwargs. Those six are
preserved verbatim in `GlomConfig`; everything else (training, mesh, backend)
layers around them without touching the model contract.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union


@dataclasses.dataclass(frozen=True)
class GlomConfig:
    """Model hyperparameters — field-for-field the reference constructor."""

    dim: int = 512
    levels: int = 6
    image_size: int = 224
    patch_size: int = 14
    consensus_self: bool = False
    local_consensus_radius: int = 0
    # Extensions beyond the reference kwargs (defaults match its hardcoded values):
    mult: int = 4  # FFW expansion, reference hardcodes 4
    channels: int = 3  # reference hardcodes RGB

    def __post_init__(self):
        if self.image_size % self.patch_size != 0:
            raise ValueError(
                f"image_size {self.image_size} not divisible by patch_size {self.patch_size}"
            )
        if self.levels < 2:
            raise ValueError("levels must be >= 2 (top-down net needs levels-1 groups)")

    @property
    def num_patches_side(self) -> int:
        return self.image_size // self.patch_size

    @property
    def num_patches(self) -> int:
        return self.num_patches_side ** 2

    @property
    def patch_dim(self) -> int:
        return self.patch_size * self.patch_size * self.channels

    @property
    def default_iters(self) -> int:
        # "twice the levels, for information to propagate up and back down"
        # (reference :105)
        return 2 * self.levels


@dataclasses.dataclass(frozen=True)
class HybridLMConfig:
    """A hybrid language model (models/hybrid_lm.py): a stack of pre-norm
    residual layers, each one mixer by `hybrid_override_pattern` — `M` a
    Mamba-2 mixer, `*` grouped-query causal attention, `E` a latent mixture
    of experts with one shared expert. Field names are those of the
    published `nemotron_h` config.json; the defaults are
    NVIDIA-Nemotron-3-Super-120B-A12B-BF16's.

    The counts are of what THIS chip holds of a layer (its share of a
    deployment that divides each layer over several chips): experts
    `expert_offset .. expert_offset + n_routed_experts` of the
    `n_routed_experts_total` the router scores, `mamba_num_heads` heads in
    `n_groups` groups, `num_attention_heads` query heads over
    `num_key_value_heads` KV heads, `vocab_size` rows of the embedding and
    the head, layers `layer_offset .. layer_offset + num_hidden_layers` of
    the pattern. Widths are never a share."""

    hidden_size: int = 4096
    hybrid_override_pattern: str = (
        "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
        "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME"
    )
    layer_offset: int = 0
    num_hidden_layers: int = 88
    # How many layers the published stack has: the out-projections' initial
    # values are scaled by 1/sqrt(2 * this) (rescale_prenorm_residual).
    num_hidden_layers_total: int = 88
    layer_norm_epsilon: float = 1e-5
    vocab_size: int = 131072
    # E: latent mixture of experts
    n_routed_experts: int = 512
    n_routed_experts_total: int = 512
    expert_offset: int = 0
    num_experts_per_tok: int = 22
    moe_latent_size: int = 1024
    moe_intermediate_size: int = 2688
    moe_shared_expert_intermediate_size: int = 5376
    routed_scaling_factor: float = 5.0
    # M: Mamba-2
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # *: grouped-query attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    # the training sequence: tokens of one packed row of the batch
    seq_len: int = 8192

    def __post_init__(self):
        if set(self.pattern) - set("ME*") or len(self.pattern) != self.num_hidden_layers:
            raise ValueError(
                f"layers {self.layer_offset}..{self.layer_offset + self.num_hidden_layers}"
                f" of a pattern of {len(self.hybrid_override_pattern)} over 'M', 'E', '*'"
            )
        if self.mamba_num_heads % self.n_groups or (
            self.num_attention_heads % self.num_key_value_heads
        ):
            raise ValueError("heads must divide evenly over their groups")
        if self.expert_offset + self.n_routed_experts > self.n_routed_experts_total:
            raise ValueError("the experts held lie outside the router's width")

    @property
    def pattern(self) -> str:
        """The mixers of the layers held here, in order."""
        return self.hybrid_override_pattern[
            self.layer_offset:self.layer_offset + self.num_hidden_layers
        ]

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def mamba_conv_dim(self) -> int:
        return self.mamba_inner + 2 * self.n_groups * self.ssm_state_size


@dataclasses.dataclass(frozen=True)
class SambaYConfig:
    """A SambaY decoder-hybrid-decoder language model (models/sambay.py):
    pre-norm residual layers `h += mixer(LN(h)); h += MLP(LN(h))` whose
    mixer is a function of the published layer index `i` of
    `num_hidden_layers_total` = N and of nothing else (`layer_kind`): Mamba-1
    on even `i <= N/2`, differential attention under a window of
    `sliding_window` keys on odd `i < N/2`, the same attention causal and
    full at `i = N/2 + 1`, and above it Gated Memory Units (even) that read
    layer N/2's scan output and cross-attention (odd) that reads layer
    N/2 + 1's keys and values. Field names are those of the published
    `phi4flash` config.json where it has them; the defaults are
    Phi-4-mini-flash-reasoning's. The Mamba sizes, `head_dim` and the
    `time_step_*` of the initial values are not in that file: they are the
    published modeling code's constants.

    `num_hidden_layers` layers from `layer_offset` on and `vocab_size` rows
    of the tied embedding are what THIS chip holds (a pipeline stage, and a
    row slice of the embedding). Widths and head counts are never a share."""

    hidden_size: int = 2560
    intermediate_size: int = 10240
    layer_norm_eps: float = 1e-5
    mb_per_layer: int = 2
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    vocab_size: int = 200064
    layer_offset: int = 0
    num_hidden_layers: int = 32
    num_hidden_layers_total: int = 32
    # Mamba-1
    mamba_expand: int = 2
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # the training sequence: tokens of one packed row of the batch
    seq_len: int = 8192

    def __post_init__(self):
        n, first = self.num_hidden_layers_total, self.layer_offset
        held = range(first, first + self.num_hidden_layers)
        if not held or first < 0 or held[-1] >= n or n % 2 or self.mb_per_layer != 2:
            raise ValueError(f"layers {first}..{first + self.num_hidden_layers} of {n}, "
                             "an even count with a Mamba layer every second one")
        kinds = self.kinds
        if ("G" in kinds and n // 2 not in held) or ("X" in kinds and n // 2 + 1 not in held):
            raise ValueError(
                f"layers {first}..{held[-1]} read layer {n // 2}'s memory or layer "
                f"{n // 2 + 1}'s keys and values, which are not among them")
        if self.num_attention_heads % self.num_key_value_heads or (
                self.num_key_value_heads % 2) or self.hidden_size % self.num_attention_heads:
            raise ValueError("query heads divide over KV heads, and KV heads pair up")

    @property
    def kinds(self) -> str:
        """The mixers of the layers held here, in order: `M` Mamba-1, `W`
        window attention, `F` full attention, `G` Gated Memory Unit, `X`
        cross-attention."""
        return "".join(layer_kind(i, self.num_hidden_layers_total)
                       for i in range(self.layer_offset,
                                      self.layer_offset + self.num_hidden_layers))

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def mamba_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def mamba_dt_rank(self) -> int:
        return -(-self.hidden_size // 16)


def layer_kind(i: int, n: int) -> str:
    """The mixer of published layer `i` of `n` (SambaYConfig's letters), with
    a Mamba layer every second one (the source's `mb_per_layer` = 2, which
    SambaYConfig holds a configuration to)."""
    if i % 2 == 0:
        return "M" if i <= n // 2 else "G"
    if i < n // 2:
        return "W"
    return "F" if i == n // 2 + 1 else "X"


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    """A Laguna language model (models/laguna.py, `model_type: laguna`):
    pre-norm residual layers `h += Attn(RMSNorm(h)); h += MLP(RMSNorm(h))`
    whose attention is, by the published layer index, causal and full (`F`
    in `layer_types`: `num_attention_heads` query heads, the first
    `partial_rotary_factor` of a head's dimensions rotated by YaRN
    frequencies) or under a window of `sliding_window` keys (`S`:
    `num_sliding_attention_heads` query heads, every dimension rotated by the
    default frequencies), over `num_key_value_heads` KV heads either way, with
    a sigmoid gate a query head on its output; and whose MLP is a dense
    SwiGLU (`D` in `mlp_layer_types`) or a router over `num_experts_total`
    SwiGLU experts, `num_experts_per_tok` a token, beside one shared expert
    (`E`). Field names are those of the published config.json where it has
    them (its per-layer lists are the two strings and the two head counts;
    its `rope_parameters` the `rope_*` and `yarn_*` fields); the defaults
    are Laguna-XS.2's.

    `num_hidden_layers` layers from `layer_offset` on, experts
    `expert_offset .. expert_offset + num_experts` of the `num_experts_total`
    the router scores, and `vocab_size` rows of the embedding and of the
    untied head are what THIS chip holds. Widths and head counts are never a
    share. `moe_rung_loads` sizes the routed experts' small row count in
    balanced loads (`hybrid_lm.row_rungs`; the default is `hybrid_lm.RUNG_LOADS`,
    one rung of two): a job whose router is out of balance says more, and pays
    for the rows."""

    hidden_size: int = 2048
    intermediate_size: int = 8192
    rms_norm_eps: float = 1e-6
    vocab_size: int = 100352
    layer_types: str = "FSSS" * 10
    mlp_layer_types: str = "D" + "E" * 39
    layer_offset: int = 0
    num_hidden_layers: int = 40
    num_hidden_layers_total: int = 40
    # attention
    num_attention_heads: int = 48
    num_sliding_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512
    partial_rotary_factor: float = 0.5
    rope_theta_full: float = 500000.0
    rope_theta_sliding: float = 10000.0
    yarn_factor: float = 64.0
    yarn_original_max_position_embeddings: int = 4096
    yarn_beta_fast: float = 64.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.4158883083359672
    # experts
    num_experts: int = 256
    num_experts_total: int = 256
    expert_offset: int = 0
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    moe_routed_scaling_factor: float = 2.5
    moe_rung_loads: int = 2
    # the training sequence: tokens of one packed row of the batch
    seq_len: int = 8192

    def __post_init__(self):
        n, first = self.num_hidden_layers_total, self.layer_offset
        if (len(self.layer_types) != n or len(self.mlp_layer_types) != n
                or set(self.layer_types) - set("FS") or set(self.mlp_layer_types) - set("DE")):
            raise ValueError(f"{n} layers want {n} letters of 'F', 'S' and of 'D', 'E'")
        if first < 0 or self.num_hidden_layers < 1 or first + self.num_hidden_layers > n:
            raise ValueError(f"layers {first}..{first + self.num_hidden_layers} of {n}")
        if (self.num_attention_heads % self.num_key_value_heads
                or self.num_sliding_attention_heads % self.num_key_value_heads):
            raise ValueError("query heads must divide evenly over the KV heads")
        if self.expert_offset + self.num_experts > self.num_experts_total:
            raise ValueError("the experts held lie outside the router's width")
        if self.rotary_dim("F") % 2 or self.rotary_dim("F") > self.head_dim:
            raise ValueError("the rotated part of a head is an even count of its dimensions")

    @property
    def kinds(self) -> Tuple[Tuple[str, str], ...]:
        """(attention, MLP) letters of the layers held here, in order."""
        held = slice(self.layer_offset, self.layer_offset + self.num_hidden_layers)
        return tuple(zip(self.layer_types[held], self.mlp_layer_types[held]))

    def heads(self, attention: str) -> int:
        """Query heads of a layer of attention kind `F` or `S`."""
        return self.num_attention_heads if attention == "F" else self.num_sliding_attention_heads

    def rotary_dim(self, attention: str) -> int:
        return int(self.head_dim * self.partial_rotary_factor) if attention == "F" else (
            self.head_dim)

    # what `hybrid_lm`'s router, sort and row ladder read of a configuration
    @property
    def n_routed_experts(self) -> int:
        return self.num_experts

    @property
    def n_routed_experts_total(self) -> int:
        return self.num_experts_total

    @property
    def routed_scaling_factor(self) -> float:
        return self.moe_routed_scaling_factor


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    """A Kimi Linear language model (models/kimi_linear.py, `model_type:
    kimi_linear`): pre-norm residual layers `h += Mixer(RMSNorm(h)); h +=
    MLP(RMSNorm(h))` whose mixer is, by the published layer number, Kimi
    Delta Attention (`K` in `layer_types`: `linear_num_heads` heads whose
    state is a `linear_head_dim` x `linear_head_dim` matrix, decayed a key
    channel at a time and corrected by a rank-one delta-rule update, behind
    three causal convolutions of `short_conv_kernel_size`) or latent
    attention without positions (`A`: `num_attention_heads` heads, queries
    of `qk_nope_head_dim + qk_rope_head_dim` straight from the hidden state,
    keys and values expanded from a normed latent of `kv_lora_rank` beside a
    `qk_rope_head_dim`-wide key part shared by the heads, nothing rotated:
    `mla_use_nope`); and whose MLP is a dense SwiGLU (the first
    `first_k_dense_replace` published layers) or a router over
    `num_experts_total` SwiGLU experts, `num_experts_per_token` a token,
    beside `num_shared_experts` shared ones. Field names are those of the
    published config.json where it has them (`linear_num_heads`,
    `linear_head_dim` and `short_conv_kernel_size` are its
    `linear_attn_config`'s `num_heads`, `head_dim` and the same;
    `layer_types` spells its `kda_layers` and `full_attn_layers`, a letter a
    layer from published layer 1 on); the defaults are
    Kimi-Linear-48B-A3B-Instruct's.

    `num_hidden_layers` layers from `layer_offset` on (0 is published layer
    1), experts `expert_offset .. expert_offset + num_experts` of the
    `num_experts_total` the router scores, and `vocab_size` rows of the
    embedding and of the untied head are what THIS chip holds. Widths and
    head counts are never a share. `moe_rung_loads` is `LagunaConfig`'s."""

    hidden_size: int = 2304
    intermediate_size: int = 9216
    rms_norm_eps: float = 1e-5
    vocab_size: int = 163840
    layer_types: str = "KKKA" * 6 + "KKA"
    first_k_dense_replace: int = 1
    layer_offset: int = 0
    num_hidden_layers: int = 27
    num_hidden_layers_total: int = 27
    # Kimi Delta Attention
    linear_num_heads: int = 32
    linear_head_dim: int = 128
    short_conv_kernel_size: int = 4
    # latent attention
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # experts
    num_experts: int = 256
    num_experts_total: int = 256
    expert_offset: int = 0
    num_experts_per_token: int = 8
    moe_intermediate_size: int = 1024
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.446
    moe_rung_loads: int = 2
    # the training sequence: tokens of one packed row of the batch
    seq_len: int = 8192

    def __post_init__(self):
        n, first = self.num_hidden_layers_total, self.layer_offset
        if len(self.layer_types) != n or set(self.layer_types) - set("KA"):
            raise ValueError(f"{n} layers want {n} letters of 'K' and 'A'")
        if first < 0 or self.num_hidden_layers < 1 or first + self.num_hidden_layers > n:
            raise ValueError(f"layers {first}..{first + self.num_hidden_layers} of {n}")
        if self.expert_offset + self.num_experts > self.num_experts_total:
            raise ValueError("the experts held lie outside the router's width")

    @property
    def kinds(self) -> Tuple[Tuple[str, str], ...]:
        """(mixer, MLP) letters of the layers held here, in order: `K` or `A`,
        and `D` dense or `E` experts."""
        held = range(self.layer_offset, self.layer_offset + self.num_hidden_layers)
        return tuple((self.layer_types[i], "D" if i < self.first_k_dense_replace else "E")
                     for i in held)

    # what `hybrid_lm`'s router, sort and row ladder read of a configuration
    @property
    def n_routed_experts(self) -> int:
        return self.num_experts

    @property
    def n_routed_experts_total(self) -> int:
        return self.num_experts_total

    @property
    def num_experts_per_tok(self) -> int:
        return self.num_experts_per_token


@dataclasses.dataclass(frozen=True)
class EvaByteConfig:
    """An EvaByte language model (models/evabyte.py, `model_type: evabyte`,
    `attention_class: eva`): a byte-level model of pre-norm residual layers
    `h += Attn(RMSNorm(h)); h += MLP(RMSNorm(h))` over a float32 residual
    stream (`fp32_skip_add`). Attention is EVA: a query at t in the aligned
    window n = t // `window_size` attends, in one softmax, the keys of its own
    window up to t and one learned summary key and value for every chunk of
    `chunk_size` positions of the windows before it; multi-head (a key and
    value head a query head), every dimension of a head rotated at
    `rope_theta`. The norms multiply by 1 + weight (`norm_add_unit_offset`).
    The MLP is a dense SwiGLU of `intermediate_size`. After the stack
    `num_pred_heads` heads predict the bytes at t + 1 .. t + `num_pred_heads`
    from position t, logits in float32 (`fp32_logits`). Field names are the
    published config.json's; the defaults are EvaByte's (6.5B).

    `num_hidden_layers` layers from `layer_offset` on and `num_attention_heads`
    of the `num_attention_heads_total` heads a layer (with their rows of the
    out-projection) are what THIS chip holds; the vocabulary's 320 rows are
    never sliced. Widths are never a share."""

    hidden_size: int = 4096
    intermediate_size: int = 11008
    rms_norm_eps: float = 1e-5
    vocab_size: int = 320
    layer_offset: int = 0
    num_hidden_layers: int = 32
    num_hidden_layers_total: int = 32
    # EVA attention
    num_attention_heads: int = 32
    num_attention_heads_total: int = 32
    head_dim: int = 128
    window_size: int = 2048
    chunk_size: int = 16
    rope_theta: float = 100000.0
    # the byte-prediction heads, side by side in the head's columns
    num_pred_heads: int = 8
    # the training sequence: bytes of one packed row of the batch
    seq_len: int = 32768

    def __post_init__(self):
        n, first = self.num_hidden_layers_total, self.layer_offset
        if first < 0 or self.num_hidden_layers < 1 or first + self.num_hidden_layers > n:
            raise ValueError(f"layers {first}..{first + self.num_hidden_layers} of {n}")
        if not 1 <= self.num_attention_heads <= self.num_attention_heads_total:
            raise ValueError("the heads held are at least one and at most all")
        if self.window_size % self.chunk_size or self.head_dim % 2:
            raise ValueError("a window is whole chunks and a head's dimensions pair up")
        if self.num_pred_heads < 1:
            raise ValueError("at least the next byte is predicted")


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    """An Ouro looped language model (models/ouro.py, `model_type: ouro`; "Scaling
    Latent Reasoning via Looped Language Models"): ONE stack of layers run
    `total_ut_steps` times over the same weights. A layer is a sandwich of four
    RMSNorms round causal attention (every dimension of a head rotated at
    `rope_theta`, no bias, no window) and a dense SwiGLU of `intermediate_size`:
    a norm before each branch and one on its output before the add. The final
    norm closes every pass: its output is what the untied head reads and what
    the next pass starts from. An exit gate (a sigmoid of one learned direction
    of that output) gives each position a distribution over the passes, and the
    loss is the passes' cross-entropies weighed by it less `exit_entropy_beta`
    times its entropy. Field names are the published config.json's; the
    defaults are Ouro-2.6B's.

    `num_hidden_layers` layers from `layer_offset` on are what THIS chip holds
    (a pipeline stage round which a row passes `total_ut_steps` times); the
    heads and the vocabulary are whole. Widths are never a share, and the
    passes are never cut."""

    hidden_size: int = 2048
    intermediate_size: int = 5632
    rms_norm_eps: float = 1e-6
    vocab_size: int = 49152
    layer_offset: int = 0
    num_hidden_layers: int = 48
    num_hidden_layers_total: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int = 128
    rope_theta: float = 1000000.0
    # the loop: passes over the held layers, and the entropy term's weight in the loss
    total_ut_steps: int = 4
    exit_entropy_beta: float = 0.05
    # the training sequence: tokens of one packed row of the batch
    seq_len: int = 4096

    def __post_init__(self):
        n, first = self.num_hidden_layers_total, self.layer_offset
        if first < 0 or self.num_hidden_layers < 1 or first + self.num_hidden_layers > n:
            raise ValueError(f"layers {first}..{first + self.num_hidden_layers} of {n}")
        if self.num_attention_heads % self.num_key_value_heads or self.head_dim % 2:
            raise ValueError("query heads share KV heads evenly and a head's dimensions pair up")
        if self.total_ut_steps < 1:
            raise ValueError("the stack runs at least once")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Parallelism layout. Axis sizes of 1 disable an axis.

    data:  batch sharding (DP) — gradient allreduce over ICI
    seq:   patch-axis sharding (SP) — ring / halo consensus
    model: dim sharding (TP) of the FFW weights

    num_slices > 1 marks a multi-slice (DCN-connected) topology: the data
    axis is laid out slice-major, so its outermost num_slices-way split
    rides DCN while everything inside a slice (the inner data split, seq,
    model) rides ICI. Axis names and logical shape are unchanged — XLA
    decomposes the data-axis allreduce hierarchically from the device
    placement (mesh_utils.create_hybrid_device_mesh).
    """

    data: int = 1
    seq: int = 1
    model: int = 1
    num_slices: int = 1

    def __post_init__(self):
        if self.num_slices > 1 and self.data % self.num_slices != 0:
            raise ValueError(
                f"data axis {self.data} not divisible by num_slices "
                f"{self.num_slices} (the DCN split is the outer data axis)"
            )

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return ("data", "seq", "model")

    @property
    def shape(self) -> Tuple[int, ...]:
        return (self.data, self.seq, self.model)

    @property
    def num_devices(self) -> int:
        return self.data * self.seq * self.model


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Batched-inference serving policy (glom_tpu/serve, docs/SERVING.md).

    The engine compiles ONE program per batch bucket ahead of traffic
    (warmup) and the batcher pads every dispatched batch up to the
    smallest admitting bucket — requests never trigger a mid-traffic
    recompile, the serving-side analog of the trainer's static-shape
    discipline."""

    # Ascending batch-size buckets the engine precompiles; a dispatch of n
    # requests pads to the smallest bucket >= n. The largest bucket is the
    # dispatch ceiling.
    buckets: Tuple[int, ...] = (1, 2, 4, 8)
    # Admission policy: dispatch when max_batch requests are waiting, or
    # when the OLDEST waiting request has aged max_delay_ms — whichever
    # comes first (latency floor vs throughput ceiling).
    max_batch: int = 8
    max_delay_ms: float = 5.0
    # Bounded request queue: submissions beyond this depth are SHED
    # immediately (backpressure — a full queue means the engine is already
    # saturated; queueing deeper only grows tail latency).
    queue_depth: int = 64
    # Forward iteration budget: an int pins the count, None uses the model
    # default (2L), "auto" enables consensus early exit (serve/early_exit:
    # up to max_auto_iters updates, stopping when no level's agreement
    # moves more than exit_threshold between iterations).
    iters: Union[int, str, None] = None  # int | "auto" | None
    exit_threshold: float = 1e-3
    min_iters: int = 1
    max_auto_iters: Optional[int] = None  # None -> model default (2L)
    # Two-tier early exit (serve/early_exit.glom_forward_tiered,
    # docs/SERVING.md "Continuation queue"): the bucket exits once this
    # FRACTION of its valid rows has individually converged (per-row
    # witness; ceil(quorum * n_valid) rows). 1.0 = every valid row must
    # converge before the bucket exits (the strictest quorum — batch-level
    # behavior). Unconverged stragglers at bucket exit re-bucket into the
    # batcher's continuation queue — carried as warm column state with the
    # REMAINING iteration budget — up to max_continuations hops; 0 hops
    # disables re-bucketing (stragglers resolve with the state they have,
    # exactly the pre-two-tier contract).
    exit_quorum: float = 1.0
    max_continuations: int = 0
    # Serve mesh (parallel/serve_mesh.py): axis sizes > 1 route every
    # bucket signature through the manual shard_map forward over
    # (data, seq) — batch rows sharded over 'data', the patch axis over
    # 'seq' — with the early-exit witness collectives legal inside the
    # while_loop body. Every bucket must be divisible by mesh_data (the
    # engine validates; a non-divisible bucket would silently pad-shard).
    mesh_data: int = 1
    mesh_seq: int = 1
    compute_dtype: str = "float32"  # "bfloat16" for MXU-native serving
    use_pallas: bool = False
    # Donate the input buffer to each compiled call so XLA reuses it for
    # outputs (None = auto: on TPU only — CPU ignores donation noisily).
    donate: Optional[bool] = None
    # Transient-dispatch retry (glom_tpu/resilience/retry.py): a failed
    # dispatch retries up to dispatch_retries times with exponential
    # backoff from retry_backoff_ms — UNLESS the watchdog says the backend
    # is down, which fails fast (never retry into a dead backend). 0
    # disables. Caller bugs (ValueError/TypeError) never retry.
    dispatch_retries: int = 2
    retry_backoff_ms: float = 25.0
    # Degradation ladder (glom_tpu/resilience/ladder.py, opt-in via
    # DynamicBatcher(ladder=...) — serve/cli.py --ladder wires it): under
    # queue pressure or a flapping backend, step down normal ->
    # capped-iters -> capped-buckets -> shed instead of jumping straight
    # to shed. degraded_iters None -> half the model budget (floor 1);
    # degraded_max_batch None -> half max_batch (floor 1).
    ladder: bool = False
    degraded_iters: Optional[int] = None
    degraded_max_batch: Optional[int] = None
    ladder_high_water: float = 0.75  # queue fill that steps DOWN a rung
    ladder_low_water: float = 0.25   # queue fill that steps back UP
    # Streaming warm-start column cache (glom_tpu/serve/column_cache.py,
    # docs/SERVING.md "Streaming"): requests carrying a session_id write
    # their converged [n, L, d] columns back under the session key and the
    # NEXT frame of the stream dispatches warm from that state (the
    # engine's warm levels0 signature), exiting iters="auto" in a fraction
    # of the cold budget. column_cache_bytes is the HARD residency budget
    # (LRU eviction, priced per entry by column_state_bytes — the
    # live-bytes model); 0 disables streaming entirely. column_cache_ttl_s
    # expires a quiet stream's entry at lookup (None = no expiry); entries
    # are additionally invalidated the moment a dispatch on their source
    # engine fails, so stale or dead-engine state never warm-starts.
    column_cache_bytes: int = 0
    column_cache_ttl_s: Optional[float] = None
    # Paged column memory (glom_tpu/serve/paged_columns.py, docs/SERVING.md
    # "Paged column memory"): page_pool_pages > 0 preallocates ONE
    # device-resident HBM buffer of [page_pool_pages, page_tokens, L, d]
    # per engine — the column-state page pool. Cached session columns then
    # live in pool pages instead of host arrays: warm dispatches assemble
    # levels0 IN-GRAPH via a page-index take (zero host<->device levels0
    # transfer on the warm path) and write-back on resolve copies the
    # converged columns device-to-device into owned pages. 0 keeps the
    # PR 8 host-array cache (every warm dispatch re-uploads its columns).
    # page_tokens is the page granularity in patch tokens; 0 resolves to
    # the largest divisor of num_patches <= 64 (resolve_page_tokens — a
    # page must tile the full-resolution row so the bucket route's
    # [bucket, n] layout maps onto whole pages).
    page_pool_pages: int = 0
    page_tokens: int = 0
    # In-place pool aliasing (docs/SERVING.md "Pool aliasing"): True
    # promotes pool write-backs from copy-on-write buffer swaps (every
    # write re-materializes the WHOLE pool buffer) to DONATED in-graph
    # scatter updates — the write-back aliases the pool's own pages, so
    # pool bytes moved per write drop from pool_bytes to the written
    # pages only. The dispatch/write-back serialization seam: dispatches
    # hold a READ PIN on the buffer snapshot (acquire_read/release_read)
    # and every aliased write advances the pool EPOCH; a write that finds
    # pins outstanding falls back to CoW LOUDLY (alias_fallback event +
    # counter) so an in-flight dispatch never reads a donated buffer.
    # False (default) keeps the CoW discipline byte-for-byte.
    pool_aliasing: bool = False
    # Ragged admission (docs/SERVING.md "Ragged admission"): requests with
    # DIFFERING patch counts (mixed resolutions/aspect ratios) share one
    # dispatch sized by total PAGES instead of padding every row to the
    # worst-row bucket shape. ragged_pages is the ascending page-count
    # ladder the ragged signatures precompile (the page-axis analog of
    # `buckets`); empty resolves to buckets x pages-per-full-row. Requires
    # local_consensus_radius == 0 (the ragged window has no 2D coordinate
    # grid to build a radius mask from — the engine validates loudly).
    ragged: bool = False
    ragged_pages: Tuple[int, ...] = ()
    # Ragged consensus gather (serve/early_exit.py, docs/SERVING.md
    # "Block-banded ragged consensus"):
    #   "windowed"      — the row-windowed per-token gather (the PR 11
    #                     form): W k/v column states duplicated per TOKEN
    #                     per iteration;
    #   "banded"        — the page-blocked band: pages are the blocks,
    #                     each token attends within its row's page band
    #                     computed from the flat [T, L, d] state — the
    #                     duplicated working set shrinks page_tokens-fold
    #                     and the output is BITWISE the windowed route at
    #                     threshold 0 (the house parity rule; locked by
    #                     tests and the --banded-ab gate);
    #   "banded-pallas" — the streaming Pallas kernel
    #                     (kernels/banded_consensus.py) reading k/v pages
    #                     in place — kernel-parity TOLERANCE, like the
    #                     fused dense route; falls back to "banded" off
    #                     TPU.
    ragged_attention: str = "windowed"
    # Delta streaming (glom_tpu/serve/paged_columns.py, docs/SERVING.md
    # "Delta streaming"): instead of rewriting a session's whole [n, L, d]
    # column state every frame, each session keeps a paged BASE plus a
    # chain of frame-to-frame DELTAS — only pages whose column residual
    # exceeds delta_page_atol are stored (0.0 = exact: a page is "changed"
    # when any BIT differs). Reconstruction is base+Σdeltas resolved to an
    # effective page map and assembled in-graph by the same page-index
    # take the paged warm path already uses (zero levels0 H2D). The chain
    # compacts base <- base+Σdeltas device-to-device at delta_chain_cap.
    # delta_base_share aliases content-identical bases across sessions
    # (hash at write-back, refcounted pool pages — two cameras on one
    # scene pay for one base). delta_incremental routes warm frames
    # through glom_forward_incremental: the early-exit witness is seeded
    # from the INPUT delta's page support, so rows whose frame did not
    # change start pre-converged (min_iters floor still applies) and a
    # small perturbation converges in ~1-2 iters. Requires a page pool;
    # exclusive with ragged admission (bucket route only for now). Any
    # delta_page_atol > 0 mode stamps the tolerance on every record the
    # compare gate reads — threshold 0 stays BITWISE.
    delta_streaming: bool = False
    delta_page_atol: float = 0.0
    delta_chain_cap: int = 4
    delta_base_share: bool = True
    delta_incremental: bool = True
    # Sharded paged route (parallel/serve_mesh.py): how a paged warm
    # dispatch materializes pool pages across the 'data' shards.
    #   "pool"   — all_gather the WHOLE pool per dispatch (the PR 11
    #              provisioning bound);
    #   "needed" — exchange ONLY the pages the dispatch references via a
    #              registered psum_scatter (dp x rows x pages-per-row
    #              page payloads — the pad-free wire);
    #   "auto"   — pick whichever moves fewer bytes at the signature's
    #              static shapes (the compile trace records the choice).
    page_gather: str = "auto"
    # Engine REJOIN after recovery (docs/RESILIENCE.md): a fan-out engine
    # marked dead re-enters service only after rejoin_threshold
    # CONSECUTIVE successful probation health dispatches (stamped
    # engine_rejoin event); 0 keeps death terminal until restart (the
    # pre-PR 8 contract). rejoin_interval_ms paces the probation probes.
    rejoin_threshold: int = 0
    rejoin_interval_ms: float = 200.0
    # Request-scoped tracing (telemetry/tracectx.py, docs/OBSERVABILITY.md
    # "Request tracing"): submit() mints a trace_id/span_id per request
    # and every downstream serve record (dispatch, continuation, shed,
    # failover, retry, cache, resolve) carries the context, so
    # `python -m glom_tpu.telemetry trace` reconstructs the causal tree.
    # Default ON. False stamps the context keys as null (explicitly
    # untraced — the schema still lints).
    trace_requests: bool = True
    # Serve latency decomposition (docs/OBSERVABILITY.md, "Capacity
    # observatory"): every dispatch record splits latency_ms into
    # queue_wait / pack / h2d / device / resolve phase fields that sum to
    # it BIT-EXACTLY (and accumulate into the per-request resolve leaf),
    # so `telemetry trace` shows where each request's time went across
    # hops. Default ON; False stamps the phase keys as null and reverts
    # latency_ms to the bare engine dispatch wall (the pre-v7 reading).
    phase_split: bool = True
    # Per-collective wall-time on the serve mesh (telemetry/comm_time.py,
    # resolved by counters.resolve_collective_timing — the
    # telemetry_level discipline): "off" (default), "sampled" (every
    # collective_timing_interval-th dispatch re-dispatches each witness /
    # gather site as its own timed sub-graph), "full" (every execution
    # bracketed by dataflow-ordered io_callbacks, inserted at the AOT
    # compile). Single-device engines have no collectives: any mode
    # resolves to "off" there, stamped.
    collective_timing: str = "off"
    collective_timing_interval: int = 16
    # SLO-driven elastic serving (glom_tpu/serve/elastic.py,
    # docs/SERVING.md "Elastic serving"): elastic=True runs an Autoscaler
    # control loop next to the batcher that reads the live capacity
    # records (headroom) plus in-process SLO breaches and CHANGES the
    # fleet — scale-out spawns a fully-warmed engine replica at runtime
    # (admission opens only after precompile), scale-in gracefully drains
    # the least-loaded engine (stop admitting -> flush -> migrate cache
    # sessions -> release devices). False (the default) keeps the static
    # --engines N fleet byte-for-byte. The policy is windowed low/high
    # water with min-dwell hysteresis and a post-action cooldown, clamped
    # to [min_engines, max_engines]:
    #   * worst eligible headroom < elastic_low_water continuously for
    #     elastic_dwell_s (or any armed upper-bound SLO breach —
    #     elastic_p99_ms / elastic_shed_rate, None = not armed) scales
    #     OUT; a breach also VETOES scale-in (breach precedence);
    #   * worst eligible headroom > elastic_high_water continuously for
    #     elastic_dwell_s scales IN (drain the max-headroom engine).
    # elastic_interval_s paces the control ticks; elastic_window_s is
    # the signal window the policy and its SLO monitor share.
    elastic: bool = False
    min_engines: int = 1
    max_engines: int = 4
    elastic_low_water: float = 0.15
    elastic_high_water: float = 0.6
    elastic_dwell_s: float = 2.0
    elastic_cooldown_s: float = 5.0
    elastic_window_s: float = 10.0
    elastic_interval_s: float = 0.5
    elastic_p99_ms: Optional[float] = None
    elastic_shed_rate: Optional[float] = None
    # Drained-husk retention (schema v9, docs/OBSERVABILITY.md "Workload
    # observatory"): a scale-in leaves the drained engine in the summary
    # as an evidence husk. None (both defaults) retains every husk
    # forever — the pre-v9 shape. husk_max keeps at most N husks (oldest
    # retire first); husk_max_age_s retires a husk once it has been
    # drained that long. Retirement folds the husk's counters into the
    # summary's husks_retired nest and stamps one engine_husk_retired
    # event, so conservation still reconciles after the trim.
    husk_max: Optional[int] = None
    husk_max_age_s: Optional[float] = None
    # Anticipatory autoscaling (schema v10, docs/SERVING.md "Anticipatory
    # autoscaling"): elastic_anticipatory=True lets the policy act on the
    # forecast load at `now + spawn_lead_time` instead of the already-
    # breached present — a positive predicted deficit over the fleet's
    # usable capacity (measured service rate x elastic_target_utilization)
    # arms scale-out and vetoes scale-in. The anticipatory signal only
    # fires once BOTH models have matured (a scored forecast_abs_err and
    # spawn-lead evidence); until then the policy is the reactive PR 14
    # semantics bit-for-bit. Every decision stamps its evidence bundle
    # (`python -m glom_tpu.telemetry audit` replays it).
    elastic_anticipatory: bool = False
    elastic_target_utilization: float = 0.8
    # Warm-pool spares: N pre-spawned, fully-warmed engine replicas held
    # OUTSIDE admission (never registered with the batcher, so a spare is
    # not a husk and serves no traffic). Scale-out promotes a spare at
    # ~0 spawn cost; scale-in demotes the drained engine back into the
    # pool instead of releasing its devices. Spare spawn latencies feed
    # the spawn-lead-time model before the first live scale-out.
    warm_pool: int = 0
    # Multi-tenant QoS (glom_tpu/serve/qos.py, docs/SERVING.md "SLO
    # classes"): named SLO classes — e.g. ("premium:weight=8,p99_ms=150",
    # "standard:weight=3", "batch:weight=1,shed_rate=0.5") — turn the
    # batcher's shared FIFO into a deficit-weighted-fair class scheduler
    # with PER-CLASS bounded lanes (batch backpressure can never fill
    # premium's lane), class-aware ladder gates (the first class in the
    # shed order degrades and sheds a rung early), class-scoped SLO rules
    # ("p99_ms[premium]=X"), and per-class decision evidence the audit
    # weighs. None (the default) keeps the classless batcher and policy
    # byte-for-byte. slo_default_class labels unclassed submits (default:
    # "standard" when declared, else the highest-weight class);
    # slo_shed_order overrides the ascending-weight default; the
    # starvation floor is each lower class's guaranteed pick share under
    # strict-priority contention.
    slo_classes: Optional[Tuple[str, ...]] = None
    slo_default_class: Optional[str] = None
    slo_shed_order: Optional[Tuple[str, ...]] = None
    slo_starvation_floor: float = 0.05

    def __post_init__(self):
        if not self.buckets:
            raise ValueError("buckets must be non-empty")
        if list(self.buckets) != sorted(set(self.buckets)):
            raise ValueError(f"buckets {self.buckets} must be strictly ascending")
        if any(b < 1 for b in self.buckets):
            raise ValueError(f"buckets {self.buckets} must be >= 1")
        if self.max_batch > max(self.buckets):
            raise ValueError(
                f"max_batch {self.max_batch} exceeds the largest bucket "
                f"{max(self.buckets)} (the dispatch ceiling)"
            )
        if self.max_batch < 1:
            raise ValueError(f"max_batch {self.max_batch} must be >= 1")
        if self.queue_depth < 1:
            raise ValueError(f"queue_depth {self.queue_depth} must be >= 1")
        if self.max_delay_ms < 0:
            raise ValueError(f"max_delay_ms {self.max_delay_ms} must be >= 0")
        if self.iters is not None and self.iters != "auto":
            if not isinstance(self.iters, int) or self.iters < 1:
                raise ValueError(
                    f"iters={self.iters!r}: an int >= 1, 'auto', or None"
                )
        if self.exit_threshold < 0:
            raise ValueError(f"exit_threshold {self.exit_threshold} must be >= 0")
        if self.min_iters < 1:
            raise ValueError(f"min_iters {self.min_iters} must be >= 1")
        if not 0.0 < self.exit_quorum <= 1.0:
            raise ValueError(
                f"exit_quorum {self.exit_quorum} outside (0, 1] (1.0 = all "
                "valid rows must converge before the bucket exits)"
            )
        if self.max_continuations < 0:
            raise ValueError(
                f"max_continuations {self.max_continuations} must be >= 0"
            )
        if self.mesh_data < 1 or self.mesh_seq < 1:
            raise ValueError(
                f"mesh_data={self.mesh_data} mesh_seq={self.mesh_seq}: "
                "serve mesh axes must be >= 1"
            )
        if self.mesh_data > 1 and any(
            b % self.mesh_data for b in self.buckets
        ):
            raise ValueError(
                f"every bucket {self.buckets} must be divisible by "
                f"mesh_data={self.mesh_data} (batch rows shard over 'data')"
            )
        if self.dispatch_retries < 0:
            raise ValueError(
                f"dispatch_retries {self.dispatch_retries} must be >= 0"
            )
        if self.retry_backoff_ms < 0:
            raise ValueError(
                f"retry_backoff_ms {self.retry_backoff_ms} must be >= 0"
            )
        if self.degraded_iters is not None and self.degraded_iters < 1:
            raise ValueError(
                f"degraded_iters {self.degraded_iters} must be >= 1 or None"
            )
        if self.degraded_max_batch is not None and self.degraded_max_batch < 1:
            raise ValueError(
                f"degraded_max_batch {self.degraded_max_batch} must be >= 1 "
                "or None"
            )
        if not 0.0 <= self.ladder_low_water < self.ladder_high_water <= 1.0:
            raise ValueError(
                f"need 0 <= ladder_low_water ({self.ladder_low_water}) < "
                f"ladder_high_water ({self.ladder_high_water}) <= 1"
            )
        if self.column_cache_bytes < 0:
            raise ValueError(
                f"column_cache_bytes {self.column_cache_bytes} must be >= 0 "
                "(0 disables the streaming column cache)"
            )
        if self.column_cache_ttl_s is not None and self.column_cache_ttl_s <= 0:
            raise ValueError(
                f"column_cache_ttl_s {self.column_cache_ttl_s} must be > 0 "
                "or None"
            )
        if self.page_pool_pages < 0:
            raise ValueError(
                f"page_pool_pages {self.page_pool_pages} must be >= 0 "
                "(0 disables the device-resident column page pool)"
            )
        if self.page_tokens < 0:
            raise ValueError(
                f"page_tokens {self.page_tokens} must be >= 0 (0 resolves "
                "from the model's patch count)"
            )
        # Ragged admission COMPOSES with the continuation queue (ISSUE
        # 16 lifted the PR 11 exclusivity): straggler rows carry their
        # flat page-aligned state through the host levels0 form
        # (glom_forward_ragged's continuation carry) and re-enter as
        # ragged rows with their remaining budget. Only the fixed route
        # stays incompatible — a fixed iteration count has no stragglers.
        if self.ragged and self.max_continuations > 0 and self.iters != "auto":
            raise ValueError(
                "ragged continuations need iters='auto': a fixed route "
                "has no convergence witness to leave stragglers behind"
            )
        if self.ragged_attention not in ("windowed", "banded", "banded-pallas"):
            raise ValueError(
                f"ragged_attention {self.ragged_attention!r}: 'windowed', "
                "'banded', or 'banded-pallas'"
            )
        if self.pool_aliasing and self.page_pool_pages <= 0:
            raise ValueError(
                "pool_aliasing needs a device page pool "
                "(page_pool_pages > 0): there is no buffer to alias"
            )
        if self.ragged_pages:
            if list(self.ragged_pages) != sorted(set(self.ragged_pages)):
                raise ValueError(
                    f"ragged_pages {self.ragged_pages} must be strictly "
                    "ascending"
                )
            if any(p < 1 for p in self.ragged_pages):
                raise ValueError(
                    f"ragged_pages {self.ragged_pages} must be >= 1"
                )
        if self.delta_streaming:
            if self.page_pool_pages <= 0:
                raise ValueError(
                    "delta_streaming needs a device page pool "
                    "(page_pool_pages > 0): delta entries are pool pages"
                )
            if self.ragged:
                raise ValueError(
                    "delta_streaming rides the bucket route only (ragged "
                    "delta chains are a documented follow-on)"
                )
        if self.delta_page_atol < 0:
            raise ValueError(
                f"delta_page_atol {self.delta_page_atol} must be >= 0 "
                "(0.0 = exact: any changed bit stores the page)"
            )
        if self.delta_chain_cap < 1:
            raise ValueError(
                f"delta_chain_cap {self.delta_chain_cap} must be >= 1"
            )
        if self.page_gather not in ("auto", "pool", "needed"):
            raise ValueError(
                f"page_gather {self.page_gather!r}: 'auto', 'pool', or "
                "'needed'"
            )
        if self.rejoin_threshold < 0:
            raise ValueError(
                f"rejoin_threshold {self.rejoin_threshold} must be >= 0 "
                "(0 keeps engine death terminal)"
            )
        if self.rejoin_interval_ms <= 0:
            raise ValueError(
                f"rejoin_interval_ms {self.rejoin_interval_ms} must be > 0"
            )
        if self.collective_timing not in ("off", "sampled", "full"):
            raise ValueError(
                f"collective_timing {self.collective_timing!r}: one of "
                "('off', 'sampled', 'full')"
            )
        if self.collective_timing_interval < 1:
            raise ValueError(
                f"collective_timing_interval "
                f"{self.collective_timing_interval} must be >= 1"
            )
        if self.min_engines < 1:
            raise ValueError(f"min_engines {self.min_engines} must be >= 1")
        if self.max_engines < self.min_engines:
            raise ValueError(
                f"max_engines {self.max_engines} must be >= min_engines "
                f"{self.min_engines}"
            )
        if not 0.0 <= self.elastic_low_water < self.elastic_high_water <= 1.0:
            raise ValueError(
                f"need 0 <= elastic_low_water ({self.elastic_low_water}) < "
                f"elastic_high_water ({self.elastic_high_water}) <= 1"
            )
        if self.elastic_dwell_s < 0 or self.elastic_cooldown_s < 0:
            raise ValueError(
                f"elastic_dwell_s {self.elastic_dwell_s} and "
                f"elastic_cooldown_s {self.elastic_cooldown_s} must be >= 0"
            )
        if self.elastic_window_s <= 0 or self.elastic_interval_s <= 0:
            raise ValueError(
                f"elastic_window_s {self.elastic_window_s} and "
                f"elastic_interval_s {self.elastic_interval_s} must be > 0"
            )
        if self.elastic_p99_ms is not None and self.elastic_p99_ms <= 0:
            raise ValueError(
                f"elastic_p99_ms {self.elastic_p99_ms} must be > 0 or None"
            )
        if self.elastic_shed_rate is not None and not (
            0.0 <= self.elastic_shed_rate <= 1.0
        ):
            raise ValueError(
                f"elastic_shed_rate {self.elastic_shed_rate} must be in "
                "[0, 1] or None"
            )
        if self.husk_max is not None and self.husk_max < 0:
            raise ValueError(
                f"husk_max {self.husk_max} must be >= 0 or None"
            )
        if self.husk_max_age_s is not None and self.husk_max_age_s < 0:
            raise ValueError(
                f"husk_max_age_s {self.husk_max_age_s} must be >= 0 or None"
            )
        if not 0.0 < self.elastic_target_utilization <= 1.0:
            raise ValueError(
                f"elastic_target_utilization "
                f"{self.elastic_target_utilization} must be in (0, 1]"
            )
        if self.warm_pool < 0:
            raise ValueError(f"warm_pool {self.warm_pool} must be >= 0")
        if not 0.0 <= self.slo_starvation_floor < 1.0:
            raise ValueError(
                f"slo_starvation_floor {self.slo_starvation_floor} must "
                "be in [0, 1)"
            )
        if self.slo_classes is not None or self.slo_shed_order is not None:
            # The one class-table resolution (glom_tpu/serve/qos.py,
            # stdlib-only — no jax rides this import): a typo'd class
            # spec, duplicate name, unknown default/shed-order entry, or
            # unsatisfiable starvation floor fails HERE, at config
            # construction, not mid-traffic. A shed order without
            # declared classes is equally a config bug.
            if not self.slo_classes:
                raise ValueError(
                    "slo_shed_order needs slo_classes: there are no "
                    "declared classes to order"
                )
            from glom_tpu.serve.qos import resolve_slo_classes

            resolve_slo_classes(self)


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Self-supervised denoising trainer (the reference's README recipe)."""

    batch_size: int = 8
    learning_rate: float = 1e-4
    weight_decay: float = 0.0
    # Learning-rate schedule: "constant" | "cosine" | "warmup_cosine".
    # Cosine decays to lr_final_fraction * learning_rate; schedule_steps
    # is the TOTAL schedule length — for warmup_cosine that INCLUDES the
    # warmup_steps of linear warmup (cosine decay then spans
    # schedule_steps - warmup_steps; optax semantics). Anything beyond
    # these composes via passing an optax optimizer to the Trainer.
    lr_schedule: str = "constant"
    schedule_steps: int = 10_000
    warmup_steps: int = 0
    lr_final_fraction: float = 0.0
    # Gradient accumulation: split each batch into grad_accum microbatches,
    # scan value_and_grad over them accumulating gradients, ONE optimizer
    # update — trains an effective batch grad_accum x larger than what
    # fits in HBM at once (batch_size must divide evenly).
    # None (the default) = AUTO-ROUTE: resolve_training_route may split the
    # batch when exact accumulation recovers the fused-loop VJP. An explicit
    # value — INCLUDING 1 — is pinned and never overridden, so a user who
    # wants the single-pass full-batch step (memory/latency A/B) sets
    # grad_accum=1 (docs/PARALLELISM.md, "Opting out of auto grad-accum").
    grad_accum: Optional[int] = None
    noise_std: float = 1.0
    # Which stacked iteration's top level feeds the reconstruction head.
    # Reference README uses index 7 for L=6/T=12 (mid-iteration top level).
    recon_iter_index: Optional[int] = None  # None -> T // 2 + 1 (7 at T=12)
    iters: Optional[int] = None  # None -> model default (2L)
    remat: bool = False  # jax.checkpoint over the scan body ("ckpt over iters")
    compute_dtype: str = "float32"  # "bfloat16" for MXU-optimal training
    use_pallas: bool = False  # fused TPU kernels on the forward hot path
    # ZeRO-style cross-replica sharded weight update (Xu et al. 2020,
    # arXiv:2004.13336 — the GSPMD "automatic cross-replica sharding of
    # weight update"). Stages:
    #   0 — replicated optimizer state, monolithic gradient allreduce
    #       (the classic DP step);
    #   1 — optimizer state sharded over the 'data' mesh axis; gradients
    #       move as reduce-scatter, each replica updates only its owned
    #       shard, updated params all-gather back;
    #   2 — additionally the gradient-accumulation buffer is sharded:
    #       each microbatch's gradients reduce-scatter immediately, so
    #       only the 1/dp shard is ever accumulated (differs from stage 1
    #       only when grad_accum > 1).
    # Resolution (dp==1 -> 0) is resolve_zero_stage in train/trainer.py —
    # the single source both trainers stamp into every metrics record.
    zero_stage: int = 0
    # Telemetry depth (glom_tpu/telemetry, docs/OBSERVABILITY.md):
    #   "off"     — no in-graph diagnostics beyond the loss (the sustained-
    #               throughput default; static analytics still stamped);
    #   "scalars" — per-step grad/update/param norms + a NaN/Inf guard,
    #               computed INSIDE the jitted step (one fused reduction),
    #               plus measured collective counters on the manual path;
    #   "full"    — scalars + per-level consensus-agreement stats (GSPMD /
    #               single-device paths; the manual shard_map path degrades
    #               to "scalars" loudly — the resolved level is stamped).
    # Resolution is telemetry.diagnostics.resolve_telemetry_level — the
    # single source both trainers stamp into every metrics record.
    telemetry_level: str = "off"
    # What the NaN/Inf guard does when a step produces a non-finite loss or
    # gradient (active only when telemetry_level != "off"):
    #   "skip" — the update is dropped in-graph (params/opt state keep
    #            their previous values; the step counter still advances)
    #            and the record carries skipped_nonfinite=1;
    #   "warn" — the update is applied as-is, the record just flags it.
    # Either way fit_loop emits a structured "anomaly" event at the next
    # logging step.
    nonfinite_policy: str = "skip"
    # Unroll the T-iteration scan into straight-line code. Removes the
    # residual-stack dynamic-slice bookkeeping scan autodiff pays per
    # iteration (~3-5% step time at the flagship config on v5e, measured
    # back-to-back). Costs compile time proportional to T; leave off for
    # large T, under remat (which exists to NOT keep per-iteration
    # residuals), and in GSPMD regions where compile time is precious.
    scan_unroll: bool = False
    # Per-collective wall-time on the manual path (docs/OBSERVABILITY.md
    # "Capacity observatory"; resolved by
    # counters.resolve_collective_timing — the telemetry_level
    # discipline): "off" (default), "sampled" (every
    # collective_timing_interval-th fit-loop logging boundary, each
    # registered zero1-schedule site is re-dispatched as its own timed
    # sub-graph and stamped as a "collective_time" record with the α-β
    # comm_time_model drift), "full" (degrades to "sampled" loudly here —
    # the jit-on-first-call trainer has no AOT seam for the io_callback
    # brackets). Only the manual zero>=1 route has registered sites; the
    # GSPMD step resolves to "off", stamped.
    collective_timing: str = "off"
    collective_timing_interval: int = 10
    seed: int = 0
