"""The five driver benchmark configurations (BASELINE.md) as named presets.

Each preset bundles the model config, a training config, and the mesh /
SP strategy the config was designed to exercise. Mesh sizes here describe
the TARGET topology; `scaled_to(num_devices)` shrinks the mesh to whatever
is actually available (e.g. the 8-device CPU test harness or one chip).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Union

from glom_tpu.utils.config import (
    EvaByteConfig,
    GlomConfig,
    HybridLMConfig,
    KimiLinearConfig,
    LagunaConfig,
    MeshConfig,
    OuroConfig,
    SambaYConfig,
    ServeConfig,
    TrainConfig,
)
from glom_tpu.utils.helpers import halo_supported


@dataclasses.dataclass(frozen=True)
class Preset:
    name: str
    description: str
    # The family the preset trains: its type picks the objective
    # (train/trainer.objective_for).
    model: Union[GlomConfig, HybridLMConfig, SambaYConfig, LagunaConfig, KimiLinearConfig,
                 EvaByteConfig, OuroConfig]
    train: TrainConfig
    mesh: MeshConfig
    sp_strategy: str = "none"  # none | ring | ulysses | halo | auto
    # Serving policy (glom_tpu/serve): the bucket ladder the engine
    # precompiles and the batcher's admission knobs. The default suits the
    # small correctness configs; the throughput presets override it.
    serve: ServeConfig = ServeConfig()

    def scaled_to(self, num_devices: int) -> "Preset":
        """Shrink the mesh to fit `num_devices`. Data parallelism is the
        elastic axis — shrink it FIRST so the structurally interesting
        axes (seq sharding, the TP hidden split) survive on small device
        counts; a scaled-down pod preset still exercises its declared
        data x seq x model composition. Divisibility is preserved: halving
        an axis keeps batch % data == 0 and num_patches % seq == 0."""
        data, seq, model = self.mesh.data, self.mesh.seq, self.mesh.model
        while data * seq * model > num_devices and data > 1:
            data //= 2
        while data * seq * model > num_devices and seq > 1:
            seq //= 2
        while data * seq * model > num_devices and model > 1:
            model //= 2
        # A scaled-down mesh is a single-slice deployment (the virtual test
        # harness, or one real slice): the multi-slice DCN split only
        # describes the full-size topology, so collapse it when shrinking.
        shrunk = (data, seq, model) != self.mesh.shape
        ns = 1 if shrunk else self.mesh.num_slices
        mesh = MeshConfig(data=data, seq=seq, model=model, num_slices=ns)
        sp = self.sp_strategy if mesh.seq > 1 else "none"
        if sp == "halo" and not halo_supported(
            mesh.seq, self.model.num_patches_side, self.model.local_consensus_radius
        ):
            # Shrinking the mesh can break halo's one-hop precondition
            # (fewer rows per shard); ring is exact for any radius.
            sp = "ring"
        return dataclasses.replace(self, mesh=mesh, sp_strategy=sp)


PRESETS: Dict[str, Preset] = {}
# The presets of the language-model families (a HybridLMConfig, a
# SambaYConfig, a LagunaConfig, a KimiLinearConfig, an EvaByteConfig or an OuroConfig model), in a table of
# their own: PRESETS stays GLOM's driver configurations, which is what the
# sharded trainers and the serving stack iterate; `get_preset` finds both.
LM_PRESETS: Dict[str, Preset] = {}


def _register(p: Preset) -> Preset:
    (PRESETS if isinstance(p.model, GlomConfig) else LM_PRESETS)[p.name] = p
    return p


# 1. MNIST 28x28, patch=7, levels=4, dim=128 — forward denoise (CPU ref)
_register(
    Preset(
        name="mnist",
        description="MNIST 28x28 p7 L4 d128 — correctness reference",
        model=GlomConfig(dim=128, levels=4, image_size=28, patch_size=7),
        train=TrainConfig(batch_size=32, learning_rate=3e-4, noise_std=0.5),
        mesh=MeshConfig(),
    )
)

# 2. CIFAR-10 32x32, patch=4, levels=5, dim=256 — denoise training
_register(
    Preset(
        name="cifar10",
        description="CIFAR-10 32x32 p4 L5 d256 — self-supervised denoise train",
        model=GlomConfig(dim=256, levels=5, image_size=32, patch_size=4),
        train=TrainConfig(
            batch_size=64, learning_rate=3e-4, noise_std=0.5,
            compute_dtype="bfloat16", use_pallas=True, scan_unroll=True,
        ),
        mesh=MeshConfig(),
    )
)

# 3. ImageNet-64, patch=8, levels=6, dim=512, local consensus window=7.
# The 8x8 patch grid sharded seq=2 holds 4 rows per shard < floor(radius)=7,
# so the one-hop halo precondition can NEVER hold for this geometry (and at
# radius 7 on side 8 the mask barely masks anyway) — an exact GLOBAL SP
# form must stand in; which one is the selector's call (see sp_strategy
# below). See `imagenet256-local` for the config where halo actually pays.
_register(
    Preset(
        name="imagenet64-local",
        description="ImageNet-64 p8 L6 d512 radius7 — local-mask path",
        model=GlomConfig(
            dim=512, levels=6, image_size=64, patch_size=8, local_consensus_radius=7
        ),
        train=TrainConfig(
            batch_size=64, learning_rate=3e-4, noise_std=0.5,
            compute_dtype="bfloat16", use_pallas=True, scan_unroll=True,
        ),
        mesh=MeshConfig(data=4, seq=2),
        # intent: local consensus. 'auto' resolves the mechanism: side 8 /
        # seq 2 gives 4 rows per shard < radius 7, so halo is geometrically
        # impossible; the selector then applies the global crossover and
        # picks ULYSSES (L=6 divides seq=2, n=64 < 2048 — the small-n
        # regime it measured fastest; the local mask rides along exactly).
        sp_strategy="auto",
    )
)

# 3b. Long-context local-consensus config where the halo path pays: a 32x32
# patch grid (n=1024) with radius 7 sharded seq=4 gives 8 rows per shard
# >= 7 halo rows, so each shard exchanges one ~22%-of-n halo with each
# neighbor instead of ring-rotating the full k/v — O(r*side) comms, not O(n).
_register(
    Preset(
        name="imagenet256-local",
        description="ImageNet-256 p8 L6 d512 radius7 — halo-exchange long-context",
        model=GlomConfig(
            dim=512, levels=6, image_size=256, patch_size=8, local_consensus_radius=7
        ),
        train=TrainConfig(
            batch_size=32, learning_rate=3e-4, noise_std=0.5,
            compute_dtype="bfloat16", use_pallas=True, scan_unroll=True,
        ),
        mesh=MeshConfig(data=2, seq=4),
        # intent: local consensus. side 32 / seq 4 = 8 rows per shard >=
        # radius 7, so 'auto' resolves to halo (one-hop neighbor exchange).
        sp_strategy="auto",
    )
)

# 4. ImageNet-224, patch=14, levels=6, dim=512 — data-parallel v5e-8
_register(
    Preset(
        name="imagenet224-dp8",
        description="ImageNet-224 p14 L6 d512 — DP over a v5e-8 slice",
        model=GlomConfig(dim=512, levels=6, image_size=224, patch_size=14),
        train=TrainConfig(
            batch_size=64, learning_rate=3e-4, noise_std=0.5,
            compute_dtype="bfloat16", use_pallas=True, scan_unroll=True,
        ),
        mesh=MeshConfig(data=8),
        # The flagship serving config: bf16 fused forward, a deeper bucket
        # ladder (heavy traffic fills big buckets; the small ones cover the
        # tail), and TWO-TIER consensus early exit — a bucket exits when
        # its fastest three-quarters quorum converges, stragglers
        # re-bucket through the continuation queue with their remaining
        # budget (docs/SERVING.md). Streaming: 1 GiB of HBM buys ~680
        # concurrent warm sessions (column_state_bytes = 256 patches x 6
        # levels x 512 dim x bf16 ~= 1.5 MiB/stream); a stream quiet for
        # a minute cold-starts its next frame. Dead engines re-admit
        # after 3 clean probation dispatches.
        serve=ServeConfig(
            buckets=(1, 2, 4, 8, 16),
            max_batch=16,
            max_delay_ms=3.0,
            queue_depth=256,
            iters="auto",
            exit_threshold=1e-3,
            min_iters=4,
            exit_quorum=0.75,
            max_continuations=2,
            compute_dtype="bfloat16",
            use_pallas=True,
            column_cache_bytes=1 << 30,
            column_cache_ttl_s=60.0,
            rejoin_threshold=3,
            # Paged column memory (docs/SERVING.md): the 1 GiB cache
            # budget lives in a device page pool — 2728 pages x 64
            # tokens x 6 levels x 512 dim x bf16 = 384 KiB/page (~682
            # full-resolution streams at 4 pages each), warm frames
            # assembled in-graph with ZERO host->device levels0 bytes.
            # Ragged admission stays a workload opt-in (it composes with
            # the continuation queue on the auto route — stragglers
            # re-enter ragged with their remaining budget). When opted in, the BANDED
            # consensus route prices the duplicated k/v working set per
            # PAGE instead of per token (64x smaller here), which is
            # what lets a 16-row ragged signature fit one chip at all;
            # aliased write-backs land pages in place instead of
            # copying the 1 GiB pool per write.
            page_pool_pages=2728,
            page_tokens=64,
            ragged_attention="banded",
            pool_aliasing=True,
        ),
    )
)

# 5. ImageNet-224, patch=14, levels=12, dim=1024 — pod-scale v5e-256, remat.
# Laid out as 4 DCN-connected slices of 64 chips: the 64-way data axis
# factors into 4 (outer, DCN) x 16 (inner, ICI); seq/model ride ICI inside
# a slice. XLA decomposes the gradient allreduce hierarchically from the
# hybrid device placement (parallel/mesh.py).
_register(
    Preset(
        name="imagenet224-pod",
        description="ImageNet-224 p14 L12 d1024 — v5e-256 pod (4 DCN slices), remat",
        model=GlomConfig(dim=1024, levels=12, image_size=224, patch_size=14),
        train=TrainConfig(
            batch_size=256,
            learning_rate=3e-4,
            noise_std=0.5,
            compute_dtype="bfloat16",
            # use_pallas rides the manual shard_map path, which composes the
            # fused kernels with the declared data x seq x model mesh: the
            # TP (model=2) hidden split is a hand-written Megatron psum in
            # parallel/manual.py, per-rank f/mp = 2048 stays MXU-tileable.
            # scan_unroll stays off: remat + unroll defeat each other.
            use_pallas=True,
            remat=True,
        ),
        mesh=MeshConfig(data=64, seq=2, model=2, num_slices=4),
        # intent: global consensus at n=256. 'auto' resolves to Ulysses
        # (L=12 divides seq=2; measured 1.46x over ring at n=256/seq=2 —
        # results/sp_crossover.jsonl).
        sp_strategy="auto",
        # Pod-scale serving: each engine replica is an 8-chip (data=4 x
        # seq=2) serve mesh (parallel/serve_mesh.py) — the d=1024/L=12
        # model batched 32-deep does not serve interactively on one chip.
        # Buckets divide by mesh_data=4; a v5e-256 pod fans out 32 such
        # replicas behind shared admission (runtime.make_engine_meshes).
        serve=ServeConfig(
            buckets=(4, 8, 16, 32),
            max_batch=32,
            max_delay_ms=5.0,
            queue_depth=512,
            iters="auto",
            exit_threshold=1e-3,
            min_iters=4,
            exit_quorum=0.75,
            max_continuations=2,
            mesh_data=4,
            mesh_seq=2,
            compute_dtype="bfloat16",
            # Streaming at pod scale: 2 GiB/replica of column cache
            # (d=1024/L=12 columns cost ~6 MiB/stream -> ~340 streams per
            # 8-chip replica, 32 replicas behind shared admission), and
            # probation rejoin so a recovered replica re-enters the
            # fan-out without a restart (docs/RESILIENCE.md).
            column_cache_bytes=2 << 30,
            column_cache_ttl_s=60.0,
            rejoin_threshold=3,
            # Paged pool per 8-chip replica: the page axis shards over
            # 'data' (pages % mesh_data == 0 — 341 pages/chip), and the
            # paged warm signature gathers it with one registered
            # all_gather (parallel/serve_mesh.py). 1364 pages x 64
            # tokens x 12 levels x 1024 dim x bf16 = 1.5 MiB/page ->
            # ~341 full-res streams resident per replica.
            page_pool_pages=1364,
            page_tokens=64,
        ),
    )
)


# 6. The second family: NVIDIA-Nemotron-3-Super-120B-A12B-BF16 (nemotron_h:
# Mamba-2, grouped-query attention, a latent mixture of 512 experts), as ONE
# chip of a deployment in which 64 chips share each layer sees it: routed
# experts divided 64 ways (8 of 512 here), the mixers' heads and the
# vocabulary 8 ways (16 of 128 Mamba-2 heads = 1 of 8 groups, 4 of 32 query
# heads over 1 of 2 KV heads, 16,384 of 131,072 rows); depth cut to one
# period of the published pattern, layers 26-36 (EMEMEMEMEM*). Every width
# is the published one. 701M parameters held; one packed sequence of 8,192
# tokens a step with per-layer recomputation fills a 16 GB chip.
_register(
    Preset(
        name="nemotron3-super-ep64tp8",
        description="Nemotron-3-Super-120B-A12B: one chip of 64 a layer "
        "(8/512 experts, 1/8 heads and vocabulary), layers 26-36, 8k tokens",
        model=HybridLMConfig(
            layer_offset=26, num_hidden_layers=11,
            n_routed_experts=8, expert_offset=40,
            mamba_num_heads=16, n_groups=1,
            num_attention_heads=4, num_key_value_heads=1,
            vocab_size=16384, seq_len=8192,
        ),
        train=TrainConfig(
            batch_size=1, learning_rate=3e-4, compute_dtype="bfloat16", remat=True,
        ),
        mesh=MeshConfig(),
    )
)

# 6b. The same family at a size the CPU holds: every mixer, a share of the
# experts (4 of 16, offset 4) — tests and functional drives, never a
# measurement.
_register(
    Preset(
        name="hybrid-lm-tiny",
        description="hybrid LM, hidden 64, ME*EM, 4 of 16 experts — CPU drives",
        model=HybridLMConfig(
            hidden_size=64, hybrid_override_pattern="ME*EM", num_hidden_layers=5,
            num_hidden_layers_total=5, vocab_size=128,
            n_routed_experts=4, n_routed_experts_total=16, expert_offset=4,
            num_experts_per_tok=4, moe_latent_size=32, moe_intermediate_size=48,
            moe_shared_expert_intermediate_size=96,
            mamba_num_heads=8, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
            chunk_size=8, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            seq_len=64,
        ),
        train=TrainConfig(batch_size=2, learning_rate=3e-4, remat=True),
        mesh=MeshConfig(),
    )
)


# 7. A third family: Phi-4-mini-flash-reasoning (phi4flash, SambaY: Mamba-1,
# differential attention under a 512-key window and in full, one shared KV
# read by cross-attention, Gated Memory Units), as ONE chip of a pipeline
# whose stages hold six layers each sees it: published layers 14-19 (Mamba,
# window attention, the Mamba layer that makes the memory, the full attention
# that makes the shared keys and values, a GMU, a cross-attention), and rows
# 0-25,007 of the tied embedding, which is divided by rows over the stage's 8
# data-parallel replicas. Every width and every head is the published one.
# 697M parameters held; one packed sequence of 8,192 tokens a step.
_register(
    Preset(
        name="phi4-mini-flash-stage6vp8",
        description="Phi-4-mini-flash-reasoning: the pipeline stage of layers "
        "14-19, an eighth of the vocabulary, 8k tokens",
        model=SambaYConfig(
            layer_offset=14, num_hidden_layers=6, vocab_size=25008, seq_len=8192,
        ),
        train=TrainConfig(
            batch_size=1, learning_rate=3e-4, compute_dtype="bfloat16", remat=True,
        ),
        mesh=MeshConfig(),
    )
)

# 7b. The same family at a size the CPU holds: the whole rule at N = 8 (every
# kind of layer, the memory and the shared keys and values made and read), a
# window shorter than the sequence.
_register(
    Preset(
        name="sambay-tiny",
        description="SambaY LM, hidden 64, 8 layers MWMWMFGX, window 16 — CPU drives",
        model=SambaYConfig(
            hidden_size=64, intermediate_size=128, num_attention_heads=8,
            num_key_value_heads=4, sliding_window=16, vocab_size=128,
            num_hidden_layers=8, num_hidden_layers_total=8, seq_len=80,
        ),
        train=TrainConfig(batch_size=2, learning_rate=3e-4, remat=True),
        mesh=MeshConfig(),
    )
)


# 8. A fourth family: Laguna-XS.2 (laguna: window and full attention mixed by
# layer with 64 and 48 query heads over 8 KV heads, two rotary schemes, a gate
# a head on the attention's output, top-8 of 256 SwiGLU experts beside a
# shared one), as ONE chip of 8 that share each layer sees it: the routed
# experts divided 8 ways (32 of 256 here, experts 96-127), the embedding and
# the untied head by rows 8 ways (12,544 of 100,352); attention, the gate, the
# router, the shared expert and the dense MLP whole. Depth: published layers
# 0-4, the leading dense layer and one whole period after it (full + dense,
# sliding, sliding, sliding, full). Every width and every head is the
# published one. 692M parameters held; two packed sequences of 8,192 tokens a
# step. `moe_rung_loads=4` is for the benchmark's traffic, not for the
# model: on uniform random ids, with the zero selection bias `assumed`, nothing
# balances this router, from about the tenth step every token of a layer
# chooses the same 8 experts on many steps, and those held here then get whole
# multiples of the 16,384 tokens. Two of the 8 overflow a rung of two loads by
# its rows of room and run the full count, 40 ms a layer longer; four loads
# hold three (four are 1% of such steps) for 13 ms a layer more than two
# (PERF.md section 6, PR 36). A job whose router is balanced leaves the default.
_register(
    Preset(
        name="laguna-xs2-ep8vp8",
        description="Laguna-XS.2: one chip of 8 a layer (32/256 experts, 1/8 of "
        "the vocabulary), layers 0-4, two 8k-token sequences a step",
        model=LagunaConfig(
            num_hidden_layers=5, num_experts=32, expert_offset=96, vocab_size=12544,
            moe_rung_loads=4, seq_len=8192,
        ),
        train=TrainConfig(
            batch_size=2, learning_rate=3e-4, compute_dtype="bfloat16", remat=True,
        ),
        mesh=MeshConfig(),
    )
)

# 8b. The same family at a size the CPU holds: both kinds of attention with
# their own head counts, the dense layer and expert layers, a share of the
# experts (4 of 16, offset 4), a window shorter than the sequence.
_register(
    Preset(
        name="laguna-tiny",
        description="Laguna LM, hidden 64, 5 layers F+D S S S F, 4 of 16 experts, "
        "window 16 — CPU drives",
        model=LagunaConfig(
            hidden_size=64, intermediate_size=160, vocab_size=128,
            layer_types="FSSSF", mlp_layer_types="DEEEE", num_hidden_layers=5,
            num_hidden_layers_total=5, num_attention_heads=4, num_sliding_attention_heads=8,
            num_key_value_heads=2, head_dim=16, sliding_window=16,
            yarn_original_max_position_embeddings=32,
            num_experts=4, num_experts_total=16, expert_offset=4, num_experts_per_tok=4,
            moe_intermediate_size=48, shared_expert_intermediate_size=48, seq_len=80,
        ),
        train=TrainConfig(batch_size=2, learning_rate=3e-4, remat=True),
        mesh=MeshConfig(),
    )
)


# 9. A fifth family: Kimi-Linear-48B-A3B-Instruct (kimi_linear: Kimi Delta
# Attention, a gated delta rule with a decay a key channel, in three layers of
# four; latent attention without positions in the fourth; top-8 of 256
# sigmoid-routed SwiGLU experts beside a shared one), as ONE chip of 32 that
# share each layer sees it: the routed experts divided 32 ways (8 of 256 here,
# experts 88-95: chip 11 of its group), the embedding and the untied head by
# rows 8 ways (20,480 of 163,840); both mixers with all their heads, the
# router, the shared expert and the dense MLP whole. Depth: published layers
# 1-5, the leading dense layer and one whole period after it (KDA + dense,
# KDA, KDA, latent attention, KDA). Every width and every head is the
# published one. 602M parameters held; one packed sequence of 16,384 tokens a
# step. `moe_rung_loads=5` is for the benchmark's traffic as Laguna's 4 is (its
# router collapses the same way, PERF.md trap 16): a held expert gets all
# 16,384 tokens or none, a balanced load here is 4,096 pairs, and a small rung
# of five, 20,480 rows, holds one such expert with the rows of room.
_register(
    Preset(
        name="kimi-linear-ep32vp8",
        description="Kimi-Linear-48B-A3B: one chip of 32 a layer (8/256 experts, 1/8 of "
        "the vocabulary), layers 1-5, one 16k-token sequence a step",
        model=KimiLinearConfig(
            num_hidden_layers=5, num_experts=8, expert_offset=88, vocab_size=20480,
            moe_rung_loads=5, seq_len=16384,
        ),
        train=TrainConfig(
            batch_size=1, learning_rate=3e-4, compute_dtype="bfloat16", remat=True,
        ),
        mesh=MeshConfig(),
    )
)

# 9b. The same family at a size the CPU holds: both mixers (K K K A K, the
# first layer dense), 4 of 16 experts (offset 4), 80 tokens.
_register(
    Preset(
        name="kimi-linear-tiny",
        description="Kimi Linear LM, hidden 64, 5 layers K+D K K A K, 4 of 16 experts "
        "— CPU drives",
        model=KimiLinearConfig(
            hidden_size=64, intermediate_size=160, vocab_size=128,
            layer_types="KKKAK", num_hidden_layers=5, num_hidden_layers_total=5,
            linear_num_heads=4, linear_head_dim=16, num_attention_heads=4,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            num_experts=4, num_experts_total=16, expert_offset=4, num_experts_per_token=4,
            moe_intermediate_size=48, seq_len=80,
        ),
        train=TrainConfig(batch_size=2, learning_rate=3e-4, remat=True),
        mesh=MeshConfig(),
    )
)


# 10. A sixth family: EvaByte (evabyte: a byte-level model whose attention is
# EVA, exact softmax inside aligned windows of 2,048 bytes joined in one
# softmax with a learned summary of every 16-byte chunk of the windows before;
# a float32 residual stream; eight byte-prediction heads), as ONE chip of 4
# that share each layer by heads sees it: 8 of the 32 heads with their rows of
# the out-projection; the MLP's 11,008, the norms, the embedding and the eight
# heads over all 320 rows whole. Depth: four consecutive published layers (all
# 32 are alike). Every width is the published one. 620M parameters held; one
# packed row of 16,384 bytes a step.
_register(
    Preset(
        name="evabyte-stage4tp4",
        description="EvaByte 6.5B: one chip of 4 a layer by heads (8/32 heads, the MLP "
        "whole), four layers, one 16k-byte row a step",
        model=EvaByteConfig(num_hidden_layers=4, num_attention_heads=8, seq_len=16384),
        train=TrainConfig(
            batch_size=1, learning_rate=3e-4, compute_dtype="bfloat16", remat=True,
        ),
        mesh=MeshConfig(),
    )
)

# 10b. The same family at a size the CPU holds: windows of 32 bytes, chunks of
# 4, three prediction heads, 80 bytes a row (two whole windows and a part).
_register(
    Preset(
        name="evabyte-tiny",
        description="EvaByte LM, hidden 64, 3 layers, 2 of 4 heads of 16, windows of 32, "
        "chunks of 4, 3 prediction heads — CPU drives",
        model=EvaByteConfig(
            hidden_size=64, intermediate_size=160, vocab_size=40, num_hidden_layers=3,
            num_hidden_layers_total=3, num_attention_heads=2, num_attention_heads_total=4,
            head_dim=16, window_size=32, chunk_size=4, num_pred_heads=3, seq_len=80,
        ),
        train=TrainConfig(batch_size=2, learning_rate=3e-4, remat=True),
        mesh=MeshConfig(),
    )
)


# 11. A seventh family: Ouro (ouro: a looped language model, one stack of
# sandwich-norm layers run four times over the same weights, the final norm
# closing every pass, an exit gate, a loss that weighs the four passes'
# cross-entropies by the gate's exit distribution), as ONE pipeline stage of 6
# sees it: 8 consecutive layers of the 48 (all are alike), round which a row
# passes four times; this chip also holds the embedding and the head over all
# 49,152 rows. Every width, every head and every pass is the published one.
# 612M parameters held; two packed rows of 4,096 tokens a step.
_register(
    Preset(
        name="ouro-2.6b-stage8",
        description="Ouro-2.6B: one pipeline stage of 6 (8/48 layers, all heads, the whole "
        "vocabulary), four passes over the same weights, two 4k-token rows a step",
        model=OuroConfig(num_hidden_layers=8, seq_len=4096),
        train=TrainConfig(
            batch_size=2, learning_rate=3e-4, compute_dtype="bfloat16", remat=True,
        ),
        mesh=MeshConfig(),
    )
)

# 11b. The same family at a size the CPU holds: two layers run four times.
_register(
    Preset(
        name="ouro-tiny",
        description="Ouro LM, hidden 64, 2 layers x 4 passes, 4 heads of 16 — CPU drives",
        model=OuroConfig(
            hidden_size=64, intermediate_size=160, vocab_size=128, num_hidden_layers=2,
            num_hidden_layers_total=2, num_attention_heads=4, num_key_value_heads=4,
            head_dim=16, total_ut_steps=4, seq_len=80,
        ),
        train=TrainConfig(batch_size=2, learning_rate=3e-4, remat=True),
        mesh=MeshConfig(),
    )
)


def get_preset(name: str) -> Preset:
    for table in (PRESETS, LM_PRESETS):
        if name in table:
            return table[name]
    raise KeyError(
        f"unknown preset {name!r}; available: {sorted(PRESETS) + sorted(LM_PRESETS)}"
    )
