from glom_tpu.kernels.banded_consensus import banded_ragged_consensus
from glom_tpu.kernels.grouped_mlp import fused_grouped_ffw, fused_grouped_ffw_lm
from glom_tpu.kernels.consensus_update import fused_consensus_update

__all__ = [
    "banded_ragged_consensus",
    "fused_consensus_update",
    "fused_grouped_ffw",
    "fused_grouped_ffw_lm",
]
