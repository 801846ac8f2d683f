from glom_tpu.train.objectives import (
    DenoiseParams,
    Objective,
    default_recon_index,
    denoise_loss,
    init_denoise,
    reconstruct,
)
from glom_tpu.train.supervise import TrainSupervisor, fit_supervised
from glom_tpu.train.temporal import temporal_rollout
from glom_tpu.train.trainer import (
    Trainer,
    TrainState,
    create_train_state,
    default_optimizer,
    make_train_step,
    objective_for,
    resolve_training_route,
)

__all__ = [
    "DenoiseParams",
    "Objective",
    "default_recon_index",
    "denoise_loss",
    "init_denoise",
    "reconstruct",
    "TrainSupervisor",
    "fit_supervised",
    "temporal_rollout",
    "Trainer",
    "TrainState",
    "create_train_state",
    "default_optimizer",
    "make_train_step",
    "objective_for",
    "resolve_training_route",
]
