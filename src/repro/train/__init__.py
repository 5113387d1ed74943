"""Training substrate: optimizer, microbatched step, checkpointing, faults."""
from repro.train.optimizer import OptConfig, adamw_update, init_opt_state, lr_at
from repro.train.train_step import count_step, make_loss_and_grads, make_train_step
from repro.train.checkpoint import (
    AsyncCheckpointer,
    restore_arrays,
    restore_sharded,
    restore_tree,
    save_checkpoint,
)
from repro.train.fault import FaultInjected, StepSupervisor
from repro.train import grad_compress

__all__ = [
    "OptConfig",
    "adamw_update",
    "init_opt_state",
    "lr_at",
    "make_loss_and_grads",
    "make_train_step",
    "AsyncCheckpointer",
    "restore_arrays",
    "restore_sharded",
    "restore_tree",
    "save_checkpoint",
    "FaultInjected",
    "StepSupervisor",
    "grad_compress",
]
