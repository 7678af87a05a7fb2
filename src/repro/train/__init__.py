from .step import TrainConfig, make_train_step, make_eval_step
from .loop import LoopConfig, jit_train_step, train, straggler_check

__all__ = ["TrainConfig", "make_train_step", "make_eval_step", "LoopConfig",
           "jit_train_step", "train", "straggler_check"]
