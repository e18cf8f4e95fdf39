from .loop import TrainRunResult, init_train_state, run_resilient_training, train_step_fn

__all__ = ["TrainRunResult", "init_train_state", "run_resilient_training", "train_step_fn"]
