from .optimizer import AdamWConfig, OptState
from .step import (TrainHyper, abstract_state, fit_remat, init_state,
                   make_loss_fn, make_prefill_step, make_serve_step,
                   make_train_step)

__all__ = ["AdamWConfig", "OptState", "TrainHyper", "abstract_state",
           "fit_remat", "init_state", "make_loss_fn", "make_prefill_step",
           "make_serve_step", "make_train_step"]
