from .. import tracing
from .fit import federated_fit, sharded_client_fit, streamed_federated_fit
from .local import LocalTrainConfig, evaluate, train_local_zampling
from .steps import TrainState, make_train_step, make_zampling_train_step

__all__ = [
    "LocalTrainConfig", "evaluate", "train_local_zampling",
    "TrainState", "make_train_step", "make_zampling_train_step",
    "federated_fit", "sharded_client_fit", "streamed_federated_fit",
]

# count compiles from the first round traced on (repro.tracing)
tracing.install()
