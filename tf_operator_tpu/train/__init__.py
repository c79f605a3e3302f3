"""Training harness: sharded train loops, telemetry, checkpointing.

The layer the reference left entirely to user containers (its operator only
ever saw exit codes); here it is library code so that a TPUJob's workload
is a config, not a program. Exceeds the reference's observability bar
(SURVEY.md §5: "TPU build should add first-class step-time/MFU telemetry").
"""

from tf_operator_tpu.train.trainer import TrainState, Trainer, TrainerConfig  # noqa: F401
from tf_operator_tpu.train.checkpoint import (  # noqa: F401
    CheckpointManager,
    WorkloadCheckpointer,
)
from tf_operator_tpu.train.metrics import (  # noqa: F401
    mfu,
    peak_flops_per_chip,
)
from tf_operator_tpu.train.data import (  # noqa: F401
    ArrayDataset,
    DeviceLoader,
    SyntheticImages,
    SyntheticTokens,
)
from tf_operator_tpu.train.profile import profile_ctx  # noqa: F401
