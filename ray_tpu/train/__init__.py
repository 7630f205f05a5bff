from ray_tpu.air.checkpoint import Checkpoint
from ray_tpu.air.config import (
    CheckpointConfig,
    FailureConfig,
    RunConfig,
    ScalingConfig,
    TrainConfig,
)
from ray_tpu.air.result import Result
from ray_tpu.air.session import (
    get_checkpoint,
    get_context,
    get_dataset_shard,
    get_mesh,
    get_world_rank,
    get_world_size,
    report,
)
from ray_tpu.train.backend import Backend, BackendConfig, JaxBackend, JaxBackendConfig
from ray_tpu.train.backend_executor import BackendExecutor, TrainingWorkerError
from ray_tpu.train.data_parallel_trainer import DataParallelTrainer
from ray_tpu.train.sharded_checkpoint import (
    restore_sharded,
    restore_train_state,
    save_sharded,
    save_train_state,
)
from ray_tpu.train.jax_trainer import (
    JaxTrainer,
    prepare_batch,
    prepare_params,
    prepare_step,
    step_device_report,
)
from ray_tpu.train.worker_group import WorkerGroup
from ray_tpu.train.observability import (
    StepProfiler,
    TrainRunRecord,
    list_runs,
)

__all__ = [
    "Backend",
    "BackendConfig",
    "BackendExecutor",
    "Checkpoint",
    "CheckpointConfig",
    "DataParallelTrainer",
    "FailureConfig",
    "JaxBackend",
    "JaxBackendConfig",
    "JaxTrainer",
    "Result",
    "RunConfig",
    "ScalingConfig",
    "StepProfiler",
    "TrainConfig",
    "TrainRunRecord",
    "TrainingWorkerError",
    "WorkerGroup",
    "list_runs",
    "get_checkpoint",
    "get_context",
    "get_dataset_shard",
    "get_mesh",
    "get_world_rank",
    "get_world_size",
    "prepare_batch",
    "prepare_params",
    "prepare_step",
    "report",
    "restore_sharded",
    "restore_train_state",
    "save_sharded",
    "save_train_state",
    "step_device_report",
]
