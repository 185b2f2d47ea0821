"""horovod_tpu — a TPU-native distributed training framework.

A ground-up re-design of Horovod 0.15.1 (the shyhuai fork, with sparse/top-k
allreduce) for TPU: the data plane is XLA collectives over the ICI/DCN mesh
(``psum`` / ``all_gather`` / collective-permute emitted from ``shard_map`` /
``pjit``), the eager frontend is an async-handle engine with Horovod's
fusion/cycle/stall-check/timeline semantics, and the optimizer wrappers are
optax/flax-native (plus a torch frontend for API parity).

Two ways to use it, mirroring the reference's two frontends:

* **Compiled SPMD** (the TF-graph analogue, and the fast path): call
  ``horovod_tpu.ops.allreduce(...)`` — or just use ``DistributedOptimizer``
  — inside your jitted step function over the ``"hvd"`` mesh axis.
* **Eager** (the PyTorch analogue): ``hvd.allreduce / allgather / broadcast``
  on rank-major arrays, with ``*_async`` + ``poll`` / ``synchronize``
  handles, background fusion cycles, and the Chrome-trace timeline.

Quick start (the reference's canonical recipe, examples/pytorch_mnist.py)::

    import horovod_tpu as hvd
    hvd.init()
    tx = hvd.DistributedOptimizer(optax.adam(1e-3 * hvd.size()))
    params = hvd.broadcast_parameters(params, root_rank=0)
    step = hvd.make_train_step(loss_fn, tx)   # compiled SPMD over the mesh
    params, opt_state, loss = step(params, opt_state, batch)  # batch rank-major
"""

from horovod_tpu.basics import (  # noqa: F401
    AXIS_NAME,
    CPU_DEVICE_ID,
    NotInitializedError,
    axis_rank,
    cross_rank,
    cross_size,
    from_per_rank,
    init,
    is_initialized,
    local_rank,
    local_size,
    mesh,
    mpi_threads_supported,
    per_rank,
    rank,
    rank_sharding,
    replicated_sharding,
    shutdown,
    size,
)
from horovod_tpu.ops.collective_ops import (  # noqa: F401
    Adasum,
    Average,
    Max,
    Min,
    ProcessSet,
    Product,
    Sum,
)
from horovod_tpu.ops.compression import Compression  # noqa: F401
from horovod_tpu.ops.powersgd import (  # noqa: F401
    ErrorFeedback,
    PowerSGDCompressor,
)
from horovod_tpu.ops.eager import (  # noqa: F401
    allgather,
    allgather_async,
    allreduce,
    allreduce_async,
    alltoall,
    alltoall_async,
    barrier,
    broadcast,
    broadcast_async,
    engine_stats,
    grouped_allreduce_eager,
    join,
    poll,
    reducescatter,
    reducescatter_async,
    sparse_allreduce,
    sparse_allreduce_async,
    synchronize,
)
from horovod_tpu.optim.distributed_optimizer import (  # noqa: F401
    DistributedOptimizer,
    TrainStepAuxResult,
    TrainStepResult,
    allgather_object,
    allreduce_gradients,
    broadcast_object,
    broadcast_optimizer_state,
    broadcast_parameters,
    make_train_step,
)
from horovod_tpu.callbacks import (  # noqa: F401
    BroadcastGlobalVariablesCallback,
    Callback,
    LearningRateScheduleCallback,
    LearningRateWarmupCallback,
    MetricAverageCallback,
    ModelCheckpointCallback,
    average_metrics,
    multiplier_schedule,
    warmup_schedule,
)
from horovod_tpu.checkpoint import (  # noqa: F401
    latest_checkpoint,
    list_checkpoints,
    load_model,
    restore_checkpoint,
    save_checkpoint,
    wait_for_checkpoints,
)
from horovod_tpu.optim.eager_optimizer import EagerDistributedOptimizer  # noqa: F401
from horovod_tpu.optim.zero import ZeroStepResult, make_zero_train_step  # noqa: F401
from horovod_tpu.optim.fsdp import (  # noqa: F401
    FsdpStepResult,
    fsdp_partition_specs,
    make_fsdp_train_step,
    shard_params,
)
from horovod_tpu.training import fit, make_eval_step  # noqa: F401
from horovod_tpu.data import (  # noqa: F401
    ShardedLoader,
    prefetch_to_device,
    shard_indices,
)
from horovod_tpu.timeline import start_timeline, stop_timeline  # noqa: F401
from horovod_tpu import ops  # noqa: F401
from horovod_tpu import elastic  # noqa: F401  (hvd.elastic.State / .run)
from horovod_tpu import metrics  # noqa: F401  (hvd.metrics.DEFAULT / .snapshot)
from horovod_tpu import monitor  # noqa: F401  (hvd.monitor.MonitorServer / aggregate_snapshots)
from horovod_tpu.basics import HorovodInternalError  # noqa: F401

__version__ = "0.1.0"
