from deep_vision_tpu.parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    make_mesh,
    replicate,
    shard_batch,
    batch_sharding,
    replicated_sharding,
)
from deep_vision_tpu.parallel.pipeline import (
    PIPE_AXIS,
    pipeline_apply,
    stack_stages,
    unstack_stages,
)

__all__ = [
    "DATA_AXIS",
    "MODEL_AXIS",
    "PIPE_AXIS",
    "pipeline_apply",
    "stack_stages",
    "unstack_stages",
    "make_mesh",
    "replicate",
    "shard_batch",
    "batch_sharding",
    "replicated_sharding",
]
