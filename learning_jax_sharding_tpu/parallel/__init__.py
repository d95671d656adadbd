"""Parallelism layers: mesh (L1), sharding placement (L2), logical axes (L3),
explicit collectives, HLO introspection, and multi-host bootstrap."""

from learning_jax_sharding_tpu.parallel.mesh import (  # noqa: F401
    DATA_AXIS,
    DEFAULT_AXIS_NAMES,
    MODEL_AXIS,
    MeshSpec,
    build_hybrid_mesh,
    build_mesh,
    force_emulated_devices,
    single_device_mesh,
)
from learning_jax_sharding_tpu.parallel.sharding import (  # noqa: F401
    P,
    assert_replicated,
    assert_shard_shape,
    col_sharded,
    is_fully_replicated,
    mesh_sharding,
    put,
    replicated,
    row_sharded,
    shard_arrays,
    shard_dims,
    shard_shapes,
    unique_shard_count,
    visualize,
)
from learning_jax_sharding_tpu.parallel.resharding import (  # noqa: F401
    DEFAULT_PAGE_TOKENS,
    Segment,
    TransferPlan,
    device_reshard,
    execute_transfer,
    plan_transfer,
    reshard_tree,
    transfer_tree,
)
from learning_jax_sharding_tpu.parallel.hlo import (  # noqa: F401
    assert_collectives,
    collective_counts,
    compiled_hlo,
)
from learning_jax_sharding_tpu.parallel.pipeline import (  # noqa: F401
    PIPE_AXIS,
    spmd_pipeline,
    stack_stage_params,
)
