"""Parallelism layer: mesh construction, sharding rules, collectives.

Replaces the reference's L3 (param broadcast + NCCL all-reduce + FSDP) and the
NCCL native backend (SURVEY.md §1) with GSPMD over a named TPU device mesh.
"""

from distributeddeeplearningspark_tpu.parallel.mesh import (
    AXIS_DATA,
    AXIS_EXPERT,
    AXIS_FSDP,
    AXIS_SEQ,
    AXIS_TENSOR,
    BATCH_AXES,
    MESH_AXES,
    MeshSpec,
    batch_sharding,
    batch_spec,
    num_data_shards,
    replicated,
    single_device_mesh,
)
from distributeddeeplearningspark_tpu.parallel.reshard import (
    SpanUnavailableError,
    project_spec,
    redistribute,
    shardings_from_record,
)
from distributeddeeplearningspark_tpu.parallel.plan import (
    DP,
    FSDP_PLAN,
    Plan,
    PlanError,
    PlanValidationError,
    compile_step_with_plan,
    plan_for_rules,
    stage_plan,
    zero_plan,
)
from distributeddeeplearningspark_tpu.parallel.sharding import (
    FSDP,
    REPLICATED,
    ShardingRules,
    add_axis_spec,
    state_shardings,
)

__all__ = [
    "AXIS_DATA",
    "AXIS_EXPERT",
    "AXIS_FSDP",
    "AXIS_SEQ",
    "AXIS_TENSOR",
    "BATCH_AXES",
    "MESH_AXES",
    "MeshSpec",
    "batch_sharding",
    "batch_spec",
    "num_data_shards",
    "replicated",
    "single_device_mesh",
    "ShardingRules",
    "REPLICATED",
    "FSDP",
    "state_shardings",
    "add_axis_spec",
    "Plan",
    "PlanError",
    "PlanValidationError",
    "compile_step_with_plan",
    "plan_for_rules",
    "stage_plan",
    "zero_plan",
    "DP",
    "FSDP_PLAN",
    "SpanUnavailableError",
    "project_spec",
    "redistribute",
    "shardings_from_record",
]
