"""byteps_tpu.parallel — multi-dimensional parallelism over the device mesh.

The reference implements data parallelism only (SURVEY §2.7); the TPU
rebuild makes DP one axis of a general ``jax.sharding.Mesh`` and adds the
axes long-context / large-model training needs: tensor parallelism (tp,
Megatron-style column/row-parallel matmuls with psum over ICI), sequence /
context parallelism (sp, ring attention via ``ppermute``), and room for
pipeline (pp) / expert (ep) axes in the mesh factory.

Everything here is shard_map-first: functions take axis *names* and are
called inside ``jax.shard_map`` over a mesh built by :func:`make_mesh`.
"""

from byteps_tpu.parallel.mesh import MeshAxes, make_mesh, factor_devices
from byteps_tpu.parallel.partitioner import (FAMILY_RULES, LOGICAL_AXES,
                                             Partitioner, resolve_spec,
                                             resolve_specs, rules_from_axes,
                                             stacked_logical_specs)
from byteps_tpu.parallel.zero3 import (make_gpt_zero3_train_step,
                                       zero3_gather_params)
from byteps_tpu.parallel.moe import (moe_ffn, moe_init, moe_specs,
                                     top1_dispatch, topk_dispatch)
from byteps_tpu.parallel.pipeline import (
    last_stage_value,
    pipeline_apply,
    stack_blocks,
    stacked_specs,
)
from byteps_tpu.parallel.ring_attention import (
    plain_attention,
    ring_attention,
    zigzag_inverse,
    zigzag_local_positions,
    zigzag_permutation,
    zigzag_ring_attention,
)
from byteps_tpu.parallel.tp import (
    col_parallel_matmul,
    row_parallel_matmul,
    maybe_psum,
)

__all__ = [
    "MeshAxes",
    "make_mesh",
    "factor_devices",
    "Partitioner",
    "LOGICAL_AXES",
    "FAMILY_RULES",
    "resolve_spec",
    "resolve_specs",
    "rules_from_axes",
    "stacked_logical_specs",
    "make_gpt_zero3_train_step",
    "zero3_gather_params",
    "moe_ffn",
    "moe_init",
    "moe_specs",
    "top1_dispatch",
    "topk_dispatch",
    "pipeline_apply",
    "stack_blocks",
    "stacked_specs",
    "last_stage_value",
    "ring_attention",
    "plain_attention",
    "zigzag_ring_attention", "zigzag_permutation", "zigzag_inverse",
    "zigzag_local_positions",
    "col_parallel_matmul",
    "row_parallel_matmul",
    "maybe_psum",
]
