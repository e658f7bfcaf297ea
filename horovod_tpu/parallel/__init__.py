"""Parallelism strategies over the device mesh.

The reference implements exactly one strategy — synchronous data parallelism
via allreduce (SURVEY.md §2.1: "TP / PP / SP / EP / CP / ring-attention:
ABSENT") — so everything here beyond :mod:`data_parallel` is an extension
built on the same mesh-axis collective layer, designed TPU-first:

- :mod:`mesh`       — multi-axis mesh construction ('dp','tp','pp','sp','ep')
- :mod:`collectives` — named-axis collective wrappers for in-jit use
- :mod:`data_parallel` — batch sharding + gradient psum (the reference's
  core capability, recast as shardings)
- :mod:`tensor_parallel` — column/row-parallel Dense + attention heads
- :mod:`ring_attention` — sequence/context parallelism for long sequences
  (ppermute ring with online-softmax accumulation)
- :mod:`ulysses`    — all-to-all sequence parallelism (DeepSpeed-Ulysses:
  reshard seq->heads, local attention, reshard back)
- :mod:`pipeline`   — schedule-driven microbatch pipeline over 'pp'
  (gpipe / 1f1b / interleaved virtual stages, forward AND backward,
  docs/pipeline.md)
- :mod:`expert`     — mixture-of-experts dispatch over 'ep' (all_to_all)
- :mod:`zero`       — the sharded weight update's rule over 'dp'
  (which dimension of a leaf takes 'dp', which optimizers allow it)
"""

from .mesh import (MeshSpec, axis_kinds, create_mesh, dcn_axes,
                   ici_axes)
from .collectives import (all_gather, all_to_all, axis_index, axis_size,
                          cross_slice_bytes, hierarchical_psum,
                          hierarchical_psum_tree, ppermute, psum,
                          psum_scatter, ring_shift)
from .data_parallel import shard_batch, allreduce_gradients_in_jit
from .pipeline import (PipelineSchedule, pipeline_apply,
                       pipeline_value_and_grad, schedule_info)

__all__ = [
    "MeshSpec", "create_mesh", "axis_kinds", "dcn_axes", "ici_axes",
    "psum", "all_gather", "ppermute", "all_to_all", "psum_scatter",
    "axis_index", "axis_size", "ring_shift",
    "hierarchical_psum", "hierarchical_psum_tree", "cross_slice_bytes",
    "shard_batch", "allreduce_gradients_in_jit",
    "PipelineSchedule", "pipeline_apply", "pipeline_value_and_grad",
    "schedule_info",
]
