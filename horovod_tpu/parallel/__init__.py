"""Parallelism strategies for horovod_tpu.

The reference implements data parallelism only (SURVEY.md §2.3); on TPU the
framework supplies the full set as first-class, mesh-native components:

* **DP / FSDP / TP** — sharding annotations over mesh axes
  (:mod:`.mesh_utils`, :mod:`.sharding`), reduced by XLA.
* **Hierarchical DP** — reduce_scatter(ICI) → psum(DCN) → all_gather(ICI)
  (:mod:`.hierarchical`), the NCCLHierarchicalAllreduce shape
  (/root/reference/horovod/common/ops/nccl_operations.cc:178-372).
* **Context parallelism / ring attention** — K/V blocks rotate around the
  'sp' ring via ppermute with flash-style online softmax
  (:mod:`.ring_attention`).
* **Sequence parallelism (Ulysses)** — all_to_all that trades the sequence
  axis for the head axis (:mod:`.ulysses`).
* **Pipeline parallelism** — microbatch schedule over the 'pp' axis with
  collective-permute activation transfer (:mod:`.pipeline`).
* **Expert parallelism (MoE)** — top-k routing + all_to_all token dispatch
  over the 'ep' axis (:mod:`.moe`).
"""

from .mesh_utils import (MeshConfig, make_training_mesh,  # noqa: F401
                         ACTIVATION_RULES, TRANSFORMER_RULES,
                         collective_census, fsdp_sharded_leaves,
                         require_axes)
from .hierarchical import hierarchical_allreduce, hierarchical_pmean  # noqa: F401
from .ring_attention import (  # noqa: F401
    ring_attention, ring_attention_flash,
)
from .ulysses import ulysses_attention  # noqa: F401
from .pipeline import pipeline_apply  # noqa: F401
from .moe import MoEMlp, moe_mlp, route_top1  # noqa: F401
