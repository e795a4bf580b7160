"""Parallelism layer: mesh construction, sharding helpers, and the
long-context/parallelism primitives built on the framework's device
collectives:

- **dp/sp/tp** — data, sequence (ring/Ulysses attention), and Megatron
  tensor parallelism (``attention``, ``layers``, the flagship model);
- routed experts (``moe``): dropless, on the device that holds them.

These are the TPU-native expression of the reference's communication
patterns (SURVEY.md §5): ring attention is the segmented-ring allreduce
shape (coll_base_allreduce.c:615) with double buffering; Ulysses is the
pairwise alltoall (coll_base_alltoall.c:132).
"""

from ompi_tpu.parallel.mesh import make_mesh, mesh_shape_for
