"""Parallelism: device meshes, collectives, sharding rules.

TPU-native replacement for the reference's NCCL/MPI communicator stack
(src/io/communicator.cc, SURVEY.md §2.2): collectives are XLA psum/
all_gather over ICI/DCN bound to mesh axes; cluster bootstrap is
jax.distributed instead of MPI_Init/ncclGetUniqueId.

Beyond reference parity (which is data-parallel only, §2.3), this package
carries tensor/sequence/pipeline sharding helpers used by the transformer
stack — long-context and multi-chip are first-class here.
"""

from .mesh import (  # noqa: F401
    make_mesh, data_parallel_mesh, factor_mesh, local_device_count,
)
from .communicator import Communicator  # noqa: F401
from .tp import (  # noqa: F401
    column_parallel, row_parallel, shard_columns, shard_rows, tp_mlp,
)
from .pipeline import gpipe, last_stage_value  # noqa: F401
