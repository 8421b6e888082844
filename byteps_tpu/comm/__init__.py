"""Data-movement layer.

Reference analogs: ``byteps/common/nccl_manager.cc`` (intra-node NCCL) →
``comm/ici.py`` (XLA collectives over the ICI mesh inside shard_map);
``3rdparty/ps-lite`` + ``byteps/common/shared_memory.cc`` (inter-node
push/pull) → ``comm/dcn.py`` (DCN parameter-server client).
"""

from byteps_tpu.comm.mesh import device_mesh, local_device_count  # noqa: F401
from byteps_tpu.comm.ici import (  # noqa: F401
    allreduce_flat,
    broadcast_flat,
    compressed_allreduce_flat,
    compressed_allreduce_local,
    compressed_reduce_scatter_flat,
    compressed_reduce_scatter_local,
)
