"""Device-mesh helpers.

The reference coordinates GPU ranks through env vars + unix sockets
(``communicator.cc``); on TPU the single-controller model makes the local
"rank table" just a ``jax.sharding.Mesh``. Multi-host rendezvous is
``jax.distributed`` (reference: ps-lite scheduler rendezvous, SURVEY §5.8).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh

from byteps_tpu.common.config import get_config


def local_device_count() -> int:
    return jax.local_device_count()


def device_mesh(
    shape: Optional[Sequence[int]] = None,
    axis_names: Optional[Tuple[str, ...]] = None,
) -> Mesh:
    """Build a mesh; default is 1-D over all devices on the dp axis."""
    cfg = get_config()
    if shape is None:
        shape = (len(jax.devices()),)
    if axis_names is None:
        axis_names = (cfg.dp_axis,) if len(shape) == 1 else tuple(
            f"ax{i}" for i in range(len(shape))
        )
    # Auto axes, as parallel/mesh.py: jax.make_mesh defaults to Explicit
    return jax.make_mesh(tuple(shape), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(shape))
