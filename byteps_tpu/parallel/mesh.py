"""Mesh factory for multi-axis parallelism.

The reference's topology is env-var process ranks (``DMLC_WORKER_ID`` ×
``BYTEPS_LOCAL_RANK``, SURVEY §5.6); on TPU the topology is a named
``jax.sharding.Mesh``. Axis convention (order matters — outermost first so
slice_ rides DCN across slices and tp/sp ride ICI within one):

    (slice_, pp, dp, sp, tp, ep)   — any axis of size 1 may be omitted.

``slice_`` is the DCN axis: one entry per TPU slice (pod span). On real
multi-slice topologies :func:`make_mesh` builds it with
``mesh_utils.create_hybrid_device_mesh`` so the outer axis crosses the
data-center network and every inner axis stays on ICI. On CPU or a single
slice the boundary is emulated by contiguous grouping so tier-1 tests can
exercise the multi-slice code paths on fake devices.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh


@dataclasses.dataclass(frozen=True)
class MeshAxes:
    """Named axis sizes for :func:`make_mesh`. Size 1 disables an axis."""

    dp: int = 1
    tp: int = 1
    sp: int = 1
    pp: int = 1
    ep: int = 1
    slice_: int = 1

    @property
    def total(self) -> int:
        return self.dp * self.tp * self.sp * self.pp * self.ep * self.slice_

    @property
    def per_slice(self) -> int:
        return self.dp * self.tp * self.sp * self.pp * self.ep

    def as_dict(self) -> Dict[str, int]:
        return {"slice_": self.slice_, "pp": self.pp, "dp": self.dp,
                "sp": self.sp, "tp": self.tp, "ep": self.ep}


def _device_slice_index(d) -> Optional[int]:
    """Real slice id of a device, or None when the runtime has no DCN
    topology (CPU, single slice)."""
    return getattr(d, "slice_index", None)


def make_mesh(axes: MeshAxes, devices: Optional[Sequence] = None) -> Mesh:
    """Build a mesh with only the non-trivial axes of ``axes``.

    Axis order is (slice_, pp, dp, sp, tp, ep) outermost→innermost: tp
    needs the tightest coupling (per-matmul psum) so it gets the innermost
    (fastest ICI neighbourhood) placement; pp crosses the slowest ICI
    links, and slice_ crosses DCN.

    With ``axes.slice_ > 1`` on a real multi-slice topology (devices carry
    distinct ``slice_index``) the device grid comes from
    ``mesh_utils.create_hybrid_device_mesh`` so slice_ is the DCN axis.
    Anywhere else the slice boundary is emulated: devices are grouped
    contiguously, ``axes.per_slice`` per emulated slice.
    """
    if devices is None:
        devices = jax.devices()
    if axes.total != len(devices):
        raise ValueError(
            f"mesh axes {axes.as_dict()} require {axes.total} devices, "
            f"have {len(devices)}"
        )
    names = []
    sizes = []
    for name, size in axes.as_dict().items():
        if size > 1:
            names.append(name)
            sizes.append(size)
    if not names:
        # Single device: expose every axis at size 1 so axis lookups
        # (tp/sp/... code asking mesh.shape["tp"]) work on the degenerate
        # mesh the same way they do on a real one.
        names = list(axes.as_dict().keys())
        sizes = [1] * len(names)
        import numpy as np

        grid = np.asarray(devices, dtype=object).reshape(tuple(sizes))
        return Mesh(grid, tuple(names))
    if axes.slice_ > 1:
        slice_ids = {_device_slice_index(d) for d in devices}
        if len(slice_ids) == axes.slice_ and None not in slice_ids:
            from jax.experimental import mesh_utils

            # names[0] is always slice_ here (first in as_dict, size > 1).
            grid = mesh_utils.create_hybrid_device_mesh(
                mesh_shape=(1,) + tuple(sizes[1:]),
                dcn_mesh_shape=(axes.slice_,) + (1,) * (len(sizes) - 1),
                devices=devices,
            )
            return Mesh(grid, tuple(names))
    # Auto axes: every shard_map/PartitionSpec here assumes them, and
    # jax.make_mesh defaults to Explicit (sharding-in-types)
    return jax.make_mesh(tuple(sizes), tuple(names), devices=devices,
                         axis_types=(AxisType.Auto,) * len(names))


def factor_devices(n: int, want_tp: int = 2, want_sp: int = 2,
                   want_pp: int = 1, want_ep: int = 1,
                   n_slices: int = 1) -> MeshAxes:
    """Heuristic factorization of ``n`` devices onto (slice_, pp, dp, sp,
    tp, ep).

    Used by the dry-run path and examples. ``n_slices`` is carved off
    first (the DCN dimension must divide ``n`` exactly — a ragged slice
    count is a topology error, so it raises rather than rounding down).
    Within one slice, ep then tp then sp are carved off innermost-first
    when they divide the remainder, then pp, and dp absorbs what's left.
    Requested factors that don't divide evenly fall back to 1 (matching
    the historical tp/sp behaviour) instead of erroring.
    """
    if n_slices < 1 or n % n_slices != 0:
        raise ValueError(f"{n} devices cannot split into {n_slices} slices")
    per_slice = n // n_slices

    def carve(rem: int, want: int) -> int:
        return want if want > 1 and rem % want == 0 and rem >= want else 1

    rem = per_slice
    ep = carve(rem, want_ep)
    rem //= ep
    tp = carve(rem, want_tp)
    rem //= tp
    sp = carve(rem, want_sp)
    rem //= sp
    pp = carve(rem, want_pp)
    rem //= pp
    return MeshAxes(dp=rem, tp=tp, sp=sp, pp=pp, ep=ep, slice_=n_slices)
