"""Kernel-backend selection shared by every op module.

One dispatch rule for the whole ops package (the reference's analog is its
compile-time CUDA/CPU split; here it's a runtime choice): Pallas on TPU,
jnp elsewhere, overridable with ``BYTEPS_KERNEL_BACKEND=pallas|jnp``
(``pallas`` off-TPU means interpret mode — see docs/env.md for the
``check_vma`` caveat). Whether a Pallas kernel runs compiled or under the
interpreter is decided here too (:func:`interpret`), and a dispatcher that
chose Pallas but must take its jnp twin for a shape says so through
:func:`note_fallback`.
"""

from __future__ import annotations

import functools
import os

import jax

from byteps_tpu.common.logging import get_logger

log = get_logger("ops")


def _on_tpu() -> bool:
    """The one platform question of the ops package (tests that compile
    for a described chip steer it here)."""
    return jax.default_backend() == "tpu"


def kernel_backend() -> str:
    env = os.environ.get("BYTEPS_KERNEL_BACKEND", "")
    if env in ("pallas", "jnp"):
        return env
    return "pallas" if _on_tpu() else "jnp"


def use_pallas() -> bool:
    return kernel_backend() == "pallas"


def interpret() -> bool:
    """``pallas_call(interpret=...)`` for every kernel: compiled on TPU,
    the Pallas interpreter anywhere else."""
    return not _on_tpu()


@functools.lru_cache(maxsize=None)
def note_fallback(kernel: str, shape: tuple, why: str) -> None:
    """The backend is Pallas but ``kernel`` takes its jnp twin for
    ``shape``: log it at WARNING, once per (kernel, shape) — the cache
    is the once."""
    log.warning("%s: shape %s takes the jnp twin, not the Pallas kernel "
                "(%s)", kernel, shape, why)
