"""Auto-tuned fused train step: retrace at tuner-chosen partition sizes.

Reference analog: the ByteScheduler tuner adjusts partition size online
while training runs (bytescheduler/common/search.py, SOSP'19 §5). On the
reference's eager engine a move just changes how the next tensors are
sliced; on the fused jit path the partition size is baked into the traced
XLA program, so a move means a retrace. ``AutoTunedStep`` owns that
machinery: it keeps one jitted executable per visited partition size
(compiles are cached, the tuner's grid is small), times each step, feeds
the tuner, and swaps executables when the tuner moves.

Credit is not a fused-path knob — inside one XLA program the compiler
orders the collectives (the raw path chains its buckets behind the
backward kernels that yield them; none of them is hidden under compute,
``jax/optimizer.py::_aggregate_buckets``) — so the tuner searches
``knobs=("partition",)``: on the raw path the size a bucket closes at.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, Optional

import jax

from byteps_tpu.common.logging import get_logger
from byteps_tpu.common.tracing import get_tracer, traced_program
from byteps_tpu.common.tuner import AutoTuner

log = get_logger("jax.tuned_step")


class AutoTunedStep:
    """Callable wrapping ``build_jit(partition_bytes) -> jitted step``.

    While the tuner is searching, every call blocks until the step's outputs
    are ready so the measured wall time is the true step time (the same
    synchronization the reference's tuner imposes); once converged, calls
    pass through without blocking and async dispatch pipelining returns.
    The warmup skip inside :class:`AutoTuner` absorbs the compile cost of a
    fresh partition size, so a retrace never pollutes its own measurement.
    """

    def __init__(
        self,
        build_jit: Callable[[Optional[int]], Callable],
        partition_bytes: int,
        interval: int = 5,
        warmup: int = 3,
        min_gain: float = 0.02,
    ) -> None:
        self._build = build_jit
        self._compiled: Dict[int, Callable] = {}
        self._pb = partition_bytes
        self.retraces = 0
        self.tuner = AutoTuner(
            apply=self._apply,
            interval=interval,
            warmup=warmup,
            min_gain=min_gain,
            partition_bytes=partition_bytes,
            knobs=("partition",),
        )

    def _apply(self, pb: int, credit: int) -> None:
        if pb != self._pb:
            log.info(
                "tuner: fused step moving to partition=%dKB%s",
                pb >> 10,
                "" if pb in self._compiled else " (will retrace)",
            )
        self._pb = pb

    @property
    def partition_bytes(self) -> int:
        """The partition size the next call will run with."""
        return self._pb

    @property
    def compiled_partition_sizes(self):
        return sorted(self._compiled)

    def __call__(self, *args):
        # always-on train-step tick (docs/observability.md): the plain
        # jitted path gets this from _finalize_step's wrapper; the tuned
        # path must stay an AutoTunedStep instance, so it ticks itself
        # (relative — the recorder may already be ahead of this
        # instance's private step count)
        from byteps_tpu.common.flight_recorder import get_flight_recorder

        get_flight_recorder().tick()
        step = self._compiled.get(self._pb)
        if step is None:
            step = traced_program("train.step", self._build(self._pb))
            self._compiled[self._pb] = step
            self.retraces += 1
        t0 = time.perf_counter()
        with get_tracer().span("train.dispatch", "TRAIN"):
            out = step(*args)
        if self.tuner.converged:
            return out
        jax.block_until_ready(out)
        self.tuner.record_step(time.perf_counter() - t0)
        return out
