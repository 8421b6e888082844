"""byteps_tpu — a TPU-native distributed training framework.

A ground-up re-design of the capabilities of joapolarbear/byteps (a fork of
bytedance/byteps; see SURVEY.md for the reference's structural analysis):

* Horovod-style ``push_pull`` / ``DistributedOptimizer`` APIs
  (reference: ``byteps/torch/__init__.py``, ``byteps/tensorflow/__init__.py``)
* tensor partitioning into ~4 MB chunks with priority = -declaration order and
  credit-limited in-flight partitions
  (reference: ``byteps/common/operations.cc``, ``byteps/common/scheduled_queue.cc``)
* pluggable gradient compression — onebit, topk, randomk, dithering, with
  error-feedback and Nesterov-momentum decorators
  (reference: ``byteps/common/compressor/``)
* hybrid parameter-server topology: intra-pod ICI collectives + a C++
  summation service over DCN
  (reference: ``byteps/server/server.cc``, ``3rdparty/ps-lite/``)

The compute path is JAX/XLA/Pallas over a ``jax.sharding.Mesh``; the host
runtime (DCN summation server, CPU reducer) is native C++.
"""

__version__ = "0.1.0"

from byteps_tpu.common.config import Config, get_config  # noqa: F401,E402


def metrics_snapshot() -> dict:
    """One JSON-safe view of the always-on telemetry plane
    (docs/observability.md): the unified metrics registry (scheduler
    stage dwell/run percentiles, per-NIC wire bytes/attempts/retries,
    pacer debt, ICI dispatch counts, fault injections, train-step
    walltime) plus the flight recorder's ring occupancy. The hook bench
    legs and tests assert against — and what ops would scrape."""
    from byteps_tpu.common.flight_recorder import get_flight_recorder
    from byteps_tpu.common.metrics import get_registry

    return {
        "metrics": get_registry().snapshot(),
        "flight_recorder": get_flight_recorder().summary(),
    }
