"""Pluggable gradient compression (reference: ``byteps/common/compressor/``).

Compressors are **pure functions over fixed-shape arrays** so they compose
with jit/vmap/shard_map, unlike the reference's stateful C++ objects; all
carried state (error feedback, momentum) lives in explicit pytrees threaded
through the optimizer (SURVEY §7 "Error-feedback state under jit").

Selection mirrors the reference's ``compression_params`` dict passed to the
framework adapters, e.g.::

    {"compressor": "onebit", "ef": "vanilla", "momentum": "nesterov",
     "scaling": True, "k": 0.01, "seed": 0}
"""

from byteps_tpu.compression.base import (  # noqa: F401
    Compressor,
    from_params,
    get_compressor,
    register_compressor,
)
from byteps_tpu.compression.fp16 import Fp16Compressor  # noqa: F401
from byteps_tpu.compression.fp8 import Fp8Compressor  # noqa: F401
from byteps_tpu.compression.onebit import OnebitCompressor  # noqa: F401
from byteps_tpu.compression.topk import TopkCompressor  # noqa: F401
from byteps_tpu.compression.randomk import RandomkCompressor  # noqa: F401
from byteps_tpu.compression.dithering import DitheringCompressor  # noqa: F401
from byteps_tpu.compression.error_feedback import (  # noqa: F401
    ef_compress,
    ef_init_state,
    momentum_init_state,
    momentum_step,
)
