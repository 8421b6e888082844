"""byteps_tpu.ops — device kernels (Pallas TPU + jnp fallbacks).

The reference implements compressors as hand-written CPU C++
(``byteps/common/compressor/impl/*``); the TPU-native equivalents are
Pallas kernels for the hot wire ops, with jnp fallbacks that share the
exact wire layout so either backend can decode the other's payloads.
Backend selection: Pallas on TPU, jnp elsewhere; override with
``BYTEPS_KERNEL_BACKEND=pallas|jnp``.
"""

from byteps_tpu.ops.chunked_ce import chunked_ce_nll, dense_ce_nll
from byteps_tpu.ops.flash_attention import (
    attention_jnp,
    flash_attention,
    flash_attention_lse,
    merge_attention,
)
from byteps_tpu.ops.grouped_matmul import grouped_matmul, grouped_matmul_jnp
from byteps_tpu.ops.onebit_kernels import (
    onebit_pack,
    onebit_unpack,
    onebit_unpack_sum,
    packed_words,
)

__all__ = [
    "attention_jnp", "chunked_ce_nll", "dense_ce_nll", "flash_attention",
    "flash_attention_lse", "merge_attention",
    "grouped_matmul", "grouped_matmul_jnp",
    "onebit_pack", "onebit_unpack", "onebit_unpack_sum", "packed_words",
]
