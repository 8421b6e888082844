"""byteps_tpu.serve — the continuous-batching inference tier.

The training side of this repo already had every serving-shaped piece
(`models/generate.py` KV cache + cached apply, `models/speculative.py`,
flash decode) but served exactly one request at a time with a
fixed-shape cache. This subsystem is the vLLM/Orca-shaped completion:

* ``paged_cache`` — a block-paged KV pool: fixed-size KV blocks
  preallocated once, per-request block tables, so sequences of wildly
  different lengths pack one device batch (PagedAttention's memory
  model). Pages are refcounted and shareable: a radix prefix index
  over committed prefill blocks (RadixAttention's organization) lets
  requests with a common prompt prefix map the SAME physical pages,
  with copy-on-write at the divergence block and LRU eviction of
  cached-but-idle pages under pool pressure
  (``BYTEPS_SERVE_PREFIX_CACHE``, default-on).
* ``scheduler`` — iteration-level request scheduling: continuous
  admission from a queue, chunked prefill so long prompts can't starve
  decoders, preemption under block-pool pressure with
  recompute-on-resume, and speculative decoding as a per-request
  policy (Orca's per-step admission instead of run-to-completion
  batches).
* ``router`` — multi-replica routing with lease/epoch replica
  liveness mirroring the PR 5 elastic-membership layer: a dead
  replica's in-flight requests re-queue to survivors.
* ``adapter_pool`` — multi-tenant LoRA multiplexing (docs/serving.md
  §multi-tenant): LoRA A/B weights paged into a fixed device-resident
  slot pool exactly like KV blocks (refcounts, LRU eviction of idle
  adapters, host registry as the reload source), so ONE replica
  serves 32+ fine-tuned variants of its base model; the packed decode
  step gathers each row's adapter by slot index
  (``ops/segmented_lora.py`` — the S-LoRA/Punica shape) with
  per-tenant fair queuing and KV quotas in the scheduler.
* ``kv_wire`` — disaggregated prefill/decode (docs/serving.md
  §disaggregation): dedicated prefill replicas stream committed KV
  blocks to their decode target over a KVCOMPRESS→KVPUSH stage
  pipeline (wire-scoped credits, token-bucket pacer, CRC + stage
  retry — the gradient tier's wire machinery reused as a KV-migration
  transport), and the same wire turns pool-pressure preemption into
  migrate-don't-evict: committed blocks MOVE to a sibling instead of
  being freed and recomputed.

Greedy outputs are pinned BIT-identical (token-for-token) to
single-request ``make_generate_fn`` runs — batching and paging are
pure throughput levers, never content changes (tests/test_serve.py).
Measured by the serve cells of ``BENCHMARK.json`` (docs/serving.md).
"""

from byteps_tpu.serve.adapter_pool import AdapterPool  # noqa: E402,F401
from byteps_tpu.serve.kv_wire import (  # noqa: E402,F401
    BlockPayload,
    KVBlockCodec,
    KVWire,
    MigrationTicket,
)
from byteps_tpu.serve.paged_cache import (  # noqa: E402,F401
    PagedKVCache,
    PoolState,
    make_paged_decode_fn,
    make_paged_prefill_fn,
)
from byteps_tpu.serve.router import Router  # noqa: E402,F401
from byteps_tpu.serve.scheduler import (  # noqa: E402,F401
    Request,
    Scheduler,
    SpecPolicy,
)
