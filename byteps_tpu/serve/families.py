"""The one seam between the serve tier and a model family.

What ``Scheduler`` and ``PagedKVCache`` need from a model — the pools'
shapes by layer kind, which layers give blocks back behind which window, the
decode program, the chunk program, the operand tree, and what the family's
cache layout cannot carry — comes from :func:`serve_family`, chosen by the
configuration's TYPE. Admission, the prefill lane, decode packing,
preemption, results and every ``serve.*`` metric are the scheduler's and the
same for every family.

* :class:`GPTFamily` (``GPTConfig``): the k/v pool and the two programs of
  ``paged_cache.py``, as they were.
* :class:`LatentFamily` (``Dots3Config``): latent pages of two layer kinds
  and the programs of ``latent_step.py``.
* :class:`WindowedKVFamily` (``Mellum2Config``): k/v pages of two layer
  kinds under ``paged_cache.py``'s own two programs, each layer told its
  kind, with a dropless expert FFN in every block.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import numpy as np

from byteps_tpu.models.gpt import GPTConfig


class PoolLayout(NamedTuple):
    """What a family's cache is made of. ``state``: the device pytree both
    programs thread (donated). ``kv_heads``: heads of a k/v payload (0: the
    layout has no such payload, so nothing of it travels a wire).
    ``window``: keys a window layer keeps, the query's own included (None:
    no window kind); ``window_blocks``: blocks of the window kind's own
    pool, its scratch block included."""

    state: Any
    kv_heads: int = 0
    window: Optional[int] = None
    window_blocks: int = 0


class LateStats:
    """What a family's programs count on the device (``pool.stats``, f32, one
    value a name of ``names``), observed into the registry once the device
    has it — a step later, when it costs no wait. A family's own subclass
    says in :meth:`observe` which series each value feeds."""

    names: tuple = ()

    def __init__(self):
        self._pending = []

    def note(self, pool) -> None:
        # a buffer of its own: the pool, stats leaf included, is donated to
        # the next program
        self._pending.append(pool.stats + 0.0)
        self.drain(block=False)

    def drain(self, block: bool) -> None:
        while self._pending and (block or self._pending[0].is_ready()):
            self.observe(dict(zip(
                self.names, np.asarray(self._pending.pop(0)).tolist())))

    def observe(self, s: dict) -> None:
        raise NotImplementedError


def window_pool_blocks(window: int, block_size: int, max_batch: int,
                       prefill_chunk: int) -> int:
    """Blocks of a window kind's pool: a request keeps the blocks of its
    last ``window - 1`` positions (and the one being filled); the one request
    a chunk runs for holds the chunk's beside them. Every admitted request
    at once, and scratch."""
    per_req = -(-(window - 1) // block_size) + 2
    admitted = max_batch + max(1, max_batch // 4)
    return 1 + admitted * per_req + -(-prefill_chunk // block_size) + 1


class _Refusing:
    """A family whose layout does not carry everything: ``REFUSED`` maps a
    feature to the message's subject, each refused at construction by
    name, a speculative request at ``submit``."""

    REFUSED: dict = {}

    def _refuse(self, name, cfg):
        raise NotImplementedError(
            f"Scheduler: {self.REFUSED[name]} is not served with the "
            f"{self.name} cache layout of {type(cfg).__name__} (asked "
            f"through {name})")

    def validate(self, params, cfg, features) -> None:
        for name, on in features.items():
            if on:
                self._refuse(name, cfg)

    def validate_request(self, req, cfg) -> None:
        if req.spec is not None:
            self._refuse("speculation", cfg)


class GPTFamily:
    """The dense GPT family over a k/v pool (``paged_cache.py``)."""

    name = "gpt"
    shares_prefixes = True          # the radix index over k/v pages

    def validate(self, params, cfg, features) -> None:
        if any("moe" in p for p in params["blocks"]):
            raise NotImplementedError(
                "Scheduler: a Switch-routed expert layer (models/moe_gpt.py) "
                "is not served — a GPTConfig's serve step runs dense-MLP "
                "blocks only (capacity routing has not been paged; dropless "
                "expert layers are served under a Mellum2Config over k/v "
                "pages and under a Dots3Config over latent pages)")

    def validate_request(self, req, cfg) -> None:
        pass

    def layout(self, params, cfg, *, block_size, pool_blocks, max_batch,
               prefill_chunk, quant) -> PoolLayout:
        from byteps_tpu.serve.paged_cache import kv_pool_state

        kv_loc = params["blocks"][0]["wk"].shape[-1] // cfg.head_dim
        return PoolLayout(
            state=kv_pool_state(cfg, block_size, pool_blocks, kv_loc, quant),
            kv_heads=kv_loc)

    def operands(self, params, cfg):
        from byteps_tpu.serve.paged_cache import serve_operands

        return serve_operands(params, cfg)

    def decode_fn(self, cfg, block_size, tp_axis, lora_sig):
        from byteps_tpu.serve.paged_cache import make_paged_decode_fn

        return make_paged_decode_fn(cfg, block_size, tp_axis, lora_sig)

    def prefill_fn(self, cfg, block_size, chunk_len, tp_axis, with_readout):
        from byteps_tpu.serve.paged_cache import make_paged_prefill_fn

        return make_paged_prefill_fn(cfg, block_size, chunk_len, tp_axis,
                                     with_readout)

    def decode_reads_pool_in_place(self, cfg, cache) -> bool:
        from byteps_tpu.serve.paged_cache import decode_uses_paged_attn

        return decode_uses_paged_attn(cfg, cache.block_size, cache.kv_heads,
                                      cache.quant)

    def late_stats(self):
        return None


class LatentFamily(_Refusing):
    """dots3 over latent pages of two layer kinds (``latent_step.py``)."""

    name = "latent"
    shares_prefixes = False         # the configuration's default is not applied

    #: what the latent layout does not carry yet, each refused at
    #: construction: ``feature -> the message's subject``
    REFUSED = {
        "prefix_cache": "the prefix cache (a window layer's released blocks "
                        "cannot be shared)",
        "speculation": "speculative decoding (the chunk program returns no "
                       "rewindable window state)",
        "adapter_pool": "LoRA adapter slabs",
        "quant_cache": "the int8 pool",
        "role": "role='prefill'|'decode' and migration over kv_wire (no "
                "payload for latent pages)",
        "tp_axis": "tensor parallelism",
    }

    def layout(self, params, cfg, *, block_size, pool_blocks, max_batch,
               prefill_chunk, quant) -> PoolLayout:
        from byteps_tpu.serve.latent_step import init_pool

        wb = window_pool_blocks(cfg.window, block_size, max_batch,
                                prefill_chunk)
        return PoolLayout(
            state=init_pool(cfg, block_size, pool_blocks, wb),
            window=cfg.window, window_blocks=wb)

    def operands(self, params, cfg):
        return params              # published in bf16: every leaf as it is

    def decode_fn(self, cfg, block_size, tp_axis, lora_sig):
        from byteps_tpu.serve.latent_step import make_latent_decode_fn

        return make_latent_decode_fn(cfg, block_size)

    def prefill_fn(self, cfg, block_size, chunk_len, tp_axis, with_readout):
        from byteps_tpu.serve.latent_step import make_latent_prefill_fn

        return make_latent_prefill_fn(cfg, block_size, chunk_len,
                                      with_readout)

    def decode_reads_pool_in_place(self, cfg, cache) -> bool:
        return False

    def late_stats(self):
        from byteps_tpu.serve.latent_step import LateStats

        return LateStats()


@functools.lru_cache(maxsize=16)
def _windowed_plan(cfg):
    """The ``StepPlan`` of a ``Mellum2Config``: each layer's row in its
    kind's pool, its window, its rotation; the expert FFN. Built once a
    configuration (a chunk is dispatched through it)."""
    from byteps_tpu.models.mellum2 import (
        FULL, SLIDING, expert_ffn, rope_freqs)
    from byteps_tpu.serve.paged_cache import LayerKind, StepPlan

    place = {li: i for kind in (FULL, SLIDING)
             for i, li in enumerate(cfg.layers_of(kind))}
    return StepPlan(tuple(
        LayerKind(place[li], cfg.window if kind == SLIDING else None,
                  rope_freqs(cfg, kind))
        for li, kind in enumerate(cfg.layer_types)), expert_ffn)


class WindowedKVFamily(_Refusing, GPTFamily):
    """Mellum2 over k/v pages of two layer kinds: the programs of
    ``paged_cache.py`` under a :class:`~paged_cache.StepPlan` that gives
    each layer its kind — a global pool for the full (YaRN) layers, a window
    pool whose blocks come back for the sliding ones — and the dropless
    expert FFN as the block's second half."""

    name = "windowed k/v"
    shares_prefixes = False

    #: what two kinds of k/v page do not carry yet, each refused at
    #: construction: ``feature -> the message's subject``
    REFUSED = {
        "prefix_cache": "the prefix cache (a window layer's released blocks "
                        "cannot be shared)",
        "speculation": "speculative decoding (a rejected draft would rewind "
                       "past blocks the window layers have given back)",
        "adapter_pool": "LoRA adapter slabs",
        "quant_cache": "the int8 pool",
        "role": "role='prefill'|'decode' and migration over kv_wire (a "
                "payload carries one kind of page)",
        "tp_axis": "tensor parallelism",
    }

    def layout(self, params, cfg, *, block_size, pool_blocks, max_batch,
               prefill_chunk, quant) -> PoolLayout:
        from byteps_tpu.models.mellum2 import FULL, SLIDING
        from byteps_tpu.serve.paged_cache import kv_pool_state

        wb = window_pool_blocks(cfg.window, block_size, max_batch,
                                prefill_chunk)
        return PoolLayout(
            state=kv_pool_state(
                cfg, block_size, pool_blocks, cfg.kv_heads, False,
                layers=len(cfg.layers_of(FULL)),
                window_layers=len(cfg.layers_of(SLIDING)), window_blocks=wb),
            kv_heads=cfg.kv_heads, window=cfg.window, window_blocks=wb)

    def operands(self, params, cfg):
        return params              # published in bf16: every leaf as it is

    def decode_fn(self, cfg, block_size, tp_axis, lora_sig):
        from byteps_tpu.serve.paged_cache import make_paged_decode_fn

        return make_paged_decode_fn(cfg, block_size, plan=_windowed_plan(cfg))

    def prefill_fn(self, cfg, block_size, chunk_len, tp_axis, with_readout):
        from byteps_tpu.serve.paged_cache import make_paged_prefill_fn

        return make_paged_prefill_fn(cfg, block_size, chunk_len,
                                     with_readout=with_readout,
                                     plan=_windowed_plan(cfg))

    def late_stats(self):
        from byteps_tpu.serve.paged_cache import StepStats

        return StepStats()


def serve_family(cfg):
    """The family that serves ``cfg``, by its type."""
    from byteps_tpu.models.dots3 import Dots3Config
    from byteps_tpu.models.mellum2 import Mellum2Config

    if isinstance(cfg, Dots3Config):
        return LatentFamily()
    if isinstance(cfg, Mellum2Config):
        return WindowedKVFamily()
    if isinstance(cfg, GPTConfig):
        return GPTFamily()
    raise TypeError(
        f"Scheduler: no serve family for a {type(cfg).__name__} "
        "(GPTConfig, Dots3Config and Mellum2Config are served)")
