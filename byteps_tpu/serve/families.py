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
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

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


class GPTFamily:
    """The dense GPT family over a k/v pool (``paged_cache.py``)."""

    name = "gpt"
    shares_prefixes = True          # the radix index over k/v pages

    def validate(self, params, cfg, features) -> None:
        if any("moe" in p for p in params["blocks"]):
            raise NotImplementedError(
                "Scheduler: a Switch-routed expert layer (models/moe_gpt.py) "
                "is not served — the GPT serve step runs dense-MLP blocks "
                "only (no-drop capacity routing has not been paged)")

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


class LatentFamily:
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

    def validate(self, params, cfg, features) -> None:
        for name, on in features.items():
            if on:
                self._refuse(name, cfg)

    def _refuse(self, name, cfg):
        raise NotImplementedError(
            f"Scheduler: {self.REFUSED[name]} is not served with the latent "
            f"cache layout of {type(cfg).__name__} (asked through {name})")

    def validate_request(self, req, cfg) -> None:
        if req.spec is not None:
            self._refuse("speculation", cfg)

    def layout(self, params, cfg, *, block_size, pool_blocks, max_batch,
               prefill_chunk, quant) -> PoolLayout:
        from byteps_tpu.serve.latent_step import init_pool

        # a request keeps the blocks of its last window - 1 positions (and
        # the one being filled); the one request a chunk runs for holds the
        # chunk's beside them. Every admitted request at once, and scratch
        per_req = -(-(cfg.window - 1) // block_size) + 2
        admitted = max_batch + max(1, max_batch // 4)
        wb = 1 + admitted * per_req + -(-prefill_chunk // block_size) + 1
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


def serve_family(cfg):
    """The family that serves ``cfg``, by its type."""
    from byteps_tpu.models.dots3 import Dots3Config

    if isinstance(cfg, Dots3Config):
        return LatentFamily()
    if isinstance(cfg, GPTConfig):
        return GPTFamily()
    raise TypeError(
        f"Scheduler: no serve family for a {type(cfg).__name__} "
        "(GPTConfig and Dots3Config are served)")
