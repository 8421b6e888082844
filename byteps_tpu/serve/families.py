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
* :class:`LatentFamily` (``Dots3Config``, ``DeepSeekV32Config``): latent
  pages of up to two layer kinds and the programs of ``latent_step.py``,
  which take the model's own pieces as a ``LatentModel``
  (:func:`latent_model`). A configuration with no window layer shares its
  pages through the prefix index.
* :class:`WindowedKVFamily` (``Mellum2Config``): k/v pages of two layer
  kinds under ``paged_cache.py``'s own two programs, each layer told its
  kind, with a dropless expert FFN in every block.
* :class:`RecurrentKVFamily` (``Qwen3NextConfig``): k/v pages for the full-
  attention layers and one slot of a state pool a request for the recurrent
  (Gated-DeltaNet) layers, under the same two programs.
* :class:`HybridKVFamily` (``FalconH1Config``): every layer owns k/v pages
  AND a slot of the state pool (a Mamba-2 mixer beside attention on one
  normed input), under the same two programs; a final chunk reads out its
  last position alone.
* :class:`SharedKVFamily` (``Phi4FlashConfig``): FOUR cache behaviours in one
  plan — one full-attention layer's k/v pages (a global pool one layer deep),
  window pages that come back, a slot of the state pool a request for the
  Mamba-1 layers, and layers that own nothing: cross-attention layers that
  read the one full layer's pages through the same table, and Gated Memory
  Units fed the last Mamba layer's scan output. A prompt chunk that reads
  nothing out ends after the full layer; a final chunk runs the layers above
  it on its last position alone.
* :class:`BlockDiffusionFamily` (``SDARConfig``): k/v pages of one kind
  under the same two programs told the block length — a decode row carries a
  block of positions, rewritten in place pass after pass, and a chunk's mask
  is block-causal; the scheduler holds each run's block state.
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
    pool, its scratch block included. ``state_slots``: slots of the
    recurrent kind's state pool, its scratch slot included (0: no such
    kind)."""

    state: Any
    kv_heads: int = 0
    window: Optional[int] = None
    window_blocks: int = 0
    state_slots: int = 0


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


def admitted_at_once(max_batch: int) -> int:
    """Requests the scheduler holds admitted: the decode rows and the
    prefilled standbys beside them (``Scheduler._admit_cap``)."""
    return max_batch + max(1, max_batch // 4)


def window_pool_blocks(window: int, block_size: int, max_batch: int,
                       prefill_chunk: int) -> int:
    """Blocks of a window kind's pool: a request keeps the blocks of its
    last ``window - 1`` positions (and the one being filled); the one request
    a chunk runs for holds the chunk's beside them. Every admitted request
    at once, and scratch."""
    per_req = -(-(window - 1) // block_size) + 2
    return 1 + admitted_at_once(max_batch) * per_req \
        + -(-prefill_chunk // block_size) + 1


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

    def block(self, cfg) -> Optional[int]:
        """Positions a decode row carries where the model generates by
        diffusion over blocks (None: a token a row a step)."""
        return None

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


def latent_model(cfg):
    """The ``models/dots3.py::LatentModel`` of a latent configuration, by
    its type: the pieces of a block ``latent_step.py``'s programs take from
    the model."""
    from byteps_tpu.models import deepseek_v32, dots3

    if isinstance(cfg, deepseek_v32.DeepSeekV32Config):
        return deepseek_v32.MODEL
    return dots3.MODEL


class LatentFamily(_Refusing):
    """A model of latent attention over latent pages (``latent_step.py``):
    dots3, with full and sliding layers, and DeepSeek-V3.2-Exp, whose layers
    are all full. A configuration with window layers does not share
    prefixes (a window layer's released blocks cannot be shared); one
    without shares its pages — latent rows and indexer keys, same blocks,
    same table — through the radix index as the GPT family shares k/v
    pages."""

    name = "latent"

    #: what the latent layout does not carry yet, each refused at
    #: construction: ``feature -> the message's subject``
    #: (``prefix_cache``: a configuration with window layers alone)
    REFUSED = {
        "prefix_cache": "the prefix cache (a configuration with window "
                        "layers: a window layer's released blocks cannot be "
                        "shared)",
        "speculation": "speculative decoding (the chunk program returns no "
                       "rewindable window state)",
        "adapter_pool": "LoRA adapter slabs",
        "quant_cache": "the int8 pool",
        "role": "role='prefill'|'decode' and migration over kv_wire (no "
                "payload for latent pages)",
        "tp_axis": "tensor parallelism",
    }

    def __init__(self, cfg):
        from byteps_tpu.models.dots3 import SLIDING

        self._model = latent_model(cfg)
        self.windowed = bool(cfg.layers_of(SLIDING))
        #: the radix index over latent pages, where every page is global
        self.shares_prefixes = not self.windowed

    def validate(self, params, cfg, features) -> None:
        super().validate(params, cfg, {
            k: v for k, v in features.items()
            if k != "prefix_cache" or self.windowed})

    def layout(self, params, cfg, *, block_size, pool_blocks, max_batch,
               prefill_chunk, quant) -> PoolLayout:
        from byteps_tpu.serve.latent_step import init_pool

        if not self.windowed:
            return PoolLayout(state=init_pool(cfg, block_size, pool_blocks,
                                              0))
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

    block = GPTFamily.block

    def late_stats(self):
        from byteps_tpu.serve.latent_step import LateStats

        return LateStats(self._model)


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

    #: the configuration's ``StepPlan`` (a subclass names its own)
    plan = staticmethod(_windowed_plan)

    def decode_fn(self, cfg, block_size, tp_axis, lora_sig):
        from byteps_tpu.serve.paged_cache import make_paged_decode_fn

        return make_paged_decode_fn(cfg, block_size, plan=self.plan(cfg))

    def prefill_fn(self, cfg, block_size, chunk_len, tp_axis, with_readout):
        from byteps_tpu.serve.paged_cache import make_paged_prefill_fn

        return make_paged_prefill_fn(cfg, block_size, chunk_len,
                                     with_readout=with_readout,
                                     plan=self.plan(cfg))

    def late_stats(self):
        from byteps_tpu.serve.paged_cache import StepStats

        return StepStats()


@functools.lru_cache(maxsize=16)
def _recurrent_plan(cfg):
    """The ``StepPlan`` of a ``Qwen3NextConfig``: each full layer's row in
    the k/v pool, each DeltaNet layer's row in the state pool, the model's
    own two first halves and its expert FFN."""
    from byteps_tpu.models.qwen3_next import (
        FULL, LINEAR, expert_ffn, full_attn_half, gdn_half)
    from byteps_tpu.serve.paged_cache import LayerKind, StepPlan

    place = {li: i for kind in (FULL, LINEAR)
             for i, li in enumerate(cfg.layers_of(kind))}
    return StepPlan(tuple(
        LayerKind(place[li], None, cfg.rope_base, state=kind == LINEAR)
        for li, kind in enumerate(cfg.layer_types)),
        expert_ffn, full_attn_half, gdn_half)


class RecurrentKVFamily(WindowedKVFamily):
    """Qwen3-Next: k/v pages for the gated full-attention layers and, for
    the Gated-DeltaNet layers, a slot of a state pool a request (the f32
    state and the convolution's tail), under ``paged_cache.py``'s two
    programs and a :class:`~paged_cache.StepPlan` that carries the model's
    own first halves (the plan is the one thing the programs' factories
    are given differently)."""

    name = "recurrent state + k/v"
    plan = staticmethod(_recurrent_plan)

    #: what a slot cannot do yet, each refused at construction:
    #: ``feature -> the message's subject``
    REFUSED = {
        "prefix_cache": "the prefix cache (a shared prefix needs the "
                        "recurrent state as it was at the sharing point: "
                        "no snapshot is kept)",
        "speculation": "speculative decoding (a rejected draft cannot "
                       "rewind a recurrent state)",
        "adapter_pool": "LoRA adapter slabs",
        "quant_cache": "the int8 pool",
        "role": "role='prefill'|'decode' and migration over kv_wire (no "
                "payload for a state slot)",
        "tp_axis": "tensor parallelism",
    }

    def pool_shapes(self, cfg):
        """``(k/v layers, state layers, a slot's state shape)`` of the two
        pools (a subclass with other layers names its own)."""
        from byteps_tpu.models.qwen3_next import FULL, LINEAR

        return (len(cfg.layers_of(FULL)), len(cfg.layers_of(LINEAR)),
                (cfg.linear_value_heads, cfg.linear_key_dim,
                 cfg.linear_value_dim))

    def layout(self, params, cfg, *, block_size, pool_blocks, max_batch,
               prefill_chunk, quant) -> PoolLayout:
        from byteps_tpu.serve.paged_cache import (
            kv_pool_state, with_state_pool)

        kv_layers, state_layers, state_shape = self.pool_shapes(cfg)
        slots = 1 + admitted_at_once(max_batch)
        pool = kv_pool_state(cfg, block_size, pool_blocks, cfg.kv_heads,
                             False, layers=kv_layers)
        return PoolLayout(
            state=with_state_pool(
                pool, state_layers, slots, state_shape,
                ((cfg.conv_kernel - 1) * cfg.conv_channels,), cfg.dtype),
            kv_heads=cfg.kv_heads, state_slots=slots)

    def late_stats(self):
        from byteps_tpu.serve.paged_cache import STATS_STATE, StepStats

        return StepStats(STATS_STATE)


@functools.lru_cache(maxsize=16)
def _hybrid_plan(cfg):
    """The ``StepPlan`` of a ``FalconH1Config``: every layer hybrid — row
    ``i`` of the k/v pool and row ``i`` of the state pool — the model's own
    first half and MLP, its embedding and logit multipliers, and a chunk's
    readout of one position (``num_logits_to_keep`` 1)."""
    from byteps_tpu.models.falcon_h1 import mixer_half, mlp
    from byteps_tpu.serve.paged_cache import LayerKind, StepPlan

    return StepPlan(tuple(LayerKind(li, None, cfg.rope_base, hybrid=True)
                          for li in range(cfg.n_layers)),
                    mlp, mixer=mixer_half,
                    embed_scale=cfg.embedding_multiplier,
                    logit_scale=cfg.lm_head_multiplier, last_logits=True)


class HybridKVFamily(RecurrentKVFamily):
    """Falcon-H1: in every layer k/v pages for the attention branch and,
    for the Mamba-2 branch beside it, a slot of a state pool a request (the
    f32 state and the convolution's tail) — both pools ``n_layers`` deep —
    under ``paged_cache.py``'s two programs and a
    :class:`~paged_cache.StepPlan` whose layers are hybrid. What a slot
    cannot do yet (``REFUSED``) is the recurrent family's."""

    name = "k/v + recurrent state in every layer"
    plan = staticmethod(_hybrid_plan)

    def pool_shapes(self, cfg):
        return (cfg.n_layers, cfg.n_layers,
                (cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim))

    def late_stats(self):
        from byteps_tpu.serve.paged_cache import STATS_SSD, StepStats

        return StepStats(STATS_SSD)


@functools.lru_cache(maxsize=16)
def _shared_kv_plan(cfg):
    """The ``StepPlan`` of a ``Phi4FlashConfig``: each Mamba layer's row in
    the state pool, each window layer's in the window pool, the ONE full
    layer's row 0 of the global pool — which the cross layers read — and the
    GMUs fed; no rotation anywhere; the model's own first halves and MLP, and
    a chunk's readout of one position."""
    from byteps_tpu.models.phi4_flash import (
        CROSS, FULL, GMU, MAMBA, WINDOW, diff_attn_half, gmu_half,
        layer_kinds, mamba_half, mlp)
    from byteps_tpu.serve.paged_cache import LayerKind, StepPlan

    seen = {MAMBA: 0, WINDOW: 0}

    def kind_of(kind):
        if kind in seen:
            seen[kind] += 1
            return LayerKind(seen[kind] - 1, state=kind == MAMBA,
                             window=cfg.window if kind == WINDOW else None)
        return LayerKind(0, reader=kind == CROSS, fed=kind == GMU)

    kinds = layer_kinds(cfg)
    assert kinds.count(FULL) == 1
    return StepPlan(tuple(kind_of(k) for k in kinds), mlp, diff_attn_half,
                    mamba_half, last_logits=True, fed=gmu_half,
                    attn_takes_kind=True)


class SharedKVFamily(RecurrentKVFamily):
    """Phi-4-mini-flash (SambaY): one full-attention layer's k/v pages that
    seven cross-attention layers read, a window pool for the sliding layers,
    a slot of a state pool a request for the Mamba-1 layers (the f32 state
    and the convolution's tail), and Gated Memory Units that keep nothing —
    under ``paged_cache.py``'s two programs and a :class:`~paged_cache.
    StepPlan` with reader and fed layers. Preemption recomputes from position
    0, as every family with a slot."""

    name = "shared k/v + window k/v + recurrent state"
    plan = staticmethod(_shared_kv_plan)

    #: what three pools at once cannot do yet, each refused at construction:
    #: ``feature -> the message's subject``
    REFUSED = {
        "prefix_cache": "the prefix cache (a shared prefix needs the "
                        "recurrent state as it was at the sharing point and "
                        "window blocks that were given back: neither is "
                        "kept)",
        "speculation": "speculative decoding (a rejected draft cannot "
                       "rewind a recurrent state, nor window blocks given "
                       "back)",
        "adapter_pool": "LoRA adapter slabs",
        "quant_cache": "the int8 pool",
        "role": "role='prefill'|'decode' and migration over kv_wire (no "
                "payload for a state slot or a window page)",
        "tp_axis": "tensor parallelism",
    }

    def layout(self, params, cfg, *, block_size, pool_blocks, max_batch,
               prefill_chunk, quant) -> PoolLayout:
        import jax.numpy as jnp

        from byteps_tpu.models.phi4_flash import MAMBA, WINDOW, layer_kinds
        from byteps_tpu.serve.paged_cache import (
            STATS_SAMBAY, kv_pool_state, with_state_pool)

        kinds = layer_kinds(cfg)
        wb = window_pool_blocks(cfg.window, block_size, max_batch,
                                prefill_chunk)
        slots = 1 + admitted_at_once(max_batch)
        pool = kv_pool_state(
            cfg, block_size, pool_blocks, cfg.kv_heads, False, layers=1,
            window_layers=kinds.count(WINDOW), window_blocks=wb)
        pool = with_state_pool(
            pool, kinds.count(MAMBA), slots, (cfg.ssm_state, cfg.d_inner),
            ((cfg.conv_kernel - 1) * cfg.d_inner,), cfg.dtype)
        return PoolLayout(
            state=pool._replace(
                stats=jnp.zeros((len(STATS_SAMBAY),), jnp.float32)),
            kv_heads=cfg.kv_heads, window=cfg.window, window_blocks=wb,
            state_slots=slots)

    def late_stats(self):
        from byteps_tpu.serve.paged_cache import STATS_SAMBAY, StepStats

        return StepStats(STATS_SAMBAY)


@functools.lru_cache(maxsize=16)
def _block_plan(cfg):
    """The ``StepPlan`` of an ``SDARConfig``: every layer global at one
    rotation, the q/k-normed first half, the expert FFN, and the block."""
    from byteps_tpu.models.sdar import expert_ffn, sdar_attn_half
    from byteps_tpu.serve.paged_cache import LayerKind, StepPlan

    return StepPlan(tuple(LayerKind(li, None, cfg.rope_base)
                          for li in range(cfg.n_layers)),
                    expert_ffn, sdar_attn_half, block=cfg.block_length)


@functools.lru_cache(maxsize=16)
def _block_pick(B: int, mask_id: int):
    import jax
    import jax.numpy as jnp

    from byteps_tpu.common.tracing import traced_program
    from byteps_tpu.models.sdar import fix_positions

    def pick(logits, state, n_fix, pass_no):
        return jnp.concatenate(fix_positions(
            logits, state[:, :B], state[:, B:], n_fix, pass_no, mask_id), 1)

    return traced_program("serve.pick", jax.jit(pick))


class BlockDiffusionFamily(WindowedKVFamily):
    """SDAR: generation by diffusion over blocks. One kind of k/v page under
    ``paged_cache.py``'s two programs and a :class:`~paged_cache.StepPlan`
    that names the block: the decode program runs a pass over a block of
    positions a row and rewrites the block's rows in place until the pass
    that commits it, the chunk program attends block-causally and reads out
    nothing (the first block starts masked). The block state, its phases and
    the commit are the scheduler's (``Scheduler._issue_decode``)."""

    name = "block-rewritten k/v"
    plan = staticmethod(_block_plan)

    #: what a page that is rewritten until its block is final cannot carry
    #: yet, each refused at construction: ``feature -> the message's
    #: subject``
    REFUSED = {
        "prefix_cache": "the prefix cache (a page is rewritten until its "
                        "block is final, and a prompt's last block is "
                        "finished by the sampler)",
        "speculation": "speculative decoding (a block is denoised in place: "
                       "there is no draft to verify)",
        "adapter_pool": "LoRA adapter slabs",
        "quant_cache": "the int8 pool",
        "role": "role='prefill'|'decode' and migration over kv_wire (a "
                "ticket carries no block state)",
        "tp_axis": "tensor parallelism",
        "temperature": "sampling at a temperature (the served schedule is "
                       "the greedy low-confidence one)",
        "denoise_steps": "a number of denoising passes that does not divide "
                         "the block",
    }

    def validate_request(self, req, cfg) -> None:
        super().validate_request(req, cfg)
        if req.temperature != 0.0:
            self._refuse("temperature", cfg)
        steps = req.denoise_steps or cfg.denoise_steps
        if steps < 1 or cfg.block_length % steps:
            self._refuse("denoise_steps", cfg)

    def block(self, cfg) -> int:
        return cfg.block_length

    def block_pick(self, cfg):
        """The jitted pick of a pass: ``(logits (R, B, V), state (R, 2B),
        n_fix (R,), pass_no (R,)) -> state`` — a row's block state is its B
        tokens (the mask token where open) and beside them the pass each was
        fixed at; ``models/sdar.py::fix_positions`` is the rule."""
        return _block_pick(cfg.block_length, cfg.mask_id)

    def layout(self, params, cfg, *, block_size, pool_blocks, max_batch,
               prefill_chunk, quant) -> PoolLayout:
        import jax.numpy as jnp

        from byteps_tpu.serve.paged_cache import STATS, kv_pool_state

        if block_size % cfg.block_length or prefill_chunk % cfg.block_length:
            raise ValueError(
                f"block_size ({block_size}) and prefill_chunk "
                f"({prefill_chunk}) must be whole blocks of "
                f"{cfg.block_length} positions")
        pool = kv_pool_state(cfg, block_size, pool_blocks, cfg.kv_heads,
                             False)
        return PoolLayout(
            state=pool._replace(stats=jnp.zeros((len(STATS),), jnp.float32)),
            kv_heads=cfg.kv_heads)


def serve_family(cfg):
    """The family that serves ``cfg``, by its type."""
    from byteps_tpu.models.deepseek_v32 import DeepSeekV32Config
    from byteps_tpu.models.dots3 import Dots3Config
    from byteps_tpu.models.falcon_h1 import FalconH1Config
    from byteps_tpu.models.mellum2 import Mellum2Config
    from byteps_tpu.models.phi4_flash import Phi4FlashConfig
    from byteps_tpu.models.qwen3_next import Qwen3NextConfig
    from byteps_tpu.models.sdar import SDARConfig

    if isinstance(cfg, SDARConfig):
        return BlockDiffusionFamily()
    if isinstance(cfg, (Dots3Config, DeepSeekV32Config)):
        return LatentFamily(cfg)
    if isinstance(cfg, Mellum2Config):
        return WindowedKVFamily()
    if isinstance(cfg, Qwen3NextConfig):
        return RecurrentKVFamily()
    if isinstance(cfg, FalconH1Config):
        return HybridKVFamily()
    if isinstance(cfg, Phi4FlashConfig):
        return SharedKVFamily()
    if isinstance(cfg, GPTConfig):
        return GPTFamily()
    raise TypeError(
        f"Scheduler: no serve family for a {type(cfg).__name__} "
        "(GPTConfig, Dots3Config, DeepSeekV32Config, Mellum2Config, "
        "Qwen3NextConfig, FalconH1Config, Phi4FlashConfig and SDARConfig "
        "are served)")
