"""Block-paged KV cache — PagedAttention's memory model over this
repo's cache machinery.

``models/generate.py`` holds one contiguous ``(B, max_seq, h, D)``
cache per batch: every request pays max_seq slots whether it uses 10
tokens or 1000, and a batch must share one fill level. Here the cache
is a preallocated pool of fixed-size KV *blocks* plus a per-request
*block table* mapping logical position ``p`` to physical slot
``(table[p // bs], p % bs)`` — heterogeneous sequence lengths pack one
device batch, memory is allocated block-at-a-time as requests grow,
and a freed request's blocks immediately serve the next admission.

Numerics are the point, not just memory: the paged paths reproduce the
dense cache's contract exactly. Nothing at or past a request's fill
level is ever read as data (the dense cache is zero-initialized and
written only below ``length``): a gathered per-request view zero-fills
those positions and the paged-attention kernel masks them, attention
applies the same global-offset causal rule per row (the kernel by each
row's ``length``, the ``attention_lse`` twin by a per-batch offset
vector), and quantized pools reuse ``_quantize_block``'s absmax
arithmetic — so on the jnp backend a request served out of the paged
pool emits tokens bit-identical to a solo ``make_generate_fn`` run
(pinned in tests/test_serve.py), and the kernel is held to the twin
(tests/test_paged_attention.py).

Pages are SHARED, not owned: every physical block carries a refcount
and a radix/prefix index maps token content → committed prefill blocks
(SGLang's RadixAttention organized over vLLM's paged pool). Requests
whose prompts share a leading prefix — the dominant traffic shape at
"millions of users" (one long system prompt, short unique tails) — map
their leading table entries to the SAME physical pages and skip the
shared prefill entirely. Divergence inside a block is copy-on-write: a
writer whose table entry has refcount > 1 gets a fresh block with the
shared contents copied (dense and int8 ``_QuantSlot`` paths), so
sharing changes where bytes live, never what attention reads — hot-
cache greedy outputs stay BIT-identical to cold runs (pinned). Cached-
but-idle prefix pages are evicted LRU under pool pressure before any
allocation fails: the prefix cache can never cause
:class:`PoolExhausted` for live traffic.

Three layers, none of which writes a transformer block: both jitted
steps run the one in ``models/gpt.py`` (``attn_half`` / ``ffn_half``) and
own only their ``attend`` — where the new keys go, what attends over them:

* :class:`PagedKVCache` — the host-side allocator: pool arrays, block
  tables + per-block refcounts, the radix prefix index,
  alloc/adopt/CoW/free/defrag, leak accounting. Block 0 is a reserved
  scratch block: inactive decode rows scatter there and no table ever
  references it, so a padded batch slot can't corrupt live state.
* :func:`make_paged_decode_fn` — ONE jitted packed decode step:
  R requests at heterogeneous positions, per-row rope/masks; its
  ``attend`` scatters the new token's K/V into the pool, then attends
  over the pool IN PLACE through the block tables
  (``ops/paged_attention.py``: each row reads its live blocks and no
  dense copy of K or V is made). Off the
  Pallas backend, or for a pool the kernel does not take (int8, a
  block that is not whole tiles), the step keeps the kernel's jnp
  twin: gather zero-masked per-request views, ``attention_lse``
  (:func:`decode_uses_paged_attn` decides, from backend and shapes).
* :func:`make_paged_prefill_fn` — chunked prefill/verify for one
  request, layer by layer: gather THIS request's blocks of one layer
  into a dense zero-masked view, run the stock ``_block_step`` of
  ``models/generate.py`` on it (the block around ``cache_attend``: what
  ``gpt_apply_cached`` runs, so the chunk is bit-identical to the
  single-request prefill by construction), scatter the chunk's newly
  written rows of that layer back in place. No other block of the pool
  is read, moved or written.

Both programs take a :class:`StepPlan`: a :class:`LayerKind` a layer (its
row in its pool, its window if it has one, what it rotates by) and the
block's second half. The GPT family is the plan "one kind"
(:func:`one_kind_plan`); a family whose sliding layers give their blocks
back (``families.WindowedKVFamily``) keeps those layers' k/v in a second,
small pool (``PoolState.wk``/``wv``) under the cache's window kind, and its
programs read two table lines, start a window layer's attention at ``fill −
window`` and count what they did into ``PoolState.stats``
(:class:`StepStats`). A third kind keeps no keys at all: a *recurrent* layer
(``LayerKind.state``; ``families.RecurrentKVFamily``) owns no table line —
a request owns one SLOT of a state pool (``PoolState.s``/``conv``) from
admission to release, its index rides at the head of the request's table
row, and the plan's ``recur`` updates the slot in place. And a layer may be
both at once (``LayerKind.hybrid``; ``families.HybridKVFamily``): a table
line AND a row of the state pool, the plan's ``mixer`` given the pool's
``attend`` and the slot. And a layer may own NOTHING (``families.
SharedKVFamily``): a *reader* (``LayerKind.reader``) projects a query only and
attends over the pages another layer wrote, through that layer's table line
(:func:`_reader_attend`); a *fed* layer (``LayerKind.fed``) is given an
activation an earlier recurrent layer of the same step handed out. A plan
whose LAST layers are of those two sorts writes no cache above a certain
layer, so a prompt chunk that reads nothing out ends there, and one that reads
out runs them on its last position alone (:func:`_cacheless_tail`).
"""

from __future__ import annotations

import functools
import itertools
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from byteps_tpu.common.metrics import get_registry
from byteps_tpu.common.tracing import traced_program
from byteps_tpu.models.generate import (
    _QuantSlot,
    _block_step,
    _embed,
    _quantize_block,
    cache_attend,
)
from byteps_tpu.models.gpt import (
    GPTConfig,
    RopeFreqs,
    _readout,
    attn_half,
    ffn_half,
    resolve_norm,
    resolve_rope,
)
from byteps_tpu.ops.backend import note_fallback, use_pallas
from byteps_tpu.ops.flash_attention import (
    attention_lse,
    flash_attention_window,
)
from byteps_tpu.ops.paged_attention import (
    paged_attention_decode,
    unsupported_reason as paged_attn_unsupported,
)
from byteps_tpu.ops.segmented_lora import segmented_lora_delta
from byteps_tpu.serve.families import LateStats


class PoolState(NamedTuple):
    """The device half of the paged cache — a pytree so the jitted
    decode/prefill steps thread it functionally.

    k/v: ``(n_layers, num_blocks, block_size, h_kv * head_dim)`` in
    ``cfg.dtype``, or int8 with ``k_scale``/``v_scale``
    ``(n_layers, num_blocks, block_size, h_kv)`` fp32 absmax scales
    (generate.py's _QuantSlot layout, block-paged). A token's heads lie
    side by side on the minor axis, so a block is one dense, tile-
    aligned ``(block_size, h_kv * head_dim)`` plane: the device stores a
    ``(..., h_kv, head_dim)`` tail with ``head_dim`` under 128 padded
    or in a dimension order of its own, and every program that touches
    the pool then converts all of it on the way in and out (PERF.md §6,
    PR 28). The layout is private to this module: views, payloads and
    the wire keep ``(..., h_kv, head_dim)``.

    A family with window layers (:class:`LayerKind`) keeps two kinds of
    page: ``k``/``v`` hold its global layers alone and ``wk``/``wv``
    ``(window layers, window blocks, block_size, h_kv * head_dim)`` the
    layers that give blocks back, in a pool of their own with its own block
    ids; ``stats`` f32 ``(len(STATS),)`` is what the program that last
    wrote the pool counted (:class:`StepStats` reads it a step late). All
    three are None for one kind of layer: no leaf, so the GPT family's
    programs are traced over the tree they always had.

    A family with recurrent layers keeps what a request carries between
    tokens in a pool of slots: ``s (recurrent layers, slots, ...)`` the
    state itself (f32) and ``conv (recurrent layers, slots, ...)`` the
    layer's short convolution tail; slot 0 is scratch. None, no leaf, for
    every other family.
    """

    k: jnp.ndarray
    v: jnp.ndarray
    k_scale: Optional[jnp.ndarray] = None
    v_scale: Optional[jnp.ndarray] = None
    wk: Optional[jnp.ndarray] = None
    wv: Optional[jnp.ndarray] = None
    stats: Optional[jnp.ndarray] = None
    s: Optional[jnp.ndarray] = None
    conv: Optional[jnp.ndarray] = None

    #: the leaves a block of the global kind has a page in, each ``(layers,
    #: blocks, ...)`` or None: what a copy of one block copies
    #: (``PagedKVCache.ensure_writable``; a latent pool names its own)
    block_leaves = ("k", "v", "k_scale", "v_scale")


class LayerKind(NamedTuple):
    """How one layer uses the cache. ``index``: its row in its pool.
    ``window``: None for a global layer (``pool.k``/``v``, table line 0,
    every key live); else the keys a query sees, its own included — a
    window layer (``pool.wk``/``wv``, table line 1, keys below ``fill -
    window`` neither held nor read). ``rope``: what it rotates by (0: not at
    all). ``state``: a recurrent layer — no keys, no table line; ``index`` is
    its row in the state pool (``pool.s``/``conv``). ``hybrid``: a global
    layer that keeps a recurrent state beside its keys — ``index`` is its row
    in the k/v pool AND in the state pool. ``reader``: a layer that owns no
    page: it projects a query only and attends over the pages ANOTHER layer
    wrote — ``index`` is that layer's row of the global pool, read through
    the same table line, and nothing is scattered. ``fed``: a layer with no
    cache at all, whose first half (``plan.fed``) is given an activation of an
    earlier layer of the same step: what the last recurrent layer before it
    handed out; ``index`` means nothing."""

    index: int
    window: Optional[int] = None
    rope: Union[float, RopeFreqs] = 0.0
    state: bool = False
    hybrid: bool = False
    reader: bool = False
    fed: bool = False


class StepPlan(NamedTuple):
    """What the two programs need of a model beyond a ``GPTConfig``'s
    fields: a :class:`LayerKind` a layer, and the block's second half —
    ``ffn(cfg, p, h) -> (out, aux f32 (3,))`` with ``aux`` = (pairs
    computed, experts with a row, heaviest expert over the mean); None is
    the dense MLP. ``attn``: the first half of a block over k/v, called as
    ``models/gpt.py::attn_half`` is with ``cfg`` before its arguments (None:
    ``attn_half`` itself). ``recur``: the first half of a recurrent layer,
    ``recur(cfg, x, p, pool.s, pool.conv, index, slots, fresh, norm_fn=,
    norm_eps=) -> (x, s, conv)`` — ``slots (R,)`` for the packed decode
    step's rows, ``()`` for a chunk of one request, which starts from a zero
    state where ``fresh``. ``block``: the model generates by diffusion over
    blocks of that many positions (a power of two that divides the page; every
    layer global) — a decode row carries a whole block and a chunk's mask is
    block-causal; None: a token a row, causal. ``mixer``: the first half of
    a hybrid layer, ``mixer(cfg, x, p, head_dim, positions, attend, rope,
    pool.s, pool.conv, index, slots, fresh, norm_fn=, norm_eps=) -> (x, carry,
    s, conv)`` — ``attend`` and ``carry`` as ``attn`` has them, the rest as
    ``recur``. ``embed_scale`` multiplies the embedding, ``logit_scale`` the
    logits; ``last_logits``: a chunk reads out its last position alone,
    ``(1, 1, vocab)`` (the scheduler keeps no other). ``fed``: the first half
    of a ``fed`` layer, ``fed(cfg, x, p, m, norm_fn=, norm_eps=) -> x`` — ``m``
    the fourth value the last ``recur`` before it returned (a plan with such
    layers has a ``recur`` that returns four). ``attn_takes_kind``: ``attn``
    is also given ``kind=``, the layer's :class:`LayerKind` (its index zeroed:
    the chunk program's index is data). A plan whose LAST layers are readers
    and fed ones only — they write no cache — runs them on a chunk's last
    position alone, and not at all in a chunk that reads nothing out
    (:func:`make_paged_prefill_fn`). Hashable: it keys the programs'
    factories."""

    kinds: Tuple[LayerKind, ...]
    ffn: Optional[Callable] = None
    attn: Optional[Callable] = None
    recur: Optional[Callable] = None
    block: Optional[int] = None
    mixer: Optional[Callable] = None
    embed_scale: float = 1.0
    logit_scale: float = 1.0
    last_logits: bool = False
    fed: Optional[Callable] = None
    attn_takes_kind: bool = False


def _window_of(plan: "StepPlan") -> Optional[int]:
    """The one window of a plan's window layers (None: it has none)."""
    windows = {k.window for k in plan.kinds} - {None}
    if len(windows) > 1:
        raise ValueError(f"one window a plan; got {sorted(windows)}")
    return windows.pop() if windows else None


def _layers_by_kind(plan: "StepPlan") -> Tuple[int, int, int]:
    """``(global, window, recurrent)`` layers of a plan: layers that read a
    line of the global table (a reader among them), of the window table, with
    a row of the state pool (a hybrid layer is a global and a recurrent
    one)."""
    n_state = sum(k.state or k.hybrid for k in plan.kinds)
    n_window = sum(k.window is not None for k in plan.kinds)
    n_keyless = sum(k.state or k.fed for k in plan.kinds)
    return len(plan.kinds) - n_keyless - n_window, n_window, n_state


def _cacheless_tail(plan: "StepPlan") -> int:
    """How many of a plan's LAST layers write no cache (readers and fed
    layers): what a chunk runs on its last position alone (0: none)."""
    n = 0
    for k in reversed(plan.kinds):
        if not (k.reader or k.fed):
            break
        n += 1
    return n


def _embed_in(plan: "StepPlan", params, tokens, positions, cfg):
    """The embedding as the plan scales it (1: nothing is traced)."""
    with jax.named_scope("embed"):
        x = _embed(params, tokens, positions, cfg)
        return x if plan.embed_scale == 1.0 \
            else x * jnp.asarray(plan.embed_scale, x.dtype)


def _logits(plan: "StepPlan", params, x, norm_fn, norm_eps):
    """The readout as the plan scales it (1: nothing is traced)."""
    with jax.named_scope("readout"):
        logits = _readout(params, x, norm_fn, norm_eps)
        return logits if plan.logit_scale == 1.0 \
            else logits * plan.logit_scale


def one_kind_plan(cfg) -> StepPlan:
    """The GPT family: every layer global, one base, the dense MLP."""
    rope = resolve_rope(cfg)
    return StepPlan(tuple(LayerKind(li, None, rope)
                          for li in range(cfg.n_layers)))


#: ``pool.stats``: what one program counted, f32. ``moe.experts_hit`` and
#: ``moe.pairs_here`` add over the ``moe.layers`` expert layers of the
#: program; keys a decode step's attention must read (a row and layer: its
#: length, or the window where that is shorter) and visible (query, key)
#: pairs of a chunk, by layer kind
STATS = ("moe.pairs_here", "moe.experts_hit", "moe.layers",
         "moe.load_max_over_mean",
         "serve.kv.decode_keys_read.full", "serve.kv.decode_keys_read.window",
         "serve.attn.prefill_pairs.full", "serve.attn.prefill_pairs.window")
#: a pool with recurrent layers counts these too: live rows of a decode
#: step and tokens of a chunk, each times the recurrent layers
STATS_STATE = STATS + ("serve.gdn.decode_rows", "serve.gdn.prefill_tokens")
#: the same two of a pool whose state is a Mamba-2 (SSD) mixer's
STATS_SSD = STATS + ("serve.ssd.decode_rows", "serve.ssd.prefill_tokens")
#: and of one whose state is a Mamba-1 selective scan's, in a plan whose last
#: layers write no cache: positions of a chunk those layers ran over (1 in a
#: chunk that reads out, 0 in one that does not)
STATS_SAMBAY = STATS + ("serve.sscan.decode_rows",
                        "serve.sscan.prefill_tokens",
                        "sambay.cross_positions")


def kv_pool_state(cfg: GPTConfig, block_size: int, pool_blocks: int,
                  kv_heads: int, quant: bool, layers: Optional[int] = None,
                  window_layers: int = 0, window_blocks: int = 0
                  ) -> PoolState:
    """The zeroed k/v pool: ``layers`` (default every layer) global ones
    and, with ``window_layers``, a window pool of ``window_blocks`` blocks
    beside it and the ``stats`` leaf."""
    n = cfg.n_layers if layers is None else layers
    shape = (n, pool_blocks, block_size, kv_heads * cfg.head_dim)
    if quant:
        return PoolState(
            k=jnp.zeros(shape, jnp.int8),
            v=jnp.zeros(shape, jnp.int8),
            k_scale=jnp.zeros(shape[:-1] + (kv_heads,), jnp.float32),
            v_scale=jnp.zeros(shape[:-1] + (kv_heads,), jnp.float32),
        )
    pool = PoolState(k=jnp.zeros(shape, cfg.dtype),
                     v=jnp.zeros(shape, cfg.dtype))
    if window_layers:
        wshape = (window_layers, window_blocks) + shape[2:]
        pool = pool._replace(wk=jnp.zeros(wshape, cfg.dtype),
                             wv=jnp.zeros(wshape, cfg.dtype),
                             stats=jnp.zeros((len(STATS),), jnp.float32))
    return pool


def with_state_pool(pool: PoolState, layers: int, slots: int,
                    state_shape: tuple, tail_shape: tuple, tail_dtype
                    ) -> PoolState:
    """``pool`` with a zeroed state pool of ``slots`` slots (slot 0 scratch)
    for ``layers`` recurrent layers beside it — a slot of a layer holds
    ``state_shape`` f32 and ``tail_shape`` of ``tail_dtype`` — and the
    ``stats`` leaf of :data:`STATS_STATE` (:data:`STATS_SSD` is as long)."""
    return pool._replace(
        s=jnp.zeros((layers, slots) + tuple(state_shape), jnp.float32),
        conv=jnp.zeros((layers, slots) + tuple(tail_shape), tail_dtype),
        stats=jnp.zeros((len(STATS_STATE),), jnp.float32))


@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_block(leaf, src, dst):
    """Block ``src`` of a pool leaf ``(layers, blocks, ...)`` copied over
    block ``dst``, in place: the leaf is donated (a latent pool's rows are
    gigabytes; an undonated update would hold two of them)."""
    return leaf.at[:, dst].set(leaf[:, src])


class PoolExhausted(RuntimeError):
    """A block allocation could not be satisfied — the scheduler's cue
    to preempt (it should never escape to callers)."""


# global pool instance sequence for per-pool gauge series
_POOL_SEQ = itertools.count()


class _PrefixNode:
    """One committed KV block in the radix prefix index.

    The index is a block-granular radix tree: a node's edge label is
    the EXACT ``block_size`` token ids its block holds (content-
    addressed — children are keyed by the raw token bytes, chained
    through the parent, so two different contexts can never collide
    the way a rolling hash could). ``tick`` is the LRU clock stamped on
    every lookup touch; eviction takes the least-recently-used
    reclaimable subtree first."""

    __slots__ = ("key", "tokens", "block", "parent", "children", "tick")

    def __init__(self, key: bytes, tokens: np.ndarray, block: int,
                 parent: "_PrefixNode"):
        self.key = key
        self.tokens = tokens
        self.block = block
        self.parent = parent
        self.children: Dict[bytes, "_PrefixNode"] = {}
        self.tick = 0


class PagedKVCache:
    """Host-side block allocator + per-request block tables.

    The pool is sized once (``pool_blocks``); block 0 is reserved as
    the scratch target for padded decode rows and is never allocated.
    ``blocks_per_req`` (``ceil(max_seq / block_size)``) caps a table;
    the compute steps take width-bucketed table rows (powers of two,
    see ``Scheduler._width``) so a short request's gather/attention
    width tracks its actual length instead of max_seq — the zero-mask
    keeps every width bit-comparable to the solo dense run.
    """

    def __init__(self, cfg, *, block_size: int,
                 pool_blocks: int, max_batch: int,
                 h_loc: Optional[int] = None, quant: bool = False,
                 layout=None):
        """``layout``: ``(block_size, pool_blocks) -> families.PoolLayout``,
        the model family's answer to what the pools hold (``Scheduler``
        passes its family's); None is the GPT family's k/v pool of ``h_loc``
        heads."""
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1; got {block_size}")
        self.cfg = cfg
        self.block_size = block_size
        self.blocks_per_req = -(-cfg.max_seq // block_size)
        if pool_blocks <= 0:   # auto: no oversubscription
            pool_blocks = 1 + max_batch * self.blocks_per_req
        if pool_blocks < 2:
            raise ValueError(
                f"pool_blocks ({pool_blocks}) must hold the reserved "
                "scratch block plus at least one allocatable block "
                "(per-request fit is validated at Scheduler.submit)")
        self.pool_blocks = pool_blocks
        self.quant = quant
        if layout is None:
            # a direct caller's k/v pool of ``h_loc`` heads: the layout the
            # GPT family answers, made here, so that one path builds a pool
            from byteps_tpu.serve.families import PoolLayout
            h = h_loc if h_loc is not None else cfg.kv_heads
            layout = lambda bs_, nb_: PoolLayout(       # noqa: E731
                state=kv_pool_state(cfg, bs_, nb_, h, quant), kv_heads=h)
        lay = layout(block_size, pool_blocks)
        self.kv_heads, self.state = lay.kv_heads, lay.state
        self.window, window_blocks = lay.window, lay.window_blocks
        # the recurrent kind: a request owns one slot of the state pool from
        # register() to release(); slot 0 is scratch (rows of a packed step
        # that hold no request write there). Nothing zeroes a slot: the
        # chunk at position 0 starts from a zero state whatever it holds,
        # which is the reset (counted here, by cause)
        self.state_slots = lay.state_slots
        self._sfree: List[int] = list(range(lay.state_slots - 1, 0, -1))
        self._slots: Dict[object, int] = {}

        # the window kind: layers that keep the blocks holding a request's
        # last ``window`` positions and hand the others back while it runs.
        # Blocks of a pool of their own (another row width), one allocator:
        # a free list, a table per request (logical block -> physical, the
        # released ones gone) and the same leak account
        self.window_blocks = window_blocks
        self._wfree: List[int] = list(range(window_blocks - 1, 0, -1))
        self._wtables: Dict[object, Dict[int, int]] = {}
        self._wnext: Dict[object, int] = {}   # first block never allocated
        # LIFO free list over blocks 1..NB-1 (0 = scratch, reserved)
        self._free: List[int] = list(range(pool_blocks - 1, 0, -1))
        self._tables: Dict[object, List[int]] = {}
        # per-block refcount: one ref per table entry referencing the
        # block plus one for its prefix-index node (if any). A shared
        # block frees only at refcount 0.
        self._ref: List[int] = [0] * pool_blocks
        self._in_use = 0                  # distinct blocks with ref > 0
        # radix prefix index over committed prefill blocks
        self._root = _PrefixNode(b"", np.zeros(0, np.int32), -1, None)  # type: ignore[arg-type]
        self._node_of_block: Dict[int, _PrefixNode] = {}
        self._lru_tick = 0
        # bumped on every commit_prefix insert: lets the scheduler's
        # mid-prefill re-match skip the walk when nothing new committed
        self.index_version = 0
        # blocks adopted from the migration wire over this pool's
        # lifetime (disaggregation / migrate-don't-evict): surfaced in
        # the PoolExhausted breakdown so a pressure post-mortem shows
        # how much of the occupancy migrated in rather than grew here
        self.migrated_in_blocks = 0
        _reg = get_registry()
        # per-POOL gauge series (global instance sequence, the PR 6
        # scheduler.s<N>/pacer.p<N> pattern): two replicas' pools must
        # not mask each other last-writer-wins
        seq = next(_POOL_SEQ)
        self._g_in_use = _reg.gauge(f"serve.pool{seq}.kv_blocks_in_use")
        self._g_prefix = _reg.gauge(f"serve.pool{seq}.prefix_blocks")
        if self.window is not None:
            self._g_latent = _reg.gauge(
                f"serve.pool{seq}.latent_blocks_in_use")
            self._g_window = _reg.gauge(
                f"serve.pool{seq}.window_blocks_in_use")
            self._c_released = _reg.counter(
                "serve.cache.window_blocks_released")
        if self.state_slots:
            self._slot_bytes = sum(
                a.nbytes // a.shape[1] for a in (self.state.s,
                                                 self.state.conv))
            self._g_slots = _reg.gauge("serve.state.slots_in_use")
            self._g_state_bytes = _reg.gauge("serve.state.bytes")
            self._c_resets = {c: _reg.counter(f"serve.state.resets.{c}")
                              for c in ("admit", "preempt")}
        self._c_alloc_fail = _reg.counter("serve.kv_alloc_failures")
        self._c_prefix_evict = _reg.counter("serve.prefix_evictions")
        self._c_cow = _reg.counter("serve.prefix.cow_blocks")

    # -- accounting ---------------------------------------------------------
    @property
    def free_blocks(self) -> int:
        return len(self._free)

    def _live_blocks(self) -> set:
        """Ground-truth occupancy: the DISTINCT physical blocks
        referenced by any live table or the prefix index — computed
        from the references themselves, not ``_ref``, so the leak pin
        stays truthful even against a refcount bookkeeping bug."""
        live = {b for t in self._tables.values() for b in t}
        live.update(self._node_of_block)
        return live

    @property
    def blocks_in_use(self) -> int:
        """Distinct physical blocks occupied (shared pages count ONCE —
        the whole point of sharing). Maintained incrementally: it moves
        only when a refcount crosses 0<->1 (_alloc_block/_decref), so
        the per-mutation gauge update stays O(1) instead of walking
        every table (check_refcounts pins it against the ground
        truth)."""
        return self._in_use

    @property
    def prefix_blocks(self) -> int:
        """Blocks held by the radix prefix index."""
        return len(self._node_of_block)

    def leaked_blocks(self) -> int:
        """Blocks neither free nor referenced by a live table or the
        prefix index — must be 0 at drain (the CI smoke's leak pin)."""
        leaked = (self.pool_blocks - 1) - len(self._free) \
            - len(self._live_blocks())
        if self.window is not None:
            leaked += (self.window_blocks - 1) - len(self._wfree) \
                - self.window_blocks_in_use
        return leaked + self.leaked_slots()

    @property
    def slots_in_use(self) -> int:
        """Slots of the state pool that registered requests hold."""
        return len(self._slots)

    def leaked_slots(self) -> int:
        """Slots neither free nor a registered request's (0 without a
        recurrent kind); counted among :meth:`leaked_blocks`."""
        if not self.state_slots:
            return 0
        return (self.state_slots - 1) - len(self._sfree) - len(self._slots)

    def slot_of(self, rid) -> int:
        return self._slots[rid]

    def _set_state_gauges(self) -> None:
        self._g_slots.set(len(self._slots))
        self._g_state_bytes.set(len(self._slots) * self._slot_bytes)

    @property
    def window_blocks_in_use(self) -> int:
        """Blocks of the window kind that live tables hold."""
        return sum(len(t) for t in self._wtables.values())

    def reclaimable_blocks(self, exclude=()) -> int:
        """Blocks LRU eviction could actually return to the free list:
        prefix-index blocks no live table references (refcount 1 —
        cached-but-idle). ``exclude`` masks blocks the caller is about
        to adopt (adoption pins them, so they stop being reclaimable
        the moment the admission that counted them proceeds)."""
        ex = set(exclude)
        return sum(1 for b in self._node_of_block
                   if self._ref[b] == 1 and b not in ex)

    def check_refcounts(self) -> None:
        """Debug/test invariant: ``_ref`` must equal the reference
        ground truth (table entries + index nodes) for every block, and
        never go negative. Raises ``AssertionError`` on drift."""
        want = [0] * self.pool_blocks
        for t in self._tables.values():
            for b in t:
                want[b] += 1
        for b in self._node_of_block:
            want[b] += 1
        assert self._ref == want, (
            f"refcount drift: {[(b, self._ref[b], want[b]) for b in range(self.pool_blocks) if self._ref[b] != want[b]]}")
        assert all(r >= 0 for r in self._ref)
        assert self._in_use == len(self._live_blocks()), (
            self._in_use, len(self._live_blocks()))
        assert self.leaked_blocks() >= 0

    def blocks_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.block_size)

    def table_len(self, rid) -> int:
        """Live blocks allocated to ``rid`` (the width buckets key)."""
        return len(self._tables[rid])

    # -- allocation ---------------------------------------------------------
    def register(self, rid, resumed: bool = False) -> None:
        """Open ``rid``'s account: an empty table a kind and, with a
        recurrent kind, a slot of the state pool — granted here, reset by
        the request's first chunk (``resumed``: after a preemption, which
        the reset is then counted under)."""
        if rid in self._tables:
            raise ValueError(f"request {rid!r} already registered")
        if self.state_slots:
            if not self._sfree:
                self._c_alloc_fail.inc()
                raise PoolExhausted(
                    f"request {rid!r} needs a state slot, all "
                    f"{self.state_slots - 1} are held")
            self._slots[rid] = self._sfree.pop()
            self._c_resets["preempt" if resumed else "admit"].inc()
            self._set_state_gauges()
        self._tables[rid] = []
        if self.window is not None:
            self._wtables[rid] = {}
            self._wnext[rid] = 0

    def _alloc_block(self) -> int:
        b = self._free.pop()
        self._ref[b] = 1
        self._in_use += 1
        return b

    def _decref(self, b: int) -> None:
        r = self._ref[b] - 1
        if r < 0:
            raise RuntimeError(
                f"refcount underflow on block {b} — a release/evict "
                "path double-freed a shared page")
        self._ref[b] = r
        if r == 0:
            self._free.append(b)
            self._in_use -= 1

    def _exhausted_msg(self, rid, need: int) -> str:
        """Occupancy breakdown so a preemption-storm post-mortem is
        diagnosable straight off the flight recorder: live (table-
        referenced) vs cached-but-idle shared-prefix vs free blocks."""
        live = {b for t in self._tables.values() for b in t}
        cached_idle = sum(1 for b in self._node_of_block if b not in live)
        leaked = self.leaked_blocks()
        return (
            f"request {rid!r} needs {need} more block(s), pool has "
            f"{len(self._free)} free — occupancy: "
            f"{self.pool_blocks - 1} allocatable = {len(live)} live + "
            f"{cached_idle} cached-prefix + {len(self._free)} free"
            + (f" + {leaked} LEAKED" if leaked else "")
            + (f"; {self.migrated_in_blocks} block(s) migrated in over "
               "this pool's lifetime"
               if self.migrated_in_blocks else ""))

    def ensure(self, rid, n_tokens: int) -> None:
        """Grow ``rid``'s table to cover ``n_tokens`` positions with
        FRESH (refcount-1, private) blocks; raises
        :class:`PoolExhausted` (allocating nothing) when the pool can't
        — all-or-nothing so a failed grow never strands blocks.
        Cached-but-idle prefix pages are LRU-evicted first: the prefix
        cache must never cause :class:`PoolExhausted` for live
        traffic."""
        table = self._tables[rid]
        need = self.blocks_for(n_tokens) - len(table)
        if need <= 0:
            return
        if need > len(self._free):
            self._evict_prefix(need - len(self._free))
        if need > len(self._free):
            self._c_alloc_fail.inc()
            raise PoolExhausted(self._exhausted_msg(rid, need))
        for _ in range(need):
            table.append(self._alloc_block())
        self._g_in_use.set(self.blocks_in_use)

    def release(self, rid) -> None:
        """Drop ``rid``'s table, decrementing each block's refcount
        (request completion, preemption, replica drain). A shared block
        returns to the pool only at refcount 0 — pages still backing
        the prefix index (or a sibling's table) stay resident."""
        table = self._tables.pop(rid)
        for b in reversed(table):
            self._decref(b)
        self._g_in_use.set(self.blocks_in_use)
        if self.window is not None:
            self._wfree.extend(self._wtables.pop(rid).values())
            del self._wnext[rid]
            self._set_kind_gauges()
        if self.state_slots:
            self._sfree.append(self._slots.pop(rid))
            self._set_state_gauges()

    def _kv_only(self, what: str) -> None:
        if self.kv_heads == 0:
            raise NotImplementedError(
                f"PagedKVCache.{what}: latent pages have no k/v payload "
                "(the GPT family's pool only)")

    # -- the window kind ----------------------------------------------------
    def _set_kind_gauges(self) -> None:
        self._g_latent.set(self.blocks_in_use)
        self._g_window.set(self.window_blocks_in_use)

    def kind_widths(self, rid) -> tuple:
        """Live blocks ``rid`` holds of each layer kind beyond the one every
        family has — ``(window blocks,)``, or ``()`` without a window kind
        (what ``serve.prefill_dispatch`` adds to its args)."""
        return () if self.window is None else (len(self._wtables[rid]),)

    def ensure_window(self, rid, n_tokens: int) -> None:
        """Grow ``rid``'s window table to cover positions below
        ``n_tokens`` (every logical block not allocated yet; those released
        behind the window stay released). All or nothing, like
        :meth:`ensure`; a cache with no window kind has nothing to do."""
        if self.window is None:
            return
        lo, hi = self._wnext[rid], self.blocks_for(n_tokens)
        if hi - lo > len(self._wfree):
            self._c_alloc_fail.inc()
            raise PoolExhausted(
                f"request {rid!r} needs {hi - lo} more window block(s), the "
                f"window pool has {len(self._wfree)} free of "
                f"{self.window_blocks - 1}")
        table = self._wtables[rid]
        for b in range(lo, hi):
            table[b] = self._wfree.pop()
        self._wnext[rid] = max(lo, hi)
        self._set_kind_gauges()

    def release_behind(self, rid, fill: int) -> int:
        """Hand back the window blocks no later query of ``rid`` can see:
        the next query sits at ``fill`` and sees ``window - 1`` positions
        before it. Returns how many were freed (0 without a window
        kind)."""
        if self.window is None:
            return 0
        table = self._wtables[rid]
        dead = [b for b in table
                if (b + 1) * self.block_size <= fill - (self.window - 1)]
        for b in dead:
            self._wfree.append(table.pop(b))
        if dead:
            self._c_released.inc(len(dead))
            self._set_kind_gauges()
        return len(dead)

    def adopt_prefix(self, rid, blocks: List[int]) -> None:
        """Seed ``rid``'s (empty) table with shared prefix pages from a
        :meth:`match_prefix` hit — each gains a reference and becomes
        read-only for this request until :meth:`ensure_writable` CoWs
        it."""
        table = self._tables[rid]
        if table:
            raise ValueError(
                f"adopt_prefix needs an empty table; {rid!r} holds "
                f"{len(table)} block(s)")
        for b in blocks:
            self._ref[b] += 1
            table.append(b)
        self._g_in_use.set(self.blocks_in_use)

    def readopt_prefix(self, rid, blocks: List[int],
                       first_block: int) -> int:
        """Mid-prefill adoption: swap ``rid``'s table entries
        ``[first_block, first_block + len(blocks))`` for shared pages a
        SIBLING committed after this request was admitted — the
        saturation shape, where everyone admits before anyone commits,
        so the admission-time lookup alone would miss almost every
        share. The displaced private blocks free immediately (or drop a
        reference if they were themselves shared). The caller only
        swaps entries at/above its prefill watermark: everything below
        is already written and stays put."""
        table = self._tables[rid]
        swapped = 0
        for i, b in enumerate(blocks):
            bi = first_block + i
            old = table[bi]
            if old == b:
                continue
            self._ref[b] += 1
            self._decref(old)
            table[bi] = b
            swapped += 1
        if swapped:
            self._g_in_use.set(self.blocks_in_use)
        return swapped

    def ensure_writable(self, rid, lo: int, hi: int) -> int:
        """Copy-on-write every block covering token positions
        ``[lo, hi)``: a table entry with refcount > 1 gets a fresh
        block with the shared contents copied — every leaf the pool keeps a
        page of the block in (``state.block_leaves``: k/v and, in the int8
        ``_QuantSlot`` pool, their scales; a latent pool's rows and indexer
        keys) — the shared page's refcount drops, and the table points at
        the private copy. Returns the number of blocks copied
        (``serve.prefix.cow_blocks`` counts them). Raises
        :class:`PoolExhausted` when no fresh block can be found even
        after LRU eviction."""
        if hi <= lo:
            return 0
        table = self._tables[rid]
        copied = 0
        for bi in range(lo // self.block_size,
                        -(-hi // self.block_size)):
            b = table[bi]
            if self._ref[b] <= 1:
                continue
            if not self._free:
                self._evict_prefix(1)
            if not self._free:
                self._c_alloc_fail.inc()
                raise PoolExhausted(self._exhausted_msg(rid, 1))
            nb = self._alloc_block()
            st = self.state
            self.state = st._replace(**{
                name: _copy_block(leaf, b, nb)
                for name in st.block_leaves
                for leaf in (getattr(st, name),) if leaf is not None})
            self._decref(b)
            table[bi] = nb
            copied += 1
        if copied:
            self._c_cow.inc(copied)
            self._g_in_use.set(self.blocks_in_use)
        return copied

    # -- radix prefix index -------------------------------------------------
    def _touch(self) -> int:
        self._lru_tick += 1
        return self._lru_tick

    def match_prefix(self, tokens,
                     full_blocks_only: bool = False
                     ) -> "tuple[List[int], int]":
        """Longest committed prefix of ``tokens`` in the radix index.

        Returns ``(blocks, n_tokens)``: a chain of full-block hits plus
        optionally ONE divergence block matched on a partial leading
        run (``n_tokens % block_size != 0`` then) — the caller adopts
        the chain, CoWs the partial tail, and starts chunked prefill at
        ``n_tokens``. Touches every matched node's LRU tick.
        ``full_blocks_only`` skips the divergence scan (a numpy compare
        over the deepest node's children) — the mid-prefill jump only
        swaps whole blocks, so it never pays for a partial match."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        bs = self.block_size
        node = self._root
        blocks: List[int] = []
        matched = 0
        tick = self._touch()
        while matched + bs <= tokens.size:
            child = node.children.get(tokens[matched:matched + bs]
                                      .tobytes())
            if child is None:
                break
            child.tick = tick
            blocks.append(child.block)
            matched += bs
            node = child
        rem = tokens[matched:]
        if rem.size and not full_blocks_only:
            # divergence block: the child sharing the longest leading
            # run with the remaining tokens (>= 1 token to be worth a
            # CoW copy)
            best, best_n = None, 0
            for child in node.children.values():
                m = min(rem.size, child.tokens.size)
                n = int(np.cumprod(child.tokens[:m] == rem[:m]).sum())
                if n > best_n:
                    best, best_n = child, n
            if best is not None:
                best.tick = tick
                blocks.append(best.block)
                matched += best_n
        return blocks, matched

    def commit_prefix(self, rid, tokens, n_tokens: int) -> int:
        """Publish ``rid``'s fully-written leading blocks (covering
        ``tokens[:n_tokens]``) into the radix index; each inserted node
        takes one reference on its block, keeping the page resident
        after the request finishes (cached-but-idle, LRU-evictable).
        Only FULL blocks are committed — a partial tail block is still
        being written and never enters the index. Returns the number of
        nodes inserted."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        bs = self.block_size
        table = self._tables[rid]
        node = self._root
        inserted = 0
        tick = self._touch()
        for bi in range(n_tokens // bs):
            seg = tokens[bi * bs:(bi + 1) * bs]
            key = seg.tobytes()
            child = node.children.get(key)
            if child is None:
                b = table[bi]
                if b in self._node_of_block:
                    # this physical page already backs a node on another
                    # chain — cannot happen for content-addressed private
                    # blocks; stop rather than alias two chains
                    break
                child = _PrefixNode(key, seg.copy(), b, node)
                node.children[key] = child
                self._node_of_block[b] = child
                self._ref[b] += 1
                inserted += 1
            # an existing node may be backed by a DIFFERENT physical
            # block (this request recomputed a prefix that was cached
            # after its admission); the chain continues through the
            # index's block — content-identical by construction
            child.tick = tick
            node = child
        if inserted:
            self.index_version += 1
            self._g_prefix.set(len(self._node_of_block))
        return inserted

    def _evict_node(self, node: _PrefixNode) -> None:
        """Drop one node (and its subtree, depth-first) from the index:
        each dropped block loses the index's reference and frees at
        refcount 0."""
        for child in list(node.children.values()):
            self._evict_node(child)
        del node.parent.children[node.key]
        del self._node_of_block[node.block]
        self._decref(node.block)
        self._c_prefix_evict.inc()

    def _evict_prefix(self, want_free: int) -> int:
        """LRU-evict cached-but-idle prefix subtrees until
        ``want_free`` blocks came back to the free list or nothing
        reclaimable remains. Victims are nodes whose block only the
        index holds (refcount 1 — evicting anything else frees no
        memory); a victim's descendants go with it (they are
        unreachable without the parent edge), shared ones merely
        leaving the index."""
        freed0 = len(self._free)
        # one snapshot, tick-sorted: eviction only ever REMOVES nodes
        # (it can't mint new refcount-1 candidates with older ticks),
        # so rescanning the whole index per evicted subtree would be
        # O(k * index) for nothing — re-check each candidate instead
        victims = sorted((n for n in self._node_of_block.values()
                          if self._ref[n.block] == 1),
                         key=lambda n: n.tick)
        for n in victims:
            if len(self._free) - freed0 >= want_free:
                break
            if self._node_of_block.get(n.block) is not n:
                continue      # went down with an ancestor's subtree
            self._evict_node(n)
        self._g_prefix.set(len(self._node_of_block))
        return len(self._free) - freed0

    def drop_prefix_cache(self) -> int:
        """Release every cached prefix page (tests, replica teardown,
        the ``BYTEPS_SERVE_PREFIX_CACHE=0`` escape hatch); live tables
        keep their references. Returns the number of nodes dropped."""
        n = len(self._node_of_block)
        for child in list(self._root.children.values()):
            self._evict_node(child)
        self._g_prefix.set(0)
        self._g_in_use.set(self.blocks_in_use)
        return n

    def table_row(self, rid, width: Optional[int] = None) -> np.ndarray:
        """``(width,)`` int32 physical-block row for the packed step
        (default ``blocks_per_req``); the unallocated tail points at
        scratch block 0 (those positions are always at/past the fill
        level, so the gather's zero-mask keeps whatever lives there out
        of the math). ``width`` must cover the live table — callers
        bucket it to a power of two so the jitted steps see a handful
        of gather shapes instead of one per request length."""
        w = self.blocks_per_req if width is None else width
        t = self._tables[rid]
        if w < len(t):
            raise ValueError(f"width {w} < live table {len(t)}")
        if self.window is not None:
            # line 0 the global kind, line 1 the window kind (0, the
            # scratch block, where a block was released or never held); with
            # a recurrent kind too, a column before them: the request's slot
            # at the head of line 0
            s = 1 if self.state_slots else 0
            rows = np.zeros((2, s + w), np.int32)
            if s:
                rows[0, 0] = self._slots[rid]
            rows[0, s:s + len(t)] = t
            wt = self._wtables[rid]
            rows[1, [s + b for b in wt]] = list(wt.values())
            return rows
        if self.state_slots:
            # the request's slot of the state pool rides at the head of its
            # row: one host array a step, as it was
            row = np.zeros(1 + w, np.int32)
            row[0] = self._slots[rid]
            row[1:1 + len(t)] = t
            return row
        row = np.zeros(w, np.int32)
        row[:len(t)] = t
        return row

    # -- migration payloads (serve/kv_wire.py) -------------------------------
    def snapshot_blocks(self, rid, lo: int, hi: int):
        """Host snapshots of ``rid``'s table blocks ``[lo, hi)`` as
        ``{block_idx: BlockPayload}`` — ONE device gather per call (not
        one per block). This is the migration wire's read side: the
        bytes are copied out verbatim (rows at/past the fill level
        carry whatever the recycled block held — the receiving gather's
        zero-mask keeps them out of the math, exactly as it does
        locally)."""
        self._kv_only("snapshot_blocks")
        from byteps_tpu.serve.kv_wire import BlockPayload

        if hi <= lo:
            return {}
        blocks = self._tables[rid][lo:hi]
        idx = jnp.asarray(blocks, jnp.int32)
        st = self.state
        # payloads carry (L, bs, h, D); the pool's minor axis is h * D,
        # so the host reshape is a view of the same bytes
        tail = (self.kv_heads, self.cfg.head_dim)
        k = jax.device_get(st.k[:, idx])          # (L, n, bs, h * D)
        v = jax.device_get(st.v[:, idx])
        k = k.reshape(k.shape[:-1] + tail)
        v = v.reshape(v.shape[:-1] + tail)
        ks = vs = None
        if st.k_scale is not None:
            ks = jax.device_get(st.k_scale[:, idx])
            vs = jax.device_get(st.v_scale[:, idx])
        return {lo + i: BlockPayload(
                    k[:, i], v[:, i],
                    None if ks is None else ks[:, i],
                    None if vs is None else vs[:, i])
                for i in range(len(blocks))}

    def write_payloads(self, block_ids, payloads) -> None:
        """Scatter migrated block contents into physical ``block_ids``
        (the adoption write side) — one device scatter per pool array
        regardless of block count. Payload dtypes are the pool's own
        (the wire codec round-trips bytes, never values), so this write
        is bit-exact by construction."""
        self._kv_only("write_payloads")
        if not block_ids:
            return
        idx = jnp.asarray(list(block_ids), jnp.int32)
        st = self.state
        L, _, bs, hd = st.k.shape

        def stacked(arrs):            # (L, bs, h, D) each → (L, n, bs, h*D)
            return jnp.asarray(np.stack(
                [np.asarray(a).reshape(L, bs, hd) for a in arrs], axis=1))

        k = stacked(p.k for p in payloads)
        v = stacked(p.v for p in payloads)
        if st.k_scale is not None:
            ks = jnp.asarray(np.stack(
                [np.asarray(p.k_scale) for p in payloads], axis=1))
            vs = jnp.asarray(np.stack(
                [np.asarray(p.v_scale) for p in payloads], axis=1))
            self.state = st._replace(
                k=st.k.at[:, idx].set(k), v=st.v.at[:, idx].set(v),
                k_scale=st.k_scale.at[:, idx].set(ks),
                v_scale=st.v_scale.at[:, idx].set(vs))
        else:
            self.state = st._replace(
                k=st.k.at[:, idx].set(k.astype(st.k.dtype)),
                v=st.v.at[:, idx].set(v.astype(st.v.dtype)))
        self.migrated_in_blocks += len(block_ids)

    def defrag(self) -> int:
        """Compact live blocks to the lowest physical ids (one device
        gather per pool array), rewriting every table, the prefix
        index, and the refcounts. A SHARED page moves once and every
        alias follows it — table aliasing and shared-page contents are
        preserved exactly (pinned in tests/test_serve_prefix.py).
        Correctness never needs this — tables make fragmentation
        invisible — but a long-lived replica's pool walks toward high
        ids and compaction restores allocation locality for the gather.
        Returns the number of blocks moved."""
        self._kv_only("defrag")
        live = sorted(self._live_blocks())
        perm = np.arange(self.pool_blocks)
        moved = 0
        for new_id, old_id in enumerate(live, start=1):
            perm[new_id] = old_id
            if new_id != old_id:
                moved += 1
        if moved == 0:
            # already compact (free-list order may still differ; reset it)
            self._free = list(range(self.pool_blocks - 1, len(live), -1))
            return 0
        remap = {old: new for new, old in enumerate(live, start=1)}
        src = jnp.asarray(perm)
        self.state = self.state._replace(
            k=self.state.k[:, src],
            v=self.state.v[:, src],
            k_scale=(None if self.state.k_scale is None
                     else self.state.k_scale[:, src]),
            v_scale=(None if self.state.v_scale is None
                     else self.state.v_scale[:, src]),
        )
        for t in self._tables.values():
            t[:] = [remap[b] for b in t]
        ref = [0] * self.pool_blocks
        for old, new in remap.items():
            ref[new] = self._ref[old]
        self._ref = ref
        self._node_of_block = {remap[b]: n
                               for b, n in self._node_of_block.items()}
        for new, node in self._node_of_block.items():
            node.block = new
        self._free = list(range(self.pool_blocks - 1, len(live), -1))
        return moved


def _gather_view(pool_l, scale_l, table, length, dtype, head_dim):
    """One layer's attention-ready per-request view(s).

    pool_l: (NB, bs, h*D); table: (..., n_blocks) int32; length:
    broadcastable per-row fill level. Returns (..., n_blocks*bs, h, D)
    in ``dtype`` with positions >= length zeroed — exactly the dense
    cache's state (zero-init, written only below the fill level), so
    freed-block garbage can never reach the masked lanes and the packed
    view is bit-comparable to a solo run's cache."""
    g = pool_l[table]                       # (..., nb, bs, h*D)
    S = g.shape[-3] * g.shape[-2]
    g = g.reshape(g.shape[:-3] + (S, -1, head_dim))
    if scale_l is not None:
        s = scale_l[table]
        s = s.reshape(s.shape[:-3] + (S,) + s.shape[-1:])
        g = (g.astype(jnp.float32) * s[..., None])   # _cache_read dequant
    g = g.astype(dtype)
    keep = jnp.arange(S) < jnp.asarray(length)[..., None]
    return jnp.where(keep[..., None, None], g, jnp.zeros((), dtype))


class StepStats(LateStats):
    """:data:`STATS` of each dispatched program of a family with window
    layers into the ``moe.*`` histograms and the ``serve.kv.*`` /
    ``serve.attn.*`` counters (docs/observability.md)."""

    def __init__(self, names: tuple = STATS):
        super().__init__()
        self.names = names
        reg = get_registry()
        self._pairs_here = reg.histogram("moe.pairs_here")
        self._experts_hit = reg.histogram("moe.experts_hit")
        self._load = reg.histogram("moe.load_max_over_mean")
        self._counters = {n: reg.counter(n) for n in names
                          if n.startswith(("serve.", "sambay."))}

    def observe(self, s: dict) -> None:
        if s["moe.layers"] > 0:
            self._pairs_here.observe(s["moe.pairs_here"])
            # of a layer: the mean over the program's expert layers
            self._experts_hit.observe(s["moe.experts_hit"] / s["moe.layers"])
            self._load.observe(s["moe.load_max_over_mean"])
        for n, c in self._counters.items():
            c.inc(int(s[n]))


def _fold_moe(total, aux):
    """A layer's ``ffn`` aux into the program's: pairs, experts hit and
    layers add; the load ratio keeps its worst layer."""
    return jnp.stack([total[0] + aux[0], total[1] + aux[1], total[2] + 1.0,
                      jnp.maximum(total[3], aux[2])])


def gather_rows(pool_a, layer, table, sel, block_size: int):
    """Rows of one layer of a pool at logical positions ``sel (..., K)`` of
    one request (``table (W,)``) or of one request a row (``table (N,
    W)``)."""
    blk = (jnp.take_along_axis(table, sel // block_size, axis=-1)
           if table.ndim == 2 else jnp.take(table, sel // block_size))
    return pool_a[layer, blk, sel % block_size]


def _window_attend_twin(q, k_pool, v_pool, wi, table, pos, window: int,
                        block_size: int):
    """The jnp twin of the windowed kernel call: each row's last ``window``
    keys gathered one by one through its table (no wider view is made),
    softmax in f32 over those at or after position 0. ``q (R, 1, H, D)``."""
    R, _, H, D = q.shape
    at = pos[:, None] - (window - 1) + jnp.arange(window)[None, :]
    kk = gather_rows(k_pool, wi, table, jnp.maximum(at, 0), block_size)
    vv = gather_rows(v_pool, wi, table, jnp.maximum(at, 0), block_size)
    kk = kk.reshape(R, window, -1, D)
    vv = vv.reshape(R, window, -1, D)
    qg = q[:, 0].reshape(R, kk.shape[2], -1, D)
    s = jnp.einsum("rhgd,rkhd->rhgk", qg.astype(jnp.float32),
                   kk.astype(jnp.float32)) * D ** -0.5
    pr = jax.nn.softmax(jnp.where((at >= 0)[:, None, None], s, -1e30), -1)
    o = jnp.einsum("rhgk,rkhd->rhgd", pr, vv.astype(jnp.float32))
    return o.reshape(R, 1, H, D).astype(q.dtype)


def _block_attend_twin(q, kk, vv, length):
    """The jnp twin of the kernel call with a block of queries a row: every
    query of ``q (R, B, H, D)`` sees the keys below its row's ``length`` of
    the gathered views ``kk``/``vv (R, S, Hkv, D)`` (the block's own are
    among them), softmax in f32."""
    R, B, H, D = q.shape
    Hkv = kk.shape[2]
    qg = q.reshape(R, B, Hkv, H // Hkv, D)
    s = jnp.einsum("rbhgd,rkhd->rhgbk", qg.astype(jnp.float32),
                   kk.astype(jnp.float32)) * D ** -0.5
    ok = jnp.arange(kk.shape[1])[None, :] < length[:, None]
    pr = jax.nn.softmax(jnp.where(ok[:, None, None, None], s, -1e30), -1)
    o = jnp.einsum("rhgbk,rkhd->rbhgd", pr, vv.astype(jnp.float32))
    return o.reshape(R, B, H, D).astype(q.dtype)


def _reader_attend(cfg, block_size: int, pool, li, tables, length):
    """A reader layer's ``attend`` (:class:`LayerKind`): nothing is written;
    each row's one query attends over keys ``[0, length[r])`` of row ``li`` of
    the global pool — another layer's, which wrote them earlier in this
    program — through the row's table ``tables (R, W)``. The kernel where the
    packed decode step takes it, else the gathered view. The carry is the
    pool, as it was."""
    def attend(q, k, v):
        del k, v                          # a reader projects a query only
        head_dim = q.shape[-1]
        if decode_uses_paged_attn(cfg, block_size,
                                  pool.k.shape[-1] // head_dim, False):
            with jax.named_scope("paged/attention"):
                o = paged_attention_decode(q[:, 0], pool.k, pool.v, tables,
                                           length, li)
        else:
            with jax.named_scope("paged/gather_kv"):
                kk = _gather_view(pool.k[li], None, tables, length, q.dtype,
                                  head_dim)
                vv = _gather_view(pool.v[li], None, tables, length, q.dtype,
                                  head_dim)
            with jax.named_scope("paged/attention"):
                o, _ = attention_lse(q, kk, vv, length - 1, 0, causal=True)
        return o, pool
    return attend


def _kind_kw(plan: "StepPlan"):
    """``kind -> the keywords plan.attn is given beside attn_half's``."""
    if plan.attn_takes_kind:
        return lambda kind: {"kind": kind._replace(index=0)}
    return lambda kind: {}


def decode_uses_paged_attn(cfg: GPTConfig, block_size: int,
                           kv_heads: int, quant: bool) -> bool:
    """Whether the packed decode step built for this pool attends
    through ``ops/paged_attention.py``'s kernel or keeps the gathered
    view and ``attention_lse``: decided from the backend and the pool's
    shapes alone (no knob), asked at trace time by the step and at build
    time by the scheduler's ``serve.decode_steps_paged_attn`` counter.
    Backend Pallas but shapes the kernel does not take: the twin, said
    once through ``note_fallback``."""
    if not use_pallas():
        return False
    dtype = jnp.int8 if quant else cfg.dtype
    why = paged_attn_unsupported(block_size, kv_heads, cfg.head_dim,
                                 dtype)
    if why is not None:
        note_fallback("paged_attn_decode",
                      (block_size, kv_heads, cfg.head_dim,
                       jnp.dtype(dtype).name), why)
        return False
    return True


# every leaf models/gpt.py::_project reads of a block: the projections and
# their biases (w3/b3 on a SwiGLU block; biases absent under use_bias=False)
_PROJECTED = tuple(k + n for k in "wb" for n in "qkvo123")


@functools.partial(jax.jit, static_argnames="dtype")
def _cast_operands(blocks, head, dtype):
    if "wte" in head:              # a tied readout: lm_head is wte.T, (d, V)
        head = {"lm_head": head["wte"].T}
    return jax.tree_util.tree_map(lambda w: w.astype(dtype), (blocks, head))


def serve_operands(params, cfg: GPTConfig):
    """The tree both serve programs are called with: the matmul operands
    cast to ``cfg.dtype`` ONCE, where the shared block would cast them in
    every decode step and prefill chunk (``_project``:
    ``p[name].astype(x.dtype)``; the readout the same) — at bf16 compute on
    an f32 tree each program otherwise streams the f32 weights from HBM to
    round them to the operands it had a step ago (PERF.md §6, PR 33).

    Cast, in one jitted call: every block leaf ``_project`` reads
    (:data:`_PROJECTED`), and the readout as an ``lm_head`` leaf of shape
    ``(d, V)`` (``wte.T`` of a tied tree), which ``_readout`` prefers.
    ``astype`` to the dtype a leaf already has lowers to nothing, so the
    model code adapts on what it can observe and every logit is
    bit-identical to the caller's tree. Everything else is the caller's
    leaf BY REFERENCE: ``wte``/``wpe`` (``_embed`` adds them in f32, then
    rounds, and reads a few rows), norm gains and biases (f32 arithmetic),
    a grafted ``"lora"`` subtree. A sharded leaf keeps its sharding (an
    elementwise cast of a committed array), and one already in
    ``cfg.dtype`` is the caller's too. Where none differs (f32 compute on
    an f32 tree), returns ``params`` itself: no second tree."""
    dtype = jnp.dtype(cfg.dtype)
    blocks = [{k: p[k] for k in _PROJECTED if k in p and p[k].dtype != dtype}
              for p in params["blocks"]]
    src = "lm_head" if "lm_head" in params else "wte"
    head = {} if params[src].dtype == dtype else {src: params[src]}
    if not jax.tree_util.tree_leaves((blocks, head)):
        return params
    blocks, head = _cast_operands(blocks, head, dtype=dtype)
    return {**params, **head,
            "blocks": [{**p, **b} for p, b in zip(params["blocks"], blocks)]}


@functools.lru_cache(maxsize=64)
def make_paged_decode_fn(cfg: GPTConfig, block_size: int,
                         tp_axis: Optional[str] = None,
                         lora_sig: Optional[tuple] = None,
                         plan: Optional[StepPlan] = None):
    """Build the jitted packed decode step.

    ``step(params, pool, toks, pos, tables) -> (logits (R, vocab) f32,
    new pool)``: R requests each feed one token at their OWN global
    position ``pos[r]`` (cache fill level — keys [0, pos) are live).
    Padded rows pass pos=0 with an all-scratch table row; their math is
    garbage-in/garbage-out into scratch block 0 and the caller ignores
    their logits. Callers pass width-bucketed tables and jit retraces
    once per bucket: the paged-attention kernel reads each row's live
    blocks whatever the width, the jnp twin gathers ``tables.shape[1] *
    block_size`` keys for every row.
    Table rows may alias SHARED prefix pages (refcount > 1): those are
    read-only by host contract — the scheduler CoWs the write-target
    block (``ensure_writable``) before this step scatters into
    ``tables[r][pos // bs]``, so the scatter below only ever lands in a
    private block (or scratch).
    ``plan`` (default :func:`one_kind_plan`: every layer global, the dense
    MLP; ``families.GPTFamily`` refuses a tree with a Switch-routed layer
    when the scheduler is built) tells each layer its kind. With window
    layers ``tables`` is ``(R, 2, W)`` — line 0 the global kind's blocks,
    line 1 the window kind's, indexed by logical block, 0 where one was
    released — and a window layer scatters into ``pool.wk``/``wv`` and
    attends over ``[pos + 1 - window, pos]`` alone: the kernel with each
    row's first key, or :func:`_window_attend_twin`. With recurrent layers
    ``tables`` is ``(R, 1 + W)``: column 0 each row's slot of the state pool
    (0, scratch, for a row that holds no request), which ``plan.recur``
    updates in place; such a layer reads no table and no key. A hybrid layer
    does both: ``plan.mixer`` is given this step's ``attend`` over the layer's
    row of the k/v pool and the rows' slots. With window AND recurrent
    layers ``tables`` is ``(R, 2, 1 + W)``, the slot at the head of line 0. A
    reader layer attends over another layer's row of the global pool and
    writes nothing (:func:`_reader_attend`); a fed layer touches no pool.
    With ``plan.block`` = B the step is a pass of block diffusion: ``toks (R,
    B)`` — row ``r``'s block as it stands, the mask token where a position is
    open — at positions ``[pos[r], pos[r] + B)`` (a block boundary: the B rows
    lie in one page). Their k/v are scattered there IN PLACE, over whatever
    an earlier pass of the same block left, every one of the B queries sees
    the ``pos + B`` keys (the kernel with ``B · H`` query rows a batch row),
    and the step returns ``logits (R, B, vocab)``. A denoising pass and the
    pass that commits the block are this one program: the host moves the fill
    level after the second.

    Multi-tenant variant: ``lora_sig=(targets, rank_bucket,
    n_adapter_slots)`` makes the step accept two trailing arguments —
    the :class:`~byteps_tpu.serve.adapter_pool.AdapterPool`'s slab dict
    and a ``(R,)`` int32 per-row slot vector — and each row adds its
    OWN adapter's low-rank delta beside every frozen matmul via
    ``ops/segmented_lora.segmented_lora_delta`` (slot 0 is the pool's
    reserved zero adapter, so base-model and padded rows stay exact
    no-ops). The rank bucket and slot count sit in the factory cache
    key: mixed-rank tenants share ONE compiled step (they're padded to
    the bucket), while a pool-geometry change gets its own wrapper
    instead of silently colliding — the retrace-count tests pin this.

    lru-cached by (cfg, block_size, tp_axis, lora_sig): every Scheduler
    replica in the process shares ONE jit wrapper, so a fresh replica
    (bench rep, failover respawn) reuses the compiled steps instead of
    paying a full retrace."""
    plan = one_kind_plan(cfg) if plan is None else plan
    norm_fn, norm_eps = resolve_norm(cfg)
    kw = dict(norm_fn=norm_fn, norm_eps=norm_eps, use_bias=cfg.use_bias)
    lora_targets = () if lora_sig is None else tuple(lora_sig[0])
    half = attn_half if plan.attn is None \
        else functools.partial(plan.attn, cfg)
    n_full, n_window, n_state = _layers_by_kind(plan)
    kind_kw = _kind_kw(plan)
    B = plan.block
    if B is not None and (n_window or n_state or block_size % B):
        raise ValueError(
            f"a block of {B} positions a row needs every layer global and a "
            f"page ({block_size}) of whole blocks")

    def _slab_delta(slabs, slots, li):
        # the block's per-projection delta hook: each row's OWN adapter,
        # from one layer's slab slice (n_slots, d_in, rb) / (n_slots, rb,
        # d_out), added where a grafted tree's lora_delta is (and after
        # it), so a pooled tenant's arithmetic is the solo grafted one
        def delta(name, xin):
            if name not in lora_targets:
                return None
            sl = slabs[name]
            return segmented_lora_delta(
                xin, sl["a"][:, li], sl["b"][:, li], slots,
                row_parallel=name in ("wo", "w2"), tp_axis=tp_axis)
        return delta

    def _pool_attend(pool, kind, blk, off, pos, tables):
        """The paged pool's ``attend``: scatter each row's new K/V into its
        block slot of the layer's row in its kind's pool, then attend over
        that pool through the kind's block tables; the carry is the
        pool."""
        li = kind.index
        kn, vn = ("k", "v") if kind.window is None else ("wk", "wv")

        def attend(q, k, v):
            R, kv_loc, head_dim = q.shape[0], k.shape[2], q.shape[-1]
            quant = pool.k_scale is not None
            # quantizing first in quant mode, so attention reads the same
            # lossy values the dense _cache_write→_cache_read roundtrip
            # produces
            with jax.named_scope("paged/scatter_kv"):
                if quant:
                    kq, ks = _quantize_block(k)
                    vq, vs = _quantize_block(v)
                    new = pool._replace(
                        k=pool.k.at[li, blk, off].set(kq.reshape(R, -1)),
                        v=pool.v.at[li, blk, off].set(vq.reshape(R, -1)),
                        k_scale=pool.k_scale.at[li, blk, off].set(ks[:, 0]),
                        v_scale=pool.v_scale.at[li, blk, off].set(vs[:, 0]),
                    )
                else:
                    # (R, h·D) rows at (blk, off) (R,), or a block's (R, B,
                    # h·D) at (blk (R, 1), off (R, B))
                    kp, vp = getattr(pool, kn), getattr(pool, vn)
                    new = pool._replace(**{
                        kn: kp.at[li, blk, off].set(
                            k.reshape(off.shape + (-1,)).astype(kp.dtype)),
                        vn: vp.at[li, blk, off].set(
                            v.reshape(off.shape + (-1,)).astype(vp.dtype))})
            length = pos + (1 if B is None else B)     # new keys included
            kp, vp = getattr(new, kn), getattr(new, vn)
            if decode_uses_paged_attn(cfg, block_size, kv_loc, quant):
                # the pool is read where it lies: the WHOLE pool is the
                # kernel's operand (a pool.k[li] operand could become a
                # pool-sized copy per layer), the layer picked in its DMAs
                with jax.named_scope("paged/attention"):
                    o = paged_attention_decode(
                        q[:, 0] if B is None else q, kp, vp, tables, length,
                        li, first=None if kind.window is None
                        else jnp.maximum(length - kind.window, 0))
            elif kind.window is not None:
                with jax.named_scope("paged/attention"):
                    o = _window_attend_twin(q, kp, vp, li, tables, pos,
                                            kind.window, block_size)
            else:
                with jax.named_scope("paged/gather_kv"):
                    kk = _gather_view(
                        new.k[li], new.k_scale[li] if quant else None,
                        tables, length, q.dtype, head_dim)
                    vv = _gather_view(
                        new.v[li], new.v_scale[li] if quant else None,
                        tables, length, q.dtype, head_dim)
                with jax.named_scope("paged/attention"):
                    if B is None:
                        o, _ = attention_lse(q, kk, vv, pos, 0, causal=True)
                    else:
                        o = _block_attend_twin(q, kk, vv, length)
            return o, new
        return attend

    # the pool is DONATED: the caller always rebinds its state to the
    # returned pool, and without aliasing XLA would copy the entire
    # (L, NB, bs, h, D) pool every step to honor functional semantics —
    # measured ~45 ms/step of pure memcpy at serving sizes on CPU
    @functools.partial(jax.jit, donate_argnums=(1,))
    def step(params, pool, toks, pos, tables, slabs=None, slots=None):
        if B is None:
            x = _embed_in(plan, params, toks[:, None], pos[:, None],
                          cfg)                                 # (R, 1, d)
            at = lambda: pos[:, None]                          # noqa: E731
        else:
            where = pos[:, None] + jnp.arange(B)               # (R, B)
            with jax.named_scope("embed"):
                x = _embed(params, toks, where, cfg)           # (R, B, d)
            at = lambda: where                                 # noqa: E731
        if n_state and tables.ndim == 3:
            state_slots, tables = tables[:, 0, 0], tables[:, :, 1:]
        elif n_state:
            state_slots, tables = tables[:, 0], tables[:, 1:]
        # one table a kind: (R, W), or (R, 2, W) with a window kind
        kind_tables = (tables,) if tables.ndim == 2 \
            else (tables[:, 0], tables[:, 1])
        blks = [jnp.take_along_axis(
            t, (pos // block_size)[:, None], axis=1)[:, 0]
            for t in kind_tables]
        off = pos % block_size
        if B is not None:          # the block's B rows, inside one page
            blks = [b[:, None] for b in blks]
            off = off[:, None] + jnp.arange(B)
        moe = None if plan.ffn is None else jnp.zeros((4,), jnp.float32)
        for li, (p, kind) in enumerate(zip(params["blocks"], plan.kinds)):
            delta = None if slabs is None else _slab_delta(slabs, slots, li)
            line = 0 if kind.window is None else 1
            if kind.state:
                # (a fourth value: what the fed layers after it are given)
                x, s, conv, *fed = plan.recur(
                    cfg, x, p, pool.s, pool.conv, kind.index, state_slots,
                    None, norm_fn=norm_fn, norm_eps=norm_eps)
                pool = pool._replace(s=s, conv=conv)
            elif kind.fed:
                x = plan.fed(cfg, x, p, fed[0], norm_fn=norm_fn,
                             norm_eps=norm_eps)
            elif kind.reader:
                x, pool = half(
                    x, p, cfg.head_dim, at,
                    _reader_attend(cfg, block_size, pool, kind.index,
                                   kind_tables[0], pos + 1), tp_axis,
                    kind.rope, delta=delta, **kw, **kind_kw(kind))
            elif kind.hybrid:
                x, pool, s, conv = plan.mixer(
                    cfg, x, p, cfg.head_dim, at,
                    _pool_attend(pool, kind, blks[line], off, pos,
                                 kind_tables[line]), kind.rope,
                    pool.s, pool.conv, kind.index, state_slots, None,
                    norm_fn=norm_fn, norm_eps=norm_eps)
                pool = pool._replace(s=s, conv=conv)
            else:
                x, pool = half(
                    x, p, cfg.head_dim, at,
                    _pool_attend(pool, kind, blks[line], off, pos,
                                 kind_tables[line]), tp_axis,
                    kind.rope, delta=delta, **kw, **kind_kw(kind))
            x, aux = ffn_half(
                x, p, tp_axis, None if plan.ffn is None
                else functools.partial(plan.ffn, cfg, p), delta=delta, **kw)
            if aux is not None:
                moe = _fold_moe(moe, aux)
        if pool.stats is not None:
            # keys each live row's attention must read (a padded row sits at
            # position 0, where no request decodes), by layer kind
            # (a block's row may decode at position 0: it is live where its
            # table names a block, and reads its whole block)
            live = pos > 0 if B is None else kind_tables[0][:, 0] > 0
            new = 1 if B is None else B
            keys = [jnp.sum(jnp.where(live, pos + new if w is None else
                                      jnp.minimum(pos + new, w), 0)) * n
                    if n else 0.0
                    for w, n in ((None, n_full),
                                 (_window_of(plan), n_window))]
            rows = (jnp.sum(live) * n_state, 0.0) if n_state else ()
            if _cacheless_tail(plan):     # STATS_SAMBAY: a chunk's count
                rows += (0.0,)
            pool = pool._replace(stats=jnp.concatenate([moe, jnp.stack(
                [jnp.asarray(v, jnp.float32)
                 for v in (*keys, 0.0, 0.0, *rows)])]))
        logits = _logits(plan, params, x, norm_fn, norm_eps)
        return (logits[:, 0] if B is None else logits), pool

    # an executable a table width (less the slot column of a state pool):
    # the W of the step's serve.decode_dispatch / serve.device_step.* args
    return traced_program(
        "serve.decode", step,
        key=lambda params, pool, toks, pos, tables, *_:
        f"W={tables.shape[-1] - bool(n_state)}")


@functools.lru_cache(maxsize=256)
def make_paged_prefill_fn(cfg: GPTConfig, block_size: int, chunk_len: int,
                          tp_axis: Optional[str] = None,
                          with_readout: bool = True,
                          plan: Optional[StepPlan] = None):
    """Build the jitted per-request prefill/verify chunk.

    ``chunk(params, pool, tokens (1, C), pos0, table (W,)) ->
    (logits (1, C, vocab) f32, new pool)``: for each layer, gather the
    request's blocks of that layer into a dense ``(1, W * block_size,
    h, D)`` view (zero at and past ``pos0``; int8 + scales as a
    ``_QuantSlot`` in quant mode; callers bucket W), run the STOCK
    ``_block_step`` on it — the layer ``gpt_apply_cached`` and so a
    solo ``make_generate_fn`` prefill runs, between the same embedding
    and readout — and scatter the C rows it wrote at ``pos0`` into the
    pool with ``.at[li, blk, off].set``, the decode step's form. The
    pool is touched per layer and per block, never as a whole: a gather
    or scatter over all layers at once makes XLA convert the entire
    pool to another layout and back around it, six pool-sized passes a
    chunk (PERF.md §6, PR 31; tests/test_tpu_compile.py holds the
    compiled chunk to none). Like the decode step, the table may alias
    shared prefix pages below ``pos0`` — read via the gather only; the
    C written rows land at/after ``pos0`` in blocks the host made
    private first. Also the speculative verify forward: C proposed
    tokens in, per-position logits out, and only the committed prefix
    of the written rows is ever counted live (the fill level rewinds
    exactly like ``speculative.py``'s cache contract).
    ``plan`` as :func:`make_paged_decode_fn` takes it. With window layers
    ``table`` is ``(2, W)``; a global layer runs the path above over line 0,
    under the flash forward kernel's causal rule (GQA by index: the narrow
    k/v is read as it is); a window layer writes the chunk's rows into
    ``pool.wk``/``wv`` through line 1 and attends under
    ``flash_attention_window`` over the ``window - 1`` rows before the chunk
    (gathered through the table; those before position 0 are padding the
    mask never lets through) and the chunk's own: no view of the request's
    whole context is made for it. With recurrent layers ``table`` is ``(1 +
    W,)``, the request's slot of the state pool first: ``plan.recur`` runs
    the chunk from the slot's state — from zero at ``pos0 == 0``, whatever
    the slot's last owner left — and leaves there what the next chunk or
    the first decode step continues from. A hybrid layer runs the path above
    over its table line with ``plan.mixer`` as its first half, which is given
    the slot too. With ``plan.last_logits`` the readout is of the chunk's
    last position alone, ``(1, 1, vocab)``. With window AND recurrent layers
    ``table`` is ``(2, 1 + W)``, the slot at the head of line 0.
    **A plan whose last layers write no cache** (readers and fed layers:
    :func:`_cacheless_tail`) **does not run them on a chunk**: with
    ``with_readout=False`` the program ENDS after the last layer that writes
    one — nothing above it leaves anything a later chunk or decode step reads
    — and with ``with_readout=True`` those layers run on the chunk's LAST
    position alone (a fed layer on the last position of what the recurrent
    layer handed out, a reader with one query over the ``pos0 + C`` keys its
    layer has just written), which is all the readout takes.
    With ``plan.block`` the chunk attends block-causally (the flash forward
    with the block a constant of its mask; ``pos0`` and C whole blocks).
    ``with_readout=False`` skips the vocab projection (an intermediate
    prefill chunk's logits are never read — at real vocab sizes that
    projection is the biggest weight stream in the chunk) and returns
    ``(None, pool)``. lru-cached like :func:`make_paged_decode_fn`."""
    C = chunk_len
    plan = one_kind_plan(cfg) if plan is None else plan
    norm_fn, norm_eps = resolve_norm(cfg)
    half = None if plan.attn is None else functools.partial(plan.attn, cfg)
    n_full, n_window, n_state = _layers_by_kind(plan)
    kind_kw = _kind_kw(plan)
    n_tail = _cacheless_tail(plan)
    n_readers = sum(k.reader for k in plan.kinds)

    def _view(pool_a, li, table, keep, *tail):
        # this request's (1, W * bs, h[, D]) view of one layer, zero past
        # the fill level. ONE indexing expression: pool_a[li][table]
        # would materialise the whole layer slice before gathering
        g = pool_a[li, table].reshape((1, keep.shape[0], -1) + tail)
        return jnp.where(keep.reshape((1, -1) + (1,) * (g.ndim - 2)), g,
                         jnp.zeros((), g.dtype))

    def _put(pool_a, at, cache_a, pos0):
        # the C rows _block_step wrote at pos0, onto the pool's flat minor
        # axis and into their blocks: in place, as the decode step's is
        rows = jax.lax.dynamic_slice_in_dim(cache_a, pos0, C, axis=1)
        return pool_a.at[at].set(rows[0].reshape(C, -1))

    def _ffn(p):
        return None if plan.ffn is None \
            else functools.partial(plan.ffn, cfg, p)

    def _window_layer(x, p, pool, wi, pos0, table, blk, off, kind):
        """A window layer of the chunk: ``(x, pool, aux)``."""
        # keys laid out before the chunk: the window - 1 it can see, and as
        # many more (masked by the window) as make the key count whole tiles
        before = kind.window - 1
        if before >= 128:
            before = -(-before // 128) * 128

        def attend(q, k, v):
            with jax.named_scope("paged/scatter_kv"):
                new = pool._replace(
                    wk=pool.wk.at[wi, blk, off].set(
                        k[0].reshape(C, -1).astype(pool.wk.dtype)),
                    wv=pool.wv.at[wi, blk, off].set(
                        v[0].reshape(C, -1).astype(pool.wv.dtype)))
            with jax.named_scope("paged/gather_kv"):
                at = jnp.maximum(pos0 - before + jnp.arange(before), 0)
                pk = gather_rows(new.wk, wi, table, at, block_size)
                pv = gather_rows(new.wv, wi, table, at, block_size)
                kk = jnp.concatenate(
                    [pk.reshape((1, before) + k.shape[2:]).astype(k.dtype),
                     k], axis=1)
                vv = jnp.concatenate(
                    [pv.reshape((1, before) + v.shape[2:]).astype(v.dtype),
                     v], axis=1)
            with jax.named_scope("paged/window_attention"):
                return flash_attention_window(
                    q, kk, vv, pos0, pos0 - before, kind.window), new

        kw = dict(norm_fn=norm_fn, norm_eps=norm_eps, use_bias=cfg.use_bias)
        x, pool = (attn_half if half is None else half)(
            x, p, cfg.head_dim, lambda: pos0 + jnp.arange(C), attend, tp_axis,
            kind.rope, **kw, **kind_kw(kind))
        x, aux = ffn_half(x, p, tp_axis, _ffn(p), **kw)
        return x, pool, aux

    @jax.jit
    def _state_layer(x, p, pool, li, pos0, slot):
        """A recurrent layer of the chunk: ``(x, pool, aux)``, and after
        them what ``plan.recur`` hands to fed layers, where it does."""
        x, s, conv, *fed = plan.recur(
            cfg, x, p, pool.s, pool.conv, li, slot, pos0 == 0,
            norm_fn=norm_fn, norm_eps=norm_eps)
        x, aux = ffn_half(x, p, tp_axis, _ffn(p), norm_fn=norm_fn,
                          norm_eps=norm_eps, use_bias=cfg.use_bias)
        return (x, pool._replace(s=s, conv=conv), aux, *fed)

    # jitted, the layer index DATA: layers of one shape share one trace.
    # A replica traces and lowers a chunk program for every tail chunk x
    # table width x readout before it serves, compile cache or not, and
    # 36 traces of the block are most of a second of host time in each.
    # ``kind``, static, carries no index: a trace a kind
    @functools.partial(jax.jit, static_argnames="kind")
    def _layer(x, p, pool, li, pos0, table, keep, blk, off, kind, slot=None):
        if kind.window is not None:
            return _window_layer(x, p, pool, li, pos0, table, blk, off, kind)
        quant = pool.k_scale is not None
        with jax.named_scope("paged/gather_kv"):
            ck = _view(pool.k, li, table, keep, cfg.head_dim)
            cv = _view(pool.v, li, table, keep, cfg.head_dim)
            if quant:
                ck = _QuantSlot(ck, _view(pool.k_scale, li, table, keep))
                cv = _QuantSlot(cv, _view(pool.v_scale, li, table, keep))
        if kind.hybrid:
            # the layer's row of the state pool is its row of the k/v pool
            x, (ck, cv), s, conv = plan.mixer(
                cfg, x, p, cfg.head_dim, lambda: pos0 + jnp.arange(C),
                cache_attend(ck, cv, pos0), kind.rope, pool.s, pool.conv, li,
                slot, pos0 == 0, norm_fn=norm_fn, norm_eps=norm_eps)
            pool = pool._replace(s=s, conv=conv)
            x, aux = ffn_half(x, p, tp_axis, _ffn(p), norm_fn=norm_fn,
                              norm_eps=norm_eps, use_bias=cfg.use_bias)
        else:
            x, ck, cv, *aux = _block_step(
                x, p, ck, cv, pos0, cfg, tp_axis, None, norm_fn=norm_fn,
                norm_eps=norm_eps, rope=kind.rope, ffn=_ffn(p),
                attn=functools.partial(half, **kind_kw(kind))
                if plan.attn_takes_kind else half, block=plan.block)
            aux = aux[0] if aux else None
        with jax.named_scope("paged/scatter_kv"):
            at = (li, blk, off)
            if quant:
                pool = pool._replace(
                    k=_put(pool.k, at, ck.q, pos0),
                    v=_put(pool.v, at, cv.q, pos0),
                    k_scale=_put(pool.k_scale, at, ck.scale, pos0),
                    v_scale=_put(pool.v_scale, at, cv.scale, pos0))
            else:
                pool = pool._replace(k=_put(pool.k, at, ck, pos0),
                                     v=_put(pool.v, at, cv, pos0))
        return x, pool, aux

    # pool donated for the same reason as the decode step
    @functools.partial(jax.jit, donate_argnums=(1,))
    def chunk(params, pool, tokens, pos0, table):
        positions = pos0 + jnp.arange(C)
        if n_state and table.ndim == 2:
            slot, table = table[0, 0], table[:, 1:]
        elif n_state:
            slot, table = table[0], table[1:]
        # one table a kind: (W,), or (2, W) with a window kind
        kind_tables = (table,) if table.ndim == 1 else (table[0], table[1])
        blks = [jnp.take(t, positions // block_size) for t in kind_tables]
        off = positions % block_size
        keep = jnp.arange(table.shape[-1] * block_size) < pos0
        x = _embed_in(plan, params, tokens, positions, cfg)
        moe = None if plan.ffn is None else jnp.zeros((4,), jnp.float32)
        n_body = len(plan.kinds) - n_tail
        for p, kind in zip(params["blocks"][:n_body], plan.kinds[:n_body]):
            line = 0 if kind.window is None else 1
            if kind.state:
                x, pool, aux, *fed = _state_layer(
                    x, p, pool, jnp.int32(kind.index), pos0, slot)
            else:
                x, pool, aux = _layer(
                    x, p, pool, jnp.int32(kind.index), pos0,
                    kind_tables[line], keep, blks[line], off,
                    kind=kind._replace(index=0),
                    **({"slot": slot} if kind.hybrid else {}))
            if aux is not None:
                moe = _fold_moe(moe, aux)
        if pool.stats is not None:
            # visible (query, key) pairs of the chunk, by layer kind: query
            # t sees t + 1 keys, or the window where that is fewer
            # (under a block-causal mask: to the end of its own block)
            def reach():
                return positions + 1 if plan.block is None \
                    else (positions // plan.block + 1) * plan.block

            pairs = [jnp.sum(reach() if w is None
                             else jnp.minimum(reach(), w)) * n
                     if n else 0.0
                     for w, n in ((None, n_full),
                                  (_window_of(plan), n_window))]
            toks = (0.0, float(C * n_state)) if n_state else ()
            if n_tail:
                # the readers' one query each sees every key so far. In a
                # chunk that reads nothing out, the global layer right below
                # the tail writes its rows and its queries feed nothing: they
                # are not computed, and not counted. Then the positions the
                # tail ran over (STATS_SAMBAY)
                below = plan.kinds[n_body - 1]
                idle = not with_readout and below.window is None \
                    and not (below.state or below.hybrid)
                pairs[0] = jnp.sum(reach()) * (n_full - n_readers - idle) + (
                    (pos0 + C) * n_readers if with_readout else 0)
                toks += (float(with_readout),)
            pool = pool._replace(stats=jnp.concatenate([moe, jnp.stack(
                [jnp.asarray(v, jnp.float32)
                 for v in (0.0, 0.0, *pairs, *toks)])]))
        if n_tail and with_readout:
            # the layers that write no cache, on the last position alone
            kw = dict(norm_fn=norm_fn, norm_eps=norm_eps,
                      use_bias=cfg.use_bias)
            x, last = x[:, -1:], (pos0 + C)[None]
            mem = fed[0][:, -1:] if fed else None
            for p, kind in zip(params["blocks"][n_body:],
                               plan.kinds[n_body:]):
                if kind.fed:
                    x = plan.fed(cfg, x, p, mem, norm_fn=norm_fn,
                                 norm_eps=norm_eps)
                else:
                    x, pool = half(
                        x, p, cfg.head_dim, lambda: last[:, None] - 1,
                        _reader_attend(cfg, block_size, pool, kind.index,
                                       kind_tables[0][None], last), tp_axis,
                        kind.rope, **kw, **kind_kw(kind))
                x, aux = ffn_half(x, p, tp_axis, _ffn(p), **kw)
        logits = None
        if with_readout:
            logits = _logits(plan, params, x[:, -1:] if plan.last_logits
                             else x, norm_fn, norm_eps)
        return logits, pool

    # the C and W of the chunk's serve.prefill_dispatch args
    return traced_program(
        "serve.prefill", chunk,
        key=lambda params, pool, tokens, pos0, table:
        f"C={C},W={table.shape[-1] - bool(n_state)},"
        f"readout={int(with_readout)}")
