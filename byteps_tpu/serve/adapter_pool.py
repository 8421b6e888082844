"""Paged LoRA adapter pool — ``paged_cache.py``'s memory model applied
to adapter *parameters* (S-LoRA's weight paging over this repo's
refcount/LRU machinery).

One base model, many tenants: each tenant's LoRA A/B weights live in a
fixed device-resident slot pool (``{target: {"a": (n_slots, L, d_in,
rank_bucket), "b": (n_slots, L, rank_bucket, d_out)}}`` float32), and
the packed decode step gathers each row's slabs by its *slot index*
(``ops/segmented_lora.py``) — N dedicated replicas collapse into one
replica with N-way weight sharing and full batch occupancy.

The allocator is deliberately the KV pool's design, re-applied:

* **Slot 0 is reserved** and all-zero forever: base-model rows and
  padded batch rows gather it and pick up an exactly-0.0 delta — no
  branches in the packed step.
* **Refcounted residency** — ``acquire`` pins an adapter for one
  holder (a request id); an adapter with live holders is NEVER evicted.
  ``release`` at refcount 0 keeps the adapter resident (cached-idle) so
  the next burst of its tenant's traffic pays no reload.
* **All-or-nothing** — a failed ``acquire`` changes nothing; when every
  slot is pinned by live adapters it raises
  :class:`~byteps_tpu.serve.paged_cache.PoolExhausted` with the
  adapter-pool occupancy breakdown (live vs cached-idle vs free,
  LEAKED if nonzero) — the KV breakdown's twin, and the scheduler's
  cue to defer the admission.
* **LRU eviction of idle adapters** — under slot pressure the
  least-recently-used cached-idle adapter loses its slot first; the
  host-side registry (the numpy slab copies ``register`` keeps) is the
  reload source, so eviction is always safe.
* **Ground-truth leak accounting** — ``leaked_slots()`` computes
  occupancy from the residency map itself, ``check_refcounts()`` pins
  the per-adapter refcounts against the holder sets (the
  ``test_serve_prefix.py`` randomized-schedule pattern, applied to
  params).
"""

from __future__ import annotations

import itertools
from typing import Any, Dict, List, Optional, Sequence, Set

import jax.numpy as jnp
import numpy as np

from byteps_tpu.common.metrics import get_registry
from byteps_tpu.models.gpt import GPTConfig
from byteps_tpu.models.lora import (
    _check_targets,
    _target_dims,
    lora_pool_slabs,
    lora_rank,
)
from byteps_tpu.serve.paged_cache import PoolExhausted

__all__ = ["AdapterPool"]

# global pool instance sequence for per-pool gauge series (the
# serve.pool<N> pattern — two replicas' adapter pools must not mask
# each other last-writer-wins)
_APOOL_SEQ = itertools.count()


class AdapterPool:
    """Device-resident LoRA slot pool + host-side adapter registry.

    ``n_slots`` counts the reserved zero slot 0; ``rank_bucket`` is the
    pool-wide padded rank (mixed-rank tenants share ONE compiled packed
    step — satellite of the lru-cache key contract in
    ``make_paged_decode_fn``); ``targets`` is the pool-wide target set
    every registered adapter must cover. Omitted sizing falls back to
    ``BYTEPS_SERVE_ADAPTER_SLOTS`` / ``BYTEPS_SERVE_ADAPTER_RANK_BUCKET``
    (the former defaults to 0 = multiplexing off, so an env-sized pool
    must be explicitly enabled).
    """

    def __init__(self, cfg: GPTConfig, *, n_slots: Optional[int] = None,
                 rank_bucket: Optional[int] = None,
                 targets: Sequence[str] = ("wq", "wv")):
        from byteps_tpu.common.config import get_config

        c = get_config()
        if n_slots is None:
            n_slots = c.serve_adapter_slots
        if rank_bucket is None:
            rank_bucket = c.serve_adapter_rank_bucket
        if n_slots < 2:
            raise ValueError(
                f"n_slots ({n_slots}) must hold the reserved zero slot "
                "plus at least one loadable slot")
        if rank_bucket < 1:
            raise ValueError(
                f"rank_bucket must be >= 1; got {rank_bucket}")
        self.cfg = cfg
        self.n_slots = n_slots
        self.rank_bucket = rank_bucket
        self.targets = _check_targets(cfg, targets)
        L = cfg.n_layers
        self.slabs: Dict[str, Dict[str, jnp.ndarray]] = {}
        for t in self.targets:
            d_in, d_out = _target_dims(cfg, t)
            self.slabs[t] = {
                "a": jnp.zeros((n_slots, L, d_in, rank_bucket),
                               jnp.float32),
                "b": jnp.zeros((n_slots, L, rank_bucket, d_out),
                               jnp.float32),
            }
        # host-side registry: the reload source (numpy slab copies) +
        # the raw adapter tree/scale for per-request grafted prefill
        self._registry: Dict[Any, Dict[str, Any]] = {}
        self._graft_cache: Dict[Any, Any] = {}
        # LIFO free list over slots 1..n_slots-1 (0 = zero, reserved)
        self._free: List[int] = list(range(n_slots - 1, 0, -1))
        self._slot: Dict[Any, int] = {}      # resident adapter -> slot
        self._ref: Dict[Any, int] = {}       # resident adapter -> pins
        self._holders: Dict[Any, Set[Any]] = {}   # ground truth for _ref
        self._lru_tick = 0
        self._last_used: Dict[Any, int] = {}
        _reg = get_registry()
        seq = next(_APOOL_SEQ)
        self._g_live = _reg.gauge(f"serve.apool{seq}.live_adapters")
        self._g_cached = _reg.gauge(f"serve.apool{seq}.cached_adapters")
        self._c_loads = _reg.counter("serve.adapter_loads")
        self._c_evict = _reg.counter("serve.adapter_evictions")
        self._c_fail = _reg.counter("serve.adapter_alloc_failures")

    # -- registry ------------------------------------------------------------
    def register(self, adapter_id, adapters: Dict[str, Any],
                 scale: float = 1.0) -> None:
        """Admit an adapter to the host registry (NOT the device pool —
        residency is demand-paged by :meth:`acquire`/:meth:`prefetch`).
        Validates rank against the pool bucket and target coverage up
        front, so a bad adapter fails here instead of at first use."""
        if adapter_id in self._registry:
            raise ValueError(f"adapter {adapter_id!r} already registered")
        slabs = lora_pool_slabs(adapters, self.cfg, self.rank_bucket,
                                scale, self.targets)
        host = {t: {"a": np.asarray(ts["a"]), "b": np.asarray(ts["b"])}
                for t, ts in slabs.items()}
        self._registry[adapter_id] = {
            "slabs": host,
            "rank": lora_rank(adapters),
            "adapters": adapters,
            "scale": scale,
        }

    def unregister(self, adapter_id) -> None:
        """Drop an adapter from the registry (and its slot, when
        cached-idle). Refuses while the adapter has live holders."""
        if self._ref.get(adapter_id, 0) > 0:
            raise ValueError(
                f"adapter {adapter_id!r} has {self._ref[adapter_id]} live "
                "holder(s) — release them before unregistering")
        if adapter_id in self._slot:
            self._evict(adapter_id)
        del self._registry[adapter_id]
        self._graft_cache.pop(adapter_id, None)

    def registered(self, adapter_id) -> bool:
        return adapter_id in self._registry

    def rank_of(self, adapter_id) -> int:
        return self._registry[adapter_id]["rank"]

    def graft(self, base_params, adapter_id):
        """The adapter's solo grafted tree (base + scaled A/B under the
        ``"lora"`` key) built from the pool's CANONICAL form — the
        rank-bucket-padded, scale-folded slabs — not the raw registered
        tree. Zero-padding is mathematically inert (the extra rank
        columns contribute exact 0.0) but it widens the thin GEMMs, and
        XLA's accumulation order is width-dependent, so a width-r graft
        and the width-bucket pool can disagree by 1 ulp on some inputs.
        Grafting the padded slabs pins ONE width everywhere: prefill
        chunks (this tree), packed decode (the device slabs), and the
        solo ``make_generate_fn`` exactness baseline all run identical
        arithmetic — the BIT-identical contract the tests enforce.
        Cached per adapter and base (the tree shares every base leaf by
        reference; only the thin adapter leaves are new, and a graft onto
        another base — the scheduler's prepared operands, a test's own
        tree — reuses them)."""
        hit = self._graft_cache.get(adapter_id)
        if hit is not None and hit[0] is base_params:
            return hit[1]
        if hit is not None:
            loras = [blk["lora"] for blk in hit[1]["blocks"]]
        else:
            host = self._registry[adapter_id]["slabs"]
            # slabs already carry b * scale (lora_pool_slabs), so
            # the graft folds scale=1 — graft_lora's output format
            loras = [{t: {"a": jnp.asarray(host[t]["a"][li]),
                          "b": jnp.asarray(host[t]["b"][li])}
                      for t in self.targets}
                     for li in range(len(base_params["blocks"]))]
        p = dict(base_params)
        p["blocks"] = [{**bp, "lora": lora}
                       for bp, lora in zip(base_params["blocks"], loras)]
        self._graft_cache[adapter_id] = (base_params, p)
        return p

    # -- accounting ----------------------------------------------------------
    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def live_adapters(self) -> int:
        return sum(1 for r in self._ref.values() if r > 0)

    @property
    def cached_adapters(self) -> int:
        return sum(1 for r in self._ref.values() if r == 0)

    def leaked_slots(self) -> int:
        """Slots neither free nor occupied by a resident adapter — must
        be 0 at drain, computed from the residency map itself (not the
        refcounts) so the pin stays truthful against bookkeeping
        drift."""
        return (self.n_slots - 1) - len(self._free) \
            - len(set(self._slot.values()))

    def check_refcounts(self) -> None:
        """Debug/test invariant: per-adapter refcounts must equal the
        holder-set ground truth; the slot map and free list must
        partition the allocatable slots. Raises AssertionError on
        drift."""
        for aid, r in self._ref.items():
            assert r == len(self._holders.get(aid, ())), (
                f"refcount drift for adapter {aid!r}: "
                f"{r} != {len(self._holders.get(aid, ()))}")
            assert r >= 0
        assert set(self._ref) == set(self._slot), (
            "resident map / refcount map diverged")
        slots = list(self._slot.values())
        assert len(slots) == len(set(slots)), "two adapters share a slot"
        assert not (set(slots) & set(self._free)), (
            "free list overlaps resident slots")
        assert 0 not in slots and 0 not in self._free, (
            "reserved zero slot was allocated")
        assert self.leaked_slots() == 0, (
            f"{self.leaked_slots()} leaked adapter slot(s)")

    def _exhausted_msg(self, adapter_id) -> str:
        """Adapter-pool occupancy breakdown — the KV pool's
        ``_exhausted_msg`` twin, so a slot-pressure post-mortem is
        diagnosable straight off the flight recorder."""
        leaked = self.leaked_slots()
        return (
            f"adapter {adapter_id!r} needs a slot, pool has "
            f"{len(self._free)} free — occupancy: "
            f"{self.n_slots - 1} allocatable = "
            f"{self.live_adapters} live adapter(s) + "
            f"{self.cached_adapters} cached-idle + "
            f"{len(self._free)} free"
            + (f" + {leaked} LEAKED" if leaked else ""))

    # -- residency -----------------------------------------------------------
    def _touch(self, adapter_id) -> None:
        self._lru_tick += 1
        self._last_used[adapter_id] = self._lru_tick

    def _load(self, adapter_id, slot: int) -> None:
        host = self._registry[adapter_id]["slabs"]
        for t in self.targets:
            ts = self.slabs[t]
            self.slabs[t] = {
                "a": ts["a"].at[slot].set(jnp.asarray(host[t]["a"])),
                "b": ts["b"].at[slot].set(jnp.asarray(host[t]["b"])),
            }
        self._c_loads.inc()

    def _evict(self, adapter_id) -> None:
        """Drop a cached-idle adapter's slot (LRU pressure, explicit
        evict, unregister). The slot's device rows go stale rather than
        zeroed — no live row can gather a freed slot, exactly like the
        KV pool's recycled blocks."""
        assert self._ref.get(adapter_id, 0) == 0
        self._free.append(self._slot.pop(adapter_id))
        del self._ref[adapter_id]
        self._holders.pop(adapter_id, None)
        self._last_used.pop(adapter_id, None)
        self._c_evict.inc()

    def _alloc_slot(self, adapter_id) -> int:
        if not self._free:
            idle = sorted(
                (aid for aid, r in self._ref.items() if r == 0),
                key=lambda aid: self._last_used.get(aid, 0))
            if idle:
                self._evict(idle[0])
        if not self._free:
            self._c_fail.inc()
            raise PoolExhausted(self._exhausted_msg(adapter_id))
        return self._free.pop()

    def acquire(self, adapter_id, holder) -> int:
        """Pin ``adapter_id`` for ``holder`` (a request id), loading it
        into a slot if it isn't resident (prefetch-on-admission: the
        scheduler acquires at admission, so the slabs are on device
        before the first packed decode touch). Returns the slot index.
        All-or-nothing: on :class:`PoolExhausted` nothing changed."""
        if adapter_id not in self._registry:
            raise KeyError(f"adapter {adapter_id!r} is not registered")
        holders = self._holders.setdefault(adapter_id, set())
        if holder in holders:
            raise ValueError(
                f"holder {holder!r} already pinned adapter "
                f"{adapter_id!r}")
        if adapter_id not in self._slot:
            slot = self._alloc_slot(adapter_id)   # may raise; no state yet
            self._slot[adapter_id] = slot
            self._ref[adapter_id] = 0
            self._load(adapter_id, slot)
        holders.add(holder)
        self._ref[adapter_id] += 1
        self._touch(adapter_id)
        self._update_gauges()
        return self._slot[adapter_id]

    def release(self, adapter_id, holder) -> None:
        """Unpin one holder. At refcount 0 the adapter STAYS resident
        (cached-idle, LRU-evictable) — the param twin of the KV pool's
        cached-but-idle prefix pages."""
        holders = self._holders.get(adapter_id)
        if not holders or holder not in holders:
            raise ValueError(
                f"holder {holder!r} does not pin adapter {adapter_id!r}")
        holders.remove(holder)
        self._ref[adapter_id] -= 1
        if self._ref[adapter_id] < 0:
            raise RuntimeError(
                f"refcount underflow on adapter {adapter_id!r}")
        self._update_gauges()

    def prefetch(self, adapter_id) -> bool:
        """Best-effort residency warm-up: load into a FREE slot only
        (never evicts — prefetch must not fight live traffic for
        slots). Returns True when the adapter is resident after the
        call."""
        if adapter_id not in self._registry:
            raise KeyError(f"adapter {adapter_id!r} is not registered")
        if adapter_id in self._slot:
            self._touch(adapter_id)
            return True
        if not self._free:
            return False
        slot = self._free.pop()
        self._slot[adapter_id] = slot
        self._ref[adapter_id] = 0
        self._load(adapter_id, slot)
        self._touch(adapter_id)
        self._update_gauges()
        return True

    def evict_idle(self, adapter_id) -> None:
        """Explicitly drop a cached-idle adapter's slot (tests, tenant
        offboarding). Refuses for live adapters — an adapter with
        running requests is NEVER evicted."""
        if adapter_id not in self._slot:
            raise KeyError(f"adapter {adapter_id!r} is not resident")
        if self._ref[adapter_id] > 0:
            raise ValueError(
                f"adapter {adapter_id!r} has {self._ref[adapter_id]} live "
                "holder(s) — live adapters are never evicted")
        self._evict(adapter_id)
        self._update_gauges()

    def slot_of(self, adapter_id) -> int:
        """The resident slot index (the packed step's per-row gather
        key). KeyError when not resident — callers acquire first."""
        return self._slot[adapter_id]

    def resident(self, adapter_id) -> bool:
        return adapter_id in self._slot

    def _update_gauges(self) -> None:
        self._g_live.set(self.live_adapters)
        self._g_cached.set(self.cached_adapters)
