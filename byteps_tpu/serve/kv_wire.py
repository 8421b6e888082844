"""KV-block wire codec + migration transport for disaggregated serving.

The serve tier's disaggregation story (docs/serving.md §disaggregation)
is the BytePS thesis — "use every link" — applied to inference: prefill
and decode stop sharing a replica, and finished KV blocks STREAM from
the prefill replica to their decode target over the same wire machinery
the gradient tier built:

* **Codec** — :class:`KVBlockCodec` turns one physical KV block (every
  layer's k/v rows, plus the int8 ``_QuantSlot`` scales in quant mode)
  into self-describing wire bytes and back BYTE-IDENTICAL. There is no
  lossy re-encode: the int8 pool is already the compressed form (the
  ``_QuantSlot`` absmax codec), and the dense pool ships its dtype raw
  — so migration can never move a request's numerics (the serve tier's
  bit-exactness contract extends across the wire, pinned in
  tests/test_serve_disagg.py).
* **Transport** — :class:`KVWire` is one emulated outbound NIC per
  source replica: a two-stage
  :class:`~byteps_tpu.common.scheduler.PipelineScheduler` pipeline
  (KVCOMPRESS → KVPUSH) with wire-scoped PUSH credits, so block ``i``'s
  bytes ride the wire while block ``i+1`` encodes — and both overlap
  the source replica's NEXT prefill chunk, which runs on the caller's
  thread. Payload bytes are paced through a
  :class:`~byteps_tpu.server.pacer.DcnPacer` token bucket
  (``BYTEPS_SERVE_DISAGG_MBPS``), the PR 1 emulated-NIC philosophy:
  loopback behaves like the DCN tier migration would actually cross.
* **Self-healing** — the frame carries a CRC32 verified at decode
  (the PR 3 chaos-stack contract: corruption is detected, never
  adopted), KVPUSH is ``Stage.retryable``, and the push resolves its
  TARGET per attempt through a router-provided callback — a dead
  decode target is a stage-retryable REMAP (the router re-points the
  request at a live sibling), not a loss.

The same transport serves migrate-don't-evict preemption: a pressured
victim's committed blocks move to a sibling replica instead of being
freed and recomputed (serve/scheduler.py ``extract_for_migration`` →
router ``_migrate_out`` → sibling ``submit_migrated``).
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
import zlib
from typing import Any, Callable, Dict, List, NamedTuple, Optional

import numpy as np

from byteps_tpu.common.faults import (
    FaultPlan,
    InjectedConnectionError,
    InjectedTimeout,
)
from byteps_tpu.common.logging import get_logger
from byteps_tpu.common.metrics import get_registry
from byteps_tpu.common.partition import Partition
from byteps_tpu.common.scheduler import (
    Handle,
    PartitionTask,
    PipelineScheduler,
    Stage,
)
from byteps_tpu.server.pacer import DcnPacer

log = get_logger("serve.kv_wire")

_MAGIC = 0x4B564231  # "KVB1"
_FLAG_QUANT = 0x1

# global NIC sequence: one KVWire per source replica, and the registry
# in-flight gauge must be a per-wire series (the PR 6 pacer.p<N> rule)
_WIRE_SEQ = itertools.count()


class KVWireError(RuntimeError):
    """Malformed/incompatible KV wire frame — not retryable (re-sending
    the same bytes cannot fix a shape/config mismatch)."""

    retryable = False


class KVWireCorruption(RuntimeError):
    """CRC mismatch on a received KV block — the frame was damaged in
    flight. Retryable: the source re-sends from its pristine payload."""

    retryable = True


class DeadTargetError(ConnectionError):
    """The resolved decode target is dead/evicted. Retryable: the stage
    retry re-resolves the target, and the router's remap points the
    request at a live sibling."""

    retryable = True


class BlockPayload(NamedTuple):
    """One physical KV block's host-side contents, every layer at once.

    k/v: ``(n_layers, block_size, h_kv, head_dim)`` in the pool dtype
    (int8 in quant mode); k_scale/v_scale: ``(n_layers, block_size,
    h_kv)`` fp32 (quant mode only, else None). These are exactly the
    pool slices ``state.k[:, b]`` etc. — the codec round-trips them
    byte-identical.
    """

    k: np.ndarray
    v: np.ndarray
    k_scale: Optional[np.ndarray] = None
    v_scale: Optional[np.ndarray] = None


@dataclasses.dataclass
class MigrationTicket:
    """Everything a decode replica needs to CONTINUE a request whose KV
    lives (or is arriving) in its pool: the request, the committed
    tokens, the decode cursor, and latency provenance. Block contents
    travel separately (streamed over the :class:`KVWire`); ``payloads``
    carries only the blocks NOT yet streamed when the ticket was cut
    (the partial tail at prefill handoff; everything for a
    migrate-don't-evict extraction).

    ``full_input`` is the token CONTEXT backing cache rows
    ``[0, cache_len)`` (prompt + any resume/emitted tokens) — what the
    receiving pool's radix index matches and commits against, so prefix
    sharing survives migration."""

    req: Any                       # serve.scheduler.Request
    emitted: List[int]
    pending: Optional[int]
    cache_len: int
    full_input: np.ndarray
    n_blocks: int
    payloads: Dict[int, BlockPayload]
    t_origin: float = 0.0
    t_submit: float = 0.0
    t_first: Optional[float] = None
    tok_s: List[float] = dataclasses.field(default_factory=list)
    preemptions: int = 0
    spec_rounds: int = 0


class KVBlockCodec:
    """Encode/decode one KV block for the migration wire.

    Frame: ``[u32 magic][u32 flags][u32 body_len][u32 crc32]`` + body,
    body = k ‖ v (‖ k_scale ‖ v_scale in quant mode), raw array bytes
    in the pool's own dtype. Shapes/dtype are bound at construction
    (both ends of a wire must agree — validated loudly at decode), so
    the frame stays self-checking without shipping shape metadata per
    block. Round-trip is BYTE-identical by construction: the body is a
    view, never a cast.
    """

    def __init__(self, n_layers: int, block_size: int, h_kv: int,
                 head_dim: int, dtype, quant: bool):
        self.n_layers = int(n_layers)
        self.block_size = int(block_size)
        self.h_kv = int(h_kv)
        self.head_dim = int(head_dim)
        self.dtype = np.dtype(dtype)
        self.quant = bool(quant)
        self._kv_shape = (self.n_layers, self.block_size, self.h_kv,
                          self.head_dim)
        self._sc_shape = self._kv_shape[:-1]
        kv_bytes = int(np.prod(self._kv_shape)) * self.dtype.itemsize
        sc_bytes = (int(np.prod(self._sc_shape)) * 4 if self.quant else 0)
        self.body_bytes = 2 * kv_bytes + 2 * sc_bytes
        self._kv_bytes = kv_bytes
        self._sc_bytes = sc_bytes

    @classmethod
    def from_pool(cls, cache) -> "KVBlockCodec":
        """Codec matching a :class:`~byteps_tpu.serve.paged_cache.
        PagedKVCache`'s pool layout."""
        L, _, bs, _ = cache.state.k.shape     # minor axis: h_kv * head_dim
        return cls(L, bs, cache.kv_heads, cache.cfg.head_dim,
                   np.dtype(cache.state.k.dtype), cache.quant)

    @property
    def frame_bytes(self) -> int:
        return 16 + self.body_bytes

    def encode(self, p: BlockPayload) -> np.ndarray:
        """BlockPayload → uint8 wire frame (CRC32-stamped)."""
        parts = [np.ascontiguousarray(p.k).view(np.uint8).ravel(),
                 np.ascontiguousarray(p.v).view(np.uint8).ravel()]
        if self.quant:
            if p.k_scale is None or p.v_scale is None:
                raise KVWireError("quant codec needs k_scale/v_scale")
            parts.append(np.ascontiguousarray(
                p.k_scale, np.float32).view(np.uint8).ravel())
            parts.append(np.ascontiguousarray(
                p.v_scale, np.float32).view(np.uint8).ravel())
        body = np.concatenate(parts)
        if body.nbytes != self.body_bytes:
            raise KVWireError(
                f"payload is {body.nbytes} B, codec expects "
                f"{self.body_bytes} B — pool layout mismatch")
        out = np.empty(16 + body.nbytes, np.uint8)
        hdr = np.asarray(
            [_MAGIC, _FLAG_QUANT if self.quant else 0, body.nbytes,
             zlib.crc32(body.tobytes()) & 0xFFFFFFFF], np.uint32)
        out[:16] = hdr.view(np.uint8)
        out[16:] = body
        return out

    def decode(self, buf: np.ndarray) -> BlockPayload:
        """uint8 wire frame → BlockPayload (CRC-verified)."""
        buf = np.ascontiguousarray(buf, np.uint8)
        if buf.nbytes < 16:
            raise KVWireError(f"short KV frame ({buf.nbytes} B)")
        magic, flags, body_len, crc = (int(x) for x in
                                       buf[:16].view(np.uint32))
        if magic != _MAGIC:
            raise KVWireError(f"bad KV frame magic {magic:#x}")
        want_flags = _FLAG_QUANT if self.quant else 0
        if flags != want_flags or body_len != self.body_bytes:
            raise KVWireError(
                f"KV frame flags/len ({flags:#x}, {body_len}) do not "
                f"match this codec ({want_flags:#x}, {self.body_bytes}) "
                "— source and target pool layouts differ")
        body = buf[16:16 + body_len]
        if body.nbytes != body_len:
            raise KVWireError(
                f"truncated KV frame: {body.nbytes}/{body_len} body B")
        if (zlib.crc32(body.tobytes()) & 0xFFFFFFFF) != crc:
            raise KVWireCorruption(
                "KV block CRC mismatch — frame damaged in flight")
        kb, sb = self._kv_bytes, self._sc_bytes
        k = body[:kb].view(self.dtype).reshape(self._kv_shape).copy()
        v = body[kb:2 * kb].view(self.dtype).reshape(self._kv_shape).copy()
        if not self.quant:
            return BlockPayload(k, v)
        ks = body[2 * kb:2 * kb + sb].view(np.float32) \
            .reshape(self._sc_shape).copy()
        vs = body[2 * kb + sb:].view(np.float32) \
            .reshape(self._sc_shape).copy()
        return BlockPayload(k, v, ks, vs)


class KVWire:
    """One source replica's outbound migration NIC.

    ``send_block`` enqueues one block: KVCOMPRESS encodes the payload to
    CRC-stamped frame bytes on a pool thread, KVPUSH (credited,
    wire-scoped release, retryable) pays the token-bucket wire time and
    delivers into the CURRENT target's staging via
    ``Scheduler.ingest_block`` — the target is re-resolved through
    ``resolve(rid)`` on every attempt, so a stage retry after
    :class:`DeadTargetError` lands on whatever live sibling the router
    remapped the request to. Credits bound in-flight encoded frames
    (COMPRESS may run ahead of a throttled wire by at most ``credit``
    blocks), exactly the PR 1 COMPRESS→PUSH overlap discipline.

    An optional :class:`~byteps_tpu.common.faults.FaultPlan` intercepts
    each push attempt (op ``"push"``): ``corrupt`` flips a byte of a
    COPY of the frame (the CRC detects it, the retry re-sends pristine
    bytes), ``timeout`` delivers then loses the ack (the re-delivery is
    idempotent — staging is keyed by (rid, block)), ``kill``/``down``
    fail the attempt outright.
    """

    def __init__(self, codec: KVBlockCodec,
                 resolve: Callable[[Any], Any], *,
                 mbps: float = 0.0, credit: int = 4,
                 fault_plan: Optional[FaultPlan] = None,
                 max_attempts: int = 10):
        self.codec = codec
        self._resolve = resolve
        self._plan = fault_plan
        self._pacer = DcnPacer(mbps) if mbps and mbps > 0 else None
        self._key_seq = itertools.count()
        _reg = get_registry()
        self._m_blocks = _reg.counter("serve.migration.blocks")
        self._m_bytes = _reg.counter("serve.migration.bytes")
        self._g_inflight = _reg.gauge(
            f"serve.kvwire{next(_WIRE_SEQ)}.inflight_blocks")
        self._inflight = 0
        self._inflight_lock = threading.Lock()
        self._sched = PipelineScheduler(
            stages=[
                Stage(name="KVCOMPRESS", fn=self._compress, pool_size=2),
                Stage(name="KVPUSH", fn=self._push, credited=True,
                      releases_credit=True, retryable=True,
                      pool_size=2, max_attempts=max_attempts,
                      retry_backoff_s=0.02),
            ],
            credit=max(1, credit),
        )

    # -- stage bodies (pool threads) ----------------------------------------
    def _compress(self, task: PartitionTask) -> np.ndarray:
        return self.codec.encode(task.payload)

    def _push(self, task: PartitionTask) -> int:
        buf = task.payload
        rid = task.context["rid"]
        bi = task.context["block"]
        deliver = buf
        inj = self._plan.intercept("push", -1) if self._plan else None
        if inj is not None:
            if inj.kind in ("kill", "down"):
                raise InjectedConnectionError(
                    f"injected {inj.kind} on KV push {rid!r}.{bi}")
            if inj.kind == "corrupt":
                deliver = buf.copy()
                FaultPlan.corrupt(deliver, inj.corrupt_at)
        if self._pacer is not None:
            self._pacer.throttle_send(int(buf.nbytes))
        target = self._resolve(rid)
        if target is None or getattr(target, "dead", False):
            raise DeadTargetError(
                f"decode target for {rid!r} is dead/unassigned")
        # decode runs target-side inside this push (CRC verified before
        # anything is staged); KVWireCorruption is retryable and the
        # retry re-sends the pristine frame
        target.ingest_block(rid, bi, deliver)
        if inj is not None and inj.kind == "timeout":
            # delivered, ack lost: the retry's re-delivery overwrites
            # the identical staged payload (idempotent by key)
            raise InjectedTimeout(
                f"injected timeout on KV push {rid!r}.{bi}")
        self._m_blocks.inc()
        self._m_bytes.inc(int(buf.nbytes))
        self._note_inflight(-1)
        return int(buf.nbytes)

    def _note_inflight(self, d: int) -> None:
        with self._inflight_lock:
            self._inflight += d
            self._g_inflight.set(self._inflight)

    # -- client surface ------------------------------------------------------
    def send_block(self, rid, block_idx: int,
                   payload: BlockPayload) -> Handle:
        """Enqueue one block; the returned handle completes when the
        target staged it (or fails after the retry budget)."""
        key = next(self._key_seq)
        part = Partition(key=key, tensor_id=key, part_idx=int(block_idx),
                         offset=0, length=self.codec.body_bytes // 4,
                         priority=0)
        handle = Handle(f"kv.{rid}.{block_idx}", 1)
        task = PartitionTask(partition=part, name=f"kv.{rid}",
                             handle=handle, payload=payload,
                             context={"rid": rid, "block": int(block_idx)})
        self._note_inflight(1)
        self._sched.enqueue([task])
        return handle

    def abandon(self, n: int = 1) -> None:
        """Router bookkeeping: ``n`` permanently-failed sends left the
        wire (their blocks will be re-sent as fresh tasks)."""
        self._note_inflight(-n)

    def shutdown(self) -> None:
        self._sched.shutdown()
