"""ctypes binding for the native DCN summation service.

Runs ``make`` on first use in each process, which (re)builds
``libbyteps_tpu_server.so`` whenever it is missing or older than a source
(``make`` + ``g++`` are part of the supported toolchain; no pybind11 in
this image, so the boundary is a C API + ctypes, reference analog: the
ctypes-free ``byteps/server/__init__.py`` loading the prebuilt native
lib).
"""

from __future__ import annotations

import ctypes
import fcntl
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

from byteps_tpu.common.logging import get_logger

log = get_logger("server.native")

_CSRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "csrc")
_SO = os.path.join(_CSRC, "libbyteps_tpu_server.so")

_lib = None
_lib_lock = threading.Lock()

# Wire codec ids — must match csrc/codec.h Codec enum.
WIRE_RAW = 0
WIRE_FP16 = 1
WIRE_ONEBIT = 2
WIRE_TOPK = 3
WIRE_DITHER = 4
WIRE_FP8 = 5


class WireCorruption(RuntimeError):
    """A CRC32-checked payload arrived corrupted (push rejected server-side
    or pull response failing the worker-side verify). Always retryable:
    the data was detected bad, never summed or consumed."""


class WorkerEvictedError(RuntimeError):
    """The server's membership layer evicted this worker's lease (it went
    silent past BYTEPS_WORKER_LEASE_MS) and rejected the op. NOT a wire
    retry candidate — re-sending the same round cannot help while the
    server refuses the worker. The PSWorker rejoins (heartbeat re-admit +
    kRounds watermark adoption) and raises this stage-retryably: the
    stage re-run drops its pinned round and mints a fresh one under the
    adopted epoch."""

    retryable = True  # stage-level, after the in-line rejoin


def _build() -> None:
    """Bring the library up to date with the sources beside it. ``make``
    decides: it rebuilds when any source or header is newer than the
    ``.so`` and is a no-op when fresh — so a tree copied with a library
    built at some other commit cannot be loaded stale. The lock (on the
    Makefile, so no extra file) keeps concurrent loaders — tests and
    benches spawn server processes — from interleaving compiles."""
    with open(os.path.join(_CSRC, "Makefile")) as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        proc = subprocess.run(
            ["make", "-C", _CSRC, "-j4"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
    if proc.returncode != 0:
        raise RuntimeError(
            f"building the native server library failed (make exit "
            f"{proc.returncode}):\n{proc.stdout[-4000:]}")


def load_lib() -> ctypes.CDLL:
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        _build()
        try:
            lib = ctypes.CDLL(_SO)
        except OSError:
            # wheel built on another platform shipped a foreign .so —
            # rebuild from the packaged sources for THIS machine
            log.warning("packaged native library unloadable; rebuilding")
            os.remove(_SO)
            _build()
            lib = ctypes.CDLL(_SO)
        lib.bps_server_start.argtypes = [
            ctypes.c_uint16, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int,
        ]
        lib.bps_server_start.restype = ctypes.c_int
        lib.bps_server_wait.argtypes = []
        lib.bps_server_stop.argtypes = []
        lib.bps_server_trace_enable.argtypes = [ctypes.c_int]
        lib.bps_fp8_to_float.argtypes = [ctypes.c_uint8]
        lib.bps_fp8_to_float.restype = ctypes.c_float
        lib.bps_float_to_fp8.argtypes = [ctypes.c_float]
        lib.bps_float_to_fp8.restype = ctypes.c_uint8
        lib.bps_server_trace_dump.argtypes = [ctypes.c_char_p]
        lib.bps_server_trace_dump.restype = ctypes.c_int
        # what-if simulator codec calibration (sim/extract.py): the
        # server's REAL decode_sum / re-encode loops, priced offline
        lib.bps_codec_decode_sum.argtypes = [
            ctypes.c_uint8, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int64,
        ]
        lib.bps_codec_decode_sum.restype = ctypes.c_int64
        lib.bps_codec_encode.argtypes = [
            ctypes.c_uint8, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_uint32, ctypes.c_uint64, ctypes.c_void_p,
            ctypes.c_int64,
        ]
        lib.bps_codec_encode.restype = ctypes.c_int64
        lib.bps_server_epoch.argtypes = []
        lib.bps_server_epoch.restype = ctypes.c_uint64
        lib.bps_server_members.argtypes = [
            ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint32),
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_uint32,
        ]
        lib.bps_server_members.restype = ctypes.c_int
        lib.bps_server_join.argtypes = [ctypes.c_int]
        lib.bps_server_join.restype = ctypes.c_int64
        lib.bps_local_init.argtypes = [ctypes.c_uint64, ctypes.c_uint64]
        lib.bps_local_init.restype = ctypes.c_int
        lib.bps_local_push.argtypes = [
            ctypes.c_uint16, ctypes.c_uint64, ctypes.c_uint8,
            ctypes.c_void_p, ctypes.c_uint64,
        ]
        lib.bps_local_push.restype = ctypes.c_int
        lib.bps_local_push2.argtypes = [
            ctypes.c_uint16, ctypes.c_uint64, ctypes.c_uint8,
            ctypes.c_uint64, ctypes.c_void_p, ctypes.c_uint64,
        ]
        lib.bps_local_push2.restype = ctypes.c_int
        lib.bps_local_pull.argtypes = [
            ctypes.c_uint64, ctypes.c_uint8, ctypes.c_uint64, ctypes.c_int,
            ctypes.c_void_p, ctypes.c_uint64,
        ]
        lib.bps_local_pull.restype = ctypes.c_int64
        lib.bps_local_pull2.argtypes = [
            ctypes.c_uint64, ctypes.c_uint8, ctypes.c_uint64, ctypes.c_int,
            ctypes.c_void_p, ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.bps_local_pull2.restype = ctypes.c_int64
        lib.bps_local_pull3.argtypes = [
            ctypes.c_uint64, ctypes.c_uint8, ctypes.c_uint64, ctypes.c_int,
            ctypes.c_void_p, ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.bps_local_pull3.restype = ctypes.c_int64
        lib.bps_client_connect.argtypes = [
            ctypes.c_char_p, ctypes.c_uint16, ctypes.c_int, ctypes.c_int,
        ]
        lib.bps_client_connect.restype = ctypes.c_void_p
        lib.bps_client_init_key.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint64,
        ]
        lib.bps_client_init_key.restype = ctypes.c_int
        lib.bps_client_push.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p,
            ctypes.c_uint64, ctypes.c_uint8, ctypes.c_uint16,
        ]
        lib.bps_client_push.restype = ctypes.c_int
        lib.bps_client_push2.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p,
            ctypes.c_uint64, ctypes.c_uint8, ctypes.c_uint16,
            ctypes.c_uint64, ctypes.c_uint32,
        ]
        lib.bps_client_push2.restype = ctypes.c_int
        lib.bps_client_pull.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p,
            ctypes.c_uint64, ctypes.c_uint64, ctypes.c_uint8,
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.bps_client_pull.restype = ctypes.c_int
        lib.bps_client_pull2.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p,
            ctypes.c_uint64, ctypes.c_uint64, ctypes.c_uint8,
            ctypes.c_int, ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint32), ctypes.c_int,
            ctypes.POINTER(ctypes.c_uint32),
        ]
        lib.bps_client_pull2.restype = ctypes.c_int
        lib.bps_client_pull3.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p,
            ctypes.c_uint64, ctypes.c_uint64, ctypes.c_uint8,
            ctypes.c_int, ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint32), ctypes.c_int,
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.bps_client_pull3.restype = ctypes.c_int
        lib.bps_client_barrier.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.bps_client_barrier.restype = ctypes.c_int
        lib.bps_client_shutdown.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.bps_client_shutdown.restype = ctypes.c_int
        lib.bps_client_ping.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
        ]
        lib.bps_client_ping.restype = ctypes.c_int
        lib.bps_client_epoch.argtypes = [ctypes.c_void_p]
        lib.bps_client_epoch.restype = ctypes.c_int
        lib.bps_client_members.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint32), ctypes.POINTER(ctypes.c_uint32),
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_uint32,
        ]
        lib.bps_client_members.restype = ctypes.c_int
        lib.bps_client_rounds.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.bps_client_rounds.restype = ctypes.c_int
        lib.bps_client_join.argtypes = [
            ctypes.c_void_p, ctypes.c_int,
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.bps_client_join.restype = ctypes.c_int
        lib.bps_client_last_error.argtypes = [ctypes.c_void_p]
        lib.bps_client_last_error.restype = ctypes.c_char_p
        lib.bps_client_is_dead.argtypes = [ctypes.c_void_p]
        lib.bps_client_is_dead.restype = ctypes.c_int
        lib.bps_client_free.argtypes = [ctypes.c_void_p]
        lib.bps_reduce_sum_f32.argtypes = [
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
            ctypes.c_int64,
        ]
        _lib = lib
        return lib


def reduce_sum_f32(dst: np.ndarray, src: np.ndarray) -> None:
    """dst += src via the native kernel (golden-testable)."""
    lib = load_lib()
    assert dst.dtype == np.float32 and src.dtype == np.float32
    assert dst.flags.c_contiguous and src.flags.c_contiguous
    lib.bps_reduce_sum_f32(
        dst.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        src.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        dst.size,
    )


class NativeClient:
    """One serial TCP connection to one summation server.

    Reference analog: a ps-lite customer. Thread-safety: the native side
    serializes per connection; use one NativeClient per scheduler pool
    thread for parallelism.
    """

    def __init__(self, host: str, port: int, timeout_ms: int = 30000,
                 recv_timeout_ms: int = 120000):
        self._lib = load_lib()
        # serializes teardown (close/shutdown): an eviction on one thread
        # can race PSWorker.shutdown() on another, and bps_client_free
        # must run at most once (double delete = heap corruption)
        self._teardown_lock = threading.Lock()
        # held across every native wire op so close() cannot free the
        # handle UNDER an in-flight call (use-after-free; observed as a
        # teardown segfault when a scheduler shutdown raced a blocked
        # pull). Uncontended in normal operation — the class contract is
        # one client per pool thread — so the only time it waits is
        # close() draining a straggler, bounded by the recv timeout.
        self._op_lock = threading.Lock()
        self._last_pull_epoch = 0
        self._last_pull_round = 0
        self._h: Optional[int] = self._lib.bps_client_connect(
            host.encode(), port, timeout_ms, recv_timeout_ms
        )
        if not self._h:
            raise ConnectionError(f"cannot reach bps server {host}:{port}")

    def init_key(self, key: int, nbytes: int) -> None:
        with self._op_lock:
            self._require_open()
            self._check(self._lib.bps_client_init_key(self._h, key, nbytes),
                        "init")

    def push(self, key: int, data, codec: int = WIRE_RAW,
             worker_id: int = 0, version: int = 0, crc: int = 0) -> None:
        """Push codec-encoded bytes (np array of any contiguous dtype).
        ``version`` != 0 arms the server's (worker, key, version) replay
        dedupe; ``crc`` != 0 (the wire convention of
        :func:`~byteps_tpu.server.wire_crc32`) is verified server-side
        before the payload is summed."""
        buf = np.ascontiguousarray(data)
        with self._op_lock:
            self._require_open()
            self._check(
                self._lib.bps_client_push2(
                    self._h, key, buf.ctypes.data, buf.nbytes, codec,
                    worker_id, version, crc,
                ),
                "push",
            )

    def pull(self, key: int, out: np.ndarray, version: int,
             codec: int = WIRE_RAW, want_crc: bool = False,
             worker_id: int = -1) -> int:
        """Pull into `out` (capacity buffer); returns actual bytes (or
        ``(bytes, crc)`` when ``want_crc`` — the caller verifies, so the
        fault-injection layer can corrupt the buffer in between).
        ``worker_id`` >= 0 refreshes that worker's membership lease
        server-side (a worker blocked in a long pull is still alive).
        The epoch the pulled ROUND closed under is retained on this
        client (:meth:`last_pull_epoch`) — the averaging divisor
        authority under elastic membership — and so is the SERVED round
        (:meth:`last_pull_round`): under bounded staleness
        (``BYTEPS_STALENESS``) the server answers from the newest closed
        round >= requested − K, and requested − served is this pull's
        effective staleness."""
        assert out.flags.c_contiguous
        with self._op_lock:
            self._require_open()
            got = ctypes.c_uint64(0)
            crc = ctypes.c_uint32(0)
            ep = ctypes.c_uint32(0)
            served = ctypes.c_uint64(0)
            self._check(
                self._lib.bps_client_pull3(
                    self._h, key, out.ctypes.data, out.nbytes, version,
                    codec, 1 if want_crc else 0, ctypes.byref(got),
                    ctypes.byref(crc), worker_id, ctypes.byref(ep),
                    ctypes.byref(served),
                ),
                "pull",
            )
            self._last_pull_epoch = int(ep.value)
            self._last_pull_round = int(served.value)
            if want_crc:
                return int(got.value), int(crc.value)
            return int(got.value)

    def last_pull_epoch(self) -> int:
        """Membership epoch (low 16 bits) the most recently pulled round
        CLOSED under — see :meth:`pull`."""
        return self._last_pull_epoch

    def last_pull_round(self) -> int:
        """The round the most recent :meth:`pull` was actually SERVED
        from (response header version) — under bounded staleness it may
        trail the requested round by up to ``BYTEPS_STALENESS``."""
        return self._last_pull_round

    def barrier(self, worker_id: int = -1) -> None:
        """``worker_id`` >= 0 also refreshes that worker's membership
        lease server-side (barrier waits can outlast a short lease)."""
        with self._op_lock:
            self._require_open()
            self._check(self._lib.bps_client_barrier(self._h, worker_id),
                        "barrier")

    def ping(self, worker_id: int = -1) -> Tuple[int, int]:
        """(server CLOCK_REALTIME ns, round-trip ns) — clock alignment.
        ``worker_id`` >= 0 makes the probe that worker's membership lease
        HEARTBEAT (and the rejoin signal when it was evicted)."""
        with self._op_lock:
            self._require_open()
            sns = ctypes.c_int64(0)
            rtt = ctypes.c_int64(0)
            self._check(
                self._lib.bps_client_ping(
                    self._h, ctypes.byref(sns), ctypes.byref(rtt),
                    worker_id,
                ),
                "ping",
            )
            return int(sns.value), int(rtt.value)

    def epoch(self) -> int:
        """Membership epoch (low 16 bits) stamped on the last response
        this connection parsed — cheap per-op change detection; query
        :meth:`members` for the full live set on a change."""
        with self._op_lock:
            if not self._h:
                return 0
            return int(self._lib.bps_client_epoch(self._h))

    def members(self) -> Tuple[int, int, "np.ndarray"]:
        """(epoch, live_count, live bitmap[num_workers]) from the server's
        membership layer."""
        with self._op_lock:
            self._require_open()
            ep = ctypes.c_uint64(0)
            live = ctypes.c_uint32(0)
            nw = ctypes.c_uint32(0)
            bitmap = (ctypes.c_uint8 * 1024)()
            self._check(
                self._lib.bps_client_members(
                    self._h, ctypes.byref(ep), ctypes.byref(live),
                    ctypes.byref(nw), bitmap, 1024,
                ),
                "members",
            )
            n = min(int(nw.value), 1024)
            return (int(ep.value), int(live.value),
                    np.frombuffer(bytes(bitmap[:n]), np.uint8).copy())

    def join(self, worker_id: int) -> int:
        """Mid-stream worker ADMISSION (kJoin; scale-up elasticity):
        admit ``worker_id`` — a fresh id (the server GROWS its
        membership table and per-key vectors) or a previously
        evicted/departed one — at a round boundary. Returns the
        post-admission membership epoch. The caller must adopt round
        watermarks (:meth:`rounds`) before its first push."""
        with self._op_lock:
            self._require_open()
            ep = ctypes.c_uint64(0)
            self._check(
                self._lib.bps_client_join(self._h, worker_id,
                                          ctypes.byref(ep)),
                "join",
            )
            return int(ep.value)

    def rounds(self) -> "np.ndarray":
        """Per-key round watermarks as an (n, 3) uint64 array of
        (key, round, nbytes) — the rejoin adoption handshake."""
        with self._op_lock:
            self._require_open()
            cap = 1 << 20  # 43k keys per fetch; far above real key counts
            out = np.empty(cap, np.uint8)
            got = ctypes.c_uint64(0)
            self._check(
                self._lib.bps_client_rounds(
                    self._h, out.ctypes.data, out.nbytes, ctypes.byref(got),
                ),
                "rounds",
            )
            n = int(got.value) // 24
            return out[: n * 24].view(np.uint64).reshape(n, 3).copy()

    def is_dead(self) -> bool:
        """True once a timeout/desync closed the underlying socket (or the
        client itself was closed); the owner should discard this client
        and connect a fresh one. Holds the op lock like every other
        native call — close() frees the handle under it, and a retiring
        NIC closes clients owned by other pool threads."""
        with self._op_lock:
            if not self._h:
                return True
            return bool(self._lib.bps_client_is_dead(self._h))

    def shutdown(self, worker_id: int = -1) -> None:
        """``worker_id`` >= 0 marks the worker DEPARTED in the server's
        membership layer (a clean goodbye, distinct from an eviction)."""
        with self._op_lock:
            with self._teardown_lock:
                if self._h:
                    self._lib.bps_client_shutdown(self._h, worker_id)

    def close(self) -> None:
        # op lock first: wait out any in-flight wire op (freeing under
        # one is a use-after-free); a later op finds _h None and raises
        with self._op_lock:
            with self._teardown_lock:
                h, self._h = self._h, None
        if h:
            self._lib.bps_client_free(h)

    def _require_open(self) -> None:
        if not self._h:
            raise RuntimeError("NativeClient is closed")

    def _check(self, rc: int, op: str) -> None:
        if rc > 0:  # server-side kErr with a message
            msg = self._lib.bps_client_last_error(self._h) or b""
            if b"crc mismatch" in msg:
                raise WireCorruption(
                    f"bps {op} rejected: {msg.decode()} (detected, "
                    "not applied; retryable)")
            if b"worker evicted" in msg:
                raise WorkerEvictedError(
                    f"bps {op} rejected: {msg.decode()}")
            raise RuntimeError(f"bps {op} rejected: {msg.decode()}")
        if rc == -11:
            raise WorkerEvictedError(
                f"bps {op} rejected: worker evicted (local/IPC path); "
                "rejoin required")
        if rc == -8:
            raise RuntimeError(
                f"bps {op} rejected: worker id out of range for the "
                "wire encoding (must be within [0, 65534])")
        if rc == -7:
            raise TimeoutError(
                f"bps {op} receive timeout (server dead or stalled); "
                "connection closed"
            )
        if rc == -6:
            raise RuntimeError(
                f"bps {op} response key mismatch (stale frame on a "
                "desynchronized stream); connection closed"
            )
        if rc != 0:
            raise RuntimeError(f"bps {op} failed (rc={rc})")

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001 - interpreter teardown
            pass
