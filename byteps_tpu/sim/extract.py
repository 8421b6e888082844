"""Lift one recorded run into a calibrated cost model.

Inputs are exactly what the instrumentation already produces (and, since
this PR, stamps with the run's resolved ``Config.snapshot()`` so a
recorded run is replayable without out-of-band knowledge of the knobs
that produced it):

* the chrome trace (``BYTEPS_TRACE_ON=1``) — per-stage spans carrying
  ``args.key`` / ``args.length``, from which we take per-stage
  service-time fits and the tensor/partition structure;
* the flight recorder's per-step ring (degraded input: per-stage run
  percentiles, no per-partition detail — ``cost_model_from_flight_dump``);
* the run's resolved config (trace metadata ``config`` row, or passed
  explicitly).

Three calibration passes, all deterministic once done:

1. **service-time fits** — per stage, ``a_us + b_us_per_byte × dense
   bytes`` least-squares over the recorded spans (single-partition-size
   runs borrow the slope from the codec table and keep the measured
   intercept);
2. **codec table** — encode/decode µs/byte for every wire codec,
   micro-measured on this host at extract time (the recorded run only
   exercised ONE codec; what-ifs over the others need their compute
   cost, and bytes-on-wire ratios are closed-form via
   ``compression/wire.py``);
3. **round slack** — replay the RECORDED config in the simulator and
   book the residual vs the measured step time as a per-round constant
   (handle assembly, enqueue overhead — everything outside the staged
   pipeline). Self-replay of the recorded config is then ~exact by
   construction, and the constant transfers across what-ifs.

See docs/whatif.md for the full list of modeling assumptions.
"""

from __future__ import annotations

import dataclasses
import statistics
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from byteps_tpu.common.logging import get_logger
from byteps_tpu.common.partition import MAX_PARTS_PER_TENSOR
from byteps_tpu.compression import wire as wire_mod
from byteps_tpu.compression.wire import WireCodec

log = get_logger("sim.extract")

# Default loopback "wire" rate when the recorded run was unthrottled and
# the spans don't pin one (bytes cross a localhost socket at memcpy-ish
# speed; the exact figure only matters for unthrottled what-ifs).
_DEFAULT_LOOPBACK_BPS = 4e9

# stage-name fallbacks (µs) when the recorded trace never exercised a
# stage — deliberately small: unknown ≠ expensive
_DEFAULT_OVERHEAD_US = {"PUSH": 150.0, "PULL": 150.0, "PULL_REQ": 50.0}


def codec_by_name(name: str) -> Optional[WireCodec]:
    """The canonical wire-codec instance for a recorded codec name
    (calibration and replay both construct a codec only here)."""
    if name in (None, "", "raw", "none"):
        return None
    if name == "fp16":
        return wire_mod.Fp16Wire()
    if name == "fp8":
        return wire_mod.Fp8Wire()
    if name == "onebit":
        return wire_mod.OnebitWire(scaling=True)
    if name == "topk":
        return wire_mod.TopkWire(k=0.01, selection="block")
    if name == "randomk":
        return wire_mod.RandomkWire(k=0.01)
    if name == "dither":
        return wire_mod.DitherWire()
    raise ValueError(f"unknown wire codec {name!r}")


def calibrate_codecs(names: Sequence[str] = ("raw", "fp16", "fp8",
                                             "onebit", "topk"),
                     nbytes: int = 4 << 20, reps: int = 2,
                     ) -> Dict[str, Dict[str, float]]:
    """Micro-measure encode/decode µs per dense byte for each codec on
    THIS host. The recorded run only exercised one codec; a what-if over
    another needs its compute cost from somewhere, and the codecs are
    pure numpy — a 4 MB sample at ``reps`` reps costs milliseconds.
    min-of-reps: codec arithmetic has no long tail, the min is the
    honest per-byte rate."""
    n = max(1, nbytes // 4)
    x = np.random.default_rng(0).standard_normal(n).astype(np.float32)
    table: Dict[str, Dict[str, float]] = {}
    # the summation server's fp32 accumulate (reduce_sum_f32 is SIMD C;
    # numpy's += is the same memory-bound operation) — priced once,
    # applied per push on the server model
    acc = np.zeros_like(x)
    sums = []
    for _ in range(reps + 1):
        t0 = time.perf_counter()
        acc += x
        sums.append(time.perf_counter() - t0)
    table["_sum"] = {"us_per_byte": min(sums[1:]) * 1e6 / (n * 4)}
    lib = _codec_lib()
    for name in names:
        codec = codec_by_name(name)
        enc_ts, dec_ts = [], []
        for _ in range(reps + 1):   # rep 0 = warmup (imports, caches)
            t0 = time.perf_counter()
            buf = (codec.encode(x, 0) if codec is not None
                   else x.view(np.uint8).ravel())
            t1 = time.perf_counter()
            if codec is not None:
                codec.decode(buf, n, 0)
            else:
                np.ascontiguousarray(buf).view(np.float32).copy()
            t2 = time.perf_counter()
            enc_ts.append(t1 - t0)
            dec_ts.append(t2 - t1)
        table[name] = {
            "encode_us_per_byte": min(enc_ts[1:]) * 1e6 / (n * 4),
            "decode_us_per_byte": min(dec_ts[1:]) * 1e6 / (n * 4),
        }
        if lib is not None:
            table[name].update(_server_codec_rates(lib, codec, x, buf,
                                                   reps))
    return table


def _codec_lib():
    """The native server library's codec-calibration surface, or None on
    an analysis-only box (no compiler / no native build) — the numpy
    rates then stand in for the server loops."""
    try:
        from byteps_tpu.server.native import load_lib

        lib = load_lib()
        lib.bps_codec_encode  # noqa: B018 — staleness probe
        return lib
    except Exception as e:  # noqa: BLE001 — calibration must degrade
        log.info("sim.extract: native codec calibration unavailable "
                 "(%s); using host-numpy rates for the server model", e)
        return None


def _server_codec_rates(lib, codec: Optional[WireCodec], x: np.ndarray,
                        payload: np.ndarray, reps: int,
                        ) -> Dict[str, float]:
    """Price the server's REAL C++ loops per dense byte: ``decode_sum``
    (push apply — decode + fp32 accumulate in one pass) and ``encode``
    (the two-way pull re-encode). These are NOT the numpy rates: onebit's
    unpack and topk's reselection differ by integer factors between the
    two implementations, and the server's side of a what-if leg must be
    priced with the server's own code."""
    n = x.size
    cid = codec.codec_id if codec is not None else 0
    payload = np.ascontiguousarray(payload)
    dst = np.zeros(n, np.float32)
    topk_k = 0
    if codec is not None and isinstance(codec, wire_mod.TopkWire):
        topk_k = int(payload[:4].view(np.uint32)[0])
    cap = int(max(payload.nbytes, n * 4) + 16)
    out = np.empty(cap, np.uint8)
    dec_ts, enc_ts = [], []
    for _ in range(reps + 1):
        t0 = time.perf_counter()
        rc = lib.bps_codec_decode_sum(cid, payload.ctypes.data,
                                      payload.nbytes, dst.ctypes.data, n)
        t1 = time.perf_counter()
        sz = lib.bps_codec_encode(cid, x.ctypes.data, n, topk_k, 0,
                                  out.ctypes.data, cap)
        t2 = time.perf_counter()
        if rc != 0 or sz <= 0:
            log.warning("sim.extract: native codec %d calibration "
                        "failed (rc=%s, sz=%s)", cid, rc, sz)
            return {}
        dec_ts.append(t1 - t0)
        enc_ts.append(t2 - t1)
    return {
        "sdecode_us_per_byte": min(dec_ts[1:]) * 1e6 / (n * 4),
        "sencode_us_per_byte": min(enc_ts[1:]) * 1e6 / (n * 4),
    }


def _fit_linear(samples: List[Tuple[float, float]],
                fallback_slope: float = 0.0,
                ) -> Tuple[float, float]:
    """(a_us, b_us_per_byte) for ``dur_us ≈ a + b·bytes``. One distinct
    size can't pin a slope — borrow ``fallback_slope`` and keep the
    measured intercept."""
    if not samples:
        return 0.0, fallback_slope
    sizes = {s for s, _ in samples}
    med = statistics.median(d for _, d in samples)
    if len(sizes) < 2:
        b = fallback_slope
        a = max(0.0, med - b * next(iter(sizes)))
        return a, b
    xs = np.array([s for s, _ in samples], dtype=np.float64)
    ys = np.array([d for _, d in samples], dtype=np.float64)
    A = np.stack([np.ones_like(xs), xs], axis=1)
    (a, b), *_ = np.linalg.lstsq(A, ys, rcond=None)
    return max(0.0, float(a)), max(0.0, float(b))


@dataclasses.dataclass
class CostModel:
    """Everything :func:`byteps_tpu.sim.engine.simulate` needs, as plain
    data (``to_dict``/``from_dict`` round-trips it — the
    ``--whatif-export`` payload)."""

    pipeline: str                              # "dcn" | "hybrid"
    # (tensor_id, name, num_elements) rows, declaration order
    tensors: List[Tuple[int, str, int]]
    # stage -> (a_us, b_us_per_byte) over DENSE bytes
    stage_fits: Dict[str, Tuple[float, float]]
    # stage -> fixed per-task overhead µs (wire stages)
    overheads: Dict[str, float]
    # codec name -> encode/decode µs per dense byte
    codec_table: Dict[str, Dict[str, float]]
    recorded: Dict[str, Any]                   # the run's resolved config
    loopback_bps: float = _DEFAULT_LOOPBACK_BPS
    min_compress_bytes: int = 65536
    round_slack_us: float = 0.0                # see module docstring
    _codec_cache: Dict[str, Optional[WireCodec]] = dataclasses.field(
        default_factory=dict, repr=False, compare=False)

    # -- structure ------------------------------------------------------------
    def partition_layout(self, partition_bytes: int,
                         ) -> List[Tuple[int, int, int, int]]:
        """(key, part_idx, length, priority) rows under a hypothetical
        partition size — the same arithmetic as
        ``common/partition.make_partitions`` (fp32 itemsize)."""
        plen = max(1, int(partition_bytes) // 4)
        rows = []
        for (tid, _name, nelems) in self.tensors:
            n_parts = max(1, -(-nelems // plen))
            for i in range(n_parts):
                off = i * plen
                rows.append((tid * MAX_PARTS_PER_TENSOR + i, i,
                             min(plen, nelems - off), -tid))
        return rows

    # -- codecs ---------------------------------------------------------------
    def _codec(self, name: str, length: int) -> Optional[WireCodec]:
        """Partition-effective codec: below BYTEPS_MIN_COMPRESS_BYTES
        every partition rides raw, matching the live pipelines."""
        if length * 4 < self.min_compress_bytes:
            return None
        if name not in self._codec_cache:
            self._codec_cache[name] = codec_by_name(name)
        return self._codec_cache[name]

    def wire_bytes(self, codec: str, length: int) -> int:
        c = self._codec(codec, length)
        return c.wire_bytes(length) if c is not None else length * 4

    def pull_wire_bytes(self, codec: str, length: int,
                        two_way: bool) -> int:
        c = self._codec(codec, length)
        if c is None:
            return length * 4
        compacted = type(c).store_elems is not WireCodec.store_elems
        if compacted:
            return c.store_elems(length) * 4
        return c.wire_bytes(length) if two_way else length * 4

    # -- rates ----------------------------------------------------------------
    def wire_rate_bps(self, throttle_mbps: float) -> float:
        if throttle_mbps and throttle_mbps > 0:
            return float(throttle_mbps) * 1e6 / 8.0
        return self.loopback_bps

    # -- service times --------------------------------------------------------
    def stage_overhead_us(self, name: str) -> float:
        return self.overheads.get(name,
                                  _DEFAULT_OVERHEAD_US.get(name, 0.0))

    def _codec_rate(self, codec: str, op: str) -> float:
        row = self.codec_table.get(codec)
        if row is None:
            row = self.codec_table.get("raw", {})
        return float(row.get(f"{op}_us_per_byte", 0.0))

    def server_push_us(self, codec: str, length: int) -> float:
        """Server-side cost of applying one push: ``decode_sum`` — the
        codec decode + fp32 accumulate in one pass. Priced by the
        native-calibrated ``sdecode`` rate (the server's own C++ loop);
        falls back to host-numpy decode + sum rates on an analysis-only
        box."""
        dense = length * 4
        eff = codec if self._codec(codec, length) is not None else "raw"
        row = self.codec_table.get(eff, {})
        if "sdecode_us_per_byte" in row:
            return float(row["sdecode_us_per_byte"]) * dense
        sum_us = self.codec_table.get("_sum", {}).get(
            "us_per_byte", 0.0) * dense
        if eff == "raw":
            return sum_us
        return sum_us + self._codec_rate(eff, "decode") * dense

    def server_pull_us(self, codec: str, length: int,
                       two_way: bool) -> float:
        """Server-side cost of preparing one pull response: re-encoding
        the aggregate for two-way codecs (a raw / one-way response is a
        memcpy, absorbed by the PULL overhead)."""
        c = self._codec(codec, length)
        if c is None or not two_way:
            return 0.0
        if type(c).store_elems is not WireCodec.store_elems:
            return 0.0  # compacted store: the store IS the response
        row = self.codec_table.get(codec, {})
        if "sencode_us_per_byte" in row:
            return float(row["sencode_us_per_byte"]) * length * 4
        return self._codec_rate(codec, "encode") * length * 4

    def compute_us(self, stage: str, codec: str, length: int) -> float:
        """Service time of a non-wire stage for one partition. COMPRESS/
        DECOMPRESS are codec-aware: the recorded codec keeps its measured
        fit, every other codec prices via the micro-calibrated table
        (plus the recorded stage intercept — dispatch cost is
        codec-independent)."""
        dense = length * 4
        eff = codec if self._codec(codec, length) is not None else "raw"
        if stage in ("COMPRESS", "DECOMPRESS"):
            op = "encode" if stage == "COMPRESS" else "decode"
            a, b = self.stage_fits.get(stage, (0.0, 0.0))
            if eff == self.recorded.get("codec", "raw") and \
                    stage in self.stage_fits:
                return a + b * dense
            return a + self._codec_rate(eff, op) * dense
        a, b = self.stage_fits.get(stage, (0.0, 0.0))
        return a + b * dense

    # -- serialization --------------------------------------------------------
    def to_dict(self) -> Dict[str, Any]:
        return {
            "pipeline": self.pipeline,
            "tensors": [list(t) for t in self.tensors],
            "stage_fits": {k: list(v) for k, v in self.stage_fits.items()},
            "overheads": dict(self.overheads),
            "codec_table": self.codec_table,
            "recorded": self.recorded,
            "loopback_bps": self.loopback_bps,
            "min_compress_bytes": self.min_compress_bytes,
            "round_slack_us": self.round_slack_us,
        }

    @classmethod
    def from_dict(cls, doc: Dict[str, Any]) -> "CostModel":
        return cls(
            pipeline=doc["pipeline"],
            tensors=[tuple(t) for t in doc["tensors"]],
            stage_fits={k: tuple(v)
                        for k, v in doc["stage_fits"].items()},
            overheads=dict(doc["overheads"]),
            codec_table=doc["codec_table"],
            recorded=doc["recorded"],
            loopback_bps=float(doc.get("loopback_bps",
                                       _DEFAULT_LOOPBACK_BPS)),
            min_compress_bytes=int(doc.get("min_compress_bytes", 65536)),
            round_slack_us=float(doc.get("round_slack_us", 0.0)),
        )


def recorded_sim_config(recorded: Dict[str, Any], rounds: int = 3):
    """The ONE recorded-config → :class:`SimConfig` mapping (self-replay,
    `rank_configs`' default base, and the goodput estimator all route
    here — a knob added to SimConfig is threaded once)."""
    from byteps_tpu.sim.engine import SimConfig

    return SimConfig(
        partition_bytes=int(recorded.get("partition_bytes", 4096000)),
        credit=int(recorded.get("scheduling_credit",
                                recorded.get("credit", 4))),
        codec=str(recorded.get("codec", "raw")),
        throttle_mbps=float(recorded.get("dcn_throttle_mbps",
                                         recorded.get("throttle_mbps",
                                                      0.0))),
        staleness=int(recorded.get("staleness", 0)),
        pod_controllers=int(recorded.get("pod_controllers", 1)),
        owner_salt=int(recorded.get("owner_salt", 0)),
        num_workers=int(recorded.get("num_worker", 1)),
        rounds=rounds,
    )


def predict_step_s(model: CostModel, cfg) -> float:
    """Simulated median step time + the calibrated per-round slack —
    THE number a validation run would table against measurement."""
    from byteps_tpu.sim.engine import simulate

    return simulate(model, cfg).step_time_s + model.round_slack_us * 1e-6


def cost_model_from_events(
    events: Sequence[Dict[str, Any]],
    config: Optional[Dict[str, Any]] = None,
    measured_step_s: Optional[float] = None,
    codec_table: Optional[Dict[str, Dict[str, float]]] = None,
) -> CostModel:
    """Extract a :class:`CostModel` from chrome-trace events.

    ``config`` defaults to the trace metadata's stamped
    ``Config.snapshot()`` (pass ``load_trace_doc`` output, or merge it
    yourself). ``measured_step_s`` — the recorded leg's measured median
    round time — calibrates the round slack; without it the slack is
    fit against the trace's own per-round makespans (which exclude the
    caller's assemble/enqueue gap).
    """
    from byteps_tpu.common.trace_analysis import (
        partition_lifecycles,
        step_makespans,
    )

    config = dict(config or {})
    recorded_codec = str(config.get("codec", "raw"))
    recorded_rate = float(config.get("dcn_throttle_mbps", 0.0))

    # per-stage samples (dense bytes, dur_us) + tensor structure, both
    # straight from the spans
    lifecycles = partition_lifecycles(events)
    pipeline = "dcn"
    stage_samples: Dict[str, List[Tuple[float, float]]] = {}
    tensor_elems: Dict[str, int] = {}
    tensor_keys: Dict[str, int] = {}
    for e in events:
        if e.get("ph") != "X":
            continue
        tid = str(e.get("tid"))
        args = e.get("args", {}) or {}
        length = args.get("length")
        if length is None:
            continue
        if tid in ("REDUCE", "COPYD2H", "COPYH2D", "ALLGATHER"):
            pipeline = "hybrid"
        stage_samples.setdefault(tid, []).append(
            (float(length) * 4.0, float(e.get("dur", 0.0))))
        key = args.get("key")
        if key is not None:
            name = str(e.get("name", "")).rsplit(".p", 1)[0]
            tensor_keys[name] = int(key) // MAX_PARTS_PER_TENSOR
    # total elements per tensor = sum of round-0 partition lengths
    for lc in lifecycles:
        if lc["round"] != 0 or lc.get("length") is None:
            continue
        name = str(lc["name"]).rsplit(".p", 1)[0]
        tensor_elems[name] = tensor_elems.get(name, 0) + int(lc["length"])
    tensors = sorted(
        (tensor_keys.get(name, i), name, n)
        for i, (name, n) in enumerate(tensor_elems.items()))
    if not tensors:
        raise ValueError("trace has no partition spans with args.length "
                         "— was BYTEPS_TRACE_ON armed over the window?")

    table = codec_table if codec_table is not None else calibrate_codecs()

    # codec-stage fits borrow the table's slope when the run used one
    # partition size (the usual case)
    fits: Dict[str, Tuple[float, float]] = {}
    for st, samples in stage_samples.items():
        if st in ("PUSH", "PULL"):
            continue
        slope = 0.0
        if st == "COMPRESS":
            slope = float(table.get(recorded_codec, {}).get(
                "encode_us_per_byte", 0.0))
        elif st == "DECOMPRESS":
            slope = float(table.get(recorded_codec, {}).get(
                "decode_us_per_byte", 0.0))
        fits[st] = _fit_linear(samples, fallback_slope=slope)

    codec_obj = codec_by_name(recorded_codec)
    min_cb = int(config.get("min_compress_bytes", 65536))
    loopback = _DEFAULT_LOOPBACK_BPS
    if recorded_rate <= 0 and "PUSH" in stage_samples:
        # unthrottled recorded run: the push spans THEMSELVES pin the
        # loopback rate (bytes / median span time)
        med = statistics.median(d for _, d in stage_samples["PUSH"])
        dense = statistics.median(s for s, _ in stage_samples["PUSH"])
        nbytes = (codec_obj.wire_bytes(int(dense // 4)) if codec_obj
                  else dense)
        if med > 0:
            loopback = max(1e6, nbytes / (med * 1e-6))
    rate = (recorded_rate * 1e6 / 8.0 if recorded_rate > 0 else loopback)

    # wire-stage overheads: per-span residual after subtracting the two
    # MODELED components the span carries — own-bytes transmission at
    # the recorded rate and the server's decode/sum (push) or re-encode
    # (pull) for the recorded codec. Later spans' durs also carry
    # sibling token-bucket debt, which the sim reproduces — so the p25
    # of the residuals (≈ the freshest-bucket spans) is the honest
    # per-op overhead, not the median.
    rec_row = table.get(recorded_codec, {})
    enc_rate = float(rec_row.get("sencode_us_per_byte",
                                 rec_row.get("encode_us_per_byte", 0.0)))
    overheads: Dict[str, float] = {}
    for st in ("PUSH", "PULL"):
        xs = [e for e in events
              if e.get("ph") == "X" and e.get("tid") == st
              and (e.get("args") or {}).get("length") is not None]
        resid = []
        for e in xs:
            length = int(e["args"]["length"])
            use_codec = (codec_obj if length * 4 >= min_cb else None)
            dense = length * 4
            if st == "PUSH":
                # the ack does not wait for the sum — a push span is
                # wire time + framing only
                nbytes = (use_codec.wire_bytes(length) if use_codec
                          else dense)
                server_us = 0.0
            else:
                if use_codec is None:
                    nbytes = dense
                    server_us = 0.0
                else:
                    compacted = (type(use_codec).store_elems
                                 is not WireCodec.store_elems)
                    nbytes = (use_codec.store_elems(length) * 4 if compacted
                              else use_codec.wire_bytes(length))
                    server_us = 0.0 if compacted else enc_rate * dense
            r = float(e["dur"]) - nbytes / rate * 1e6 - server_us
            resid.append(max(0.0, r))
        # the MIN residual is the freshest-bucket span (overheads can't
        # be negative, so anything the min still carries is genuine
        # fixed cost); every later span also carries sibling bucket
        # debt, which the sim reproduces — calibrating on a median
        # would double-count a whole transmission
        overheads[st] = min(resid) if resid else _DEFAULT_OVERHEAD_US[st]

    model = CostModel(
        pipeline=pipeline,
        tensors=tensors,
        stage_fits=fits,
        overheads=overheads,
        codec_table=table,
        recorded={
            "codec": recorded_codec,
            "partition_bytes": int(config.get("partition_bytes", 4096000)),
            "scheduling_credit": int(config.get("scheduling_credit", 4)),
            "dcn_throttle_mbps": recorded_rate,
            "staleness": int(config.get("staleness", 0)),
            "pod_controllers": int(config.get("pod_controllers", 1)),
            "owner_salt": int(config.get("owner_salt", 0)),
            "num_worker": int(config.get("num_worker", 1)),
        },
        loopback_bps=loopback,
        min_compress_bytes=min_cb,
    )

    # round-slack calibration: self-replay the recorded config and book
    # the residual vs the measured step time as a per-round constant
    makespans = step_makespans(lifecycles)
    rounds = max(1, len(makespans))
    recorded_step_s = measured_step_s
    if recorded_step_s is None and makespans:
        recorded_step_s = statistics.median(
            m["makespan_us"] for m in makespans) * 1e-6
    if recorded_step_s:
        from byteps_tpu.sim.engine import simulate

        sim = simulate(model, recorded_sim_config(
            model.recorded, rounds=min(3, rounds)))
        model.round_slack_us = (recorded_step_s - sim.step_time_s) * 1e6
        log.info("sim.extract: self-replay %.1fms vs recorded %.1fms "
                 "-> round slack %.1fus",
                 sim.step_time_s * 1e3, recorded_step_s * 1e3,
                 model.round_slack_us)
    return model


def cost_model_from_flight_dump(
    doc: Dict[str, Any],
    config: Optional[Dict[str, Any]] = None,
    codec_table: Optional[Dict[str, Dict[str, float]]] = None,
) -> CostModel:
    """DEGRADED extraction from a flight-recorder post-mortem dump: the
    per-step ring has per-stage run p50s but no per-partition spans, so
    stage costs are flat fits, the payload size comes from the wire
    counters (bytes pushed / steps seen), and the round slack from the
    ring's own ``step_ms``. Good enough to rank configs; the chrome
    trace is the first-class input."""
    config = dict(config or doc.get("config") or {})
    steps = [s for s in doc.get("steps", []) if s.get("stages")]
    if not steps:
        raise ValueError("flight dump has no per-step stage snapshots "
                         "(BYTEPS_FLIGHT_RECORDER_STEPS=0?)")
    counters = (doc.get("metrics", {}).get("counters", {})
                or steps[-1].get("counters", {}))
    pushed = float(counters.get("wire.push_bytes", 0.0))
    # wire.push_bytes is cumulative over the WHOLE run while the ring is
    # bounded — divide by the absolute step span the counters cover, not
    # the ring length (a long run's post-mortem keeps only the tail)
    last_step = steps[-1].get("step")
    n_steps = max(1, int(last_step) if last_step else len(steps))
    round_bytes = pushed / n_steps if pushed else 4096000.0
    recorded_codec = str(config.get("codec", "raw"))
    codec_obj = codec_by_name(recorded_codec)
    if codec_obj is not None and pushed:
        # wire counters saw ENCODED bytes; invert the codec's ratio at
        # the recorded partition size to recover dense bytes
        plen = max(1, int(config.get("partition_bytes", 4096000)) // 4)
        ratio = codec_obj.wire_bytes(plen) / (plen * 4.0)
        round_bytes /= max(ratio, 1e-9)
    nelems = max(1, int(round_bytes // 4))

    fits: Dict[str, Tuple[float, float]] = {}
    pipeline = "dcn"
    for st in steps[-1]["stages"]:
        if st in ("REDUCE", "COPYD2H", "COPYH2D", "ALLGATHER"):
            pipeline = "hybrid"
        p50s = [s["stages"][st].get("run_p50_us") for s in steps
                if st in s.get("stages", {})]
        p50s = [p for p in p50s if p]
        if p50s and st not in ("PUSH", "PULL"):
            fits[st] = (float(statistics.median(p50s)), 0.0)
    step_ms = [s.get("step_ms") for s in steps if s.get("step_ms")]
    table = codec_table if codec_table is not None else calibrate_codecs()
    model = CostModel(
        pipeline=pipeline,
        tensors=[(0, "flight", nelems)],
        stage_fits=fits,
        overheads={},
        codec_table=table,
        recorded={
            "codec": recorded_codec,
            "partition_bytes": int(config.get("partition_bytes", 4096000)),
            "scheduling_credit": int(config.get("scheduling_credit", 4)),
            "dcn_throttle_mbps": float(config.get("dcn_throttle_mbps",
                                                  0.0)),
            "staleness": int(config.get("staleness", 0)),
            "pod_controllers": int(config.get("pod_controllers", 1)),
            "owner_salt": int(config.get("owner_salt", 0)),
            "num_worker": int(config.get("num_worker", 1)),
        },
        min_compress_bytes=int(config.get("min_compress_bytes", 65536)),
    )
    if step_ms:
        from byteps_tpu.sim.engine import simulate

        sim = simulate(model, recorded_sim_config(model.recorded, 3))
        model.round_slack_us = (
            statistics.median(step_ms) * 1e3 - sim.step_time_s * 1e6)
    return model
