"""Throttled-DCN fast lane: token-bucket pacer semantics, the tier-1
2-codec × 1-rate smoke race through the full DcnCore pipeline, and the
COMPRESS↔PUSH overlap contract (compress of chunk i+1 strictly inside the
push window of chunk i) asserted from the chrome trace.

The pacer (``server/pacer.py``, ``BYTEPS_DCN_THROTTLE_MBPS``) emulates the
slow cross-pod networks gradient compression exists for (SURVEY §6) on
plain loopback — no root/netem — which is what lets CI exercise the
compression-wins regime on every run. A sweep over rates and codecs is
not measured anywhere: no benchmark cell runs the DCN tier.
"""

import json
import time

import numpy as np
import pytest

from byteps_tpu.server.pacer import DcnPacer, TokenBucket, pacer_from_mbps

BASE_PORT = 24300


# ---- token bucket semantics (pure unit tier) --------------------------------
def test_token_bucket_paces_sustained_rate():
    # 8 MB/s; burst 64 KB; five 1 MB charges must take ~ (5MB-burst)/rate
    tb = TokenBucket(8e6, burst_bytes=64 << 10)
    t0 = time.perf_counter()
    for _ in range(5):
        tb.throttle(1 << 20)
    elapsed = time.perf_counter() - t0
    want = (5 * (1 << 20) - (64 << 10)) / 8e6
    assert elapsed >= want * 0.9, (elapsed, want)
    assert elapsed < want * 3 + 0.5, (elapsed, want)


def test_token_bucket_burst_absorbs_small_messages():
    tb = TokenBucket(1e6, burst_bytes=1 << 20)  # 1 MB burst, slow rate
    t0 = time.perf_counter()
    for _ in range(8):
        assert tb.throttle(4096) == 0.0  # rides the burst, never sleeps
    assert time.perf_counter() - t0 < 0.2


def test_token_bucket_deficit_serializes_threads():
    """Concurrent senders share the bucket: total bytes / total time may
    not exceed the configured rate (the shared-NIC model)."""
    import threading

    tb = TokenBucket(16e6, burst_bytes=64 << 10)
    done = []

    def body():
        for _ in range(4):
            tb.throttle(256 << 10)
        done.append(1)

    ts = [threading.Thread(target=body) for _ in range(4)]
    t0 = time.perf_counter()
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    elapsed = time.perf_counter() - t0
    total = 4 * 4 * (256 << 10)
    assert len(done) == 4
    # rate ceiling honored within tolerance (sleep granularity)
    assert total / elapsed <= 16e6 * 1.25, (total / elapsed)


def test_pacer_from_mbps_gating():
    assert pacer_from_mbps(0) is None
    assert pacer_from_mbps(-5) is None
    p = pacer_from_mbps(80)
    assert isinstance(p, DcnPacer)
    # 80 Mbps = 10 MB/s per direction
    assert p.send.rate == pytest.approx(10e6)
    assert p.recv.rate == pytest.approx(10e6)
    with pytest.raises(ValueError):
        DcnPacer(0)


def test_psworker_reads_throttle_from_env(monkeypatch):
    """BYTEPS_DCN_THROTTLE_MBPS plumbs through Config into PSWorker
    without touching the wire (no server needed before the first op)."""
    monkeypatch.setenv("BYTEPS_DCN_THROTTLE_MBPS", "200")
    from byteps_tpu.common import config as config_mod

    config_mod.reset_config()
    from byteps_tpu.server import PSWorker

    w = PSWorker(servers=[("127.0.0.1", 1)])  # never connected
    assert w.pacer is not None and w.pacer.mbps == 200.0
    w2 = PSWorker(servers=[("127.0.0.1", 1)], throttle_mbps=0)
    assert w2.pacer is None


# ---- the tier-1 smoke race (2 codecs × 1 rate, CPU loopback) ---------------
def _run_core(rate_mbps, partition_bytes, port, trace=False,
              monkeypatch=None):
    """Fresh config + server + DcnCore at the given emulated rate."""
    from byteps_tpu.common import config as config_mod
    from byteps_tpu.common.dcn_adapter import DcnCore
    from byteps_tpu.server import start_server

    cfg = config_mod.Config(
        num_worker=1, num_server=1,
        dcn_throttle_mbps=float(rate_mbps),
        partition_bytes=partition_bytes,
        trace_on=trace,
    )
    config_mod.set_config(cfg)
    if trace:
        from byteps_tpu.common import tracing

        tracing.reset_tracer()
    start_server(port=port, num_workers=1, engine_threads=4,
                 async_mode=False)
    return DcnCore(servers=[("127.0.0.1", port)])


def test_throttled_smoke_raw_vs_onebit():
    """The every-run variant of the throttled race: raw fp32 and onebit
    push+pull 2 MB through the COMPRESS → PUSH → PULL → DECOMPRESS
    pipeline at an emulated 100 Mbps. Asserts (a) numerics: the raw
    round returns the pushed vector and onebit returns sign·mean|x| per
    partition; (b) the pacer actually engaged (booked the wire bytes);
    (c) the compressed round beats the raw round end-to-end — the
    fast-lane claim, at smoke scale."""
    from byteps_tpu.common import config as config_mod
    from byteps_tpu.common.dcn_adapter import DcnCore
    from byteps_tpu.compression import wire
    from byteps_tpu.server import stop_server

    core = _run_core(100, 256 * 1024, BASE_PORT + 1)
    try:
        n = 512 * 1024  # 2 MB over 8 × 256 KB partitions
        flat = np.random.default_rng(3).standard_normal(n).astype(
            np.float32)
        # warmup: key init + connection setup off the clock; timed legs
        # take the best of 2 rounds (CI boxes run this suite 2-core with
        # other servers' teardown threads still draining)
        DcnCore.assemble(core.push_pull_async(flat, name="smoke.raw"))
        t_raw = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            out_raw = DcnCore.assemble(
                core.push_pull_async(flat, name="smoke.raw"))
            t_raw = min(t_raw, time.perf_counter() - t0)
        np.testing.assert_allclose(out_raw, flat, rtol=1e-6)

        ob = wire.OnebitWire(scaling=True)
        DcnCore.assemble(
            core.push_pull_async(flat, name="smoke.onebit", codec=ob))
        p0 = core.worker.bytes_pushed
        t_ob = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            out_ob = DcnCore.assemble(
                core.push_pull_async(flat, name="smoke.onebit", codec=ob))
            t_ob = min(t_ob, time.perf_counter() - t0)
        ob_pushed = (core.worker.bytes_pushed - p0) // 2
        # numerics: per partition, ±mean|x| with x's signs
        plen = 256 * 1024 // 4
        for off in range(0, n, plen):
            seg_in, seg_out = flat[off:off + plen], out_ob[off:off + plen]
            np.testing.assert_allclose(
                np.abs(seg_out), np.mean(np.abs(seg_in)), rtol=1e-5)
            np.testing.assert_array_equal(
                np.sign(seg_out), np.where(seg_in >= 0, 1, -1))
        # the pacer engaged and booked every pushed byte
        assert core.worker.pacer is not None
        assert core.worker.pacer.sent_bytes >= core.worker.bytes_pushed
        # wire: ~32x fewer payload bytes...
        assert ob_pushed * 25 < n * 4, ob_pushed
        # ...and the end-to-end win on the emulated slow link. raw moves
        # 2 MB/dir at 12.5 MB/s — a ≥160 ms wire floor per direction
        # (partially overlapped) — while onebit's ~66 KB/dir costs ~5 ms
        # of wire plus codec+server CPU (~50-80 ms on a 2-core CI box):
        # the margin sits near 3x, so the 1.5x bound has real headroom
        # (at 200 Mbps it measured 1.49x and flaked).
        assert t_ob < t_raw / 1.5, (t_ob, t_raw)
    finally:
        core.shutdown()
        stop_server()
        config_mod.reset_config()


def test_compress_push_overlap_visible_in_trace(tmp_path, monkeypatch):
    """The overlap acceptance contract: in a traced throttled run, the
    COMPRESS span of some chunk i+1 must lie strictly inside the PUSH
    span of an earlier chunk i — the stage split buys wall-clock only if
    codec work actually hides behind the wire."""
    monkeypatch.setenv("BYTEPS_TRACE_DIR", str(tmp_path))
    from byteps_tpu.common import config as config_mod, tracing
    from byteps_tpu.common.dcn_adapter import DcnCore
    from byteps_tpu.compression import wire
    from byteps_tpu.server import stop_server

    core = _run_core(80, 256 * 1024, BASE_PORT + 2, trace=True)
    try:
        n = 1024 * 1024  # 4 MB → 16 partitions of 256 KB
        flat = np.random.default_rng(5).standard_normal(n).astype(
            np.float32)
        # fp16 keeps real bytes on the paced wire (128 KB/partition →
        # ~13 ms push spans at 80 Mbps) so there IS a window for the
        # next chunk's encode to land inside
        f16 = wire.Fp16Wire()
        DcnCore.assemble(
            core.push_pull_async(flat, name="ov", codec=f16), timeout=120)
        tracer = tracing.get_tracer()
        path = tracer.dump(str(tmp_path / "overlap_trace.json"))
        assert path is not None
        doc = json.load(open(path))
        ev = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        comp = {e["name"]: (e["ts"], e["ts"] + e["dur"])
                for e in ev if e["tid"] == "COMPRESS"}
        push = {e["name"]: (e["ts"], e["ts"] + e["dur"])
                for e in ev if e["tid"] == "PUSH"}
        assert len(comp) == 16 and len(push) == 16, (len(comp), len(push))

        def pidx(name):
            return int(name.rsplit(".p", 1)[1])

        overlapped = [
            (pidx(cn), pidx(pn))
            for cn, (c0, c1) in comp.items()
            for pn, (p0, p1) in push.items()
            if pidx(cn) > pidx(pn) and c0 >= p0 and c1 <= p1
        ]
        # at least one later chunk compressed strictly inside an earlier
        # chunk's wire window
        assert overlapped, (comp, push)
    finally:
        core.shutdown()
        stop_server()
        tracing.reset_tracer()
        config_mod.reset_config()
