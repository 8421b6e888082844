"""Sharded-wire hierarchical push_pull (the BytePS "use every link"
dataflow): ICI reduce-scatter / all-gather primitives, rendezvous
partition ownership, the owner-routed DCN stages, per-owner credit
pools, owner failover × server-replay composition, and this PR's
satellites (init marked-after-success, the single wire_seed definition,
the device_get COPYD2H contract).

Tier-1: bit-exact sharded-vs-unsharded pins (raw AND compressed — the
sharding changes WHICH NIC carries each partition, never the bytes) at
2, 3 and 4 pod controllers, with the per-NIC byte counts that show the
wire divided, and the owner-death chaos smoke. The deeper failover
sweep is slow-tier. No test here asserts a time or a ratio of times.
"""

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.common import config as config_mod
from byteps_tpu.common.partition import (
    OwnerTable,
    Partition,
    owner_for_key,
)
from byteps_tpu.server import start_server_any_port, stop_server

BASE_PORT = 26400


def _start_server_any_port(port, **kw):
    # wide stride keeps the probes clear of the other tests' port blocks
    return start_server_any_port(port, attempts=4, stride=53, **kw)


@pytest.fixture(autouse=True)
def _cleanup_server():
    yield
    stop_server()


# ---- ICI primitives (pure collective tier) ----------------------------------
def test_reduce_scatter_allgather_roundtrip_bit_exact(mesh8):
    """reduce_scatter + all_gather must reproduce the allreduce sum
    BIT-exactly (psum_scatter sums each segment in the same order psum
    does) — the invariant that lets the sharded stage graph default on.
    Includes a ragged length (L % n != 0: the scatter pads, the gather
    trims)."""
    from byteps_tpu.comm.ici import (
        all_gather_flat,
        allreduce_flat,
        reduce_scatter_flat,
    )

    for L in (8 * 125, 1003):
        x = jnp.asarray(
            np.random.RandomState(L).randn(8, L).astype(np.float32))
        full = np.asarray(allreduce_flat(x, mesh8, "dp", average=False))
        segs = reduce_scatter_flat(x, mesh8, "dp")
        n_seg = -(-L // 8) * 8
        assert segs.shape == (n_seg,)
        # concatenated owner segments ARE the sum (host view)
        np.testing.assert_array_equal(
            np.asarray(segs).reshape(-1)[:L], full)
        # and the ICI tail reassembles them exactly
        back = all_gather_flat(segs, mesh8, "dp", length=L)
        np.testing.assert_array_equal(np.asarray(back), full)


# ---- ownership (pure unit tier) ---------------------------------------------
def test_owner_table_rendezvous_properties():
    keys = list(range(0, 4000, 7))
    t = OwnerTable(4, salt=0)
    place = {k: t.owner(k) for k in keys}
    # deterministic and reasonably spread
    assert place == {k: t.owner(k) for k in keys}
    counts = [sum(1 for o in place.values() if o == r) for r in range(4)]
    assert all(c > len(keys) // 8 for c in counts), counts
    # rendezvous property: killing owner 2 moves ONLY owner 2's keys
    assert t.fail(2)
    for k in keys:
        if place[k] != 2:
            assert t.owner(k) == place[k], k
        else:
            assert t.owner(k) != 2
    assert not t.fail(2)  # already dead
    assert t.fail(1) and t.fail(3)
    assert not t.fail(0), "must refuse to kill the last controller"
    # a different salt reshuffles placement
    t2 = OwnerTable(4, salt=99)
    assert any(t2.owner(k) != place[k] for k in keys)


def test_owner_for_key_matches_server_hash_shape():
    """The owner hash mirrors PSWorker._server_for_live's rendezvous form
    so the two failover layers compose: each moves only the dead
    member's keys."""
    live = {0, 1, 3}
    for k in range(50):
        o = owner_for_key(k, live, salt=0)
        assert o in live


# ---- scheduler: per-owner credit pools --------------------------------------
def test_scheduler_owner_credit_pools_isolate_and_refill():
    """One owner's stalled wire must not starve a sibling owner's issue
    slots (per-NIC queue model), and every pool refills — zero leak."""
    from byteps_tpu.common.scheduler import (
        Handle,
        PartitionTask,
        PipelineScheduler,
        Stage,
    )

    release = threading.Event()
    done = []

    def fn(task):
        if task.partition.owner == 0:
            release.wait(10.0)
        done.append((task.partition.owner, task.partition.key))
        return task.partition.key

    sched = PipelineScheduler(
        stages=[Stage("W", fn, credited=True, pool_size=4,
                      releases_credit=True)],
        credit=1, credit_scope="owner",
    )

    def mk(key, owner):
        p = Partition(key=key, tensor_id=0, part_idx=key, offset=0,
                      length=1, priority=0, owner=owner)
        return PartitionTask(partition=p, name="t",
                             handle=Handle("t", 1))

    tasks = [mk(0, 0), mk(1, 1), mk(2, 1), mk(3, 1)]
    sched.enqueue(tasks)
    deadline = time.time() + 5
    while time.time() < deadline and len(done) < 3:
        time.sleep(0.01)
    # owner 1's three tasks all completed (credit 1 recycled through its
    # own pool) while owner 0's task still holds owner 0's only credit —
    # with a GLOBAL pool of 1 nothing past the first task could run
    assert sorted(done) == [(1, 1), (1, 2), (1, 3)], done
    release.set()
    deadline = time.time() + 5
    while time.time() < deadline and len(done) < 4:
        time.sleep(0.01)
    assert len(done) == 4
    pools = sched.credit_pools()
    assert all(v == sched._credit_total for v in pools.values()), pools
    sched.shutdown()


# ---- sharded DcnCore: equivalence + wire division ---------------------------
def _run_core_rounds(port, pod_controllers, codec=None, rounds=3,
                     nelems=120000, fault_specs=None):
    from byteps_tpu.common.dcn_adapter import DcnCore

    cfg = dataclasses.replace(
        config_mod.Config.from_env(), num_worker=1, num_server=1,
        partition_bytes=65536, min_compress_bytes=0)
    config_mod.set_config(cfg)
    port = _start_server_any_port(port, num_workers=1, engine_threads=2,
                                  async_mode=False)
    core = DcnCore(servers=[("127.0.0.1", port)],
                   pod_controllers=pod_controllers,
                   fault_specs=fault_specs)
    outs = []
    try:
        flat = np.random.default_rng(7).standard_normal(nelems).astype(
            np.float32)
        for r in range(rounds):
            h = core.push_pull_async(flat + r, name="eq", codec=codec)
            outs.append(DcnCore.assemble(h, timeout=60.0).copy())
        per_nic = [(w.bytes_pushed, w.bytes_pulled) for w in core.workers]
        pools = core.scheduler.credit_pools()
        failovers = core.owner_failovers
        counters = [w.get_counters() for w in core.workers]
    finally:
        core.shutdown()
        stop_server()
        config_mod.reset_config()
    return outs, per_nic, pools, failovers, counters


def test_sharded_matches_unsharded_bit_exact_raw_and_compressed():
    """THE equivalence pin: sharding moves partitions onto different NICs
    but every byte on the wire is identical (same partitioning, same
    wire_seed, same server dataflow) — so raw is bit-exact and the
    compressed wire decodes to the bit-identical values too."""
    from byteps_tpu.compression import wire

    ref_raw, _, _, _, _ = _run_core_rounds(BASE_PORT + 1, 1)
    shard_raw, per_nic, pools, _, _ = _run_core_rounds(BASE_PORT + 2, 4)
    for a, b in zip(ref_raw, shard_raw):
        np.testing.assert_array_equal(a, b)
    # the wire genuinely divided: >1 NIC active, none carried everything
    active = [p for p, _ in per_nic if p > 0]
    total = sum(active)
    assert len(active) >= 3, per_nic
    assert max(active) < 0.6 * total, per_nic
    assert all(v == 4 for v in pools.values()), pools  # zero credit leak

    ref_ob, _, _, _, _ = _run_core_rounds(
        BASE_PORT + 3, 1, codec=wire.OnebitWire(scaling=True))
    shard_ob, _, _, _, _ = _run_core_rounds(
        BASE_PORT + 4, 4, codec=wire.OnebitWire(scaling=True))
    for a, b in zip(ref_ob, shard_ob):
        np.testing.assert_array_equal(a, b)


# ---- satellite: init marked inited only after success -----------------------
def test_failed_init_is_retried_not_skipped(monkeypatch):
    """The needs_init regression: a failed key init must re-run on the
    stage retry — the old code marked the key inited BEFORE init_key ran,
    so the retry skipped it and every later push hit an uninitialized
    server key."""
    from byteps_tpu.common.dcn_adapter import DcnCore

    cfg = dataclasses.replace(config_mod.Config.from_env(), num_worker=1,
                              num_server=1)
    config_mod.set_config(cfg)
    port = _start_server_any_port(BASE_PORT + 5, num_workers=1,
                                  engine_threads=2, async_mode=False)
    core = DcnCore(servers=[("127.0.0.1", port)])
    calls = {"n": 0}
    real_init = core.worker.init_key

    def flaky_init(key, nbytes):
        calls["n"] += 1
        if calls["n"] == 1:
            raise ConnectionError("injected: init never reached server")
        real_init(key, nbytes)

    monkeypatch.setattr(core.worker, "init_key", flaky_init)
    try:
        flat = np.linspace(-1, 1, 2048, dtype=np.float32)
        h = core.push_pull_async(flat, name="initreg")
        out = DcnCore.assemble(h, timeout=30.0)
        np.testing.assert_array_equal(out, flat)
        assert calls["n"] == 2, calls  # failed once, RE-RAN on retry
    finally:
        core.shutdown()


def test_failed_init_retried_under_fault_injection(monkeypatch):
    """Same regression through the real fault plan: ``init:kill@op=1``
    (the first init attempt never reaches the server) with the wire
    retry budget at 0, so only the STAGE retry can heal it — which
    requires the fixed after-success marking."""
    from byteps_tpu.common.dcn_adapter import DcnCore

    monkeypatch.setenv("BYTEPS_RETRY_LIMIT", "0")
    monkeypatch.setenv("BYTEPS_FAULT_SPEC", "init:kill@op=1")
    config_mod.reset_config()
    cfg = dataclasses.replace(config_mod.Config.from_env(), num_worker=1,
                              num_server=1)
    config_mod.set_config(cfg)
    port = _start_server_any_port(BASE_PORT + 6, num_workers=1,
                                  engine_threads=2, async_mode=False)
    core = DcnCore(servers=[("127.0.0.1", port)])
    try:
        flat = np.linspace(0, 1, 1024, dtype=np.float32)
        h = core.push_pull_async(flat, name="initfault")
        out = DcnCore.assemble(h, timeout=30.0)
        np.testing.assert_array_equal(out, flat)
        counters = core.worker.get_counters()
        assert counters["injected_kill"] >= 1, counters
    finally:
        core.shutdown()


# ---- satellite: ONE wire_seed definition ------------------------------------
def test_wire_seed_single_definition_across_paths():
    """The PRNG contract (randomk index agreement) has exactly one
    definition: the jax hybrid stages and the host DcnCore stages must
    derive the IDENTICAL seed for the same (tensor, round, partition) —
    they used to compute different ones."""
    from byteps_tpu.common.scheduler import Handle, PartitionTask
    from byteps_tpu.compression import from_params
    from byteps_tpu.compression.wire import wire_seed

    import byteps_tpu.jax as bps

    name, version, part_idx = "grad.7", 5, 3
    p = Partition(key=42, tensor_id=0, part_idx=part_idx, offset=0,
                  length=8, priority=0)
    spec = from_params(None)  # seed 0
    task = PartitionTask(partition=p, name=name, handle=Handle(name, 1),
                         context={"version": version, "spec": spec})
    jax_seed = bps._wire_seed(task)
    host_seed = wire_seed(name, version, part_idx)
    assert jax_seed == host_seed
    # a CompressionSpec user seed salts the shared helper, same contract
    spec7 = from_params({"compressor": "randomk", "seed": 7})
    task.context["spec"] = spec7
    assert bps._wire_seed(task) == wire_seed(name, version, part_idx,
                                             salt=7)
    assert bps._wire_seed(task) != host_seed


# ---- satellite: COPYD2H via device_get --------------------------------------
def test_d2h_stage_contract(mesh8):
    """COPYD2H uses jax.device_get: f32 + C-contiguous always, trimmed to
    the partition, and WRITABLE whenever EF/momentum are configured (the
    compress stage's state arithmetic may mutate in place); the
    stateless path may hand back a zero-copy read-only host view."""
    from byteps_tpu.common.scheduler import Handle, PartitionTask
    from byteps_tpu.comm.ici import reduce_scatter_flat
    from byteps_tpu.compression import from_params

    import byteps_tpu.jax as bps

    L = 1003  # ragged: the scattered payload is padded to 8*126
    x = jnp.asarray(np.random.RandomState(0).randn(8, L).astype(np.float32))
    scattered = reduce_scatter_flat(x, mesh8, "dp")
    want = np.asarray(x).sum(0)

    p = Partition(key=0, tensor_id=0, part_idx=0, offset=0, length=L,
                  priority=0)

    def run(spec):
        t = PartitionTask(partition=p, name="t", handle=Handle("t", 1),
                          context={"spec": spec}, payload=scattered)
        return bps._d2h_stage(t)

    out = run(from_params(None))
    assert out.dtype == np.float32 and out.flags.c_contiguous
    assert out.shape == (L,)
    np.testing.assert_allclose(out, want, rtol=1e-6)

    out_ef = run(from_params({"compressor": "onebit", "ef": "vanilla"}))
    assert out_ef.flags.writeable and out_ef.flags.c_contiguous
    out_ef += 1.0  # the EF path may mutate in place
    # atol: (x + 1) - 1 loses low mantissa bits of small x in f32 — the
    # mutation round trip itself costs up to ~eps(1) = 6e-8 absolute
    np.testing.assert_allclose(out_ef - 1.0, want, rtol=1e-6, atol=1e-7)


# ---- failover × ownership chaos smoke (tier-1) ------------------------------
def test_owner_death_chaos_smoke_converges_bit_identical(monkeypatch):
    """THE failover × ownership smoke: a 2-controller sharded pod where
    owner 1's NIC dies mid-run (injected kills from wire-op 3 onward,
    wire retries exhausted). The remapped rounds must converge
    BIT-identically to the clean run — round-counter adoption keeps the
    server's replay watermark consistent — with exactly one owner
    failover and zero credit leak."""
    monkeypatch.setenv("BYTEPS_RETRY_LIMIT", "1")
    monkeypatch.setenv("BYTEPS_RETRY_BACKOFF_MS", "2")
    config_mod.reset_config()
    clean, _, _, _, _ = _run_core_rounds(BASE_PORT + 7, 2, rounds=6)
    chaos, per_nic, pools, failovers, counters = _run_core_rounds(
        BASE_PORT + 8, 2, rounds=6,
        fault_specs=[None, "push:kill@op=3.."])
    for r, (a, b) in enumerate(zip(clean, chaos)):
        np.testing.assert_array_equal(a, b, err_msg=f"round {r}")
    assert failovers == 1, failovers
    assert counters[1]["injected_kill"] >= 1, counters
    assert all(v == 4 for v in pools.values()), pools  # zero credit leak
    # after the remap the surviving NIC carried the rest of the traffic
    assert per_nic[0][0] > per_nic[1][0], per_nic


def test_owner_dead_server_view_fails_over_not_degrades():
    """Composition regression: every controller NIC runs its OWN health
    monitor (pings ride its own connections), so a dead owner NIC can
    manifest as THAT worker's live-server set emptying while its siblings
    still reach every server. The push stage must fail the owner over to
    a sibling — the result stays the true global sum — not silently
    degrade the owner's partitions to pod-LOCAL sums while other pods
    keep summing globally."""
    from byteps_tpu.common.dcn_adapter import DcnCore

    cfg = dataclasses.replace(
        config_mod.Config.from_env(), num_worker=1, num_server=1,
        partition_bytes=65536, min_compress_bytes=0)
    config_mod.set_config(cfg)
    port = _start_server_any_port(BASE_PORT + 120, num_workers=1,
                                  engine_threads=2, async_mode=False)
    core = DcnCore(servers=[("127.0.0.1", port)], pod_controllers=2)
    try:
        flat = np.random.default_rng(11).standard_normal(120000).astype(
            np.float32)
        h = core.push_pull_async(flat, name="hv")
        want = DcnCore.assemble(h, timeout=60.0).copy()
        np.testing.assert_array_equal(want, flat)  # 1 pod: sum == input
        # premise: the rendezvous hash gave owner 1 some partitions
        assert core.workers[1].bytes_pushed > 0
        # owner 1's private view loses every server — what its health
        # monitor records when the NIC (not the servers) died
        core.workers[1]._live.clear()
        h = core.push_pull_async(flat + 1, name="hv")
        got = DcnCore.assemble(h, timeout=60.0)
        np.testing.assert_array_equal(got, flat + 1)  # still GLOBAL sums
        assert core.owner_failovers == 1
        assert core.owners.live() == {0}
        assert not getattr(h, "degraded_parts", None)
    finally:
        core.shutdown()
        stop_server()
        config_mod.reset_config()


def test_total_outage_walks_owners_down_then_degrades():
    """A genuine all-servers outage with MANY controllers must walk every
    owner down — each failover costs one stage attempt, so PUSH/PULL
    max_attempts scale with the controller count — and then degrade to
    the pod-local sum, not fail the handle with retries exhausted."""
    from byteps_tpu.common.dcn_adapter import DcnCore

    cfg = dataclasses.replace(
        config_mod.Config.from_env(), num_worker=1, num_server=1,
        partition_bytes=65536, min_compress_bytes=0)
    config_mod.set_config(cfg)
    port = _start_server_any_port(BASE_PORT + 130, num_workers=1,
                                  engine_threads=2, async_mode=False)
    core = DcnCore(servers=[("127.0.0.1", port)], pod_controllers=4)
    try:
        flat = np.random.default_rng(13).standard_normal(120000).astype(
            np.float32)
        h = core.push_pull_async(flat, name="to")
        np.testing.assert_array_equal(
            DcnCore.assemble(h, timeout=60.0), flat)
        for w in core.workers:  # every NIC's private view: all servers gone
            w._live.clear()
        h = core.push_pull_async(flat + 1, name="to")
        got = DcnCore.assemble(h, timeout=60.0)
        # 1 pod: the degraded pod-local contribution == the global sum
        np.testing.assert_array_equal(got, flat + 1)
        assert core.owner_failovers == 3  # walked 3 owners down
        assert len(core.owners.live()) == 1
        assert getattr(h, "degraded_parts", None)  # last one DEGRADED
    finally:
        core.shutdown()
        stop_server()
        config_mod.reset_config()


@pytest.mark.slow
def test_owner_failover_full_sweep(monkeypatch):
    """Slow-tier sweep: owner death under a COMPRESSED wire and more
    rounds/partitions, against the clean sharded run; also the
    owner-death-during-PULL path (kills on pull attempts)."""
    from byteps_tpu.compression import wire

    monkeypatch.setenv("BYTEPS_RETRY_LIMIT", "1")
    monkeypatch.setenv("BYTEPS_RETRY_BACKOFF_MS", "2")
    config_mod.reset_config()
    for off, spec in ((10, "push:kill@op=4.."), (14, "pull:kill@op=4..")):
        clean, _, _, _, _ = _run_core_rounds(
            BASE_PORT + off, 3, rounds=8, nelems=200000,
            codec=wire.OnebitWire(scaling=True))
        chaos, _, pools, failovers, _ = _run_core_rounds(
            BASE_PORT + off + 1, 3, rounds=8, nelems=200000,
            codec=wire.OnebitWire(scaling=True),
            fault_specs=[None, spec, None])
        for r, (a, b) in enumerate(zip(clean, chaos)):
            np.testing.assert_array_equal(a, b,
                                          err_msg=f"{spec} round {r}")
        assert failovers == 1
        assert all(v == 4 for v in pools.values()), pools


def test_handoff_fences_dead_worker_and_adopts_rounds():
    """The mint-vs-export race regression: ``hand_off_owner`` fences the
    dying controller's worker BEFORE exporting its round counters, so a
    push thread that resolved the owner pre-failover gets a
    stage-retryable FailedOverError instead of minting a round invisible
    to the survivors' adopted counters (the server's replay dedupe would
    silently drop the survivor's re-mint of the same number)."""
    from byteps_tpu.server import FailedOverError, PSWorker, hand_off_owner

    workers = [PSWorker(servers=[("127.0.0.1", 1)], worker_id=3)
               for _ in range(2)]
    try:
        owners = OwnerTable(2)
        assert workers[0].mint_version(11) == 1
        assert workers[0].mint_version(11) == 2
        assert workers[0].mint_version(29) == 1

        live = hand_off_owner(workers, owners, 0)
        assert live == {0, 1}  # PRE-fail set, for partition diffing
        assert owners.live() == {1}
        # the dead worker is fenced: a racing stale-owner push cannot
        # mint past the exported snapshot, pinned or not
        with pytest.raises(FailedOverError):
            workers[0].mint_version(11)
        with pytest.raises(FailedOverError):
            workers[0].mint_version(11, pinned=2)
        # the survivor adopted the counters and continues the sequence
        # gaplessly — rounds 3 and 2, not a restart from 1
        assert workers[1].mint_version(11) == 3
        assert workers[1].mint_version(29) == 2

        # already-dead and last-controller handoffs are refused
        assert hand_off_owner(workers, owners, 0) is None
        assert hand_off_owner(workers, owners, 1) is None
        assert owners.live() == {1}
    finally:
        for w in workers:
            w.close()


def test_owner_wire_death_excludes_server_side_conditions():
    """ServerDownError regression: a server-down window that outlasts the
    wire retry budget names the SERVER as the culprit — classifying it as
    owner death would let one slow-to-detect server outage serially kill
    every healthy controller routing at it. Only errors whose common
    element is the owner's own NIC qualify — a dead NIC resurfaces as a
    refused/reset reconnect (ConnectionError); a recv TimeoutError or a
    CRC-detected corrupt payload blames a slow/misbehaving server at
    least as plausibly, so those stage-retry instead."""
    from byteps_tpu.common.dcn_adapter import owner_wire_death
    from byteps_tpu.common.faults import InjectedConnectionError, \
        ServerDownError
    from byteps_tpu.server import FailedOverError, NoLiveServersError
    from byteps_tpu.server.native import WireCorruption

    assert owner_wire_death(ConnectionError("socket died"))
    assert owner_wire_death(InjectedConnectionError("injected kill"))
    # server-side conditions: the failover/degraded machinery owns these
    assert not owner_wire_death(TimeoutError("recv timed out"))
    assert not owner_wire_death(WireCorruption("crc mismatch"))
    assert not owner_wire_death(ServerDownError("server 0 down window"))
    assert not owner_wire_death(NoLiveServersError("all dead"))
    assert not owner_wire_death(FailedOverError("key moved"))
    assert not owner_wire_death(RuntimeError("kErr: size mismatch"))


# ---- jax hybrid pipeline: sharded stage graph -------------------------------
def _jax_hybrid_outputs(monkeypatch, port, sharded, controllers,
                        n_rounds=3):
    import byteps_tpu.jax as bps

    monkeypatch.setenv("BYTEPS_FORCE_DISTRIBUTED", "1")
    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    monkeypatch.setenv("DMLC_NUM_SERVER", "1")
    monkeypatch.setenv("BYTEPS_PARTITION_BYTES", "65536")
    monkeypatch.setenv("BYTEPS_MIN_COMPRESS_BYTES", "0")
    monkeypatch.setenv("BYTEPS_HYBRID_SHARDED", "1" if sharded else "0")
    monkeypatch.setenv("BYTEPS_POD_CONTROLLERS", str(controllers))
    port = _start_server_any_port(port, num_workers=1, engine_threads=2,
                                  async_mode=False)
    # PSWorker() (unlike DcnCore(servers=...)) derives the server address
    # from config: server 0 listens on DMLC_PS_ROOT_PORT + 1
    monkeypatch.setenv("DMLC_PS_ROOT_PORT", str(port - 1))
    config_mod.reset_config()
    bps.init()
    try:
        rs = np.random.RandomState(3)
        x = jnp.asarray(rs.randn(8, 50000).astype(np.float32))
        outs = {}
        for r in range(n_rounds):
            outs[f"raw{r}"] = np.asarray(
                bps.push_pull(x + r, average=False, name="g"))
        outs["avg"] = np.asarray(bps.push_pull(x, average=True, name="a"))
        outs["onebit"] = np.asarray(bps.push_pull(
            x, average=False, name="c",
            compression_params={"compressor": "onebit",
                                "ef": "vanilla"}))
        per_nic = [w.bytes_pushed for w in bps._state.psworkers]
        n_stages = len(bps._state.scheduler.stages)
    finally:
        bps.shutdown()
        stop_server()
        bps._state.__init__()
        config_mod.reset_config()
    return outs, per_nic, n_stages


@pytest.mark.parametrize("controllers", [2, 3])
def test_jax_sharded_graph_matches_unsharded_bit_exact(monkeypatch,
                                                       controllers):
    """End-to-end jax hybrid pin: the sharded stage graph (reduce-scatter
    head, owner-routed wire, all-gather tail) returns BIT-identical
    push_pull results to the classic allreduce-then-push-everything
    graph — raw and compressed (the wire bytes are identical; only the
    topology changed). The sharded run must also split bytes across >1
    NIC (at two controllers: both carry bytes) and carry the extra
    ALLGATHER stage."""
    port = BASE_PORT + 20 + 2 * (controllers - 2)
    ref, ref_nics, ref_stages = _jax_hybrid_outputs(
        monkeypatch, port, sharded=False, controllers=1)
    shd, nics, n_stages = _jax_hybrid_outputs(
        monkeypatch, port + 1, sharded=True, controllers=controllers)
    assert set(ref) == set(shd)
    for k in ref:
        np.testing.assert_array_equal(ref[k], shd[k], err_msg=k)
    assert ref_stages == 7 and n_stages == 8  # +ALLGATHER tail
    assert len(ref_nics) == 1 and len(nics) == controllers
    assert sum(1 for b in nics if b > 0) >= 2, nics
    assert sum(nics) == sum(ref_nics)  # same total wire bytes, divided
