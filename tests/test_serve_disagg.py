"""Disaggregated prefill/decode + KV migration (serve/kv_wire.py,
docs/serving.md §disaggregation).

The acceptance bars, straight from the tier's exactness contract
extended across the wire:

* a KV block survives encode → wire bytes → decode BYTE-identical,
  dense and int8 ``_QuantSlot`` (scales included);
* a MIGRATED request's greedy output is BIT-identical to the
  never-migrated (colocated) run and to solo ``make_generate_fn``;
* zero leaked blocks on every pool after drain, in every leg;
* decode-target death and mid-migration death are DETERMINISTIC via
  the ``replica<N>:`` fault scope, and cost a remap, never a loss.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from byteps_tpu.common.faults import (
    FaultPlan,
    parse_fault_spec,
    rules_to_spec,
)
from byteps_tpu.common.metrics import get_registry
from byteps_tpu.models import GPTConfig, gpt_init
from byteps_tpu.models.generate import make_generate_fn
from byteps_tpu.serve import Request, Router, Scheduler
from byteps_tpu.serve.kv_wire import (
    BlockPayload,
    KVBlockCodec,
    KVWire,
    KVWireCorruption,
    KVWireError,
)

CFG = GPTConfig.tiny()


@pytest.fixture(scope="module")
def params():
    return gpt_init(jax.random.PRNGKey(0), CFG)


def _solo(params, req, quant=False):
    gen = make_generate_fn(CFG, req.max_new, quant_cache=quant)
    out = gen(params, jnp.asarray(req.prompt)[None], jax.random.PRNGKey(0),
              0.0)
    return np.asarray(out)[0]


def _mk_requests(n, rng, prompt_lens=(9, 14, 6, 11), max_news=(8, 5, 10)):
    return [Request(rid=f"r{i}",
                    prompt=rng.integers(
                        0, CFG.vocab_size,
                        prompt_lens[i % len(prompt_lens)]).astype(np.int32),
                    max_new=max_news[i % len(max_news)])
            for i in range(n)]


def _counters():
    return get_registry().snapshot()["counters"]


# ---- the codec: bit-exactness pin across the wire ---------------------------
@pytest.mark.parametrize("quant", [False, True])
def test_kv_codec_roundtrip_byte_identical(quant):
    rng = np.random.default_rng(3)
    dtype = np.int8 if quant else np.float32
    codec = KVBlockCodec(n_layers=3, block_size=8, h_kv=2, head_dim=4,
                         dtype=dtype, quant=quant)
    shape = (3, 8, 2, 4)
    if quant:
        k = rng.integers(-128, 128, shape).astype(np.int8)
        v = rng.integers(-128, 128, shape).astype(np.int8)
        ks = rng.standard_normal(shape[:-1]).astype(np.float32)
        vs = rng.standard_normal(shape[:-1]).astype(np.float32)
        p = BlockPayload(k, v, ks, vs)
    else:
        p = BlockPayload(rng.standard_normal(shape).astype(np.float32),
                         rng.standard_normal(shape).astype(np.float32))
    buf = codec.encode(p)
    assert buf.nbytes == codec.frame_bytes
    q = codec.decode(buf)
    for a, b in zip(p, q):
        if a is None:
            assert b is None
        else:
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(a, b)
    # and literally byte-identical through a second encode
    np.testing.assert_array_equal(buf, codec.encode(q))


def test_kv_codec_detects_corruption_and_mismatch():
    codec = KVBlockCodec(2, 4, 2, 4, np.float32, quant=False)
    p = BlockPayload(np.ones((2, 4, 2, 4), np.float32),
                     np.zeros((2, 4, 2, 4), np.float32))
    buf = codec.encode(p)
    bad = buf.copy()
    bad[40] ^= 0xFF                      # body byte -> CRC must trip
    with pytest.raises(KVWireCorruption):
        codec.decode(bad)
    # a differently-shaped codec refuses the frame loudly (config
    # mismatch is NOT retryable — re-sending cannot fix it)
    other = KVBlockCodec(2, 8, 2, 4, np.float32, quant=False)
    with pytest.raises(KVWireError):
        other.decode(buf)
    with pytest.raises(KVWireError):
        codec.decode(buf[:8])


def test_kv_wire_corruption_retries_to_clean_delivery(params):
    """An injected corrupt flips a byte of the delivered frame: the
    target's CRC rejects it, the stage retry re-sends the pristine
    bytes, and the staged payload is exact."""
    sched = Scheduler(params, CFG, max_batch=2, block_size=4)
    sched.cache.register("w")
    sched.cache.ensure("w", 8)
    sched.cache.state = sched.cache.state._replace(
        k=sched.cache.state.k.at[:].add(1.0))
    payloads = sched.cache.snapshot_blocks("w", 0, 2)
    plan = FaultPlan(parse_fault_spec("push:corrupt@op=1"), seed=0)
    wire = KVWire(sched.kv_codec, resolve=lambda rid: sched, fault_plan=plan)
    try:
        handles = [wire.send_block("w", bi, p)
                   for bi, p in payloads.items()]
        for h in handles:
            h.wait(timeout=30)
        assert sched.staged_blocks("w") == {0, 1}
        staged = sched.pop_staged("w")
        for bi, p in payloads.items():
            np.testing.assert_array_equal(staged[bi].k, p.k)
            np.testing.assert_array_equal(staged[bi].v, p.v)
        assert plan.counters()["corrupt"] == 1
        assert _counters()["scheduler.stage_retries"] >= 1
    finally:
        wire.shutdown()
        sched.cache.release("w")
    assert sched.cache.leaked_blocks() == 0


# ---- tier-1 disagg smoke: 2 replicas, migration, bit-exact, no leaks --------
@pytest.mark.parametrize("quant", [False, True])
def test_disagg_smoke_bit_exact_and_leak_free(params, quant):
    """One prefill + one decode replica, every request migrating over
    the KV wire (threshold 1): outputs BIT-identical to solo AND to the
    never-migrated colocated run, zero leaked blocks on both pools, and
    the role split holds — the prefill replica never built the packed
    decode step, the decode replica never built a prefill chunk."""
    rng = np.random.default_rng(7)
    reqs = _mk_requests(4, rng)
    pre = Scheduler(params, CFG, max_batch=3, prefill_chunk=4,
                    role="prefill", replica_id=1, quant_cache=quant)
    dec = Scheduler(params, CFG, max_batch=3, prefill_chunk=4,
                    role="decode", replica_id=0, quant_cache=quant)
    router = Router([dec], prefill_replicas=[pre], lease_ms=5000,
                    prompt_threshold=1)
    try:
        res = router.run(reqs)
    finally:
        router.close()
    colo = Scheduler(params, CFG, max_batch=3, prefill_chunk=4,
                     quant_cache=quant)
    colo_res = colo.serve([Request(rid=r.rid, prompt=r.prompt,
                                   max_new=r.max_new) for r in reqs])
    for r in reqs:
        want = _solo(params, r, quant=quant)
        np.testing.assert_array_equal(res[r.rid]["tokens"], want)
        np.testing.assert_array_equal(colo_res[r.rid]["tokens"], want)
        assert res[r.rid]["ttft_s"] is not None
    assert pre.cache.leaked_blocks() == 0
    assert dec.cache.leaked_blocks() == 0
    pre.cache.check_refcounts()
    dec.cache.check_refcounts()
    snap = _counters()
    assert snap["serve.migration.adopted"] == len(reqs)
    assert snap["serve.migration.in_requests"] == len(reqs)
    assert snap["serve.migration.blocks"] >= len(reqs)
    assert snap["serve.migration.bytes"] > 0
    assert snap["serve.migration.recompute_tokens"] == 0
    # the jit-factory split (cold-start/HBM satellite): neither
    # dedicated replica ever touched the other role's program
    assert pre._decode_fn is None
    assert not dec._prefill_built
    assert dec.cache.migrated_in_blocks > 0


def test_disagg_short_prompts_stay_on_decode_tier(params):
    """Admission classification: prompts under the threshold prefill in
    place on the decode replica (no migration round-trip), long ones
    ride the prefill tier."""
    rng = np.random.default_rng(11)
    short = Request(rid="s", prompt=rng.integers(
        0, CFG.vocab_size, 4).astype(np.int32), max_new=4)
    long_ = Request(rid="l", prompt=rng.integers(
        0, CFG.vocab_size, 16).astype(np.int32), max_new=4)
    pre = Scheduler(params, CFG, max_batch=2, prefill_chunk=4,
                    role="prefill", replica_id=1)
    dec = Scheduler(params, CFG, max_batch=2, prefill_chunk=4,
                    replica_id=0)
    router = Router([dec], prefill_replicas=[pre], lease_ms=5000,
                    prompt_threshold=10)
    try:
        assert router.submit(short) == 0        # decode replica, in place
        assert router.submit(long_) == 1        # prefill replica, migrates
        while not router.finished(["s", "l"]):
            router.step()
    finally:
        router.close()
    for r in (short, long_):
        np.testing.assert_array_equal(router.results[r.rid]["tokens"],
                                      _solo(params, r))
    assert _counters()["serve.migration.adopted"] == 1


# ---- migrate-don't-evict ----------------------------------------------------
def test_migrate_dont_evict_zero_recompute(params):
    """A tight pool on replica A forces pressure; with migration armed
    the victim's blocks MOVE to roomy replica B instead of being freed:
    recompute-token count stays 0, no classic preemption fires, outputs
    bit-exact, both pools leak-free."""
    rng = np.random.default_rng(13)
    a = Scheduler(params, CFG, max_batch=2, prefill_chunk=8,
                  block_size=4, pool_blocks=1 + 10, replica_id=0)
    b = Scheduler(params, CFG, max_batch=2, prefill_chunk=8,
                  block_size=4, replica_id=1)
    router = Router([a, b], lease_ms=5000, migrate_preempt=True)
    reqs = [Request(rid=f"m{i}", prompt=rng.integers(
        0, CFG.vocab_size, 14).astype(np.int32), max_new=10)
        for i in range(4)]
    try:
        res = router.run(reqs)
    finally:
        router.close()
    for r in reqs:
        np.testing.assert_array_equal(res[r.rid]["tokens"],
                                      _solo(params, r))
    snap = _counters()
    assert snap["serve.migration.out_requests"] >= 1
    assert snap["serve.migration.adopted"] >= 1
    assert snap["serve.migration.recompute_tokens"] == 0
    assert snap.get("serve.preempted", 0) == 0
    assert a.cache.leaked_blocks() == 0 and b.cache.leaked_blocks() == 0


# ---- leaving a replica with a decode step unread ----------------------------
def _stepped_to_an_unread_step(params, reqs, picked, **kw):
    """A scheduler stepped until the device has picked ``picked`` tokens for
    the first request, the last of them unread in the step in flight, which
    carries a row of every request."""
    sched = Scheduler(params, CFG, max_batch=len(reqs), prefill_chunk=8,
                      block_size=4, **kw)
    for r in reqs:
        sched.submit(r)
    while not (sched._flight is not None
               and all(r.rid in sched._flight.rows for r in reqs)
               and sched._tokens_picked(sched._runs[reqs[0].rid]) == picked):
        assert sched.step()
    return sched


def test_extract_for_migration_reads_the_unread_step_first(params):
    """A ticket cut while a step is unread carries that step's token and
    the row it wrote; adopted elsewhere, the request ends on its solo
    tokens, and so does the neighbour that stayed."""
    reqs = _mk_requests(2, np.random.default_rng(3), max_news=(10, 9))
    a = _stepped_to_an_unread_step(params, reqs, 5, replica_id=0)
    victim, stays = reqs
    run = a._runs[victim.rid]
    picked = a._tokens_picked(run)
    assert picked == len(run.emitted) + 1
    ticket = a.extract_for_migration(victim.rid)
    assert a._flight is None
    assert len(ticket.emitted) == picked and ticket.pending == ticket.emitted[-1]
    assert ticket.cache_len == len(victim.prompt) + picked - 1
    assert _counters()["serve.pipeline_drains.migrate"] == 1
    b = Scheduler(params, CFG, max_batch=2, prefill_chunk=8, block_size=4,
                  replica_id=1)
    assert b.submit_migrated(ticket, dict(ticket.payloads))
    for sched in (a, b):
        while not sched.finished:
            sched.step()
    np.testing.assert_array_equal(b.results[victim.rid]["tokens"],
                                  _solo(params, victim))
    np.testing.assert_array_equal(a.results[stays.rid]["tokens"],
                                  _solo(params, stays))
    assert _counters()["serve.migration.recompute_tokens"] == 0
    assert a.cache.leaked_blocks() == 0 and b.cache.leaked_blocks() == 0


def test_drain_incomplete_hands_over_every_token_the_device_picked(params):
    """``drain_incomplete`` with a step unread: what it returns for each
    request includes that step's token, a request that token completes is a
    result and not a leftover, and a survivor resumes the rest exactly."""
    reqs = _mk_requests(2, np.random.default_rng(4), max_news=(6, 9))
    # the first request's last token is the unread one
    a = _stepped_to_an_unread_step(params, reqs, 6, replica_id=0)
    assert not a.finished
    picked = {r.rid: a._tokens_picked(a._runs[r.rid]) for r in reqs}
    left = a.drain_incomplete()
    assert a.finished and a.cache.leaked_blocks() == 0
    np.testing.assert_array_equal(a.results[reqs[0].rid]["tokens"],
                                  _solo(params, reqs[0]))
    ((req, emitted),) = left
    assert req.rid == reqs[1].rid and len(emitted) == picked[req.rid]
    b = Scheduler(params, CFG, max_batch=2, prefill_chunk=8, block_size=4,
                  replica_id=1)
    b.submit(req, resume_tokens=emitted)
    while not b.finished:
        b.step()
    np.testing.assert_array_equal(b.results[req.rid]["tokens"],
                                  _solo(params, req))
    assert _counters()["serve.pipeline_drains.migrate"] == 1


def test_migrate_preempt_off_recomputes(params):
    """The escape hatch: with migration off the same pressure takes the
    classic evict path — recompute tokens charged, outputs unchanged."""
    rng = np.random.default_rng(13)
    a = Scheduler(params, CFG, max_batch=2, prefill_chunk=8,
                  block_size=4, pool_blocks=1 + 10, replica_id=0)
    b = Scheduler(params, CFG, max_batch=2, prefill_chunk=8,
                  block_size=4, replica_id=1)
    router = Router([a, b], lease_ms=5000, migrate_preempt=False)
    reqs = [Request(rid=f"m{i}", prompt=rng.integers(
        0, CFG.vocab_size, 14).astype(np.int32), max_new=10)
        for i in range(4)]
    try:
        res = router.run(reqs)
    finally:
        router.close()
    for r in reqs:
        np.testing.assert_array_equal(res[r.rid]["tokens"],
                                      _solo(params, r))
    snap = _counters()
    assert snap.get("serve.migration.out_requests", 0) == 0
    assert snap["serve.preempted"] >= 1
    assert snap["serve.migration.recompute_tokens"] > 0
    assert a.cache.leaked_blocks() == 0 and b.cache.leaked_blocks() == 0


# ---- deterministic death legs (replica<N>: fault scope) ---------------------
def test_decode_target_death_remaps_not_loses(params):
    """replica1:kill@op=1 — the decode target dies before completing a
    single step while migrations are assigned to it: the lease evicts
    it, the wire's stage retries remap every pending migration to the
    survivor, and every request still finishes BIT-exact with zero
    leaks on the live pools."""
    rng = np.random.default_rng(17)
    plan = FaultPlan(parse_fault_spec("replica1:kill@op=1"), seed=0,
                     worker_id=1)
    pre = Scheduler(params, CFG, max_batch=2, prefill_chunk=4,
                    role="prefill", replica_id=2)
    d0 = Scheduler(params, CFG, max_batch=2, prefill_chunk=4,
                   replica_id=0)
    d1 = Scheduler(params, CFG, max_batch=2, prefill_chunk=4,
                   replica_id=1, fault_plan=plan)
    router = Router([d0, d1], prefill_replicas=[pre], lease_ms=50,
                    prompt_threshold=1)
    reqs = _mk_requests(6, rng)
    try:
        res = router.run(reqs)
    finally:
        router.close()
    for r in reqs:
        np.testing.assert_array_equal(res[r.rid]["tokens"],
                                      _solo(params, r))
    assert d1.dead and router.live_replicas() == [0, 2]
    assert d0.cache.leaked_blocks() == 0
    assert pre.cache.leaked_blocks() == 0
    snap = _counters()
    assert snap["serve.router.evictions"] == 1
    # at least one migration was bound for the victim and got remapped
    assert snap["serve.migration.retargets"] >= 1
    assert snap["serve.migration.adopted"] == len(reqs)


def test_prefill_replica_death_degrades_to_colocated(params):
    """The only prefill replica dies mid-stream: its parked load drains
    back through classification, which — with no prefill tier left —
    falls back to colocated prefill on the decode replicas. Outputs
    bit-exact, survivors leak-free."""
    rng = np.random.default_rng(19)
    plan = FaultPlan(parse_fault_spec("replica2:kill@op=3"), seed=0,
                     worker_id=2)
    pre = Scheduler(params, CFG, max_batch=2, prefill_chunk=4,
                    role="prefill", replica_id=2, fault_plan=plan)
    d0 = Scheduler(params, CFG, max_batch=3, prefill_chunk=4,
                   replica_id=0)
    router = Router([d0], prefill_replicas=[pre], lease_ms=50,
                    prompt_threshold=1)
    reqs = _mk_requests(5, rng)
    try:
        res = router.run(reqs)
    finally:
        router.close()
    for r in reqs:
        np.testing.assert_array_equal(res[r.rid]["tokens"],
                                      _solo(params, r))
    assert pre.dead and router.live_replicas() == [0]
    assert d0.cache.leaked_blocks() == 0
    assert _counters()["serve.router.evictions"] == 1


# ---- fault grammar: replica<N> scope ----------------------------------------
def test_replica_scope_grammar_round_trip():
    spec = "replica2:kill@op=4;replica:slow@ms=20;replica1:hang@ms=5"
    rules = parse_fault_spec(spec)
    assert [r.scope for r in rules] == ["replica"] * 3
    assert rules[0].worker == 2 and rules[1].worker is None
    assert parse_fault_spec(rules_to_spec(rules)) == rules


def test_replica_scope_structured_errors():
    with pytest.raises(ValueError, match="replica<N>"):
        parse_fault_spec("replicaX:kill")
    with pytest.raises(ValueError, match="kill|hang|slow"):
        parse_fault_spec("replica1:corrupt@p=0.5")
    with pytest.raises(ValueError, match="kill|hang|slow"):
        parse_fault_spec("replica1:join@step=3")
    with pytest.raises(ValueError, match="kill|hang|slow"):
        parse_fault_spec("replica:timeout")


def test_replica_scope_targets_one_replica_only(params):
    """The same spec string handed to every replica fires on exactly
    the named one, and never on wire ops."""
    rules = parse_fault_spec("replica1:kill@op=2")
    r0 = Scheduler(params, CFG, max_batch=2, replica_id=0,
                   fault_plan=FaultPlan(rules, seed=0, worker_id=0))
    r1 = Scheduler(params, CFG, max_batch=2, replica_id=1,
                   fault_plan=FaultPlan(rules, seed=0, worker_id=1))
    rng = np.random.default_rng(23)
    reqs = _mk_requests(2, rng)
    r0.serve(reqs)                       # replica 0: plan never fires
    for r in reqs:
        np.testing.assert_array_equal(r0.results[r.rid]["tokens"],
                                      _solo(params, r))
    from byteps_tpu.common.faults import WorkerKilledError

    r1.submit(Request(rid="x", prompt=reqs[0].prompt, max_new=4))
    r1.step()
    with pytest.raises(WorkerKilledError):
        r1.step()
    assert r1.dead
    # a wire-shaped op never matches the replica scope
    plan = FaultPlan(rules, seed=0, worker_id=1)
    assert plan.intercept("push", 0) is None
    assert plan.intercept("serve", -1) is not None


def test_router_rejects_mismatched_pool_layouts(params):
    """The wire codec frames the pool's own bytes — replicas with
    different block sizes (or quant modes) can never exchange blocks,
    and the router says so at construction instead of looping a
    terminal wire error."""
    pre = Scheduler(params, CFG, block_size=16, role="prefill",
                    replica_id=1)
    dec = Scheduler(params, CFG, block_size=4, replica_id=0)
    with pytest.raises(ValueError, match="pool layout"):
        Router([dec], prefill_replicas=[pre], prompt_threshold=1)
    q = Scheduler(params, CFG, block_size=4, quant_cache=True,
                  replica_id=2)
    with pytest.raises(ValueError, match="pool layout"):
        Router([dec, q], migrate_preempt=True)
    # colocated without migration does not care
    Router([dec, q], migrate_preempt=False)


# ---- slow sweep: the full disagg matrix -------------------------------------
@pytest.mark.slow
def test_disagg_full_sweep(params):
    """2 prefill + 2 decode replicas, throttled wire, mixed lengths,
    spec requests, quant off/on, pressure-driven migrate-preempt and a
    mid-migration decode death — every leg bit-exact and leak-free."""
    from byteps_tpu.serve.scheduler import SpecPolicy

    for quant in (False, True):
        rng = np.random.default_rng(29)
        pre = [Scheduler(params, CFG, max_batch=3, prefill_chunk=4,
                         block_size=4, role="prefill", replica_id=10 + i,
                         quant_cache=quant) for i in range(2)]
        dec = [Scheduler(params, CFG, max_batch=3, prefill_chunk=4,
                         block_size=4, pool_blocks=1 + 24,
                         replica_id=i, quant_cache=quant)
               for i in range(2)]
        router = Router(dec, prefill_replicas=pre, lease_ms=5000,
                        prompt_threshold=8, wire_mbps=200.0,
                        migrate_preempt=True)
        reqs = _mk_requests(10, rng,
                            prompt_lens=(14, 4, 18, 9), max_news=(8, 6))
        if not quant:
            base = rng.integers(0, CFG.vocab_size, 4).astype(np.int32)
            reqs.append(Request(rid="spec",
                                prompt=np.tile(base, 3)[:10], max_new=8,
                                spec=SpecPolicy("lookup", spec_len=3)))
        try:
            res = router.run(reqs)
        finally:
            router.close()
        for r in reqs:
            np.testing.assert_array_equal(
                res[r.rid]["tokens"], _solo(params, r, quant=quant)), \
                (quant, r.rid)
        for s in pre + dec:
            assert s.cache.leaked_blocks() == 0, (quant, s.replica_id)
            s.cache.check_refcounts()
    snap = _counters()
    assert snap["serve.migration.adopted"] > 0


@pytest.mark.slow
def test_prefix_sharing_survives_migration(params):
    """Two requests sharing a long prompt prefix, both migrated to the
    same decode replica: the second adoption maps the shared leading
    blocks out of the decode pool's radix index instead of duplicating
    them — prefix sharing survives the wire."""
    rng = np.random.default_rng(31)
    shared = rng.integers(0, CFG.vocab_size, 12).astype(np.int32)
    reqs = [Request(rid=f"p{i}",
                    prompt=np.concatenate(
                        [shared, rng.integers(0, CFG.vocab_size, 3)
                         .astype(np.int32)]),
                    max_new=5) for i in range(2)]
    pre = Scheduler(params, CFG, max_batch=2, prefill_chunk=4,
                    block_size=4, role="prefill", replica_id=1)
    dec = Scheduler(params, CFG, max_batch=2, prefill_chunk=4,
                    block_size=4, replica_id=0)
    router = Router([dec], prefill_replicas=[pre], lease_ms=5000,
                    prompt_threshold=1)
    try:
        # serial so the first adoption commits before the second lands
        res = dict(router.run([reqs[0]]))
        res.update(router.run([reqs[1]]))
    finally:
        router.close()
    for r in reqs:
        np.testing.assert_array_equal(res[r.rid]["tokens"],
                                      _solo(params, r))
    snap = _counters()
    assert snap["serve.migration.adopted"] == 2
    # the decode pool shared at least the fully-shared leading blocks
    assert snap["serve.prefix_saved_tokens"] >= 12
    assert pre.cache.leaked_blocks() == 0
    assert dec.cache.leaked_blocks() == 0
    dec.cache.check_refcounts()
