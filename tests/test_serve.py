"""Continuous-batching serve tier (byteps_tpu/serve, docs/serving.md).

The acceptance bar is EXACTNESS, not closeness: every request served
out of the paged pool — batched with strangers, chunk-prefilled,
preempted and resumed, speculated, or failed over to another replica —
must emit tokens BIT-identical to a solo greedy ``make_generate_fn``
run. Plus the operational pins: zero leaked KV blocks at drain, and
deterministic replica death under the PR 3/5 ``worker:kill`` fault
scope."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from byteps_tpu.common.faults import FaultPlan, parse_fault_spec
from byteps_tpu.common.metrics import get_registry
from byteps_tpu.models import GPTConfig, gpt_init
from byteps_tpu.models.generate import (
    gpt_apply_cached,
    init_cache,
    make_generate_fn,
)
from byteps_tpu.serve import Request, Router, Scheduler, SpecPolicy
from byteps_tpu.serve.paged_cache import (
    PagedKVCache,
    PoolExhausted,
    PoolState,
    make_paged_prefill_fn,
)

CFG = GPTConfig.tiny()


@pytest.fixture(scope="module")
def params():
    return gpt_init(jax.random.PRNGKey(0), CFG)


def _mk_requests(n, rng, spec=None, arrival=None):
    """Mixed prompt/output lengths — the heterogeneity continuous
    batching exists for."""
    reqs = []
    for i in range(n):
        T0 = [4, 9, 14, 6, 11, 5][i % 6]
        mn = [8, 5, 10][i % 3]
        prompt = rng.integers(0, CFG.vocab_size, T0).astype(np.int32)
        reqs.append(Request(rid=f"r{i}", prompt=prompt, max_new=mn,
                            spec=spec,
                            arrival_s=arrival[i] if arrival else 0.0))
    return reqs


def _solo(params, req, quant=False):
    """The golden: this request alone through make_generate_fn."""
    gen = make_generate_fn(CFG, req.max_new, quant_cache=quant)
    out = gen(params, jnp.asarray(req.prompt)[None], jax.random.PRNGKey(0),
              0.0)
    return np.asarray(out)[0]


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _drive(sched, clock, max_iters=5000):
    it = 0
    while not sched.finished:
        sched.step()
        clock.t += 0.005
        it += 1
        assert it < max_iters, "scheduler failed to drain"


# ---- paged cache unit behavior ----------------------------------------------
def test_paged_cache_alloc_free_defrag():
    cache = PagedKVCache(CFG, block_size=8, pool_blocks=9, max_batch=2)
    assert cache.free_blocks == 8          # block 0 reserved for scratch
    cache.register("a")
    cache.register("b")
    cache.ensure("a", 17)                  # 3 blocks
    cache.ensure("b", 8)                   # 1 block
    assert cache.blocks_in_use == 4 and cache.free_blocks == 4
    assert 0 not in cache.table_row("a")[:3]
    # all-or-nothing on exhaustion: nothing allocated by a failed grow
    with pytest.raises(PoolExhausted):
        cache.ensure("b", 8 * 6)
    assert cache.blocks_in_use == 4
    # release returns every block; leak accounting stays zero
    cache.release("a")
    assert cache.free_blocks == 7 and cache.leaked_blocks() == 0
    # defrag compacts live blocks to the lowest ids and preserves tables
    cache.ensure("b", 24)
    before = [cache.state.k[:, b] for b in cache.table_row("b")[:3]]
    cache.defrag()
    row = cache.table_row("b")[:3]
    assert sorted(row) == [1, 2, 3], row
    after = [cache.state.k[:, b] for b in row]
    for x, y in zip(before, after):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert cache.leaked_blocks() == 0
    with pytest.raises(ValueError):
        cache.register("b")                # duplicate rid


def test_submit_validation(params):
    sched = Scheduler(params, CFG, max_batch=2)
    with pytest.raises(ValueError, match="max_seq"):
        sched.submit(Request(rid="too-long",
                             prompt=np.arange(10, dtype=np.int32),
                             max_new=CFG.max_seq))
    with pytest.raises(ValueError, match="max_new"):
        sched.submit(Request(rid="no-new",
                             prompt=np.arange(4, dtype=np.int32),
                             max_new=0))
    with pytest.raises(ValueError, match="greedy-only"):
        sched.submit(Request(rid="spec-sampled",
                             prompt=np.arange(4, dtype=np.int32),
                             max_new=4, temperature=1.0,
                             spec=SpecPolicy("lookup")))


# ---- the prefill chunk against the dense cache it stands for ----------------
def _bits(a):
    """An array's bytes, so NaN poison compares equal to itself."""
    a = np.asarray(a)
    return a.view(f"u{a.dtype.itemsize}")


# jitted like the chunk: op by op, XLA's CPU backend rounds the int8 scales'
# division another way in the last bit
_dense_step = jax.jit(gpt_apply_cached, static_argnames=("cfg", "readout"))


@pytest.mark.parametrize("with_readout", [True, False],
                         ids=["readout", "no_readout"])
@pytest.mark.parametrize("pos0", [8, 6], ids=["on_block", "off_block"])
@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
def test_prefill_chunk_is_the_dense_step_and_touches_only_its_rows(
        params, quant, pos0, with_readout):
    """One chunk of C tokens at ``pos0`` through the paged pool against
    ``gpt_apply_cached`` on the dense cache the table stands for: logits
    and the C written rows bit-equal, and every other slot of a poisoned
    pool — the blocks shared below ``pos0``, the request's own rows past
    the chunk, strangers' blocks, scratch — bit-unchanged."""
    bs, C, NB = 4, 4, 12
    table = np.array([5, 2, 9, 7], np.int32)
    S = table.size * bs
    L, hD = CFG.n_layers, CFG.kv_heads * CFG.head_dim
    toks = np.random.default_rng(3).integers(
        0, CFG.vocab_size, pos0 + C).astype(np.int32)
    _, dense = _dense_step(params, jnp.asarray(toks[None, :pos0]),
                          init_cache(CFG, 1, max_seq=S, quant=quant),
                          cfg=CFG, readout=False)

    # the pool: poison everywhere, the prefix's rows where the table says
    live = np.arange(pos0)
    at = (slice(None), table[live // bs], live % bs)
    fields = {}
    for name in ("k", "v") + (("k_scale", "v_scale") if quant else ()):
        src = np.asarray(getattr(dense, name))[:, 0]      # (L, S, h[, D])
        minor = CFG.kv_heads if name.endswith("scale") else hD
        a = np.full((L, NB, bs, minor),
                    77 if src.dtype == np.int8 else np.nan, src.dtype)
        a[at] = src[:, :pos0].reshape(L, pos0, minor)
        fields[name] = a
    chunk = make_paged_prefill_fn(CFG, bs, C, None, with_readout)
    logits, pool = chunk(
        params, PoolState(**{n: jnp.asarray(a) for n, a in fields.items()}),
        jnp.asarray(toks[None, pos0:]), jnp.int32(pos0), jnp.asarray(table))

    want_logits, want = _dense_step(
        params, jnp.asarray(toks[None, pos0:]), dense, cfg=CFG,
        readout=with_readout)
    if with_readout:
        assert np.array_equal(_bits(logits), _bits(want_logits))
    else:
        assert logits is None and want_logits is None
    new = pos0 + np.arange(C)
    written = np.zeros((NB, bs), bool)
    written[table[new // bs], new % bs] = True
    for name, before in fields.items():
        after = np.asarray(getattr(pool, name))
        rows = np.asarray(getattr(want, name))[:, 0, pos0:pos0 + C]
        assert np.array_equal(
            _bits(after[:, table[new // bs], new % bs]),
            _bits(rows.reshape(L, C, -1))), name
        assert np.array_equal(_bits(after[:, ~written]),
                              _bits(before[:, ~written])), name


# ---- the CI acceptance smoke: continuous admission, bit-exact, no leaks -----
def test_serve_bit_identical_mixed_lengths_continuous(params):
    """6 mixed-length requests admitted CONTINUOUSLY (staggered
    arrivals on a virtual clock, batch smaller than the request count
    so admission interleaves with decode): every request's tokens are
    BIT-identical to its solo make_generate_fn run; zero KV blocks leak
    at drain; the serve.* series saw the traffic."""
    rng = np.random.default_rng(7)
    clock = _FakeClock()
    arrivals = [0.0, 0.0, 0.02, 0.05, 0.08, 0.12]
    reqs = _mk_requests(6, rng, arrival=arrivals)
    sched = Scheduler(params, CFG, max_batch=3, prefill_chunk=8,
                      clock=clock)
    for r in reqs:
        sched.submit(r)
    _drive(sched, clock)
    for r in reqs:
        got = sched.results[r.rid]["tokens"]
        want = _solo(params, r)
        np.testing.assert_array_equal(got, want), r.rid
    assert sched.cache.leaked_blocks() == 0
    # every block is either free or a resident shared-prefix page
    assert (sched.cache.free_blocks + sched.cache.prefix_blocks
            == sched.cache.pool_blocks - 1)
    snap = get_registry().snapshot()
    assert snap["counters"]["serve.admitted"] == 6
    assert snap["counters"]["serve.completed"] == 6
    assert snap["histograms"]["serve.ttft_ms"]["count"] == 6
    assert snap["counters"]["serve.decode_tokens"] > 0
    # every request has latency accounting
    for r in reqs:
        res = sched.results[r.rid]
        assert res["ttft_s"] is not None and res["total_s"] >= 0


def test_prefill_chunking_exact(params):
    """Prompts longer than the prefill chunk are fed in pieces across
    iterations (the long-prompt starvation fix) — tokens unchanged."""
    rng = np.random.default_rng(11)
    reqs = [Request(rid="long0",
                    prompt=rng.integers(0, CFG.vocab_size, 21).astype(
                        np.int32), max_new=8),
            Request(rid="long1",
                    prompt=rng.integers(0, CFG.vocab_size, 17).astype(
                        np.int32), max_new=6)]
    sched = Scheduler(params, CFG, max_batch=2, prefill_chunk=4)
    res = sched.serve(reqs)
    for r in reqs:
        np.testing.assert_array_equal(res[r.rid]["tokens"],
                                      _solo(params, r))
    assert sched.cache.leaked_blocks() == 0


def test_preemption_recompute_on_resume_exact(params):
    """A pool too small for both requests forces a preemption; the
    victim resumes by recomputing prompt + committed tokens and its
    final output is still bit-identical. Zero leaks afterwards."""
    rng = np.random.default_rng(13)
    reqs = [Request(rid=f"p{i}",
                    prompt=rng.integers(0, CFG.vocab_size, 14).astype(
                        np.int32), max_new=10) for i in range(2)]
    sched = Scheduler(params, CFG, max_batch=2, prefill_chunk=8,
                      block_size=4, pool_blocks=1 + 9)
    res = sched.serve(reqs)
    for r in reqs:
        np.testing.assert_array_equal(res[r.rid]["tokens"],
                                      _solo(params, r))
    assert sum(res[r.rid]["preemptions"] for r in reqs) > 0, \
        "pool was large enough that preemption never engaged"
    assert sched.cache.leaked_blocks() == 0
    assert get_registry().snapshot()["counters"]["serve.preempted"] > 0


def test_quant_pool_matches_quant_solo(params):
    """int8 paged pool == int8 dense cache, token for token."""
    rng = np.random.default_rng(17)
    reqs = _mk_requests(4, rng)
    sched = Scheduler(params, CFG, max_batch=4, quant_cache=True)
    res = sched.serve(reqs)
    for r in reqs:
        np.testing.assert_array_equal(res[r.rid]["tokens"],
                                      _solo(params, r, quant=True))
    assert sched.cache.leaked_blocks() == 0


def test_speculative_lookup_exact_and_accepting(params):
    """Prompt-lookup speculation: greedy output identical at any accept
    rate, and on repetitive context the verify rounds number fewer than
    the emitted tokens (i.e. some round committed > 1)."""
    rng = np.random.default_rng(19)
    reqs = []
    for i in range(3):
        base = rng.integers(0, CFG.vocab_size, 4).astype(np.int32)
        prompt = np.tile(base, 3)[:10]
        reqs.append(Request(rid=f"s{i}", prompt=prompt, max_new=10,
                            spec=SpecPolicy("lookup", spec_len=4)))
    sched = Scheduler(params, CFG, max_batch=3, prefill_chunk=16)
    res = sched.serve(reqs)
    rounds = 0
    for r in reqs:
        np.testing.assert_array_equal(res[r.rid]["tokens"],
                                      _solo(params, r))
        rounds += res[r.rid]["spec_rounds"]
    total = sum(r.max_new for r in reqs)
    assert 0 < rounds < total, (rounds, total)
    assert sched.cache.leaked_blocks() == 0
    snap = get_registry().snapshot()
    assert snap["counters"]["serve.spec_rounds"] == rounds
    # spec requests never take plain decode steps (that would desync a
    # draft cache): every post-prefill token rode a spec round, and
    # acceptance made rounds average > 1 committed token
    spec_tok = snap["counters"]["serve.spec_tokens"]
    assert spec_tok >= total - len(reqs), (spec_tok, total)
    assert spec_tok > rounds, (spec_tok, rounds)
    assert snap["counters"]["serve.decode_tokens"] == 0


@pytest.mark.slow
def test_speculative_draft_model_exact(params):
    """Draft-MODEL speculation (make_speculative_generate_fn's proposal
    semantics in-loop): a shallow draft proposes, one verify forward
    per round commits — output still bit-identical to plain greedy."""
    rng = np.random.default_rng(23)
    draft_cfg = GPTConfig(vocab_size=CFG.vocab_size, max_seq=CFG.max_seq,
                          d_model=32, n_heads=2, n_layers=1, d_ff=64)
    draft_params = gpt_init(jax.random.PRNGKey(5), draft_cfg)
    pol = SpecPolicy("draft", spec_len=3, draft_params=draft_params,
                     draft_cfg=draft_cfg)
    reqs = _mk_requests(3, rng, spec=pol)
    sched = Scheduler(params, CFG, max_batch=3)
    res = sched.serve(reqs)
    for r in reqs:
        np.testing.assert_array_equal(res[r.rid]["tokens"],
                                      _solo(params, r))
    assert sched.cache.leaked_blocks() == 0


# ---- tier-1 prefix-cache smoke (docs/serving.md §prefix cache) -------------
def test_prefix_smoke_second_request_skips_shared_blocks(params):
    """Two requests sharing a long prompt prefix: the second maps the
    shared blocks out of the radix index and its prefill SKIPS them
    (serve.prefix_saved_tokens counts the skipped volume); outputs are
    bit-exact vs a cold prefix-off run and vs solo make_generate_fn;
    zero leaked blocks after drain."""
    rng = np.random.default_rng(31)
    shared = rng.integers(0, CFG.vocab_size, 12).astype(np.int32)
    reqs = [Request(rid=f"pc{i}",
                    prompt=np.concatenate(
                        [shared,
                         rng.integers(0, CFG.vocab_size, 3).astype(
                             np.int32)]),
                    max_new=6) for i in range(2)]
    sched = Scheduler(params, CFG, max_batch=2, prefill_chunk=4,
                      block_size=4)
    res = {}
    for r in reqs:                       # sequential: #2 sees #1's commits
        res.update(sched.serve([r]))
    # snapshot BEFORE the cold twin runs (the registry is process-wide)
    snap = get_registry().snapshot()["counters"]
    cold = Scheduler(params, CFG, max_batch=2, prefill_chunk=4,
                     block_size=4, prefix_cache=False)
    for r in reqs:
        want = _solo(params, r)
        np.testing.assert_array_equal(res[r.rid]["tokens"], want)
    cold_res = cold.serve([Request(rid="cold", prompt=reqs[1].prompt,
                                   max_new=6)])
    np.testing.assert_array_equal(res[reqs[1].rid]["tokens"],
                                  cold_res["cold"]["tokens"])
    assert snap["serve.prefix_hits"] >= 1
    # the hit skipped at least the fully-shared blocks (3 × 4 tokens)
    assert snap["serve.prefix_saved_tokens"] >= 12
    # the skipped chunks were never computed: total prefilled tokens ==
    # total prompt tokens minus exactly the saved volume
    assert snap["serve.prefill_tokens"] == \
        sum(len(r.prompt) for r in reqs) - snap["serve.prefix_saved_tokens"]
    assert snap["serve.prefix_misses"] == 1
    sched.cache.check_refcounts()
    assert sched.cache.leaked_blocks() == 0
    assert (sched.cache.free_blocks + sched.cache.prefix_blocks
            == sched.cache.pool_blocks - 1)


# ---- replica death: the router's lease/epoch failover -----------------------
def test_replica_death_requeues_to_survivor(params):
    """Deterministic worker:kill at the victim replica's 4th scheduler
    op: the lease expires, the epoch bumps exactly once, every in-flight
    request re-queues to the survivor, outputs stay bit-identical, and
    the survivor drains leak-free."""
    rng = np.random.default_rng(29)
    plan = FaultPlan(parse_fault_spec("worker:kill@op=4"), seed=0,
                     worker_id=1)
    r0 = Scheduler(params, CFG, max_batch=3, replica_id=0)
    r1 = Scheduler(params, CFG, max_batch=3, replica_id=1,
                   fault_plan=plan)
    router = Router([r0, r1], lease_ms=50)
    reqs = _mk_requests(6, rng)
    res = router.run(reqs)
    for r in reqs:
        np.testing.assert_array_equal(res[r.rid]["tokens"],
                                      _solo(params, r))
    assert router.epoch == 1
    assert r1.dead and router.live_replicas() == [0]
    assert r0.cache.leaked_blocks() == 0
    # the victim's share finished on the survivor, stamped epoch 1
    moved = [r.rid for r in reqs if res[r.rid]["replica"] == 0
             and res[r.rid]["epoch"] == 1]
    assert moved, "no request completed on the survivor after the bump"
    snap = get_registry().snapshot()
    assert snap["counters"]["serve.router.evictions"] == 1
    assert snap["counters"]["serve.router.requeued"] >= 1


# ---- offered-load sweep (the bench leg), slow ------------------------------
@pytest.mark.slow
def test_bench_serve_quick_sweep():
    """bench.py --mode serve end to end at a toy size: artifact shape,
    latency percentiles present, serve >= sequential at saturation
    (the real >= 2x bar is the checked-in BENCH_serve.json's trend
    floor; a CI box only pins structure + sanity)."""
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    import bench

    res = bench.bench_serve(reps=1, n_requests=6, quick=True)
    assert res["unit"] == "x serve vs sequential tokens/s"
    assert res["value"] > 0
    sat = res["results"]["saturation"]
    for k in ("ttft_ms_p50", "ttft_ms_p99", "token_ms_p50",
              "token_ms_p99", "tokens_per_s"):
        assert k in sat, k
    assert res["sequential"]["sec_med"] > 0
    assert "telemetry" in res
    # shared-prefix race leg: both sides present, speedup computed (the
    # real >= 2x bar is the checked-in artifact's trend floor; the leg
    # itself asserts on/off bit-exactness in-run)
    assert res["prefix_ttft_p50_speedup"] > 0
    assert res["prefix_ttft_p99_speedup"] > 0
    for leg in ("prefix_shared_on", "prefix_shared_off"):
        assert res["results"][leg]["ttft_ms_p50"] > 0, leg
    # disaggregation legs: race structure present, speedup computed,
    # migrate-don't-evict eliminated the recompute bill (the real
    # >= 1.5x / ~1.0 bars are the checked-in artifact's trend floors;
    # both legs assert bit-exactness in-run)
    assert res["disagg_ttft_p99_speedup"] > 0
    assert res["migrate_recompute_saved"] == 1.0
    race = res["results"]["disagg_race"]
    for side in ("disagg", "colocated"):
        assert race[side]["ttft_ms_p99_short"] > 0, side
    assert res["results"]["migrate_preempt"]["off"]["recompute_tokens"] > 0
    assert res["results"]["migrate_preempt"]["on"]["migrated_requests"] >= 1
