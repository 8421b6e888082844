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


# ---- a step's tokens are read one step late (docs/serving.md §the iteration) -
# long enough for prompts of 16-200 with 40 new tokens; the matrices x8 so
# that greedy decoding does not settle on one token
BIG = GPTConfig(vocab_size=256, max_seq=256, d_model=64, n_heads=4,
                n_layers=2, d_ff=128)
MIXED = dict(lens=[16, 200, 47, 120, 33, 64, 150, 21, 90, 180],
             news=[1, 2, 8, 40, 40, 8, 2, 1, 40, 8],
             temps=[0.0, 0.8, 0.0, 0.8, 0.0, 0.8, 0.0, 0.8, 0.0, 0.0])
# what the parent of PR 36 (commit 6312baa: every step's tokens read before
# the next is issued) served for _mixed() on the same scheduler arguments
PARENT_EMITTED = {
    0: [76], 1: [200, 137], 2: [143, 58, 149, 139, 140, 123, 222, 2],
    3: [203, 39, 173, 72, 49, 203, 203, 179, 36, 121, 75, 75, 177, 159, 213,
        91, 132, 203, 234, 134, 123, 175, 218, 203, 21, 103, 167, 102, 6, 100,
        163, 137, 116, 191, 97, 67, 60, 86, 235, 237],
    4: [203, 116, 31, 27, 31, 31, 31, 31, 31, 31, 31, 31, 31, 31, 31, 31, 31,
        31, 31, 31, 31, 31, 31, 222, 116, 116, 203, 203, 218, 218, 218, 218,
        218, 203, 203, 134, 95, 95, 122, 218],
    5: [6, 18, 128, 203, 99, 0, 192, 53], 6: [122, 29], 7: [102],
    8: [155, 95, 58, 200, 95, 120, 58, 58, 58, 58, 31, 27, 102, 167, 134,
        194, 72, 191, 64, 172, 203, 114, 123, 222, 222, 28, 222, 116, 116,
        179, 222, 141, 222, 31, 36, 50, 200, 95, 159, 123],
    9: [207, 187, 86, 86, 86, 86, 137, 203]}


@pytest.fixture(scope="module")
def big_params():
    return jax.tree_util.tree_map(
        lambda w: w * 8 if w.ndim >= 2 else w,
        gpt_init(jax.random.PRNGKey(0), BIG))


def _mixed(only=None, over=None):
    rng = np.random.default_rng(36)
    reqs = [Request(rid=i, max_new=m, temperature=t, seed=100 + i,
                    prompt=rng.integers(0, BIG.vocab_size, n)
                    .astype(np.int32))
            for i, (n, m, t) in enumerate(zip(
                MIXED["lens"], MIXED["news"], MIXED["temps"]))]
    reqs = [r for r in reqs if only is None or r.rid in only]
    for r in reqs:
        for k, v in (over or {}).get(r.rid, {}).items():
            setattr(r, k, v)
    return reqs


def _big_solo(big_params, req):
    gen = make_generate_fn(BIG, req.max_new)
    return np.asarray(gen(big_params, jnp.asarray(req.prompt)[None],
                          jax.random.PRNGKey(0), 0.0))[0, len(req.prompt):]


def _counters():
    return get_registry().snapshot()["counters"]


@pytest.fixture(scope="module")
def mixed_served(big_params):
    sched = Scheduler(big_params, BIG, max_batch=3, prefill_chunk=32,
                      block_size=8)
    res = sched.serve(_mixed())
    return res, sched.cache.leaked_blocks()


@pytest.mark.parametrize("rid", range(len(MIXED["lens"])))
def test_mixed_batch_serves_the_parents_tokens(big_params, mixed_served, rid):
    """Prompts of 16-200, max_new 1, 2, 8 and 40, greedy and sampled at 0.8
    in one batch of three rows: each request's tokens are those the parent
    commit served, and a greedy one's are its solo static-cache run's."""
    res, leaked = mixed_served
    (req,) = _mixed(only={rid})
    assert res[rid]["emitted"].tolist() == PARENT_EMITTED[rid]
    if req.temperature == 0.0:
        np.testing.assert_array_equal(res[rid]["emitted"],
                                      _big_solo(big_params, req))
    assert len(res[rid]["token_s"]) == req.max_new
    assert leaked == 0


@pytest.mark.parametrize("max_new,steps,drains", [(1, 0, 0), (2, 1, 1)])
def test_a_run_that_never_decodes_and_one_whose_only_step_is_its_last(
        big_params, max_new, steps, drains):
    """max_new 1 ends at its prefill's token: no decode step. max_new 2
    takes one, known to be its last when it is issued: it is read by the
    next step(), which issues nothing."""
    (req,) = _mixed(only={2}, over={2: {"max_new": max_new}})
    sched = Scheduler(big_params, BIG, max_batch=3, prefill_chunk=32,
                      block_size=8)
    res = sched.serve([req])
    assert res[2]["emitted"].tolist() == PARENT_EMITTED[2][:max_new]
    snap = get_registry().snapshot()
    assert snap["histograms"]["serve.batch_occupancy"]["count"] == steps
    assert snap["counters"]["serve.pipeline_drains.idle"] == drains
    assert snap["counters"]["serve.decode_steps_overlapped"] == 0
    assert sched.cache.leaked_blocks() == 0


def test_eos_in_the_middle_drops_one_row_of_the_step_already_issued(
        big_params):
    """Request 2's fourth greedy token as its eos_id: it finishes there,
    the row it had in the step issued before that token was read is
    dropped, its blocks come back and its neighbours' tokens do not
    move."""
    reqs = _mixed(only={2, 4, 8},
                  over={2: {"eos_id": PARENT_EMITTED[2][3]}})
    sched = Scheduler(big_params, BIG, max_batch=3, prefill_chunk=32,
                      block_size=8)
    res = sched.serve(reqs)
    assert res[2]["emitted"].tolist() == PARENT_EMITTED[2][:4]
    for rid in (4, 8):
        assert res[rid]["emitted"].tolist() == PARENT_EMITTED[rid]
    snap = _counters()
    assert snap["serve.decode_rows_dropped"] == 1
    assert snap["serve.decode_tokens"] == 3 + 39 + 39
    assert sched.cache.leaked_blocks() == 0
    assert sched.finished


def test_preemption_reads_the_unread_step_first(big_params):
    """A pool too small for two growing requests: when a block cannot be
    had with a step unread, the step is read before a victim is picked, so
    the victim's recompute input is every token the device picked for it;
    both requests still end on the parent's tokens."""
    reqs = _mixed(only={4, 8})
    sched = Scheduler(big_params, BIG, max_batch=2, prefill_chunk=32,
                      block_size=8, pool_blocks=1 + 24)
    seen = []
    preempt = sched._preempt

    def watched(run):
        seen.append((sched._flight, sched._first, len(run.emitted)))
        preempt(run)
        assert run.full_input.tolist() == (
            run.req.prompt.tolist() + PARENT_EMITTED[run.req.rid][:seen[-1][2]])

    sched._preempt = watched
    res = sched.serve(reqs)
    assert seen and all(f is None and p is None for f, p, _ in seen)
    assert sum(res[r]["preemptions"] for r in (4, 8)) == len(seen)
    for rid in (4, 8):
        assert res[rid]["emitted"].tolist() == PARENT_EMITTED[rid]
    assert _counters()["serve.pipeline_drains.preempt"] >= 1
    assert sched.cache.leaked_blocks() == 0


def test_unread_tokens_keep_the_scheduler_unfinished(big_params):
    """A lone request: while its last token is picked and unread,
    ``finished`` is False; the next step() issues nothing, reads and
    commits it, and says it made progress."""
    from byteps_tpu.common.tracing import get_tracer

    (req,) = _mixed(only={9})
    sched = Scheduler(big_params, BIG, max_batch=3, prefill_chunk=32,
                      block_size=8)
    sched.submit(req)
    run = sched._runs[9]
    while sched._tokens_picked(run) < req.max_new:
        assert sched.step()
    assert sched._flight is not None and len(run.emitted) == req.max_new - 1
    assert not sched.finished and 9 not in sched.results
    issued = sum(1 for e in get_tracer().spans()
                 if e[0] in ("serve.decode_dispatch",
                             "serve.prefill_dispatch"))
    assert sched.step()
    assert sched.finished and sched._flight is None
    assert sched.results[9]["emitted"].tolist() == PARENT_EMITTED[9]
    assert issued == sum(1 for e in get_tracer().spans()
                         if e[0] in ("serve.decode_dispatch",
                                     "serve.prefill_dispatch"))
    assert _counters()["serve.pipeline_drains.idle"] == 1
    assert not sched.step()                 # and nothing is left to do


def test_every_decode_step_is_overlapped_or_drained(big_params):
    """A backlog over three rows: each decode step either has the next one
    issued before it is read, or is read with none behind it."""
    sched = Scheduler(big_params, BIG, max_batch=3, prefill_chunk=32,
                      block_size=8)
    sched.serve(_mixed())
    snap = get_registry().snapshot()
    steps = snap["histograms"]["serve.batch_occupancy"]["count"]
    c = snap["counters"]
    assert c["serve.decode_steps_overlapped"] \
        == steps - c["serve.pipeline_drains"]
    assert c["serve.pipeline_drains"] == c["serve.pipeline_drains.idle"]
    assert c["serve.decode_steps_overlapped"] > 0.9 * steps
    assert c["serve.decode_tokens"] == sum(MIXED["news"]) - len(MIXED["news"])
    assert c["serve.decode_rows_dropped"] == 0


def test_adapter_rows_and_a_speculative_request_beside_plain_decoders(
        params):
    """Rows that gather adapter slabs, a base-model row and a prompt-lookup
    speculative request (which reads its tokens at once, every round) in one
    scheduler: each greedy output is its solo run's, as on the parent."""
    from byteps_tpu.models.lora import lora_init
    from byteps_tpu.serve.adapter_pool import AdapterPool

    pool = AdapterPool(CFG, n_slots=3, rank_bucket=4, targets=("wq", "wv"))
    for i, rank in enumerate((2, 4)):
        ad = lora_init(jax.random.PRNGKey(10 + i), CFG, rank, ("wq", "wv"))
        for bi, blk in enumerate(ad["blocks"]):
            for t in blk:
                blk[t]["b"] = 0.02 * jax.random.normal(
                    jax.random.fold_in(jax.random.PRNGKey(10 + i), bi),
                    blk[t]["b"].shape)
        pool.register(f"a{i}", ad)
    rng = np.random.default_rng(5)
    motif = rng.integers(0, CFG.vocab_size, 5).astype(np.int32)
    reqs = [Request(rid="a0", prompt=rng.integers(0, 256, 9).astype(np.int32),
                    max_new=10, adapter="a0", tenant="t0"),
            Request(rid="a1", prompt=rng.integers(0, 256, 13).astype(np.int32),
                    max_new=7, adapter="a1", tenant="t1"),
            Request(rid="base", prompt=rng.integers(0, 256, 6)
                    .astype(np.int32), max_new=12),
            Request(rid="spec", prompt=np.tile(motif, 3), max_new=9,
                    spec=SpecPolicy("lookup", spec_len=3))]
    sched = Scheduler(params, CFG, max_batch=3, block_size=8, pool_blocks=40,
                      prefill_chunk=4, adapter_pool=pool)
    res = sched.serve(list(reqs))
    for r in reqs:
        golden = params if r.adapter is None else pool.graft(params, r.adapter)
        np.testing.assert_array_equal(res[r.rid]["tokens"], _solo(golden, r))
    assert res["spec"]["spec_rounds"] > 0
    assert _counters()["serve.decode_steps_overlapped"] > 0
    assert sched.cache.leaked_blocks() == 0 and pool.leaked_slots() == 0
