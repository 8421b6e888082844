"""The spans the serve scheduler and the train-step wrapper record on the
tracer's always-on ring (docs/observability.md §spans): what each covers,
how they nest, and that the per-request phases add up to the latencies the
results carry."""

import collections
import math

import numpy as np
import pytest

import jax

from byteps_tpu.common.metrics import get_registry
from byteps_tpu.common.tracing import get_tracer
from byteps_tpu.models import GPTConfig, gpt_init
from byteps_tpu.serve import Request, Scheduler

CFG = GPTConfig.tiny()
CHUNK = 8
ITERATION_CHILDREN = {
    "serve.admit", "serve.prefill_dispatch", "serve.prefill_sync",
    "serve.decode_pack", "serve.decode_dispatch", "serve.decode_sync",
    "serve.commit", "serve.spec_round"}


@pytest.fixture(scope="module")
def params():
    return gpt_init(jax.random.PRNGKey(0), CFG)


def _requests(lengths, max_new=5, seed=3):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, max_new=max_new,
                    prompt=rng.integers(0, CFG.vocab_size, n)
                    .astype(np.int32))
            for i, n in enumerate(lengths)]


@pytest.fixture
def served(params):
    """Four requests through a two-slot scheduler, which admits three at
    once (the fourth waits for a slot): results and the ring."""
    reqs = _requests([5, 12, 19, 7])
    sched = Scheduler(params, CFG, max_batch=2, prefill_chunk=CHUNK,
                      block_size=4)
    results = sched.serve(reqs)
    return reqs, results, get_tracer().spans()


def _by_name(ring):
    out = collections.defaultdict(list)
    for e in ring:
        out[e[0]].append(e)
    return out


def test_queued_plus_prefill_is_ttft_to_the_last_bit(served):
    reqs, results, ring = served
    spans = _by_name(ring)
    for r in reqs:
        (q,) = [e for e in spans["serve.request.queued"] if e[5] == (r.rid,)]
        (p,) = [e for e in spans["serve.request.prefill"]
                if e[5] == (r.rid,)]
        (d,) = [e for e in spans["serve.request.decode"] if e[5] == (r.rid,)]
        assert q[2] + p[2] == results[r.rid]["ttft_s"]
        assert q[2] + p[2] + d[2] == results[r.rid]["total_s"]
        assert q[1] + q[2] == p[1] and p[1] + p[2] == d[1]   # contiguous
    # the fourth request found no room and waited for a whole request
    waits = {e[5][0]: e[2] for e in spans["serve.request.queued"]}
    assert waits[3] > 10 * waits[0]
    hist = get_registry().snapshot()["histograms"]
    assert hist["serve.queue_wait_ms"]["count"] == 4
    assert hist["serve.prefill_ms"]["count"] == 4
    assert math.isclose(
        hist["serve.queue_wait_ms"]["sum"] + hist["serve.prefill_ms"]["sum"],
        hist["serve.ttft_ms"]["sum"], rel_tol=1e-9)


def test_children_lie_inside_their_iteration_and_do_not_overlap(served):
    """Every lane span is a child of its iteration and none overlaps
    another, in the new order too: ``serve.decode_sync`` and
    ``serve.commit`` of the step before sit after ``serve.decode_dispatch``
    in the same iteration. (A read forced from inside ``serve.decode_pack``
    by a preemption nests there instead; this traffic has none.)"""
    _, _, ring = served
    iterations = {e[3]: e for e in ring if e[0] == "serve.iteration"}
    assert [e[5][0] for e in iterations.values()] == \
        list(range(1, len(iterations) + 1))
    children = collections.defaultdict(list)
    for e in ring:
        if e[0] in ITERATION_CHILDREN:
            assert e[4] in iterations, e      # its parent is an iteration
            children[e[4]].append(e)
        elif e[0].startswith("serve.request."):
            assert e[4] == 0
    assert children
    for sid, kids in children.items():
        _, t0, dur, *_ = iterations[sid]
        kids.sort(key=lambda e: e[1])
        assert kids[0][1] >= t0 and kids[-1][1] + kids[-1][2] <= t0 + dur
        for a, b in zip(kids, kids[1:]):
            assert a[1] + a[2] <= b[1], (a, b)


def test_a_step_is_committed_after_the_next_one_was_issued(served):
    """The order of an iteration: the packed step is issued, then the step
    BEFORE it is read (``serve.decode_sync``) and committed
    (``serve.commit``). So step n's commit starts after step n+1's dispatch
    has ended, unless nothing was left to decode and n was read with no
    step behind it; a final chunk's first token is read last of all."""
    _, _, ring = served
    spans = _by_name(ring)
    issues = sorted(spans["serve.decode_dispatch"], key=lambda e: e[1])
    commits = sorted(spans["serve.commit"], key=lambda e: e[1])
    syncs = sorted(spans["serve.decode_sync"], key=lambda e: e[1])
    assert len(issues) == len(commits) == len(syncs) > 0
    c = get_registry().snapshot()["counters"]
    overlapped = 0
    for n, (commit, sync) in enumerate(zip(commits, syncs)):
        assert sync[1] + sync[2] <= commit[1]
        assert issues[n][1] + issues[n][2] <= sync[1]     # its own step
        if n + 1 < len(issues) and issues[n + 1][4] == commit[4]:
            # read in the iteration that issued the step after it
            assert issues[n + 1][1] + issues[n + 1][2] <= sync[1]
            overlapped += 1
        else:
            # read with no step behind it: that iteration issued none
            assert not any(e[4] == commit[4] for e in issues)
    assert overlapped == c["serve.decode_steps_overlapped"]
    assert len(issues) - overlapped == c["serve.pipeline_drains"] > 0
    by_iteration = collections.defaultdict(list)
    for e in ring:
        if e[0] in ITERATION_CHILDREN:
            by_iteration[e[4]].append(e)
    for first in spans["serve.prefill_sync"]:
        last = max(by_iteration[first[4]], key=lambda e: e[1])
        assert last is first


def test_one_prefill_dispatch_per_chunk(served):
    reqs, _, ring = served
    chunks = collections.Counter(
        e[5][0] for e in ring if e[0] == "serve.prefill_dispatch")
    finals = collections.Counter(
        e[5][0] for e in ring
        if e[0] == "serve.prefill_dispatch" and e[5][3])
    for r in reqs:
        assert chunks[r.rid] == math.ceil(len(r.prompt) / CHUNK)
        assert finals[r.rid] == 1
    assert sum(1 for e in ring if e[0] == "serve.prefill_sync") == len(reqs)
    # every decode step: pack, dispatch, and one sync and commit (a step
    # later); an iteration packs whether or not it finds a row to decode
    n = sum(1 for e in ring if e[0] == "serve.decode_dispatch")
    assert n > 0
    assert sum(1 for e in ring if e[0] == "serve.decode_pack") >= n
    for name in ("serve.decode_sync", "serve.commit"):
        assert sum(1 for e in ring if e[0] == name) == n


def test_preempted_request_gets_a_second_queued_span(params):
    reqs = _requests([14, 14], max_new=10, seed=13)
    sched = Scheduler(params, CFG, max_batch=2, prefill_chunk=8,
                      block_size=4, pool_blocks=1 + 9)
    results = sched.serve(reqs)
    victims = [r.rid for r in reqs if results[r.rid]["preemptions"]]
    assert victims, "pool was large enough that preemption never engaged"
    spans = _by_name(get_tracer().spans())
    for rid in victims:
        queued = [e[5] for e in spans["serve.request.queued"]
                  if e[5][0] == rid]
        assert queued[0] == (rid,) and queued[1] == (rid, "resumed")
        cut = [e for n in ("serve.request.prefill", "serve.request.decode")
               for e in spans[n] if e[5] == (rid, "preempted")]
        assert len(cut) == results[rid]["preemptions"]
        # whatever happened in between, the phases tile the request's life
        mine = sorted((e for n in ("queued", "prefill", "decode")
                       for e in spans[f"serve.request.{n}"]
                       if e[5][0] == rid), key=lambda e: e[1])
        for a, b in zip(mine, mine[1:]):
            assert a[1] + a[2] == b[1]
        assert math.isclose(sum(e[2] for e in mine),
                            results[rid]["total_s"], rel_tol=1e-12)


def test_train_dispatch_once_per_call_of_a_factory_built_step():
    import optax

    from byteps_tpu.models.train import make_gpt_train_step
    from byteps_tpu.parallel import MeshAxes, make_mesh

    mesh = make_mesh(MeshAxes(dp=1), devices=jax.devices()[:1])
    step, p, opt, bsh = make_gpt_train_step(CFG, mesh, optax.sgd(0.1))
    tok = jax.device_put(np.zeros((2, 16), np.int32), bsh)
    for _ in range(3):
        loss, p, opt = step(p, opt, tok, tok)
    jax.block_until_ready(loss)
    spans = [e for e in get_tracer().spans() if e[0] == "train.dispatch"]
    assert len(spans) == 3
    assert all(e[2] > 0 for e in spans)
