"""The cycle builder and the balanced order (run by hand:
``python -m pytest benchmark/tests -q``; not part of tier-1)."""

import hashlib

import numpy as np
import pytest

from benchmark import harness, traffic_gen

HERE = harness.HERE


def _spec(name):
    t = harness.load_json(HERE, "traffic", name + ".json")
    return harness.merged(
        harness.load_json(HERE, "traffic", t["multiset"] + ".json"), t)


def test_quantiles_are_seed_free_and_clipped():
    p = _spec("chat-open-0.8knee")["prompt"]
    a = traffic_gen.lognormal_quantiles(40, **p)
    assert a == traffic_gen.lognormal_quantiles(40, **p) == sorted(a)
    assert min(a) >= p["min"] and max(a) <= p["max"]
    assert all(x % p["round_to"] == 0 for x in a)
    # the median of the log-normal sits in the middle
    assert abs(a[20] - p["median"]) <= p["round_to"]


def test_same_requests_for_two_seeds_but_other_tokens_and_jitter():
    spec = _spec("chat-open-0.8knee")
    runs = [traffic_gen.chat_schedule(spec, seed, 50.0, 50257, 1024)
            for seed in (1, 2147483999)]
    shapes = [[(len(r.prompt), r.max_new, r.measured) for r in run]
              for run in runs]
    assert shapes[0] == shapes[1]          # the same work in the same order
    assert not np.array_equal(runs[0][-1].prompt, runs[1][-1].prompt)
    assert [r.due_s for r in runs[0]] != [r.due_s for r in runs[1]]
    rate = spec["arrivals"]["rate_per_s"]
    ramp = spec["ramp_seconds"]
    for run in runs:
        assert all(len(r.prompt) + r.max_new <= 1024 for r in run)
        due = [r.due_s for r in run]
        assert due == sorted(due) and due[-1] < ramp + 50.0 + 1.0 / rate
        assert abs(sum(r.measured for r in run) - rate * 50.0) <= 1
        assert not run[0].measured and run[-1].measured
    cycle = traffic_gen.chat_cycle(spec)
    assert [(len(r.prompt), r.max_new) for r in runs[0][:len(cycle)]] == cycle


def test_the_cycle_is_seed_free_and_balanced():
    spec = _spec("chat-backlog-sat")
    cycle = traffic_gen.chat_cycle(spec)
    assert cycle == traffic_gen.chat_cycle(spec) and len(cycle) == 32
    g = spec["group"]
    for part, key in ((0, "prompt"), (1, "output")):
        values = traffic_gen.lognormal_quantiles(len(cycle), **spec[key])
        assert sorted(c[part] for c in cycle) == values
        strata = np.array_split(np.asarray(values), g)
        for j in range(0, len(cycle), g):
            for k, v in enumerate(sorted(c[part] for c in cycle[j:j + g])):
                assert strata[k][0] <= v <= strata[k][-1]


def test_every_group_of_eight_is_balanced():
    spec = _spec("chat-backlog-sat")
    g = spec["group"]
    n = 128
    for key in ("prompt", "output"):
        values = traffic_gen.lognormal_quantiles(n, **spec[key])
        strata = np.array_split(np.asarray(values), g)
        order = traffic_gen.balanced_order(
            values, g, np.random.default_rng(5))
        assert sorted(order) == values
        for j in range(0, n, g):
            run = sorted(order[j:j + g])
            # one from each stratum: the k-th smallest lies in stratum k
            for k, v in enumerate(run):
                assert strata[k][0] <= v <= strata[k][-1]


def test_backlog_is_due_at_zero_and_outlasts_the_run():
    spec = _spec("chat-backlog-sat")
    run = traffic_gen.chat_schedule(spec, 3, 48.0, 50257, 1024)
    assert all(r.due_s == 0.0 and r.measured for r in run)
    assert len(run) == int(np.ceil(
        spec["arrivals"]["requests_per_second_of_run"]
        * (spec["ramp_seconds"] + 48.0)))


def _digest(run):
    h = hashlib.sha256()
    for r in run:
        h.update(repr((r.rid, r.due_s, r.max_new, r.measured)).encode())
        h.update(r.prompt.tobytes())
    return len(run), h.hexdigest()


# what chat_schedule(spec, seed, 50.0, 50257, 1024) gave at PR 25, before a
# backlog could be topped up: rids, due times, shapes and prompt bytes
PR25 = {
    ("chat-backlog-sat", 1): (260, "1d50d6207a51f4bd02df0edc0892b589"
                                   "26c1100a01e6e330777342eb34b1aae6"),
    ("chat-backlog-sat", 2147483999): (260, "e1dd651ddbee31a61bbdcde5f0d91beb"
                                            "6962ff0b1a099f90e44461c9d2807ae9"),
    ("chat-open-0.8knee", 1): (84, "548b813bada1c0262ef06e3880bb5599"
                                   "a0846fcd2fe935608300543c0695d03b"),
    ("chat-open-0.8knee", 2147483999): (84, "044c20faa5505c21737a80e0c0482a19"
                                            "b808f8ff0a69f775ea2f7e1ed0bd668f"),
}


@pytest.mark.parametrize("mix,seed", sorted(PR25))
def test_the_requests_known_up_front_are_those_of_pr25(mix, seed):
    run = traffic_gen.chat_schedule(_spec(mix), seed, 50.0, 50257, 1024)
    assert _digest(run) == PR25[mix, seed]
    if mix == "chat-backlog-sat":
        assert _digest(traffic_gen.Backlog(
            _spec(mix), seed, 50.0, 50257, 1024).initial) == PR25[mix, seed]


@pytest.mark.parametrize("waiting,want", [
    (400, 0), (129, 0), (128, 0),          # at queued_min: not yet
    (127, 32), (96, 32), (95, 64), (1, 128), (0, 128)])
def test_top_up_fires_under_queued_min_in_whole_cycles(waiting, want):
    assert traffic_gen.top_up(waiting, 128, 32) == want
    assert (waiting + want >= 128) and want % 32 == 0


def test_a_refill_continues_where_the_last_submission_stopped():
    spec = _spec("chat-backlog-sat")
    qmin = spec["arrivals"]["queued_min"]
    cycle = traffic_gen.chat_cycle(spec)
    assert qmin == 128 and len(cycle) == spec["cycle"] == 32
    b = traffic_gen.Backlog(spec, 7, 50.0, 50257, 1024)
    assert len(b.initial) == 260 and b.refill(qmin) == []
    first, second = b.refill(qmin - 1), b.refill(qmin - 40)
    assert (len(first), len(second)) == (32, 64)
    made = b.initial + first + second
    assert [r.rid for r in made] == list(range(260 + 96))
    # 260 is not a multiple of 32: the refill goes on at 260 mod 32
    assert [(len(r.prompt), r.max_new) for r in made] == \
        [cycle[i % 32] for i in range(len(made))]
    assert all(r.due_s == 0.0 and r.prompt.dtype == np.int32
               and r.prompt.max() < 50257 for r in made)
    # request i is a function of the seed and i alone, however it was
    # reached: one refill of three cycles gives the same 96
    c = traffic_gen.Backlog(spec, 7, 50.0, 50257, 1024)
    again = c.refill(qmin - 65)
    assert len(again) == 96 and all(
        x.rid == y.rid and np.array_equal(x.prompt, y.prompt)
        for x, y in zip(first + second, again))
    other = traffic_gen.Backlog(spec, 8, 50.0, 50257, 1024).refill(0)
    assert not np.array_equal(other[0].prompt, first[0].prompt)


def test_the_rehearsal_has_a_queued_min_of_its_own():
    spec = _spec("chat-backlog-sat")
    small = harness.merged(spec, spec["rehearsal"])
    assert 0 < small["arrivals"]["queued_min"] < \
        spec["arrivals"]["queued_min"]
    # a mix without the parameter is never refilled
    del small["arrivals"]["queued_min"]
    assert traffic_gen.Backlog(small, 1, 3.0, 256, 128).refill(0) == []


def test_warmup_touches_every_program_the_multiset_can_need():
    spec = _spec("chat-open-0.8knee")
    bs, chunk = 16, 32
    shapes = traffic_gen.warmup_shapes(spec, bs, chunk, 1024)

    def width(tokens):
        blocks, w = -(-tokens // bs), 1
        while w < blocks:
            w *= 2
        return w

    def programs(plen, new):
        out, w = set(), width(plen + 1)
        done = 0
        while done < plen:
            c = min(chunk, plen - done)
            done += c
            out.add(("prefill", c, done == plen, w))
        out |= {("decode", width(n + 1)) for n in range(plen, plen + new - 1)}
        return out

    warmed = set().union(*(programs(p, n) for p, n in shapes))
    for n in (32, 40):
        for p in traffic_gen.lognormal_quantiles(n, **spec["prompt"]):
            need = {x for x in programs(p, 1) if x[0] == "prefill"}
            assert need <= warmed, (p, need - warmed)
    assert {("decode", w) for w in (2, 4, 8, 16, 32, 64)} <= warmed


def test_train_batches_repeat_from_the_seed():
    a = next(traffic_gen.train_batches(2147483999, 50257, 2, 8))
    b = next(traffic_gen.train_batches(2147483999, 50257, 2, 8))
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[0][:, 1:],
                                                         a[1][:, :-1])
