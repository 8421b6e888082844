"""The cycle builder and the balanced order (run by hand:
``python -m pytest benchmark/tests -q``; not part of tier-1)."""

import numpy as np

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
    assert all(r.due_s == 0.0 for r in run)
    assert len(run) == int(np.ceil(
        spec["arrivals"]["requests_per_second_of_run"]
        * (spec["ramp_seconds"] + 48.0)))


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
