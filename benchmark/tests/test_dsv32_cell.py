"""The DeepSeek-V3.2-Exp cell's own files: ``flops_dsv32.py`` on shapes small
enough to count by hand, the session generator's order, token ids and
warm-up set (``traffic_docqa.py``), and that the two rooflines' reader finds
nothing to read (and does not raise) where the program keeps no such series —
the parent of the PR that added the cell."""

import numpy as np

from benchmark import flops_dsv32 as fd
from benchmark import harness, traffic_docqa as td
from benchmark.readers import kernel_roofline

G = {"n_layers": 5, "index_n_heads": 4, "index_head_dim": 8, "n_heads": 2,
     "qk_nope_dim": 6, "qk_rope_dim": 2, "v_head_dim": 4}


def test_index_scores_count_pairs_and_computed_queries():
    # 100 (query, key) pairs of 3 computed tokens on 5 layers: a product of
    # 8 multiply-adds a head and pair; a query's 4 x 8 values in bf16 and 4
    # weights in f32; a score written in f32 a pair
    need = fd.index_scores({"serve.dsa.prefill_scored_pairs": 100,
                            "serve.prefill_tokens": 3}, G)
    assert need == {"flops": 100 * 2 * 4 * 8,
                    "bytes": 15 * 4 * (8 * 2 + 4) + 100 * 4}


def test_selected_attention_counts_picked_pairs_alone():
    need = fd.selected_attention({"serve.dsa.prefill_selected_keys": 40,
                                  "serve.prefill_tokens": 3}, G)
    assert need == {"flops": 40 * 2 * 2 * (8 + 4),
                    "bytes": 15 * 2 * (8 + 4) * 2}
    # at the published widths: 16.4 kFLOP a scored pair, 81.9 kFLOP a
    # picked pair (128 heads of 192 + 128)
    pub = dict(G, index_n_heads=64, index_head_dim=128, n_heads=128,
               qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128)
    one = {"serve.dsa.prefill_scored_pairs": 1,
           "serve.dsa.prefill_selected_keys": 1, "serve.prefill_tokens": 0}
    assert fd.index_scores(one, pub)["flops"] == 16384
    assert fd.selected_attention(one, pub)["flops"] == 81920


class _Run:
    reduced = type("R", (), {"first": [], "w0": 0.0, "w1": 1e9})()
    device = {"kind": "TPU v5 lite"}
    config = {"flops": "flops_dsv32", "gpt_config": G}


def test_the_reader_returns_nothing_where_the_program_counts_nothing():
    # a parent without the series: no counters at all, or none of these
    assert kernel_roofline.read(_Run(), {}, ["dsa_index_scores"],
                                "index_scores") is None
    counters = {"start": {}, "end": {"x": 1}, "trace_start": {"x": 0}}
    hists = {"start": {}, "end": {"y": {"sum": 1.0}},
             "trace_start": {"y": {"sum": 0.0}}}
    assert kernel_roofline.read(
        _Run(), {"counters": counters, "histograms": hists},
        ["dsa_index_scores"], "index_scores") is None


def _spec(rehearse=False):
    t = harness.load_json(harness.HERE, "traffic",
                          "docqa-reuse-backlog-sat.json")
    return harness.merged(t, t["rehearsal"]) if rehearse else t


def test_every_four_requests_hold_one_first_ask_and_three_later_ones():
    spec = _spec()
    asks = [td.ask_of(i, spec) for i in range(400)]
    assert asks[:4] == [(0, 0), None, None, None]
    assert all(a is not None for a in asks[24:])          # the steady mix
    for n in range(6, 100):
        four = asks[4 * n:4 * n + 4]
        assert [a for _, a in four] == [0, 1, 2, 3]
        assert len({d for d, _ in four}) == 4
    # two asks of one document lie 9 requests apart, each asked 4 times
    where = {}
    for i, da in enumerate(asks):
        if da is not None:
            where.setdefault(da[0], []).append(i)
    assert all(np.diff(v).tolist() == [9, 9, 9]
               for d, v in where.items() if d < 80)


def test_token_ids_are_a_function_of_seed_and_index_and_share_no_part():
    spec, vocab = _spec(rehearse=True), 128
    a = [next(g) for g in [td.requests(spec, 7, vocab, 128)] for _ in range(40)]
    b = td.Backlog(dict(spec, ramp_seconds=0.0), 7, 1.0, vocab, 128).initial
    assert all(np.array_equal(x.prompt, y.prompt) and x.rid == y.rid
               and x.max_new == y.max_new for x, y in zip(a, b))
    other = next(td.requests(spec, 8, vocab, 128))
    assert not np.array_equal(other.prompt[1:], a[0].prompt[1:])
    docs = td.doc_cycle(spec)
    by_doc = {}
    for r in a:
        d, ask = td.ask_of(r.rid, spec)
        n = docs[d % len(docs)]
        by_doc.setdefault(d, []).append((r.prompt[:n], r.prompt[n]))
        assert r.prompt[0] == d % vocab
        assert len(r.prompt) - n == spec["questions"][ask % 2]
    for asks in by_doc.values():
        assert all(np.array_equal(asks[0][0], doc) for doc, _ in asks)
        firsts = [q0 for _, q0 in asks]
        assert len(set(firsts)) == len(firsts)


def test_the_warm_up_holds_every_chunk_length_at_every_width():
    spec = _spec()
    warm = td.warmup_asks(spec, 128, 2048)

    def width(n):
        w = 1
        while w < -(-n // 128):
            w <<= 1
        return w

    first = {(width(d + q + 1), (d + q - 1) % 2048 + 1)
             for d, q, later in warm if not later}
    later = {(width(d + q + 1), q) for d, q, later in warm if later}
    docs = set(td.doc_cycle(spec))
    assert first == {(width(d + 257), (d + 255) % 2048 + 1) for d in docs}
    assert later == {(width(d + q + 1), q) for d in docs for q in (256, 512)}
    assert {w for w, _ in first} == {64, 128, 256}
    assert {t for _, t in first} == {256, 1280}
    # a later ask follows a first ask of its document
    seen = set()
    for d, q, is_later in warm:
        assert not is_later or d in seen
        seen.add(d)
