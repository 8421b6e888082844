"""Sessions over shared documents: the generator of a ``docqa`` mix, beside
``traffic_gen.py`` (whose quantiles, balanced order, request type and
top-up rule it uses as they stand).

A mix's ``documents`` give a seed-free cycle of document lengths, its
``questions`` the lengths of the fresh tokens an ask appends (ask ``a`` takes
``questions[a % len]``), ``asks`` how often a document is asked, ``output`` a
seed-free cycle of ``cycle`` answer lengths (one a request of the cycle).

**Order, staggered so that every ``asks`` consecutive requests hold one first
ask and ``asks - 1`` later ones: request ``asks · n + a`` is ask ``a`` of
document ``n - stride · a``** (left out while that is negative), so two asks
of one document lie ``asks · stride + 1`` requests apart: the earlier one
has prefilled and committed its blocks when the later one is admitted.

Token ids are a pure function of the seed and the document (or the document
and the ask): document ``d`` begins with token ``d mod vocab`` and the
``asks`` questions of a document begin with ``asks`` different tokens, so no
block is shared IN PART by chance — a partial hit would start a chunk one
token into a block, at a length no warm-up compiled. Nothing here names a
cell, a configuration or a mix.
"""

from __future__ import annotations

from typing import Dict, Iterator, List

import numpy as np

from benchmark.traffic_gen import (
    ChatRequest,
    balanced_order,
    lognormal_quantiles,
    top_up,
)


def doc_cycle(spec: Dict) -> List[int]:
    """The seed-free cycle of document lengths."""
    d = spec["documents"]
    return balanced_order(
        lognormal_quantiles(d["cycle"], **d["length"]), d["group"],
        np.random.default_rng(spec["order_seed"]))


def answer_cycle(spec: Dict) -> List[int]:
    """The seed-free cycle of answer lengths, one a request of a cycle."""
    return balanced_order(
        lognormal_quantiles(spec["cycle"], **spec["output"]), spec["group"],
        np.random.default_rng(spec["order_seed"] + 1))


def document(seed: int, d: int, length: int, vocab: int) -> np.ndarray:
    toks = np.random.default_rng([seed, 0, d]).integers(
        0, vocab, length).astype(np.int32)
    toks[0] = d % vocab
    return toks


def question(seed: int, d: int, a: int, length: int, vocab: int) -> np.ndarray:
    toks = np.random.default_rng([seed, 1 + a, d]).integers(
        0, vocab, length).astype(np.int32)
    # a document's questions begin with different tokens
    toks[0] = (int(np.random.default_rng([seed, 0, d]).integers(vocab))
               + a) % vocab
    return toks


def ask_of(i: int, spec: Dict):
    """``(document, ask)`` of request ``i``, or None where the stream has no
    such request yet."""
    asks, stride = int(spec["asks"]), int(spec["stride"])
    n, a = divmod(i, asks)
    d = n - stride * a
    return (d, a) if d >= 0 else None


def prompt_of(spec: Dict, seed: int, d: int, a: int, vocab: int,
              docs: List[int]) -> np.ndarray:
    """The tokens of ask ``a`` of document ``d`` (``docs``: the cycle of
    lengths, made once a stream)."""
    qs = spec["questions"]
    return np.concatenate([
        document(seed, d, docs[d % len(docs)], vocab),
        question(seed, d, a, int(qs[a % len(qs)]), vocab)])


def requests(spec: Dict, seed: int, vocab: int,
             max_seq: int) -> Iterator[ChatRequest]:
    """Requests in stream order without end, all due at 0; request ``i``
    (its ``rid``) is the same whenever it is made."""
    answers, docs = answer_cycle(spec), doc_cycle(spec)
    i = 0
    while True:
        da = ask_of(i, spec)
        if da is not None:
            prompt = prompt_of(spec, seed, da[0], da[1], vocab, docs)
            new = int(answers[i % len(answers)])
            if len(prompt) + new > max_seq:
                raise ValueError(
                    f"prompt {len(prompt)} + output {new} > {max_seq}")
            yield ChatRequest(rid=i, due_s=0.0, prompt=prompt, max_new=new,
                              measured=True)
        i += 1


class Backlog:
    """``traffic_gen.Backlog`` over :func:`requests`: ``initial`` due at 0,
    then whole cycles whenever fewer than ``queued_min`` wait."""

    def __init__(self, spec: Dict, seed: int, seconds: float, vocab: int,
                 max_seq: int):
        arr = spec["arrivals"]
        self._source = requests(spec, seed, vocab, max_seq)
        self._cycle = int(spec["cycle"])
        self.queued_min = int(arr.get("queued_min", 0))
        n = int(np.ceil(arr["requests_per_second_of_run"]
                        * (float(spec["ramp_seconds"]) + seconds)))
        self.initial = [next(self._source) for _ in range(n)]

    def refill(self, waiting: int) -> List[ChatRequest]:
        return [next(self._source) for _ in
                range(top_up(waiting, self.queued_min, self._cycle))]


def warmup_asks(spec: Dict, block_size: int, chunk: int):
    """``(document length, question length, later)`` of the requests served
    alone before the window, in order, so that every program it can need
    exists: for each power-of-two table width and tail chunk a FIRST ask of
    the cycle reaches (ask 0's question; whole chunks without a readout
    before the tail), a first ask of the shortest such document; for each
    width and question length a LATER ask reaches, a later ask of the
    shortest such document, after a first ask of it (the adoption path; a
    chunk of the question's length with a readout). Each decodes a few
    tokens (the packed decode at its width; a context grows into no width
    that no prompt reaches, or this raises). Seed-free."""
    def width(n_tokens):
        w, n = 1, -(-n_tokens // block_size)
        while w < n:
            w <<= 1
        return w

    qs = [int(q) for q in spec["questions"]]
    docs = sorted(set(doc_cycle(spec)))
    first, later = {}, {}
    for n_doc in docs:
        L = n_doc + qs[0]
        first.setdefault((width(L + 1), (L - 1) % chunk + 1), n_doc)
        for q in sorted(set(qs)):
            later.setdefault((width(n_doc + q + 1), q), n_doc)
    warmed = {w for w, _ in first} | {w for w, _ in later}
    grown = {width(n + q + max(answer_cycle(spec))) for n in docs for q in qs}
    if not grown <= warmed:
        raise ValueError(f"decode reaches table widths {sorted(grown)}, "
                         f"prompts only {sorted(warmed)}")
    shapes = []
    for n_doc in docs:
        asked = [q for (_, q), n in sorted(later.items()) if n == n_doc]
        if n_doc in first.values() or asked:
            shapes.append((n_doc, qs[0], False))
        shapes.extend((n_doc, q, True) for q in asked)
    return shapes
