"""The one generator every traffic mix goes through.

A mix is a data file under ``benchmark/traffic/``; this module turns its
parameters and ``--seed`` into what a driver feeds the system. Nothing here
names a cell, a configuration or a mix.

Chat mixes share one *request cycle* that does not depend on the seed:
``cycle`` prompt lengths at the quantiles ``(i + 0.5) / cycle`` of a
log-normal, as many output lengths likewise, paired and put in a *balanced*
order drawn once from the constant ``order_seed``: the cycle is cut into
consecutive groups of ``group`` and each group holds one prompt from each
``group``-th of the prompt quantiles (outputs likewise, drawn independently).
A run walks the cycle from its start, over and over. ``--seed`` chooses the
token ids (and the weights) and, in an open loop, the jitter of the arrivals
— not the shapes or their order. The reason is measured (PERF.md section 6,
PR 24): the paged decode step takes as long as the widest context resident
in the batch asks for, so which requests meet in the batch is work, and a
seeded order moved the saturated cell's tokens/s by 12% between seeds.
"""

from __future__ import annotations

import dataclasses
import statistics
from typing import Dict, List, Sequence

import numpy as np

_NORMAL = statistics.NormalDist()


def lognormal_quantiles(n: int, median: float, sigma: float, min: int,
                        max: int, round_to: int = 1) -> List[int]:
    """``n`` lengths at the quantiles ``(i + 0.5) / n`` of a log-normal with
    that median and sigma, rounded to a multiple of ``round_to`` and clipped
    to ``[min, max]``; ascending. No randomness."""
    out = []
    for i in range(n):
        x = median * np.exp(sigma * _NORMAL.inv_cdf((i + 0.5) / n))
        x = int(round(x / round_to)) * round_to
        out.append(int(np.clip(x, min, max)))
    return out


def balanced_order(values: Sequence[int], group: int,
                   rng: np.random.Generator) -> List[int]:
    """A seeded order of ``values`` (ascending) in which every consecutive
    run of ``group`` holds one value from each of the ``group`` strata the
    ascending list is cut into (the last run may be short when ``group``
    does not divide ``len(values)``)."""
    strata = [list(rng.permutation(s)) for s in
              np.array_split(np.asarray(values), group)]
    out: List[int] = []
    for j in range(max(len(s) for s in strata)):
        run = [s[j] for s in strata if j < len(s)]
        out.extend(int(v) for v in rng.permutation(run))
    return out


@dataclasses.dataclass
class ChatRequest:
    rid: int
    due_s: float            # offset from the start of the traffic
    prompt: np.ndarray      # int32 token ids
    max_new: int
    measured: bool          # due inside the measured window


def chat_cycle(spec: Dict):
    """The seed-free cycle of (prompt_len, max_new) pairs of ``spec``."""
    n, g = spec["cycle"], spec["group"]
    rng = np.random.default_rng(spec["order_seed"])
    prompts = balanced_order(lognormal_quantiles(n, **spec["prompt"]), g, rng)
    outputs = balanced_order(lognormal_quantiles(n, **spec["output"]), g, rng)
    return list(zip(prompts, outputs))


def chat_requests(spec: Dict, rng: np.random.Generator, vocab: int,
                  max_seq: int):
    """Requests ``0, 1, 2, ...`` without end, all due at 0: request ``i``
    has the shape ``cycle[i % len(cycle)]`` and the next ``prompt_len``
    token ids of ``rng``, so its prompt is a pure function of the
    generator's seed and ``i``."""
    cycle = chat_cycle(spec)
    i = 0
    while True:
        plen, new = cycle[i % len(cycle)]
        if plen + new > max_seq:
            raise ValueError(f"prompt {plen} + output {new} > {max_seq}")
        yield ChatRequest(
            rid=i, due_s=0.0, max_new=int(new), measured=True,
            prompt=rng.integers(0, vocab, plen).astype(np.int32))
        i += 1


def chat_schedule(spec: Dict, seed: int, seconds: float, vocab: int,
                  max_seq: int) -> List[ChatRequest]:
    """Every request of one run that is known before it starts, in due
    order; request ``i`` has the shape ``cycle[i % len(cycle)]`` and token
    ids from the seed.

    ``arrivals.kind == "paced"``: an open loop at ``rate_per_s``; request
    ``i`` is due at ``(i + 0.5 + u) / rate``, ``u`` uniform in ``+-jitter``
    from the seed. Those whose unjittered due time lies in ``[ramp_seconds,
    ramp_seconds + seconds)`` are the measured ones: the same requests for
    every seed. None arrives after the window.

    ``arrivals.kind == "backlog"``: the first ``requests_per_second_of_run
    * (ramp + seconds)`` requests of ``Backlog``, all due at 0; the window
    opens after ``ramp_seconds`` and whatever commits a token inside it
    counts.
    """
    arr = spec["arrivals"]
    ramp = float(spec["ramp_seconds"])
    if arr["kind"] == "backlog":
        return Backlog(spec, seed, seconds, vocab, max_seq).initial
    if arr["kind"] != "paced":
        raise ValueError(f"unknown arrivals kind {arr['kind']!r}")
    rng = np.random.default_rng(seed)
    rate, jit = float(arr["rate_per_s"]), float(arr["jitter"])
    n = int(np.ceil(rate * (ramp + seconds) - 0.5))
    due = [(i + 0.5 + rng.uniform(-jit, jit)) / rate for i in range(n)]
    source = chat_requests(spec, rng, vocab, max_seq)
    return [dataclasses.replace(
        next(source), due_s=float(due[i]),
        measured=ramp <= (i + 0.5) / rate < ramp + seconds)
        for i in range(n)]


def top_up(waiting: int, queued_min: int, cycle: int) -> int:
    """How many requests a driver submits when it sees ``waiting`` of them
    queued: none at ``queued_min`` or above, else the fewest whole cycles
    that bring the queue back to ``queued_min``."""
    if waiting >= queued_min:
        return 0
    return cycle * -(-(queued_min - waiting) // cycle)


class Backlog:
    """A ``backlog`` mix: ``initial``, the ``requests_per_second_of_run *
    (ramp + seconds)`` requests due at 0 before the run starts, and after
    them as many whole cycles as the run asks for, each time the driver
    sees fewer than ``queued_min`` requests waiting (``refill``). Request
    ``i`` is the same whenever it is made, so a run that never refills
    submits exactly ``initial``."""

    def __init__(self, spec: Dict, seed: int, seconds: float, vocab: int,
                 max_seq: int):
        arr = spec["arrivals"]
        self._source = chat_requests(spec, np.random.default_rng(seed),
                                     vocab, max_seq)
        self._cycle = int(spec["cycle"])
        self.queued_min = int(arr.get("queued_min", 0))
        n = int(np.ceil(arr["requests_per_second_of_run"]
                        * (float(spec["ramp_seconds"]) + seconds)))
        self.initial = [next(self._source) for _ in range(n)]

    def refill(self, waiting: int) -> List[ChatRequest]:
        """The next requests for a queue of ``waiting``: ``top_up`` of
        them, continuing where the last submission stopped."""
        return [next(self._source) for _ in
                range(top_up(waiting, self.queued_min, self._cycle))]


def warmup_shapes(spec: Dict, block_size: int, chunk: int, max_seq: int):
    """The fixed set served before the window so that every program the
    window can need exists: for each power-of-two table width, a prompt
    whose last chunk is a whole chunk and one whose last chunk is each other
    tail the multiset has, each served alone for a few tokens (prefill with
    and without readout, and the packed decode, at that width). Returns
    (prompt_len, max_new) pairs, seed-free."""
    p = spec["prompt"]
    step = p["round_to"]
    tails = sorted({(n - 1) % chunk + 1
                    for n in range(p["min"], p["max"] + 1, step)
                    if n % step == 0} | {chunk})
    longest = min(max_seq, p["max"] + spec["output"]["max"])
    shapes, width = [], 1
    while True:
        # tables of (width/2, width] blocks; a request of n prompt tokens
        # holds blocks_for(n + 1) when admitted
        lo_blocks = width // 2 + 1 if width > 1 else 1
        lo, hi = (lo_blocks - 1) * block_size, width * block_size - 1
        for tail in tails:
            fits = [n for n in range(max(lo, p["min"]), min(hi, p["max"]) + 1)
                    if n % step == 0 and (n - 1) % chunk + 1 == tail]
            if fits:
                shapes.append((fits[0], max(1, min(4, hi + 1 - fits[0]))))
        if width * block_size >= longest:
            break
        width *= 2
    return shapes


def train_batches(seed: int, vocab: int, batch: int, seq: int):
    """An endless stream of (tokens, targets) int32 host batches, token ids
    uniform below ``vocab``, a pure function of the seed and the step."""
    rng = np.random.default_rng(seed)
    while True:
        toks = rng.integers(0, vocab, (batch, seq + 1)).astype(np.int32)
        yield toks[:, :-1], toks[:, 1:]
