"""The Qwen3-Next serving cell: ``Scheduler`` with a ``Qwen3NextConfig`` under
the loop of ``drivers/serve.py`` (``drive``, imported as it stands), with what
is this model's carried here: one bf16 weight tree made on the device, the
warm-up rule of a mix whose outputs are long (below), readings of the
program's ``moe.*`` / ``serve.kv.*`` / ``serve.attn.*`` / ``serve.gdn.*`` /
``serve.state.*`` series at the window's ends and at the start of the trace,
the k/v pool's and the slot pool's occupancy sampled between steps, and the
model's own reference.

Warm-up: every (table width, tail chunk) a prompt of the cycle reaches, the
shortest such prompt served alone for two tokens (its chunks with and without
readout, the packed decode step at its width) — and, for a decode width that
prompt + output reaches and no prompt's own table does, one more prompt on the
grid wide enough for it. Outputs run to 3,072 tokens: decoding a warm request
to its end, as ``serve_dots3.warmup_shapes`` would, is minutes of set-up.

``correct``, decided outside the window on what the timed path produced,
against one reference forward (``configs/qwen3next_reference.py``, f32, token
by token through every DeltaNet layer) over prompt + emitted tokens for each
of two requests. The long one is drawn by the seed among the requests of
``check_long_prompt_min`` tokens or more that were DECODING WHEN THE WINDOW
CLOSED, with what it has emitted so far, because its slot and its pages are
still in the pools: **the recurrent state and the convolution tail the timed
programs left in its slot are read back and held to the reference's after as
many positions** — ``state_err`` on layer 0 (its input is the embedding, so
the number is the program's own arithmetic), ``deep_state_err`` the worst of
the other DeltaNet layers, ``tail_err`` the worst convolution tail — and the k
and v rows of the two full layers are read back through its block table
(``full_row_err``). The short one is a completed request of
``check_short_prompt_max`` or less. For both: at the first, a middle and the
last generated position the reference's largest logit less its logit of the
served token (``logit_tolerance``), the mean of that gap over every generated
position (``mean_logit_gap_max``), and the router's picks on the reference's
own layer-0 router input (``route_mismatch_max``). And: no failed request, no
leaked block and no leaked slot, every ``max_new`` met, the queue never empty
inside the window, every decode step through the paged-attention kernel and
the state-update kernel (on a TPU). The limits' two readings each:
``traffic/assist-longgen-backlog-sat.json``, taken by
``controls/qwen3next_limits.py``.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict

from benchmark import harness, metrics, traffic_gen
from benchmark.configs import qwen3next_reference
from benchmark.drivers.serve import COUNTERS, HISTOGRAMS, SPANS, drive
from benchmark.drivers.serve_dots3 import _program_gauges, _rel_err
# a program without the model cannot run the cell: fail here, before any
# device is claimed
from byteps_tpu.models.qwen3_next import (
    FULL,
    LINEAR,
    Qwen3NextConfig,
    qwen3_next_block_init,
    qwen3_next_head_init,
)

QN_HISTOGRAMS = HISTOGRAMS + ("moe.pairs_here", "moe.experts_hit",
                              "moe.load_max_over_mean")
QN_COUNTERS = COUNTERS + ("serve.kv.decode_keys_read.full",
                          "serve.attn.prefill_pairs.full",
                          "serve.gdn.decode_rows",
                          "serve.gdn.prefill_tokens",
                          "serve.state.resets.admit",
                          "serve.state.resets.preempt",
                          "serve.decode_steps_paged_attn",
                          "gdn.decode_kernel", "gdn.decode_twin")
REF_BLOCK = 128         # queries a block of the reference's attention
REF_PAD = 1024          # contexts are padded to this: few reference programs


def _reading(sched, now: float) -> Dict:
    """The program's series at one instant, the late ones flushed first (a
    wait for the device, at the window's ends and the start of the trace
    only)."""
    import byteps_tpu

    sched.flush_stats()
    snap = byteps_tpu.metrics_snapshot()["metrics"]
    return {"t": now,
            "histograms": {k: snap["histograms"].get(k, {"count": 0,
                                                         "sum": 0.0})
                           for k in QN_HISTOGRAMS},
            "counters": {k: snap["counters"].get(k, 0)
                         for k in QN_COUNTERS}}


class _TraceMarked:
    """The run handed to ``drive``, with the program's series read when the
    trace starts: a kernel's traced time is held against what the program
    counted in the same iterations."""

    def __init__(self, h, sched):
        self._h, self._sched, self.at_trace = h, sched, None

    def __getattr__(self, name):
        return getattr(self._h, name)

    def start_trace(self):
        self.at_trace = _reading(self._sched, time.monotonic())
        self._h.start_trace()


def build_config(h):
    import jax.numpy as jnp

    kw = dict(h.config["gpt_config"])
    kw["dtype"] = jnp.dtype(kw["dtype"]).type
    return Qwen3NextConfig(**kw)


def make_params(cfg, seed: int):
    """The bf16 tree, on the device, a jitted call a layer kind."""
    import jax

    keys = jax.random.split(jax.random.PRNGKey(seed), 1 + cfg.n_layers)
    tree = jax.jit(functools.partial(qwen3_next_head_init, cfg=cfg))(keys[0])
    init = {kind: jax.jit(functools.partial(qwen3_next_block_init, cfg=cfg,
                                            kind=kind))
            for kind in (FULL, LINEAR)}
    tree["blocks"] = [init[kind](keys[1 + li])
                      for li, kind in enumerate(cfg.layer_types)]
    return jax.block_until_ready(tree)


def warmup_shapes(spec, block_size: int, chunk: int):
    """``(prompt_len, max_new)`` pairs, each served alone before the window
    (module docstring). Seed-free."""
    def width(n_tokens):
        w, n = 1, -(-n_tokens // block_size)
        while w < n:
            w <<= 1
        return w

    cycle = traffic_gen.chat_cycle(spec)
    seen, shapes = set(), []
    for plen in sorted({p for p, _ in cycle}):
        key = (width(plen + 1), (plen - 1) % chunk + 1)
        if key not in seen:
            seen.add(key)
            shapes.append((plen, 2))
    step = int(spec["prompt"]["round_to"])
    for w in sorted({width(p + o) for p, o in cycle}
                    - {k[0] for k in seen}):
        plen = (w // 2 * block_size // step + 1) * step
        while width(plen + 1) != w:
            plen += step
        shapes.append((plen, 2))
    return shapes


def take_running(sched, cfg, long_min: int, rng):
    """One request that was decoding when the window closed, a prompt of
    ``long_min`` or more, drawn by the seed, and **what the timed programs
    left in the pools for it**: its slot's state and convolution tail of
    every DeltaNet layer, and per full layer the k and v rows of its
    ``cached`` positions read through its block table as it stands. What the
    device had picked and the host had not read is read first, so that
    ``emitted`` names every token the state has seen but the last. None
    where no such request runs."""
    import numpy as np

    sched._drain_in_flight("idle")
    runs = [r for r in sched._running
            if r.state == "decode" and not isinstance(r.req.rid, str)
            and len(r.req.prompt) >= long_min and r.emitted]
    if not runs:
        return None
    run = runs[int(rng.integers(len(runs)))]
    cache, pool, n = sched.cache, sched.cache.state, run.cache_len
    bs = cache.block_size
    row = cache.table_row(run.req.rid)
    slot, blocks = int(row[0]), row[1:1 + -(-n // bs)]

    def rows(pool_a):
        a = np.asarray(pool_a[:, blocks]).astype(np.float32)
        return a.reshape(a.shape[0], -1, a.shape[-1])[:, :n]

    return {"rid": run.req.rid, "prompt": np.asarray(run.req.prompt),
            "emitted": np.asarray(run.emitted, np.int32), "cached": n,
            "slot": slot,
            "S": np.asarray(pool.s[:, slot]),
            "tail": np.asarray(pool.conv[:, slot]).astype(np.float32)
            .reshape(pool.s.shape[0], cfg.conv_kernel - 1, -1),
            "k": rows(pool.k), "v": rows(pool.v)}


def pool_errors(cfg, taken, layers, tail_shift: int = 0) -> Dict:
    """What the pools held of one request (:func:`take_running`) against the
    reference after as many positions: the size of the difference over the
    size of the reference's. ``state_err``: layer 0's recurrent state;
    ``deep_state_err``: the worst of the other DeltaNet layers, whose inputs
    already differ by what bf16 did to the layers before; ``tail_err``: the
    worst convolution tail; ``full_row_err``: the worst full layer's k beside
    v over every cached position. ``tail_shift``: the slot's tail held to the
    reference's that many positions EARLY — a tail one token stale, for the
    limits' second reading."""
    import numpy as np

    n = taken["cached"]
    state, tail = {}, {}
    for i, li in enumerate(cfg.layers_of(LINEAR)):
        state[li] = _rel_err(taken["S"][i], layers[li]["S"])
        want = np.asarray(layers[li]["tail"], np.float32)
        got = taken["tail"][i]
        if tail_shift:
            # the reference's rows for positions n - 3 - shift ..: its last
            # rows moved down, the slot's first rows dropped
            want, got = want[:-tail_shift], got[tail_shift:]
        tail[li] = _rel_err(got, want)
    full = {}
    for i, li in enumerate(cfg.layers_of(FULL)):
        got = np.concatenate([taken["k"][i], taken["v"][i]], -1)
        want = np.concatenate([np.asarray(layers[li][k][:n], np.float32)
                               for k in ("k", "v")], -1)
        full[li] = _rel_err(got, want)
    first = cfg.layers_of(LINEAR)[0]
    deep = [e for li, e in state.items() if li != first]
    return {"state_err": state[first],
            "deep_state_err": max(deep) if deep else 0.0,
            "tail_err": max(tail.values()),
            "full_row_err": max(full.values()),
            "state_errs_by_layer": [state[li] for li in sorted(state)],
            "tail_errs_by_layer": [tail[li] for li in sorted(tail)],
            "full_row_errs_by_layer": [full[li] for li in sorted(full)]}


def serve(h) -> Dict:
    """The run up to the comparison: weights, scheduler, warm-up, the
    window. Returns what the window showed, the completed requests, the
    request taken from the pools at the window's close, and the weights (the
    pools are gone: the reference's f32 blocks need the room)."""
    import numpy as np

    from byteps_tpu.serve import Request, Scheduler

    t = h.traffic
    spec = harness.merged(
        harness.load_json(harness.HERE, "traffic", t["multiset"] + ".json"),
        t)
    cfg = build_config(h)
    sv = h.config["assumed"]["serve"]
    vocab = int(h.config["source_vocab_size"])
    params = make_params(cfg, h.seed)
    sched = Scheduler(
        params, cfg, max_batch=sv["max_batch"], block_size=sv["block_size"],
        pool_blocks=sv["pool_blocks"], prefill_chunk=sv["prefill_chunk"],
        prefix_cache=False)

    # every program the window can need, each served alone
    wrng = np.random.default_rng(h.seed + 1)
    warm = warmup_shapes(spec, sv["block_size"], sv["prefill_chunk"])
    for i, (plen, new) in enumerate(warm):
        sched.submit(Request(rid=f"warm{i}", max_new=new,
                             prompt=wrng.integers(0, vocab, plen)
                             .astype(np.int32)))
        while not sched.finished:
            sched.step()
        sched.results.pop(f"warm{i}")
    sched.flush_stats()

    def submit(r, base):
        sched.submit(Request(rid=r.rid, prompt=r.prompt, max_new=r.max_new,
                             arrival_s=base + r.due_s))

    # the two pools' occupancy, sampled where the loop reads the queue:
    # between two steps, two host integers
    waiting, tokens = _program_gauges()
    held = []

    def waiting_and_sample():
        held.append((time.monotonic(), sched.cache.blocks_in_use,
                     sched.cache.slots_in_use))
        return waiting()

    backlog = traffic_gen.Backlog(spec, h.seed, h.seconds, vocab, cfg.max_seq)
    marked = _TraceMarked(h, sched)
    seen = drive(marked, sched, spec, submit, backlog.initial, backlog,
                 waiting_and_sample, tokens,
                 reading=functools.partial(_reading, sched))
    peak = h.memory_peak_bytes()
    h.reduce_trace(SPANS)
    running = len(sched._running)
    taken = take_running(sched, cfg, int(spec["check_long_prompt_min"]),
                         np.random.default_rng(h.seed + 2))
    inside = [(b, s) for at, b, s in held
              if seen["start"]["t"] <= at <= seen["end"]["t"]]
    return {"cfg": cfg, "params": params, "spec": spec, "seen": seen,
            "peak": peak, "taken": taken, "warm": warm,
            "at_trace": marked.at_trace,
            "global_blocks_in_use_mean":
                float(np.mean([b for b, _ in inside])) if inside else None,
            "state_slots_in_use_mean":
                float(np.mean([s for _, s in inside])) if inside else None,
            "state_slots": sched.cache.state_slots,
            "results": {k: v for k, v in sched.results.items()
                        if not isinstance(k, str)},
            # blocks and slots held by requests still running when the
            # window closed are live, not leaked: 0 means none is
            # unaccounted
            "leaked": sched.cache.leaked_blocks(),
            "leaked_slots": sched.cache.leaked_slots(),
            "running_at_close": running}


def check(h, st, over=None, long_only: bool = False,
          tail_shift: int = 0) -> Dict:
    """The comparison with the reference (module docstring), each number
    beside its limit. ``over``: keys laid over the reference's ``hp`` (a
    state kept in bf16, the decay after the update, no scale on q, every dim
    rotated, the shared expert ungated) and ``tail_shift`` (a tail one token
    stale) for the limits' second readings
    (``benchmark/controls/qwen3next_limits.py``): never set in a run that
    decides ``correct``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from byteps_tpu.parallel.moe import softmax_topk_route

    cfg, params, spec, taken = st["cfg"], st["params"], st["spec"], st["taken"]
    results = st["results"]
    by_rid = {r.rid: r for r in st["seen"]["reqs"]}
    hp = {k: v for k, v in dataclasses.asdict(cfg).items() if k != "dtype"}
    hp.update(over or {})
    # (prompt, emitted, what the pools held of it)
    sample = []
    if taken is not None:
        sample.append((taken["rid"], taken["prompt"], taken["emitted"], taken))
    short_max = int(spec["check_short_prompt_max"])
    shorts = sorted(r for r in results if len(by_rid[r].prompt) <= short_max)
    if shorts and not long_only:
        rid = int(np.random.default_rng(h.seed + 3).choice(shorts))
        sample.append((rid, np.asarray(by_rid[rid].prompt),
                       np.asarray(results[rid]["emitted"]), None))
    qb = REF_BLOCK if not h.rehearse else 4
    pad = REF_PAD if not h.rehearse else 4
    gaps, means, route, seconds = [], [], [], []
    pool = {}
    for rid, prompt, emitted, held in sample:
        t0 = time.monotonic()
        full = np.concatenate([prompt, emitted])
        n = len(prompt)
        toks = np.zeros(-(-len(full) // pad) * pad, np.int32)
        toks[:len(full)] = full
        lo = (n - 1) // qb * qb
        logits, layers = qwen3next_reference.forward(
            params, jnp.asarray(toks), hp,
            state_at=held["cached"] if held is not None else 0, lo=lo,
            qb=qb, router_layers=(0,))
        logits = np.asarray(logits, np.float32)
        rows = logits[n - 1 - lo:n - 1 - lo + len(emitted)]
        every = rows.max(-1) - rows[np.arange(len(emitted)), emitted]
        for j in sorted({0, len(emitted) // 2, len(emitted) - 1}):
            gaps.append(float(every[j]))
        means.append(float(every.mean()))
        if held is not None:
            pool = pool_errors(cfg, held, layers, tail_shift)
            pool["stale_tail_err"] = pool_errors(cfg, held, layers,
                                                 1)["tail_err"]
        # the program's router on the reference's own router input, layer 0
        idx, _ = jax.jit(functools.partial(softmax_topk_route, k=cfg.top_k))(
            layers[0]["router_input"], params["blocks"][0]["moe"]["wg"])
        route.append(int(jnp.sum(jnp.any(
            jnp.sort(idx, -1) != jnp.sort(layers[0]["router_picks"], -1),
            -1))))
        del logits, layers
        seconds.append(time.monotonic() - t0)
    return {"checked_requests": [s[0] for s in sample],
            "checked_prompt_lens": [len(s[1]) for s in sample],
            "checked_emitted": [len(s[2]) for s in sample],
            "long_prompt_checked": taken is not None,
            "max_logit_gap": max(gaps) if gaps else None,
            "logit_gaps": gaps,
            "mean_logit_gap": max(means) if means else None,
            "mean_logit_gaps": means,
            "route_mismatch": max(route) if route else None,
            **pool, "check_seconds": seconds,
            **{k: spec[k] for k in LIMITS.values()}}


#: a number of :func:`check` -> the key of the traffic file that limits it
LIMITS = {"max_logit_gap": "logit_tolerance",
          "mean_logit_gap": "mean_logit_gap_max",
          "route_mismatch": "route_mismatch_max",
          "state_err": "state_err_max",
          "deep_state_err": "deep_state_err_max",
          "tail_err": "tail_err_max",
          "full_row_err": "full_row_err_max"}


def over_limit(chk: Dict) -> list:
    """The numbers of one :func:`check` that are missing or over their
    limits: empty is what ``correct`` needs of the comparison."""
    return [k for k, lim in LIMITS.items()
            if chk.get(k) is None or chk[k] > chk[lim]]


def run(h, st=None) -> Dict:
    st = serve(h) if st is None else st
    chk = check(h, st)
    seen, results = st["seen"], st["results"]
    start, end = seen["start"], seen["end"]
    at_trace = st["at_trace"]

    def moved(kind, name, a=start, b=end):
        return b[kind][name] - a[kind][name] if kind == "counters" else \
            b[kind][name]["count"] - a[kind][name]["count"]

    tokens = moved("histograms", "serve.ttft_ms") \
        + moved("histograms", "serve.token_ms")
    completed = moved("counters", "serve.completed")
    decode_steps = moved("histograms", "serve.batch_occupancy")
    through_kernel = moved("counters", "serve.decode_steps_paged_attn")
    resets = moved("counters", "serve.state.resets.admit") \
        + moved("counters", "serve.state.resets.preempt")
    by_rid = {r.rid: r for r in seen["reqs"]}
    failed = over_limit(chk)
    if not all(len(results[r]["emitted"]) == by_rid[r].max_new
               for r in results):
        failed.append("max_new")
    if not (seen["queued_min"] is not None and seen["queued_min"] > 0):
        failed.append("queued_min")
    if st["leaked"] != 0:
        failed.append("leaked_blocks")
    if st["leaked_slots"] != 0:
        failed.append("leaked_slots")
    if not chk["long_prompt_checked"]:
        failed.append("long_prompt_checked")
    # on the chip the kernel paths are the only ones timed: the gathered
    # attention twin and the gather-update-scatter state twin are not
    if h.device.get("platform") == "tpu":
        if through_kernel != decode_steps:
            failed.append("decode_steps_paged_attn")
        if end["counters"]["gdn.decode_twin"] \
                or not end["counters"]["gdn.decode_kernel"]:
            failed.append("gdn_decode_kernel")
    return {
        "correct": not failed, "attempted": completed, "failed": 0,
        "end_to_end": {"serve_tokens_per_s": metrics.window_rate(
            tokens, 0, end["t"], start["t"])},
        "memory_peak_bytes": st["peak"],
        "histograms": {"start": start["histograms"],
                       "end": end["histograms"],
                       **({"trace_start": at_trace["histograms"]}
                          if at_trace else {})},
        "counters": {"start": start["counters"], "end": end["counters"],
                     **({"trace_start": at_trace["counters"]}
                        if at_trace else {})},
        "requests_completed": completed, "elapsed_s": end["t"] - start["t"],
        "prefill_tokens": moved("counters", "serve.prefill_tokens"),
        "state_resets": resets,
        "global_blocks_in_use_mean": st["global_blocks_in_use_mean"],
        "state_slots_in_use_mean": st["state_slots_in_use_mean"],
        "queued_min_in_window": seen["queued_min"],
        "notes": {**chk, "not_correct_by": failed,
                  "requests": len(seen["reqs"]),
                  "completed_in_window": completed,
                  "tokens_in_window": tokens,
                  "decode_steps_in_window": decode_steps,
                  "decode_steps_paged_attn": through_kernel,
                  "leaked_blocks": st["leaked"],
                  "leaked_slots": st["leaked_slots"],
                  "state_slots": st["state_slots"],
                  "running_at_close": st["running_at_close"],
                  "preempted": moved("counters", "serve.preempted"),
                  "prefill_tokens_in_window":
                      moved("counters", "serve.prefill_tokens"),
                  "queued_min_in_window": seen["queued_min"],
                  "refills": seen["refills"],
                  "refill_ms_total": seen["refill_ms_total"],
                  "tokens_by_slice": seen["tokens_by_slice"],
                  "warmup_shapes": st["warm"],
                  "cache_dir": h.cache_dir},
    }
