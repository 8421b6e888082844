"""Serving cells: the benchmark's own loop over ``Scheduler.step()`` (the
body of ``Scheduler.serve``), with a span around each step and each wait for
an arrival. Every request known before the run is submitted up front with
its ``arrival_s``; the scheduler times each from when it was due, so this
generator cannot run late, and no lateness is reported. A ``backlog`` mix is
kept full besides: between two steps the loop reads how many requests wait
and submits whole cycles, due at once, when they are fewer than the mix's
``queued_min`` (``traffic_gen.Backlog``)."""

from __future__ import annotations

import functools
import re
import time
from typing import Callable, Dict, List

from benchmark import harness, metrics, traffic_gen
from benchmark.configs import gpt2_reference

SPANS = ("serve.step", "idle.wait_arrival", "serve.submit")
HISTOGRAMS = ("serve.ttft_ms", "serve.token_ms", "serve.batch_occupancy")
COUNTERS = ("serve.completed", "serve.prefill_tokens", "serve.decode_tokens",
            "serve.preempted")
QUEUE_DEPTH = re.compile(r"^serve\.r\d+\.queue_depth$")
SLICES = 10                # the window's committed tokens, by tenth of it


def _reading(now: float) -> Dict:
    import byteps_tpu

    snap = byteps_tpu.metrics_snapshot()["metrics"]
    return {"t": now,
            "histograms": {k: snap["histograms"].get(k, {"count": 0})
                           for k in HISTOGRAMS},
            "counters": {k: snap["counters"].get(k, 0) for k in COUNTERS}}


def _program_gauges():
    """(waiting, tokens): two cheap reads of the program's registry, for
    between two steps. ``waiting()`` is the one ``serve.r<n>.queue_depth``
    gauge (this process has made one Scheduler), the number of requests
    submitted and not admitted; ``tokens()`` the count of generated tokens
    committed so far, as ``_reading`` counts them."""
    from byteps_tpu.common.metrics import get_registry

    reg = get_registry()
    depth, = (k for k in reg.snapshot_scalars("serve.r")["gauges"]
              if QUEUE_DEPTH.match(k))
    gauge = reg.gauge(depth)
    hists = [reg.histogram(k) for k in ("serve.ttft_ms", "serve.token_ms")]
    return (lambda: int(gauge.value()),
            lambda: sum(x.count() for x in hists))


def drive(h, sched, spec: Dict, submit: Callable, initial: List,
          backlog=None, waiting: Callable = None, tokens: Callable = None,
          reading: Callable = _reading) -> Dict:
    """The loop of one run: ``initial`` submitted up front, the window
    opened ``ramp_seconds`` later and closed ``h.seconds`` after that, the
    scheduler stepped until then (``drain``: until it has finished). With a
    ``backlog`` (``traffic_gen.Backlog``), ``waiting()`` is read before
    every step and what ``backlog.refill`` answers is submitted. Returns
    the two readings, every request submitted, and what the queue did."""
    base = time.monotonic() + 0.05
    with h.span("serve.submit"):
        for r in initial:
            submit(r, base)
    reqs = list(initial)
    due = sorted(base + r.due_s for r in reqs)
    t_open = base + float(spec["ramp_seconds"])
    t_close = t_open + h.seconds
    start = end = queued_min = None
    nxt = idle = refills = 0
    refill_s = 0.0
    slices: List[int] = []
    while True:
        now = time.monotonic()
        if start is None and now >= t_open:
            start = reading(h.open_window())
        if start is not None and end is None:
            if now >= t_close:
                end = reading(time.monotonic())
                h.close_window()
                if not spec["drain"]:
                    break
            elif h.trace_due(start["t"], now):
                h.start_trace()
        if end is not None and sched.finished:
            break
        if backlog is not None:
            depth = waiting()
            if start is not None and end is None:
                queued_min = depth if queued_min is None \
                    else min(queued_min, depth)
                if now >= start["t"] + len(slices) * h.seconds / SLICES:
                    slices.append(tokens())
            if depth < backlog.queued_min:
                t0 = time.monotonic()
                with h.span("serve.submit"):
                    more = backlog.refill(depth)
                    for r in more:
                        submit(r, base)
                reqs.extend(more)
                refills += 1
                refill_s += time.monotonic() - t0
        with h.span("serve.step"):
            progress = sched.step()
        if progress:
            idle = 0
            continue
        now = time.monotonic()
        while nxt < len(due) and due[nxt] <= now:
            nxt += 1
        marks = [m for m in (due[nxt] if nxt < len(due) else None,
                             t_open if start is None else None,
                             t_close if end is None else None)
                 if m is not None]
        if nxt >= len(due):
            idle += 1
            if idle > 10000:
                raise RuntimeError("serve: no progress and nothing due")
        with h.span("idle.wait_arrival"):
            time.sleep(max(0.0, min(min(marks) - now, 0.05))
                       if marks else 1e-4)
    if backlog is not None:
        slices.append(tokens())
    return {"start": start, "end": end, "reqs": reqs, "refills": refills,
            "refill_ms_total": refill_s * 1e3, "queued_min": queued_min,
            "tokens_by_slice": [b - a for a, b in zip(slices, slices[1:])]}


def run(h) -> Dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from byteps_tpu.models import gpt_init
    from byteps_tpu.serve import Request, Scheduler

    t = h.traffic
    spec = harness.merged(
        harness.load_json(harness.HERE, "traffic", t["multiset"] + ".json"),
        t)
    cfg = h.gpt_config()
    sv = h.config["assumed"]["serve"]
    vocab = int(h.config["source_vocab_size"])
    params = jax.block_until_ready(jax.jit(functools.partial(
        gpt_init, cfg=cfg))(jax.random.PRNGKey(h.seed)))
    sched = Scheduler(
        params, cfg, max_batch=sv["max_batch"], block_size=sv["block_size"],
        pool_blocks=sv["pool_blocks"], prefill_chunk=sv["prefill_chunk"],
        prefix_cache=sv.get("prefix_cache", False),
        quant_cache=sv.get("quant_cache", False))

    # every program the window can need, each served alone
    wrng = np.random.default_rng(h.seed + 1)
    for i, (plen, new) in enumerate(traffic_gen.warmup_shapes(
            spec, sv["block_size"], sv["prefill_chunk"], cfg.max_seq)):
        sched.submit(Request(rid=f"warm{i}", max_new=new,
                             prompt=wrng.integers(0, vocab, plen)
                             .astype(np.int32)))
        while not sched.finished:
            sched.step()
        sched.results.pop(f"warm{i}")

    def submit(r, base):
        sched.submit(Request(rid=r.rid, prompt=r.prompt, max_new=r.max_new,
                             arrival_s=base + r.due_s))

    source = (spec, h.seed, h.seconds, vocab, cfg.max_seq)
    backlog = traffic_gen.Backlog(*source) \
        if spec["arrivals"]["kind"] == "backlog" else None
    seen = drive(h, sched, spec, submit,
                 backlog.initial if backlog else
                 traffic_gen.chat_schedule(*source),
                 backlog, *_program_gauges())
    start, end, reqs = seen["start"], seen["end"], seen["reqs"]
    peak = h.memory_peak_bytes()
    h.reduce_trace(SPANS)

    results = {k: v for k, v in sched.results.items()
               if not isinstance(k, str)}
    elapsed = end["t"] - start["t"]
    tokens = sum(end["histograms"][k]["count"] - start["histograms"][k]["count"]
                 for k in ("serve.ttft_ms", "serve.token_ms"))
    completed = (end["counters"]["serve.completed"]
                 - start["counters"]["serve.completed"])
    if spec["arrivals"]["kind"] == "paced":
        chat = metrics.chat_metrics(
            results, [r.rid for r in reqs if r.measured])
        attempted, failed = chat["attempted"], chat["failed"]
        e2e = {"ttft_mean_ms": chat["ttft_mean_ms"],
               "itl_p95_ms": chat["itl_p95_ms"]}
        leaked = sched.cache.leaked_blocks()
        pool = [r.rid for r in reqs if r.measured and r.rid in results]
    else:
        chat = {"ttft_ms": [], "itl_ms": []}
        attempted, failed, leaked = completed, 0, 0
        e2e = {"serve_tokens_per_s": metrics.window_rate(
            tokens, 0, end["t"], start["t"])}
        pool = sorted(results)

    # outside the window: a seeded sample of completed requests against
    # the plain reference, at the first, a middle and the last generated
    # position; one shape, one compile
    ref = jax.jit(functools.partial(
        gpt2_reference.logits_at, n_heads=cfg.n_heads, eps=cfg.norm_eps))
    by_rid = {r.rid: r for r in reqs}
    crng = np.random.default_rng(h.seed + 2)
    sample = [int(x) for x in crng.choice(
        pool, size=min(int(spec["check_requests"]), len(pool)),
        replace=False)] if pool else []
    gaps = []
    for rid in sample:
        emitted = np.asarray(results[rid]["emitted"])
        full = np.concatenate([by_rid[rid].prompt, emitted])
        plen = len(by_rid[rid].prompt)
        for pos in sorted({0, len(emitted) // 2, len(emitted) - 1}):
            ctx = np.zeros((1, cfg.max_seq), np.int32)
            ctx[0, :plen + pos] = full[:plen + pos]
            lg = np.asarray(ref(params, jnp.asarray(ctx),
                                jnp.int32(plen + pos - 1)), np.float32)
            gaps.append(float(lg.max() - lg[int(emitted[pos])]))
    tol = float(spec["logit_tolerance"])
    lengths_ok = all(len(results[r]["emitted"]) == by_rid[r].max_new
                     for r in results)
    # tokens/s of a queue that ran empty is the arrival rate, not the
    # program's: a backlog that did not hold is not a result
    saturated = backlog is None or seen["queued_min"] > 0
    return {
        "correct": (bool(sample) and max(gaps) <= tol and failed == 0
                    and leaked == 0 and lengths_ok and saturated),
        "attempted": attempted, "failed": failed,
        "end_to_end": e2e, "memory_peak_bytes": peak,
        "ttft_ms": chat["ttft_ms"], "itl_ms": chat["itl_ms"],
        "histograms": {"start": start["histograms"],
                       "end": end["histograms"]},
        "requests_completed": completed, "elapsed_s": elapsed,
        "queued_min_in_window": seen["queued_min"],
        "notes": {"checked_requests": sample, "max_logit_gap": max(gaps)
                  if gaps else None, "logit_tolerance": tol,
                  "requests": len(reqs), "completed_in_window": completed,
                  "tokens_in_window": tokens, "leaked_blocks": leaked,
                  "preempted": end["counters"]["serve.preempted"]
                  - start["counters"]["serve.preempted"],
                  "prefill_tokens_in_window":
                      end["counters"]["serve.prefill_tokens"]
                      - start["counters"]["serve.prefill_tokens"],
                  "queued_min_in_window": seen["queued_min"],
                  "refills": seen["refills"],
                  "refill_ms_total": seen["refill_ms_total"],
                  "tokens_by_slice": seen["tokens_by_slice"],
                  "cache_dir": h.cache_dir},
    }
