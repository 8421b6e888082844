"""Serving cells: the benchmark's own loop over ``Scheduler.step()`` (the
body of ``Scheduler.serve``), with a span around each step and each wait for
an arrival. Every request is submitted up front with its ``arrival_s``; the
scheduler times each from when it was due, so this generator cannot run
late, and no lateness is reported."""

from __future__ import annotations

import functools
import time
from typing import Dict

from benchmark import harness, metrics, traffic_gen
from benchmark.configs import gpt2_reference

SPANS = ("serve.step", "idle.wait_arrival", "serve.submit")
HISTOGRAMS = ("serve.ttft_ms", "serve.token_ms", "serve.batch_occupancy")
COUNTERS = ("serve.completed", "serve.prefill_tokens", "serve.decode_tokens",
            "serve.preempted")


def _reading(now: float) -> Dict:
    import byteps_tpu

    snap = byteps_tpu.metrics_snapshot()["metrics"]
    return {"t": now,
            "histograms": {k: snap["histograms"].get(k, {"count": 0})
                           for k in HISTOGRAMS},
            "counters": {k: snap["counters"].get(k, 0) for k in COUNTERS}}


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

    reqs = traffic_gen.chat_schedule(spec, h.seed, h.seconds, vocab,
                                     cfg.max_seq)
    base = time.monotonic() + 0.05
    with h.span("serve.submit"):
        for r in reqs:
            sched.submit(Request(rid=r.rid, prompt=r.prompt,
                                 max_new=r.max_new,
                                 arrival_s=base + r.due_s))
    due = sorted(base + r.due_s for r in reqs)
    t_open = base + float(spec["ramp_seconds"])
    t_close = t_open + h.seconds
    start = end = None
    nxt = idle = 0
    while True:
        now = time.monotonic()
        if start is None and now >= t_open:
            start = _reading(h.open_window())
        if start is not None and end is None:
            if now >= t_close:
                end = _reading(time.monotonic())
                h.close_window()
                if not spec["drain"]:
                    break
            elif h.trace_due(start["t"], now):
                h.start_trace()
        if end is not None and sched.finished:
            break
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
    return {
        "correct": (bool(sample) and max(gaps) <= tol and failed == 0
                    and leaked == 0 and lengths_ok),
        "attempted": attempted, "failed": failed,
        "end_to_end": e2e, "memory_peak_bytes": peak,
        "ttft_ms": chat["ttft_ms"], "itl_ms": chat["itl_ms"],
        "histograms": {"start": start["histograms"],
                       "end": end["histograms"]},
        "requests_completed": completed, "elapsed_s": elapsed,
        "notes": {"checked_requests": sample, "max_logit_gap": max(gaps)
                  if gaps else None, "logit_tolerance": tol,
                  "requests": len(reqs), "completed_in_window": completed,
                  "tokens_in_window": tokens, "leaked_blocks": leaked,
                  "preempted": end["counters"]["serve.preempted"]
                  - start["counters"]["serve.preempted"],
                  "prefill_tokens_in_window":
                      end["counters"]["serve.prefill_tokens"]
                      - start["counters"]["serve.prefill_tokens"],
                  "cache_dir": h.cache_dir},
    }
