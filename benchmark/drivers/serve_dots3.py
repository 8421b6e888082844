"""The dots3 serving cell: ``Scheduler`` with a ``Dots3Config`` under the
loop of ``drivers/serve.py`` (``drive``, imported as it stands), with what
that driver cannot do for this model carried here: one bf16 weight tree made
on the device, a warm-up of every program the window can need (each tail
chunk x table width x readout, and the decode step at every width a prompt
plus its output reaches), readings of the program's own ``moe.*`` /
``serve.dsa.*`` series at the window's ends and at the start of the trace,
and the model's own reference.

``correct``, decided outside the window on what the timed path produced,
against one blocked reference forward (``configs/dots3_reference.py``, f32)
over prompt + emitted tokens for each of two requests. The long one is drawn
by the seed among the requests of ``check_long_prompt_min`` tokens or more
that were DECODING WHEN THE WINDOW CLOSED (so on some seeds a context of the
last key bucket), with what it has emitted so far, because its pages are
still in the pool: **the latent rows and indexer keys the timed programs
wrote for it are read back through its block tables and held to the
reference's** (``latent_row_err_max``, ``index_key_err_max`` on the first
full layer, whose input is exact; ``deep_row_err_max`` on every other
layer) — the numbers a narrower pool moves first. The short one is a
completed request of ``check_short_prompt_max`` or less. For both: at the
first, a middle and the last generated position the reference's largest
logit less its logit of the served token (``logit_tolerance``) and the mean
of that gap over every generated position (``mean_logit_gap_max``); the
program's picked set on the reference's own first-full-layer input against
the reference's (``select_mismatch_max``) and the router's picks on the
reference's own router input (``route_mismatch_max``). And: no failed
request, no leaked block of either kind, every ``max_new`` met, the queue
never empty inside the window. The limits' two readings each:
``traffic/longdoc-backlog-sat.json``, taken by ``controls/dots3_limits.py``.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict

from benchmark import harness, metrics, traffic_gen
from benchmark.configs import dots3_reference
from benchmark.drivers.serve import COUNTERS, HISTOGRAMS, QUEUE_DEPTH, SPANS
from benchmark.drivers.serve import drive
# a program without the model cannot run the cell: fail here, before any
# device is claimed
from byteps_tpu.models.dots3 import (
    FULL,
    SLIDING,
    Dots3Config,
    dots3_block_init,
    dots3_head_init,
)

DOTS_HISTOGRAMS = HISTOGRAMS + ("moe.pairs_here", "moe.load_max_over_mean",
                                "serve.dsa.selected_per_query")
DOTS_COUNTERS = COUNTERS + ("serve.dsa.scored_pairs",
                            "serve.dsa.prefill_scored_pairs",
                            "serve.dsa.prefill_selected_keys",
                            "serve.dsa.selected_keys",
                            "serve.cache.window_blocks_released")
REF_BLOCK = 128         # queries a block of the reference's forward
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
                           for k in DOTS_HISTOGRAMS},
            "counters": {k: snap["counters"].get(k, 0)
                         for k in DOTS_COUNTERS}}


def _program_gauges():
    """``drivers/serve.py``'s two reads of the program's registry, of the
    NEWEST scheduler's ``serve.r<n>.queue_depth``: ``controls/
    dots3_limits.py`` makes a scheduler a seed in one process."""
    from byteps_tpu.common.metrics import get_registry

    reg = get_registry()
    depth = max((k for k in reg.snapshot_scalars("serve.r")["gauges"]
                 if QUEUE_DEPTH.match(k)),
                key=lambda k: int(k.split(".")[1][1:]))
    gauge = reg.gauge(depth)
    hists = [reg.histogram(k) for k in ("serve.ttft_ms", "serve.token_ms")]
    return (lambda: int(gauge.value()),
            lambda: sum(x.count() for x in hists))


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
    if h.rehearse:               # the period at the rehearsal's depth
        kw.pop("layer_types", None)
    elif "layer_types" in kw:
        kw["layer_types"] = tuple(kw["layer_types"])
    return Dots3Config(**kw)


def make_params(cfg, seed: int):
    """The bf16 tree, on the device, a jitted call a layer."""
    import jax

    keys = jax.random.split(jax.random.PRNGKey(seed), 1 + cfg.n_layers)
    tree = jax.jit(functools.partial(dots3_head_init, cfg=cfg))(keys[0])
    tree["blocks"] = [
        jax.jit(functools.partial(dots3_block_init, cfg=cfg, li=li))(
            keys[1 + li]) for li in range(cfg.n_layers)]
    return jax.block_until_ready(tree)


def warmup_shapes(spec: Dict, block_size: int, chunk: int):
    """(prompt_len, max_new) pairs, each served alone before the window:
    for every (table width, tail chunk) the cycle's prompts reach, the
    shortest such prompt with an output that crosses into every further
    width its longest output reaches. Seed-free."""
    def width(n_tokens):
        w, n = 1, -(-n_tokens // block_size)
        while w < n:
            w <<= 1
        return w

    out_max = max(o for _, o in traffic_gen.chat_cycle(spec))
    seen, shapes = set(), []
    for plen in sorted({p for p, _ in traffic_gen.chat_cycle(spec)}):
        key = (width(plen + 1), (plen - 1) % chunk + 1)
        if key in seen:
            continue
        seen.add(key)
        new = 2
        if width(plen + out_max) != key[0]:     # decode at the next width
            new = out_max
        shapes.append((plen, new))
    return shapes


def _rel_err(got, want) -> float:
    """The size of ``got - want`` over the size of ``want`` (root of the
    summed squares)."""
    import numpy as np

    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.sqrt(np.sum((got - want) ** 2)
                         / max(float(np.sum(want ** 2)), 1e-30)))


def take_running(sched, cfg, long_min: int, rng):
    """One request that was decoding when the window closed, a prompt of
    ``long_min`` or more, drawn by the seed, and **what the timed programs
    left in the pool for it**: per full layer the latent rows and indexer
    keys of its ``cached`` positions, per sliding layer the rows of the last
    ``window - 1``, read through its block tables as they stand. None where
    no such request runs."""
    import numpy as np

    runs = [r for r in sched._running
            if r.state == "decode" and not isinstance(r.req.rid, str)
            and len(r.req.prompt) >= long_min and r.emitted]
    if not runs:
        return None
    run = runs[int(rng.integers(len(runs)))]
    cache, pool, n = sched.cache, sched.cache.state, run.cache_len
    bs = cache.block_size
    table = cache.table_row(run.req.rid)
    blocks = table[0, :-(-n // bs)]
    w_lo = max(0, n - (cfg.window - 1))
    w_blocks = table[1, w_lo // bs:-(-n // bs)]

    def rows(pool_a, blk, lo, hi):
        a = np.asarray(pool_a[:, blk]).astype(np.float32)
        return a.reshape(a.shape[0], -1, a.shape[-1])[:, lo:hi]

    return {"rid": run.req.rid, "prompt": np.asarray(run.req.prompt),
            "emitted": np.asarray(run.emitted, np.int32), "cached": n,
            "kv": rows(pool.kv, blocks, 0, n),
            "ki": rows(pool.ki, blocks, 0, n),
            "wkv": rows(pool.wkv, w_blocks, w_lo % bs, w_lo % bs + n - w_lo),
            "w_lo": w_lo}


def pool_errors(cfg, taken, layers, shift: int = 0) -> Dict:
    """The pool's rows of one request (:func:`take_running`) against what the
    reference says a cache holds of each layer: ``latent_row_err`` and
    ``index_key_err`` on the first full layer, whose input is the embedding
    itself (what a narrower pool moves first), ``deep_row_err`` the worst of
    every other layer's, whose inputs already differ by what bf16 did to
    the layers before. ``shift``: the reference's rows taken that many
    positions early — a cache one position stale, for the limits' second
    reading."""
    import numpy as np

    def want(li, names, lo, hi):
        c, at = layers[li]["cache"], layers[li]["in_lo"]
        return np.concatenate(
            [np.asarray(c[k][lo - at - shift:hi - at - shift], np.float32)
             for k in names], axis=-1)

    n, by_layer = taken["cached"], {}
    a = cfg.dims(FULL)
    for fi, li in enumerate(cfg.layers_of(FULL)):
        by_layer[li] = [
            _rel_err(taken["kv"][fi][shift:, :a.row],
                     want(li, ("c_kv", "k_rope"), shift, n)),
            _rel_err(taken["ki"][fi][shift:], want(li, ("ki",), shift, n))]
    a = cfg.dims(SLIDING)
    for wi, li in enumerate(cfg.layers_of(SLIDING)):
        by_layer[li] = [_rel_err(
            taken["wkv"][wi][:, :a.row],
            want(li, ("c_kv", "k_rope"), taken["w_lo"], n))]
    first = cfg.layers_of(FULL)[0]
    deep = [e for li, errs in by_layer.items() if li != first for e in errs]
    return {"latent_row_err": by_layer[first][0],
            "index_key_err": by_layer[first][1],
            "deep_row_err": max(deep) if deep else 0.0,
            # [rows, keys] of a full layer, [rows] of a sliding one
            "row_errs_by_layer": [by_layer[li] for li in sorted(by_layer)]}


def serve(h) -> Dict:
    """The run up to the comparison: weights, scheduler, warm-up, the
    window. Returns what the window showed, the completed requests, the
    request taken from the pool at the window's close, and the weights (the
    pool is gone: the reference's f32 blocks need the room)."""
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

    backlog = traffic_gen.Backlog(spec, h.seed, h.seconds, vocab, cfg.max_seq)
    marked = _TraceMarked(h, sched)
    seen = drive(marked, sched, spec, submit, backlog.initial, backlog,
                 *_program_gauges(),
                 reading=functools.partial(_reading, sched))
    peak = h.memory_peak_bytes()
    h.reduce_trace(SPANS)
    taken = take_running(sched, cfg, int(spec["check_long_prompt_min"]),
                         np.random.default_rng(h.seed + 2))
    return {"cfg": cfg, "params": params, "spec": spec, "seen": seen,
            "peak": peak, "taken": taken, "warm": warm,
            "at_trace": marked.at_trace,
            "results": {k: v for k, v in sched.results.items()
                        if not isinstance(k, str)},
            # blocks held by requests still running when the window closed
            # are live, not leaked: 0 means none is unaccounted
            "leaked": sched.cache.leaked_blocks(),
            "running_at_close": len(sched._running)}


def check(h, st, over=None, long_only: bool = False) -> Dict:
    """The comparison with the reference (module docstring), each number
    beside its limit. ``over``: keys laid over the reference's ``hp`` (a
    narrower cache, a shorter window) for the limits' second readings
    (``benchmark/controls/dots3_limits.py``): never set in a run that decides
    ``correct``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from byteps_tpu.models import dots3
    from byteps_tpu.models.gpt import _rmsnorm
    from byteps_tpu.ops.dsa_index import index_scores
    from byteps_tpu.parallel.moe import sigmoid_topk_route
    from byteps_tpu.serve.latent_step import select_mask

    cfg, params, spec, taken = st["cfg"], st["params"], st["spec"], st["taken"]
    results = st["results"]
    by_rid = {r.rid: r for r in st["seen"]["reqs"]}
    hp = {k: (v if not isinstance(v, tuple) else list(v))
          for k, v in dataclasses.asdict(cfg).items() if k != "dtype"}
    hp.update(over or {})
    # (prompt, emitted, what the pool held of it)
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
    gaps, means, select, route, seconds = [], [], [], [], []
    pool = {}
    for rid, prompt, emitted, held in sample:
        t0 = time.monotonic()
        full = np.concatenate([prompt, emitted])
        n = len(prompt)
        toks = np.zeros(-(-len(full) // pad) * pad, np.int32)
        toks[:len(full)] = full
        logits, lo, layers = dots3_reference.forward(
            params, jnp.asarray(toks), hp, n_tail=len(toks) - (n - 1), qb=qb)
        logits = np.asarray(logits, np.float32)
        rows = logits[n - 1 - lo:n - 1 - lo + len(emitted)]
        every = rows.max(-1) - rows[np.arange(len(emitted)), emitted]
        for j in sorted({0, len(emitted) // 2, len(emitted) - 1}):
            gaps.append(float(every[j]))
        means.append(float(every.mean()))
        if held is not None:
            pool = pool_errors(cfg, held, layers)
            pool["stale_row_err"] = pool_errors(
                cfg, held, layers, shift=1)["deep_row_err"]
        # the program's indexer on the reference's own input of the first
        # full layer: the last block of queries against every key
        li = cfg.layers_of(FULL)[0]
        p = params["blocks"][li]
        S = len(toks)
        nq = min(qb, S)

        @jax.jit
        def picked(x_in):
            hh = _rmsnorm(x_in.astype(cfg.dtype)[None], p["ln1_g"],
                          eps=cfg.norm_eps)
            pos = jnp.arange(S)
            ki = dots3.index_keys(hh, p["idx"], pos, cfg)[0]
            c_q = dots3.latents(hh[:, S - nq:], p, pos[S - nq:], cfg, FULL)[0]
            qi, w = dots3.index_queries(c_q, hh[:, S - nq:], p["idx"],
                                        pos[S - nq:], cfg)
            return select_mask(index_scores(qi[0], ki, w[0], S - nq),
                               cfg.index_topk)

        mine = np.asarray(picked(layers[li]["input"]))
        ref_sel = np.asarray(layers[li]["selected"])[-nq:]
        worst = 0.0
        for a, b in zip(mine, ref_sel):
            a, b = set(np.flatnonzero(a).tolist()), set(b[b >= 0].tolist())
            worst = max(worst, len(a ^ b) / 2 / max(len(b), 1))
        select.append(worst)
        # the program's router on the reference's own router input
        le = cfg.first_k_dense
        moe = params["blocks"][le]["moe"]
        idx, _ = jax.jit(functools.partial(
            sigmoid_topk_route, k=cfg.top_k, scale=cfg.routed_scaling))(
                layers[le]["router_input"], moe["wg"], moe["router_bias"])
        route.append(int(jnp.sum(jnp.any(
            jnp.sort(idx, -1) != jnp.sort(layers[le]["router_picks"], -1),
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
            "select_mismatch": max(select) if select else None,
            "route_mismatch": max(route) if route else None,
            **pool, "check_seconds": seconds,
            **{k: spec[k] for k in LIMITS.values()}}


#: a number of :func:`check` -> the key of the traffic file that limits it
LIMITS = {"max_logit_gap": "logit_tolerance",
          "mean_logit_gap": "mean_logit_gap_max",
          "select_mismatch": "select_mismatch_max",
          "route_mismatch": "route_mismatch_max",
          "latent_row_err": "latent_row_err_max",
          "index_key_err": "index_key_err_max",
          "deep_row_err": "deep_row_err_max"}


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
    by_rid = {r.rid: r for r in seen["reqs"]}
    failed = over_limit(chk)
    if not all(len(results[r]["emitted"]) == by_rid[r].max_new
               for r in results):
        failed.append("max_new")
    if not (seen["queued_min"] is not None and seen["queued_min"] > 0):
        failed.append("queued_min")
    if st["leaked"] != 0:
        failed.append("leaked_blocks")
    if not chk["long_prompt_checked"]:
        failed.append("long_prompt_checked")
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
        "window_blocks_released": moved(
            "counters", "serve.cache.window_blocks_released"),
        "queued_min_in_window": seen["queued_min"],
        "notes": {**chk, "not_correct_by": failed,
                  "requests": len(seen["reqs"]),
                  "completed_in_window": completed,
                  "tokens_in_window": tokens,
                  "leaked_blocks": st["leaked"],
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
