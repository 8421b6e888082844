"""The Mellum2 serving cell: ``Scheduler`` with a ``Mellum2Config`` under the
loop of ``drivers/serve.py`` (``drive``, imported as it stands) and the
warm-up rule of ``drivers/serve_dots3.py`` (every tail chunk x table width x
readout, the decode step at every width a prompt plus its output reaches),
with what is this model's carried here: one bf16 weight tree made on the
device, readings of the program's ``moe.*`` / ``serve.kv.*`` / ``serve.attn.*``
series at the window's ends and at the start of the trace, the global pool's
occupancy sampled between steps, and the model's own reference.

``correct``, decided outside the window on what the timed path produced,
against one blocked reference forward (``configs/mellum2_reference.py``, f32)
over prompt + emitted tokens for each of two requests. The long one is drawn
by the seed among the requests of ``check_long_prompt_min`` tokens or more
that were DECODING WHEN THE WINDOW CLOSED, with what it has emitted so far,
because its pages are still in the pools: **the k and v rows the timed
programs wrote for it are read back through its block tables of both kinds
and held to the reference's** — ``kv_row_err`` on layer 0 (sliding; its input
is the embedding, so the number is bf16's own rounding; the last ``window -
1`` positions, the ones still held), ``full_row_err`` on the first full layer
over EVERY cached position, ``deep_row_err`` the worst of the other layers.
The short one is a completed request of ``check_short_prompt_max`` or less.
For both: at the first, a middle and the last generated position the
reference's largest logit less its logit of the served token
(``logit_tolerance``), the mean of that gap over every generated position
(``mean_logit_gap_max``), and the router's picks on the reference's own
layer-0 router input (``route_mismatch_max``). And: no failed request, no
leaked block of either kind, every ``max_new`` met, the queue never empty
inside the window, window blocks given back, every decode step through the
paged-attention kernel (on a TPU). The limits' two readings each:
``traffic/code-mixedctx-backlog-sat.json``, taken by
``controls/mellum2_limits.py``.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict

from benchmark import harness, metrics, traffic_gen
from benchmark.configs import mellum2_reference
from benchmark.drivers.serve import COUNTERS, HISTOGRAMS, SPANS, drive
from benchmark.drivers.serve_dots3 import (
    _program_gauges,
    _rel_err,
    warmup_shapes,
)
# a program without the model cannot run the cell: fail here, before any
# device is claimed
from byteps_tpu.models.mellum2 import (
    FULL,
    SLIDING,
    Mellum2Config,
    mellum2_block_init,
    mellum2_head_init,
)

MEL_HISTOGRAMS = HISTOGRAMS + ("moe.pairs_here", "moe.experts_hit",
                               "moe.load_max_over_mean")
MEL_COUNTERS = COUNTERS + ("serve.kv.decode_keys_read.full",
                           "serve.kv.decode_keys_read.window",
                           "serve.attn.prefill_pairs.full",
                           "serve.attn.prefill_pairs.window",
                           "serve.cache.window_blocks_released",
                           "serve.decode_steps_paged_attn")
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
                           for k in MEL_HISTOGRAMS},
            "counters": {k: snap["counters"].get(k, 0)
                         for k in MEL_COUNTERS}}


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
    return Mellum2Config(**kw)


def make_params(cfg, seed: int):
    """The bf16 tree, on the device, a jitted call a layer."""
    import jax

    keys = jax.random.split(jax.random.PRNGKey(seed), 1 + cfg.n_layers)
    tree = jax.jit(functools.partial(mellum2_head_init, cfg=cfg))(keys[0])
    init = jax.jit(functools.partial(mellum2_block_init, cfg=cfg))
    tree["blocks"] = [init(keys[1 + li]) for li in range(cfg.n_layers)]
    return jax.block_until_ready(tree)


def take_running(sched, cfg, long_min: int, rng):
    """One request that was decoding when the window closed, a prompt of
    ``long_min`` or more, drawn by the seed, and **what the timed programs
    left in the pools for it**: per full layer the k and v rows of its
    ``cached`` positions, per sliding layer those of the last ``window - 1``,
    read through its block tables as they stand. None where no such request
    runs."""
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

    w_span = (w_lo % bs, w_lo % bs + n - w_lo)
    return {"rid": run.req.rid, "prompt": np.asarray(run.req.prompt),
            "emitted": np.asarray(run.emitted, np.int32), "cached": n,
            "k": rows(pool.k, blocks, 0, n), "v": rows(pool.v, blocks, 0, n),
            "wk": rows(pool.wk, w_blocks, *w_span),
            "wv": rows(pool.wv, w_blocks, *w_span), "w_lo": w_lo}


def pool_errors(cfg, taken, layers, shift: int = 0) -> Dict:
    """The pools' rows of one request (:func:`take_running`) against what the
    reference says a cache holds of each layer, k beside v: ``kv_row_err`` on
    layer 0, whose input is the embedding itself (what a narrower pool moves
    first), ``full_row_err`` on the first full layer over every cached
    position, ``deep_row_err`` the worst of every other layer's, whose inputs
    already differ by what bf16 did to the layers before. ``shift``: the
    reference's rows taken that many positions early — pages one position
    stale, for the limits' second reading."""
    import numpy as np

    def want(li, lo, hi):
        c, at = layers[li]["cache"], layers[li]["in_lo"]
        return np.concatenate(
            [np.asarray(c[k][lo - at - shift:hi - at - shift], np.float32)
             for k in ("k", "v")], axis=-1)

    n, by_layer = taken["cached"], {}
    for fi, li in enumerate(cfg.layers_of(FULL)):
        got = np.concatenate([taken["k"][fi], taken["v"][fi]], -1)
        by_layer[li] = _rel_err(got[shift:], want(li, shift, n))
    for wi, li in enumerate(cfg.layers_of(SLIDING)):
        got = np.concatenate([taken["wk"][wi], taken["wv"][wi]], -1)
        lo = taken["w_lo"]
        by_layer[li] = _rel_err(got[max(0, shift - lo):],
                                want(li, max(lo, shift), n))
    first_full = cfg.layers_of(FULL)[0]
    deep = [e for li, e in by_layer.items() if li not in (0, first_full)]
    return {"kv_row_err": by_layer[0],
            "full_row_err": by_layer[first_full],
            "deep_row_err": max(deep) if deep else 0.0,
            "row_errs_by_layer": [by_layer[li] for li in sorted(by_layer)]}


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

    # the global pool's occupancy, sampled where the loop reads the queue:
    # between two steps, a host integer
    waiting, tokens = _program_gauges()
    held = []

    def waiting_and_sample():
        held.append((time.monotonic(), sched.cache.blocks_in_use))
        return waiting()

    backlog = traffic_gen.Backlog(spec, h.seed, h.seconds, vocab, cfg.max_seq)
    marked = _TraceMarked(h, sched)
    seen = drive(marked, sched, spec, submit, backlog.initial, backlog,
                 waiting_and_sample, tokens,
                 reading=functools.partial(_reading, sched))
    peak = h.memory_peak_bytes()
    h.reduce_trace(SPANS)
    taken = take_running(sched, cfg, int(spec["check_long_prompt_min"]),
                         np.random.default_rng(h.seed + 2))
    inside = [n for at, n in held
              if seen["start"]["t"] <= at <= seen["end"]["t"]]
    return {"cfg": cfg, "params": params, "spec": spec, "seen": seen,
            "peak": peak, "taken": taken, "warm": warm,
            "at_trace": marked.at_trace,
            "global_blocks_in_use_mean":
                float(np.mean(inside)) if inside else None,
            "results": {k: v for k, v in sched.results.items()
                        if not isinstance(k, str)},
            # blocks held by requests still running when the window closed
            # are live, not leaked: 0 means none is unaccounted
            "leaked": sched.cache.leaked_blocks(),
            "running_at_close": len(sched._running)}


def check(h, st, over=None, long_only: bool = False) -> Dict:
    """The comparison with the reference (module docstring), each number
    beside its limit. ``over``: keys laid over the reference's ``hp`` (a
    narrower cache, no YaRN, another window) for the limits' second readings
    (``benchmark/controls/mellum2_limits.py``): never set in a run that
    decides ``correct``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from byteps_tpu.parallel.moe import softmax_topk_route

    cfg, params, spec, taken = st["cfg"], st["params"], st["spec"], st["taken"]
    results = st["results"]
    by_rid = {r.rid: r for r in st["seen"]["reqs"]}
    hp = {k: (v if not isinstance(v, tuple) else list(v))
          for k, v in dataclasses.asdict(cfg).items() if k != "dtype"}
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
        logits, lo, layers = mellum2_reference.forward(
            params, jnp.asarray(toks), hp, n_tail=len(toks) - (n - 1), qb=qb,
            router_layers=(0,))
        logits = np.asarray(logits, np.float32)
        rows = logits[n - 1 - lo:n - 1 - lo + len(emitted)]
        every = rows.max(-1) - rows[np.arange(len(emitted)), emitted]
        for j in sorted({0, len(emitted) // 2, len(emitted) - 1}):
            gaps.append(float(every[j]))
        means.append(float(every.mean()))
        if held is not None:
            pool = pool_errors(cfg, held, layers)
            pool["stale_row_err"] = min(
                pool_errors(cfg, held, layers, shift=1)[k]
                for k in ("full_row_err", "deep_row_err"))
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
          "kv_row_err": "kv_row_err_max",
          "full_row_err": "full_row_err_max",
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
    decode_steps = moved("histograms", "serve.batch_occupancy")
    through_kernel = moved("counters", "serve.decode_steps_paged_attn")
    released = moved("counters", "serve.cache.window_blocks_released")
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
    if released <= 0:
        failed.append("window_blocks_released")
    # the gathered twin at a 256-block table would copy (24, 32768, 512) a
    # layer and step: on the chip the kernel path is the only one timed
    if h.device.get("platform") == "tpu" and through_kernel != decode_steps:
        failed.append("decode_steps_paged_attn")
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
        "window_blocks_released": released,
        "global_blocks_in_use_mean": st["global_blocks_in_use_mean"],
        "queued_min_in_window": seen["queued_min"],
        "notes": {**chk, "not_correct_by": failed,
                  "requests": len(seen["reqs"]),
                  "completed_in_window": completed,
                  "tokens_in_window": tokens,
                  "decode_steps_in_window": decode_steps,
                  "decode_steps_paged_attn": through_kernel,
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
