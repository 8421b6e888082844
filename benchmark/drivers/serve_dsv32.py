"""The DeepSeek-V3.2-Exp serving cell: ``Scheduler`` with a
``DeepSeekV32Config`` and the PREFIX INDEX ON under the loop of
``drivers/serve.py`` (``drive``, imported as it stands), over the sessions of
``traffic_docqa.py`` (documents asked several times). What that loop cannot
do for this model is carried here, as ``drivers/serve_dots3.py`` carries
dots3's (whose trace mark, gauges and error measure are imported): one bf16
weight tree made on the device, a warm-up of every program the window can
need (a first ask for each table width and tail chunk, a LATER ask for each
width and question length: the adoption path), readings of the program's own
``moe.*`` / ``serve.dsa.*`` / ``serve.prefix*`` series at the window's ends
and at the start of the trace, the index's blocks sampled between steps, and
the model's own reference.

``correct``, decided outside the window on what the timed path produced,
against one blocked reference forward (``configs/deepseek_v32_reference.py``,
f32) over prompt + emitted tokens for each of TWO requests: a **first ask** of
``check_first_prompt_min`` tokens or more that hit nothing, and a **later ask
whose whole document was adopted** from the prefix index (it wrote none of
the document's pages; ``Scheduler.result``'s ``prefix_hit_tokens``). Each is
drawn by the seed among the requests decoding when the window closed that
have emitted ``check_emitted_min`` tokens or more, with what it has emitted
so far (its rows read back through its block table, decode-written rows
included), or, where none qualifies, among the completed
ones whose prompt's blocks are all still in the index (read back through the
index). For both: **the latent rows and indexer keys the timed programs left
in the pool against the reference's cache** (``latent_row_err_max``,
``index_key_err_max`` on layer 0, whose input is exact; ``deep_row_err_max``
on every other layer), at the first, a middle and the last generated position
the reference's largest logit less its logit of the served token
(``logit_tolerance``) and the mean of that gap (``mean_logit_gap_max``), the
program's picked set on the reference's own layer-0 input against the
reference's (``select_mismatch_max``) and the group-limited router's picks on
the reference's own router input (``route_mismatch_max``). And: no failed
request, no leaked block, every ``max_new`` met, the queue never empty inside
the window, and **the share of prompt tokens that were hits at
``hit_share_min`` or more**. The limits' two readings each:
``traffic/docqa-reuse-backlog-sat.json``, taken by
``controls/dsv32_limits.py``.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict

from benchmark import metrics, traffic_docqa
from benchmark.configs import deepseek_v32_reference as reference
from benchmark.drivers.serve import COUNTERS, HISTOGRAMS, SPANS, drive
from benchmark.drivers import serve_dots3
from benchmark.drivers.serve_dots3 import _program_gauges, _rel_err
from benchmark.drivers.serve_falconh1 import _longest_iterations
# a program without the model cannot run the cell: fail here, before any
# device is claimed
from byteps_tpu.models.deepseek_v32 import (
    DeepSeekV32Config,
    dsv32_block_init,
    dsv32_head_init,
)

DSV_HISTOGRAMS = HISTOGRAMS + ("moe.pairs_here", "moe.load_max_over_mean",
                               "moe.groups_hit",
                               "serve.dsa.selected_per_query")
DSV_COUNTERS = COUNTERS + ("serve.dsa.scored_pairs",
                           "serve.dsa.prefill_scored_pairs",
                           "serve.dsa.prefill_selected_keys",
                           "serve.dsa.selected_keys",
                           "serve.prefix_hits", "serve.prefix_misses",
                           "serve.prefix_saved_tokens",
                           "serve.prefix_evictions",
                           "serve.prefix.cow_blocks")
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
                           for k in DSV_HISTOGRAMS},
            "counters": {k: snap["counters"].get(k, 0)
                         for k in DSV_COUNTERS}}


class _TraceMarked(serve_dots3._TraceMarked):
    """dots3's mark, with THIS cell's series read when the trace starts."""

    def start_trace(self):
        self.at_trace = _reading(self._sched, time.monotonic())
        self._h.start_trace()


def build_config(h):
    import jax.numpy as jnp

    kw = dict(h.config["gpt_config"])
    kw["dtype"] = jnp.dtype(kw["dtype"]).type
    return DeepSeekV32Config(**kw)


def make_params(cfg, seed: int):
    """The bf16 tree, on the device, a jitted call a layer."""
    import jax

    keys = jax.random.split(jax.random.PRNGKey(seed), 1 + cfg.n_layers)
    tree = jax.jit(functools.partial(dsv32_head_init, cfg=cfg))(keys[0])
    tree["blocks"] = [
        jax.jit(functools.partial(dsv32_block_init, cfg=cfg, li=li))(
            keys[1 + li]) for li in range(cfg.n_layers)]
    return jax.block_until_ready(tree)


def _pool_rows(pool, blocks, n: int):
    """``(kv, ki)`` f32 of the first ``n`` positions of ``blocks``, every
    layer: ``(layers, n, width)`` each."""
    import numpy as np

    def rows(leaf):
        a = np.asarray(leaf[:, blocks]).astype(np.float32)
        return a.reshape(a.shape[0], -1, a.shape[-1])[:, :n]

    return rows(pool.kv), rows(pool.ki)


def take(sched, spec: Dict, reqs_by_rid: Dict, rng, later: bool):
    """One checked request and **what the timed programs left in the pool
    for it**: a first ask (``later`` False: a prompt of
    ``check_first_prompt_min`` or more, no position adopted) or a later ask
    (its whole document adopted). Of the requests decoding when the window
    closed that have emitted ``check_emitted_min`` tokens or more (the mean
    gap of a single position is that position's gap) where one qualifies
    (``cached`` positions through its table), else of the completed ones
    whose prompt is still whole in the index (the prompt's positions
    through the index). None where neither has one."""
    import numpy as np

    cache, bs = sched.cache, sched.cache.block_size
    first_min = int(spec["check_first_prompt_min"])
    emitted_min = int(spec["check_emitted_min"])

    docs = traffic_docqa.doc_cycle(spec)

    def fits(rid, hit):
        d, a = traffic_docqa.ask_of(rid, spec)
        n_doc = docs[d % len(docs)]
        if later:
            return a > 0 and hit >= n_doc
        return a == 0 and hit == 0 \
            and len(reqs_by_rid[rid].prompt) >= first_min

    runs = [r for r in sched._running
            if r.state == "decode" and not isinstance(r.req.rid, str)
            and len(r.emitted) >= emitted_min and r.preemptions == 0
            and fits(r.req.rid, r.prefix_hit)]
    if runs:
        run = runs[int(rng.integers(len(runs)))]
        n = run.cache_len
        blocks = cache.table_row(run.req.rid)[:-(-n // bs)]
        kv, ki = _pool_rows(cache.state, blocks, n)
        return {"rid": run.req.rid, "prompt": np.asarray(run.req.prompt),
                "emitted": np.asarray(run.emitted, np.int32), "cached": n,
                "kv": kv, "ki": ki, "through": "table",
                "prefix_hit_tokens": run.prefix_hit}
    done = []
    for rid, res in sorted(sched.results.items()):
        if isinstance(rid, str) or res["preemptions"] \
                or not fits(rid, res["prefix_hit_tokens"]):
            continue
        prompt = np.asarray(reqs_by_rid[rid].prompt)
        blocks, n = cache.match_prefix(prompt, full_blocks_only=True)
        if n == len(prompt) // bs * bs:
            done.append((rid, blocks, n))
    if not done:
        return None
    rid, blocks, n = done[int(rng.integers(len(done)))]
    kv, ki = _pool_rows(cache.state, np.asarray(blocks, np.int32), n)
    return {"rid": rid, "prompt": np.asarray(reqs_by_rid[rid].prompt),
            "emitted": np.asarray(sched.results[rid]["emitted"], np.int32),
            "cached": n, "kv": kv, "ki": ki, "through": "index",
            "prefix_hit_tokens": sched.results[rid]["prefix_hit_tokens"]}


def pool_errors(cfg, taken, layers, shift: int = 0) -> Dict:
    """The pool's rows of one request (:func:`take`) against what the
    reference says a cache holds of each layer: ``latent_row_err`` and
    ``index_key_err`` on layer 0, whose input is the embedding itself (what
    a narrower pool moves first), ``deep_row_err`` the worst of every other
    layer's, whose inputs already differ by what bf16 did to the layers
    before. ``shift``: the reference's rows taken that many positions early
    — a cache one position stale, for the limits' second reading."""
    import numpy as np

    def want(li, names, lo, hi):
        c = layers[li]["cache"]
        return np.concatenate(
            [np.asarray(c[k][lo - shift:hi - shift], np.float32)
             for k in names], axis=-1)

    n, a, by_layer = taken["cached"], cfg.dims(), []
    for li in range(cfg.n_layers):
        by_layer.append([
            _rel_err(taken["kv"][li][shift:, :a.row],
                     want(li, ("c_kv", "k_rope"), shift, n)),
            _rel_err(taken["ki"][li][shift:], want(li, ("ki",), shift, n))])
    deep = [e for errs in by_layer[1:] for e in errs]
    return {"latent_row_err": by_layer[0][0],
            "index_key_err": by_layer[0][1],
            "deep_row_err": max(deep) if deep else 0.0,
            "row_errs_by_layer": by_layer}          # [rows, keys] a layer


def warm_up(sched, spec: Dict, sv: Dict, vocab: int, seed: int):
    """Every program the window can need, each request served alone
    (``traffic_docqa.warmup_asks``); the index is emptied after it, so the
    window starts with no page of a warm-up document."""
    import numpy as np

    from byteps_tpu.serve import Request

    warm = traffic_docqa.warmup_asks(spec, sv["block_size"],
                                     sv["prefill_chunk"])
    for i, (n_doc, n_q, later) in enumerate(warm):
        # a warm-up document is named by its length: a later ask finds the
        # first ask's pages
        doc = traffic_docqa.document(seed + 1, n_doc, n_doc, vocab)
        q = np.random.default_rng([seed + 1, 7, i]).integers(
            0, vocab, n_q).astype(np.int32)
        q[0] = (doc[1] + 1 + i) % vocab
        sched.submit(Request(rid=f"warm{i}", max_new=3,
                             prompt=np.concatenate([doc, q])))
        while not sched.finished:
            sched.step()
        res = sched.results.pop(f"warm{i}")
        if bool(res["prefix_hit_tokens"] >= n_doc) != later:
            raise RuntimeError(
                f"warm-up request {i} {(n_doc, n_q, later)} adopted "
                f"{res['prefix_hit_tokens']} positions")
    sched.cache.drop_prefix_cache()
    sched.flush_stats()
    return warm


def serve(h) -> Dict:
    """The run up to the comparison: weights, scheduler, warm-up, the
    window. Returns what the window showed, the completed requests, the two
    requests taken from the pool at the window's close, and the weights (the
    pool is gone: the reference's f32 blocks need the room)."""
    import numpy as np

    from byteps_tpu.serve import Request, Scheduler

    spec = h.traffic
    cfg = build_config(h)
    sv = h.config["assumed"]["serve"]
    vocab = int(h.config["source_vocab_size"])
    params = make_params(cfg, h.seed)
    sched = Scheduler(
        params, cfg, max_batch=sv["max_batch"], block_size=sv["block_size"],
        pool_blocks=sv["pool_blocks"], prefill_chunk=sv["prefill_chunk"],
        prefix_cache=sv["prefix_cache"])
    warm = warm_up(sched, spec, sv, vocab, h.seed)

    def submit(r, base):
        sched.submit(Request(rid=r.rid, prompt=r.prompt, max_new=r.max_new,
                             arrival_s=base + r.due_s))

    # the index's blocks, sampled where the loop reads the queue: between
    # two steps, a host integer
    waiting, tokens = _program_gauges()
    held = []

    def waiting_and_sample():
        held.append((time.monotonic(), sched.cache.prefix_blocks))
        return waiting()

    backlog = traffic_docqa.Backlog(spec, h.seed, h.seconds, vocab,
                                    cfg.max_seq)
    marked = _TraceMarked(h, sched)
    seen = drive(marked, sched, spec, submit, backlog.initial, backlog,
                 waiting_and_sample, tokens,
                 reading=functools.partial(_reading, sched))
    peak = h.memory_peak_bytes()
    h.reduce_trace(SPANS)
    by_rid = {r.rid: r for r in seen["reqs"]}
    rng = np.random.default_rng(h.seed + 2)
    taken = [take(sched, spec, by_rid, rng, later) for later in (False, True)]
    inside = [n for at, n in held
              if seen["start"]["t"] <= at <= seen["end"]["t"]]
    return {"cfg": cfg, "params": params, "spec": spec, "seen": seen,
            "peak": peak, "taken": taken, "warm": warm,
            "at_trace": marked.at_trace,
            "prefix_blocks_mean": float(np.mean(inside)) if inside else None,
            "idle_prefix_blocks_at_close": sched.cache.reclaimable_blocks(),
            "results": {k: v for k, v in sched.results.items()
                        if not isinstance(k, str)},
            # blocks held by requests still running when the window closed
            # or by the index are live, not leaked: 0 means none is
            # unaccounted
            "leaked": sched.cache.leaked_blocks(),
            "running_at_close": len(sched._running)}


def check(h, st, over=None, only=None) -> Dict:
    """The comparison with the reference (module docstring), each number
    beside its limit: the worst of the two requests'. ``over``: keys laid
    over the reference's ``hp`` (a narrower cache, no ``m²``, no group
    limit) for the limits' second readings
    (``benchmark/controls/dsv32_limits.py``): never set in a run that decides
    ``correct``. ``only``: 0 or 1, one of the two requests alone."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from byteps_tpu.models import dots3
    from byteps_tpu.models.deepseek_v32 import latents
    from byteps_tpu.models.gpt import _rmsnorm
    from byteps_tpu.ops.dsa_index import index_scores
    from byteps_tpu.parallel.moe import sigmoid_group_topk_route
    from byteps_tpu.serve.latent_step import select_mask

    cfg, params = st["cfg"], st["params"]
    spec = st["spec"]
    hp = {k: v for k, v in dataclasses.asdict(cfg).items() if k != "dtype"}
    hp.update(over or {})
    sample = [t for i, t in enumerate(st["taken"])
              if t is not None and only in (None, i)]
    qb = REF_BLOCK if not h.rehearse else 4
    pad = REF_PAD if not h.rehearse else 4
    le = cfg.first_k_dense
    by_request = []
    for held in sample:
        t0 = time.monotonic()
        prompt, emitted = held["prompt"], held["emitted"]
        full = np.concatenate([prompt, emitted])
        n = len(prompt)
        toks = np.zeros(-(-len(full) // pad) * pad, np.int32)
        toks[:len(full)] = full
        S = len(toks)
        nq = min(qb, S)
        logits, lo, layers = reference.forward(
            params, jnp.asarray(toks), hp, n_tail=S - (n - 1), qb=qb,
            keep={"cache": None, "selected": (0,), "router_input": (le,),
                  "router_picks": (le,)})
        logits = np.asarray(logits, np.float32)
        at = logits[n - 1 - lo:n - 1 - lo + len(emitted)]
        every = at.max(-1) - at[np.arange(len(emitted)), emitted]
        gaps = [float(every[j]) for j in
                sorted({0, len(emitted) // 2, len(emitted) - 1})]
        errs = pool_errors(cfg, held, layers)
        stale = pool_errors(cfg, held, layers, shift=1)["deep_row_err"]
        # the program's indexer on the reference's own input of layer 0 (the
        # embedding): the last block of queries against every key
        @jax.jit
        def picked(wte, p, tokens):
            hh = _rmsnorm(wte[tokens].astype(cfg.dtype)[None], p["ln1_g"],
                          eps=cfg.norm_eps)
            pos = jnp.arange(S)
            ki = dots3.index_keys(hh, p["idx"], pos, cfg)[0]
            c_q = latents(hh[:, S - nq:], p, pos[S - nq:], cfg)[0]
            qi, w = dots3.index_queries(c_q, hh[:, S - nq:], p["idx"],
                                        pos[S - nq:], cfg)
            return select_mask(index_scores(qi[0], ki, w[0], S - nq),
                               cfg.index_topk)

        got = np.asarray(picked(params["wte"], params["blocks"][0],
                                jnp.asarray(toks)))
        ref_sel = np.asarray(layers[0]["selected"])[-nq:]
        worst = 0.0
        for a, b in zip(got, ref_sel):
            a, b = set(np.flatnonzero(a).tolist()), set(b[b >= 0].tolist())
            worst = max(worst, len(a ^ b) / 2 / max(len(b), 1))
        # the program's group-limited router on the reference's own router
        # input of the first expert layer
        moe = params["blocks"][le]["moe"]
        idx, _ = jax.jit(functools.partial(
            sigmoid_group_topk_route, k=cfg.top_k, scale=cfg.routed_scaling,
            n_group=cfg.n_group, topk_group=cfg.topk_group))(
                layers[le]["router_input"], moe["wg"], moe["router_bias"])
        route = int(jnp.sum(jnp.any(
            jnp.sort(idx, -1) != jnp.sort(layers[le]["router_picks"], -1),
            -1)))
        del logits, layers
        by_request.append({
            "rid": held["rid"], "prompt_len": n, "emitted": len(emitted),
            "cached": held["cached"], "through": held["through"],
            "prefix_hit_tokens": held["prefix_hit_tokens"],
            "logit_gaps": gaps, "max_logit_gap": max(gaps),
            "mean_logit_gap": float(every.mean()),
            "select_mismatch": worst, "route_mismatch": route, **errs,
            "stale_row_err": stale, "seconds": time.monotonic() - t0})

    def worst_of(key, pick=max):
        return pick(r[key] for r in by_request) if by_request else None

    return {"first_ask_checked": st["taken"][0] is not None,
            "later_ask_checked": st["taken"][1] is not None,
            "checked": by_request,
            **{k: worst_of(k) for k in LIMITS},
            "stale_row_err": worst_of("stale_row_err", min),
            "check_seconds": [r["seconds"] for r in by_request],
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
    seen, results, spec = st["seen"], st["results"], st["spec"]
    start, end = seen["start"], seen["end"]
    at_trace = st["at_trace"]

    def moved(kind, name, a=start, b=end):
        return b[kind][name] - a[kind][name] if kind == "counters" else \
            b[kind][name]["count"] - a[kind][name]["count"]

    tokens = moved("histograms", "serve.ttft_ms") \
        + moved("histograms", "serve.token_ms")
    completed = moved("counters", "serve.completed")
    computed = moved("counters", "serve.prefill_tokens")
    saved = moved("counters", "serve.prefix_saved_tokens")
    share = saved / (saved + computed) if saved + computed else 0.0
    by_rid = {r.rid: r for r in seen["reqs"]}
    failed = over_limit(chk)
    if not all(len(results[r]["emitted"]) == by_rid[r].max_new
               for r in results):
        failed.append("max_new")
    if not (seen["queued_min"] is not None and seen["queued_min"] > 0):
        failed.append("queued_min")
    if st["leaked"] != 0:
        failed.append("leaked_blocks")
    failed.extend(k for k in ("first_ask_checked", "later_ask_checked")
                  if not chk[k])
    if share < float(spec["hit_share_min"]):
        failed.append("hit_share")
    observed = {
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
        "prefill_tokens": computed,
        "prefix_hit_token_share_pct": 100.0 * share,
        "prefix_blocks_mean": st["prefix_blocks_mean"],
        "queued_min_in_window": seen["queued_min"],
        "notes": {**chk, "not_correct_by": failed,
                  "requests": len(seen["reqs"]),
                  "completed_in_window": completed,
                  "tokens_in_window": tokens,
                  "leaked_blocks": st["leaked"],
                  "running_at_close": st["running_at_close"],
                  "preempted": moved("counters", "serve.preempted"),
                  "prefill_tokens_in_window": computed,
                  "prefix_saved_tokens_in_window": saved,
                  "prompt_tokens_in_window": saved + computed,
                  "prefix_hit_token_share": share,
                  "hit_share_min": float(spec["hit_share_min"]),
                  "prefix_hits": moved("counters", "serve.prefix_hits"),
                  "prefix_misses": moved("counters", "serve.prefix_misses"),
                  "prefix_evictions":
                      moved("counters", "serve.prefix_evictions"),
                  "prefix_cow_blocks":
                      moved("counters", "serve.prefix.cow_blocks"),
                  "idle_prefix_blocks_at_close":
                      st["idle_prefix_blocks_at_close"],
                  "selected_keys_per_query": metrics.histogram_window_mean(
                      end["histograms"]["serve.dsa.selected_per_query"],
                      start["histograms"]["serve.dsa.selected_per_query"]),
                  "queued_min_in_window": seen["queued_min"],
                  "refills": seen["refills"],
                  "refill_ms_total": seen["refill_ms_total"],
                  "tokens_by_slice": seen["tokens_by_slice"],
                  "warmup_asks": st["warm"],
                  "cache_dir": h.cache_dir},
    }
    # an untraced run's line has no compiles_in_window: a stalled window
    # says here whether it compiled, and where it lost its time
    observed["notes"]["compiles_in_window"] = h.compiles
    observed["notes"]["longest_iterations"] = _longest_iterations(h, observed)
    return observed
