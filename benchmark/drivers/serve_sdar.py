"""The SDAR serving cell: ``Scheduler`` with an ``SDARConfig`` under the loop
of ``drivers/serve.py`` (``drive``, imported as it stands), with what is this
model's carried here: one bf16 weight tree made on the device, the passes a
request asks (a list in the traffic file, by request number), a warm-up of
every chunk program and of the decode program at every table width, readings
of the program's ``serve.block.*`` / ``moe.*`` / ``serve.kv.*`` /
``serve.attn.*`` series at the window's ends and at the start of the trace,
the pool's occupancy sampled between steps, and the model's own reference.
(The loop, the readings' shape and the trace mark are ``serve_mellum2.py``'s,
copied: a driver the benchmark has is not edited.)

``correct``, decided outside the window on what the timed path produced,
against the plain reference (``configs/sdar_reference.py``, f32) for each of
two requests. The long one is drawn by the seed among the requests of
``check_long_prompt_min`` tokens or more that were DECODING WHEN THE WINDOW
CLOSED, with what it has committed so far, because its pages are still in the
pool; the short one is a completed request of ``check_short_prompt_max`` or
less. One block-causal forward of prompt + committed tokens each, and then:

* **the k and v rows the timed programs left for the long one, read back
  through its block table over every committed position, against the
  reference's** — ``kv_row_err`` on layer 0 (its input is the embedding, so
  the number is bf16's own rounding), ``deep_row_err`` the worst layer below
  it, ``last_row_err`` the deepest. A block whose rows are a denoising pass's
  and not the committing pass's shows in the first two, a chunk that
  attended causally in the third.
* **the sampler replayed from the recorded passes**: for the first, a middle
  and the last block of each request, at every pass, the reference's logits
  over prefix + the block as it stood (rebuilt from the pass each token was
  fixed at; the prefix's rows are the forward's own). ``max_logit_gap`` /
  ``mean_logit_gap``: the reference's largest logit (the mask token left out)
  less its logit of the served token, at each position that pass fixed.
  ``confidence_gap``: of the positions open at a pass, the reference's
  confidence of the ones IT would fix less that of the ones the program
  fixed, rank by rank, as a share of the first — by value, never by index:
  random weights make the confidences of a block's positions nearly equal.
* the router's picks on the reference's own layer-0 router input
  (``route_mismatch``).

And: no failed request, no leaked block, every ``max_new`` met exactly, the
queue never empty inside the window, every decode step through the
paged-attention kernel (on a TPU), and every token the program counted
(``serve.ttft_ms`` + ``serve.token_ms``) a token of a finished or running
request. The limits' two readings each: ``traffic/
chat-blockgen-backlog-sat.json``, taken by ``controls/sdar_limits.py``.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict

from benchmark import harness, metrics, traffic_gen
from benchmark.configs import sdar_reference
from benchmark.drivers.serve import COUNTERS, HISTOGRAMS, SPANS, drive
from benchmark.drivers.serve_dots3 import _program_gauges, _rel_err
# a program without the model cannot run the cell: fail here, before any
# device is claimed
from byteps_tpu.models.sdar import (
    SDARConfig,
    sdar_block_init,
    sdar_head_init,
)

SD_HISTOGRAMS = HISTOGRAMS + ("moe.pairs_here", "moe.experts_hit",
                              "moe.load_max_over_mean", "serve.block.passes",
                              "serve.block_ms")
SD_COUNTERS = COUNTERS + ("serve.kv.decode_keys_read.full",
                          "serve.attn.prefill_pairs.full",
                          "serve.kv.block_rows_rewritten",
                          "serve.block.row_passes",
                          "serve.block.commit_row_passes",
                          "serve.block.commits",
                          "serve.block.positions_fixed",
                          "serve.decode_steps_paged_attn",
                          "serve.decode_steps_overlapped")
REF_BLOCK = 128         # queries a block of the reference's forward
REF_PAD = 256           # contexts are padded to this: few reference programs


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
                           for k in SD_HISTOGRAMS},
            "counters": {k: snap["counters"].get(k, 0)
                         for k in SD_COUNTERS}}


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
    return SDARConfig(**kw)


def make_params(cfg, seed: int):
    """The bf16 tree, on the device, a jitted call a layer."""
    import jax

    keys = jax.random.split(jax.random.PRNGKey(seed), 1 + cfg.n_layers)
    tree = jax.jit(functools.partial(sdar_head_init, cfg=cfg))(keys[0])
    init = jax.jit(functools.partial(sdar_block_init, cfg=cfg))
    tree["blocks"] = [init(keys[1 + li]) for li in range(cfg.n_layers)]
    return jax.block_until_ready(tree)


def warmup_shapes(spec: Dict, block_size: int, chunk: int):
    """Prompt lengths, each served alone for one block before the window: for
    every (table width, tail chunk) the cycle's prompts reach, the shortest
    such prompt. Its chunks compile the chunk programs (none reads out), its
    one block the decode program at that width — and every width a request
    grows into is some longer prompt's width at admission (checked).
    Seed-free."""
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
            shapes.append(plen)
    reached = {width(p + o) for p, o in cycle}
    if not reached <= {w for w, _ in seen}:
        raise ValueError(f"decode reaches table widths {sorted(reached)} "
                         f"that no prompt is admitted at: {sorted(seen)}")
    return shapes


def take_running(sched, long_min: int, rng):
    """One request that was decoding when the window closed, a prompt of
    ``long_min`` or more, drawn by the seed, and **what the timed programs
    left in the pool for it**: per layer the k and v rows of its committed
    positions, read through its block table as it stands; with them the pass
    each committed token was fixed at. Whatever the device had picked is
    read first, so ``emitted`` and the fill level say the same. None where no
    such request runs."""
    import numpy as np

    sched._drain_in_flight("migrate")
    runs = [r for r in sched._running
            if r.state == "decode" and not isinstance(r.req.rid, str)
            and len(r.req.prompt) >= long_min and r.emitted]
    if not runs:
        return None
    run = runs[int(rng.integers(len(runs)))]
    cache, pool, n = sched.cache, sched.cache.state, run.cache_len
    assert n == len(run.req.prompt) + len(run.emitted), \
        (n, len(run.req.prompt), len(run.emitted))
    blocks = cache.table_row(run.req.rid)[:-(-n // cache.block_size)]

    def rows(pool_a):
        a = np.asarray(pool_a[:, blocks]).astype(np.float32)
        return a.reshape(a.shape[0], -1, a.shape[-1])[:, :n]

    return {"rid": run.req.rid, "prompt": np.asarray(run.req.prompt),
            "emitted": np.asarray(run.emitted, np.int32),
            "fixed_at": np.asarray(run.fixed_at, np.int32),
            "steps": run.req.denoise_steps, "cached": n,
            "k": rows(pool.k), "v": rows(pool.v)}


def pool_errors(taken, layers) -> Dict:
    """The pool's rows of one request (:func:`take_running`) against what the
    reference says a cache holds of each layer, k beside v, over every
    committed position: ``kv_row_err`` on layer 0, whose input is the
    embedding itself, ``deep_row_err`` the worst of the layers below it,
    whose inputs already differ by what bf16 did to the layers before (layer
    1 reads worst: a token whose layer-0 router picks flip between bf16's
    input and f32's changes by a whole expert), and ``last_row_err`` the
    deepest layer's, where that noise has washed out and what is left is what
    every layer above it saw — the one number a causal mask moves by
    several times its served reading."""
    import numpy as np

    n = taken["cached"]
    by_layer = []
    for li, layer in enumerate(layers):
        got = np.concatenate([taken["k"][li], taken["v"][li]], -1)
        want = np.concatenate([np.asarray(layer[k][:n], np.float32)
                               for k in ("k", "v")], -1)
        by_layer.append(_rel_err(got, want))
    return {"kv_row_err": by_layer[0], "deep_row_err": max(by_layer[1:]),
            "last_row_err": by_layer[-1], "row_errs_by_layer": by_layer}


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
    # token ids are drawn below the mask token, the vocabulary's last row
    vocab = int(h.config["source_vocab_size"]) - 1
    passes = [int(p) for p in spec["denoise_steps_by_request"]]
    params = make_params(cfg, h.seed)
    sched = Scheduler(
        params, cfg, max_batch=sv["max_batch"], block_size=sv["block_size"],
        pool_blocks=sv["pool_blocks"], prefill_chunk=sv["prefill_chunk"],
        prefix_cache=False)

    # the pool's occupancy, sampled where the loop reads the queue: between
    # two steps, a host integer; tokens counted from here on
    waiting, tokens = _program_gauges()
    tokens_before = tokens()
    held = []

    # every program the window can need, each served alone
    wrng = np.random.default_rng(h.seed + 1)
    warm = warmup_shapes(spec, sv["block_size"], sv["prefill_chunk"])
    for i, plen in enumerate(warm):
        sched.submit(Request(rid=f"warm{i}", max_new=cfg.block_length,
                             denoise_steps=1,
                             prompt=wrng.integers(0, vocab, plen)
                             .astype(np.int32)))
        while not sched.finished:
            sched.step()
        sched.results.pop(f"warm{i}")
    sched.flush_stats()

    def submit(r, base):
        sched.submit(Request(rid=r.rid, prompt=r.prompt, max_new=r.max_new,
                             denoise_steps=passes[r.rid % len(passes)],
                             arrival_s=base + r.due_s))

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
    taken = take_running(sched, int(spec["check_long_prompt_min"]),
                         np.random.default_rng(h.seed + 2))
    inside = [n for at, n in held
              if seen["start"]["t"] <= at <= seen["end"]["t"]]
    running = [r for r in sched._running if not isinstance(r.req.rid, str)]
    return {"cfg": cfg, "params": params, "spec": spec, "seen": seen,
            "peak": peak, "taken": taken, "warm": warm, "passes": passes,
            "at_trace": marked.at_trace,
            "blocks_in_use_mean": float(np.mean(inside)) if inside else None,
            "blocks_in_use_max": max(inside) if inside else None,
            "results": {k: v for k, v in sched.results.items()
                        if not isinstance(k, str)},
            # every token the program counted is a finished or a running
            # request's (the warm-up's were counted before the window)
            "tokens_counted": tokens() - tokens_before,
            "tokens_warm": len(warm) * cfg.block_length,
            "tokens_running": sum(len(r.emitted) for r in running),
            # blocks held by requests still running when the window closed
            # are live, not leaked: 0 means none is unaccounted
            "leaked": sched.cache.leaked_blocks(),
            "running_at_close": len(sched._running)}


def replay(params, hp, prompt, emitted, fixed_at, layers, sampled):
    """The sampler's passes over the blocks ``sampled`` (indices of the
    request's generated blocks) rebuilt from the record and run through the
    reference: per pass the block as it stood (a position is open before pass
    ``p`` iff it was fixed at ``p`` or later), the reference's logits over
    prefix + block, and from them the logit gaps at the positions that pass
    fixed and the confidence gap of the pass. Returns ``(logit gaps, relative
    confidence gaps)``, a number a fixed position and a pass."""
    import jax.numpy as jnp
    import numpy as np

    B, mask = hp["block_length"], hp["mask_id"]
    full = np.concatenate([prompt, emitted])
    at = np.concatenate([np.zeros(len(prompt), np.int32), fixed_at])
    first = len(prompt) // B * B
    gaps, conf_gaps = [], []
    for bi in sampled:
        lo = first + bi * B
        toks, when = full[lo:lo + B], at[lo:lo + B]
        if len(toks) < B:
            continue                    # a request ended inside this block
        prefix = [(layer["k"][:lo], layer["v"][:lo]) for layer in layers]
        for p in range(1, int(when.max()) + 1):
            stood = np.where(when >= p, mask, toks).astype(np.int32)
            logits, _ = sdar_reference.forward(
                params, jnp.asarray(stood), hp, start=lo, prefix=prefix)
            logits = np.array(logits, np.float32)
            logits[:, mask] = -np.inf
            fixed = np.flatnonzero(when == p)
            for i in fixed:
                gaps.append(float(logits[i].max() - logits[i, toks[i]]))
            _, ref_fixed, conf = sdar_reference.fix(
                logits, stood, len(fixed), mask)
            want = np.sort(conf[ref_fixed])[::-1]
            got = np.sort(conf[fixed])[::-1]
            conf_gaps.append(float(np.max((want - got) / want[0])))
    return gaps, conf_gaps


def check(h, st, over=None, long_only: bool = False) -> Dict:
    """The comparison with the reference (module docstring), each number
    beside its limit. ``over``: keys laid over the reference's ``hp`` (a
    causal mask, a narrower cache, a bf16 router or bf16 products),
    ``stale_pass`` (the reference's rows of a block as its last denoising
    pass left them) or ``wrong_order`` (the record read as if each block's
    passes had fixed their positions in the reverse order: what a program
    that fixed the LEAST confident positions first would have served) for the
    limits' second readings
    (``benchmark/controls/sdar_limits.py``): never set in a run that decides
    ``correct``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from byteps_tpu.parallel.moe import softmax_topk_route

    cfg, params, spec, taken = st["cfg"], st["params"], st["spec"], st["taken"]
    results = st["results"]
    by_rid = {r.rid: r for r in st["seen"]["reqs"]}
    hp = {k: v for k, v in dataclasses.asdict(cfg).items() if k != "dtype"}
    over = dict(over or {})
    stale = over.pop("stale_pass", False)
    wrong_order = over.pop("wrong_order", False)
    hp.update(over)
    # (rid, prompt, emitted, fixed_at, what the pool held of it)
    sample = []
    if taken is not None:
        sample.append((taken["rid"], taken["prompt"], taken["emitted"],
                       taken["fixed_at"], taken))
    short_max = int(spec["check_short_prompt_max"])
    shorts = sorted(r for r in results if len(by_rid[r].prompt) <= short_max)
    if shorts and not long_only:
        rid = int(np.random.default_rng(h.seed + 3).choice(shorts))
        sample.append((rid, np.asarray(by_rid[rid].prompt),
                       np.asarray(results[rid]["emitted"]),
                       np.asarray(results[rid]["fixed_at"]), None))
    qb = REF_BLOCK if not h.rehearse else 4
    pad = REF_PAD if not h.rehearse else 4
    B = cfg.block_length
    gaps, conf_gaps, route, seconds, pool = [], [], [], [], {}
    for rid, prompt, emitted, fixed_at, held in sample:
        t0 = time.monotonic()
        full = np.concatenate([prompt, emitted])
        toks = np.zeros(-(-len(full) // pad) * pad, np.int32)
        toks[:len(full)] = full
        if stale:
            # each block as its last denoising pass fed it: the positions
            # that pass fixed still hold the mask token
            n = len(prompt)
            last = np.maximum.reduceat(
                fixed_at, np.arange(0, len(fixed_at), B)).repeat(B)
            toks[n:n + len(emitted)] = np.where(
                fixed_at == last[:len(fixed_at)], cfg.mask_id, emitted)
        _, layers = sdar_reference.forward(
            params, jnp.asarray(toks), hp, logits_from=None, qb=qb,
            router_layers=(0,))
        if held is not None:
            pool = pool_errors(held, layers)
        if wrong_order:
            last = np.maximum.reduceat(
                fixed_at, np.arange(0, len(fixed_at), B)).repeat(B)
            fixed_at = np.where(fixed_at > 0,
                                last[:len(fixed_at)] + 1 - fixed_at, 0)
        n_blocks = len(emitted) // B
        g, c = replay(params, hp, prompt, emitted, fixed_at, layers,
                      sorted({0, n_blocks // 2, max(n_blocks - 1, 0)}))
        gaps.extend(g)
        conf_gaps.extend(c)
        # the program's router on the reference's own router input, layer 0
        idx, _ = jax.jit(functools.partial(softmax_topk_route, k=cfg.top_k))(
            layers[0]["router_input"], params["blocks"][0]["moe"]["wg"])
        route.append(int(jnp.sum(jnp.any(
            jnp.sort(idx, -1) != jnp.sort(layers[0]["router_picks"], -1),
            -1))))
        del layers
        seconds.append(time.monotonic() - t0)
    return {"checked_requests": [s[0] for s in sample],
            "checked_prompt_lens": [len(s[1]) for s in sample],
            "checked_emitted": [len(s[2]) for s in sample],
            "checked_passes": [st["passes"][s[0] % len(st["passes"])]
                               for s in sample],
            "long_prompt_checked": taken is not None,
            "max_logit_gap": max(gaps) if gaps else None,
            "mean_logit_gap": float(np.mean(gaps)) if gaps else None,
            "positions_replayed": len(gaps),
            "confidence_gap": max(conf_gaps) if conf_gaps else None,
            "passes_replayed": len(conf_gaps),
            "route_mismatch": max(route) if route else None,
            **pool, "check_seconds": seconds,
            **{k: spec[k] for k in LIMITS.values()}}


#: a number of :func:`check` -> the key of the traffic file that limits it
LIMITS = {"max_logit_gap": "logit_tolerance",
          "mean_logit_gap": "mean_logit_gap_max",
          "confidence_gap": "confidence_gap_max",
          "route_mismatch": "route_mismatch_max",
          "kv_row_err": "kv_row_err_max",
          "deep_row_err": "deep_row_err_max",
          "last_row_err": "last_row_err_max"}


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
    by_rid = {r.rid: r for r in seen["reqs"]}
    accounted = st["tokens_warm"] + st["tokens_running"] \
        + sum(len(r["emitted"]) for r in results.values())
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
    if st["tokens_counted"] != accounted:
        failed.append("tokens_accounted")
    # the gathered twin would copy every row's whole context a layer and
    # pass: on the chip the kernel path is the only one timed
    if h.device.get("platform") == "tpu" and through_kernel != decode_steps:
        failed.append("decode_steps_paged_attn")
    row_passes = moved("counters", "serve.block.row_passes")
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
        "blocks_committed": moved("counters", "serve.block.commits"),
        "blocks_in_use_mean": st["blocks_in_use_mean"],
        "queued_min_in_window": seen["queued_min"],
        "notes": {**chk, "not_correct_by": failed,
                  "requests": len(seen["reqs"]),
                  "completed_in_window": completed,
                  "tokens_in_window": tokens,
                  "decode_steps_in_window": decode_steps,
                  "decode_steps_paged_attn": through_kernel,
                  "decode_steps_overlapped":
                      moved("counters", "serve.decode_steps_overlapped"),
                  "serve.block.row_passes": row_passes,
                  "serve.block.commit_row_passes":
                      moved("counters", "serve.block.commit_row_passes"),
                  "serve.block.positions_fixed":
                      moved("counters", "serve.block.positions_fixed"),
                  "serve.kv.block_rows_rewritten":
                      moved("counters", "serve.kv.block_rows_rewritten"),
                  "tokens_per_row_pass":
                      tokens / row_passes if row_passes else None,
                  "tokens_counted": st["tokens_counted"],
                  "tokens_accounted": accounted,
                  "leaked_blocks": st["leaked"],
                  "blocks_in_use_max": st["blocks_in_use_max"],
                  "running_at_close": st["running_at_close"],
                  "preempted": moved("counters", "serve.preempted"),
                  "prefill_tokens_in_window":
                      moved("counters", "serve.prefill_tokens"),
                  "queued_min_in_window": seen["queued_min"],
                  "refills": seen["refills"],
                  "refill_ms_total": seen["refill_ms_total"],
                  "tokens_by_slice": seen["tokens_by_slice"],
                  "warmup_prompts": st["warm"],
                  "cache_dir": h.cache_dir},
    }
