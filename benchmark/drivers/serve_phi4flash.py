"""The Phi-4-mini-flash serving cell: ``Scheduler`` with a ``Phi4FlashConfig``
under the loop of ``drivers/serve.py`` (``drive``, imported as it stands), with
what is this model's carried here: one bf16 weight tree made on the device
(one jitted call a layer KIND), the warm-up rule of ``serve_qwen3next``
(imported), readings of the program's ``serve.kv.*`` / ``serve.attn.*`` /
``serve.sscan.*`` / ``sambay.*`` / ``serve.state.*`` series at the window's
ends and at the start of the trace, the three pools' occupancy sampled between
steps, and the model's own reference. (The loop around them is
``serve_falconh1``'s, copied: ``ROADMAP.md`` C13.)

``correct``, decided outside the window on what the timed path produced,
against one reference forward (``configs/phi4flash_reference.py``, f32, EVERY
layer on EVERY position, token by token through the nine scans) over prompt +
emitted tokens for each of two requests. The long one is drawn by the seed
among the requests of ``check_long_prompt_min`` tokens or more that were
DECODING WHEN THE WINDOW CLOSED, with what it has emitted so far, because its
slot, its window blocks and its layer-17 pages are still in the pools: **the
f32 state and the convolution tail the timed programs left in its slot, of all
nine scan layers, are read back and held to the reference's after as many
positions** — ``state_err`` on layer 0 (its input is the embedding, so the
number is the program's own arithmetic), ``deep_state_err`` the worst of the
other eight, ``tail_err`` the worst convolution tail — **the LIVE rows of the
eight window layers** (``window_row_err``: its last 511 positions, through the
window table) **and layer 17's k and v over every cached position through the
block table** (``full_row_err``: the rows a chunk that stopped after layer 17
wrote are among them). The short one is a completed request of
``check_short_prompt_max`` or less. For both: at the first, a middle and the
last generated position the reference's largest logit less its logit of the
served token (``logit_tolerance``), and the mean of those
(``mean_logit_gap_max``). And: no failed request, no leaked block, window
block or slot, every ``max_new`` met, the queue never empty inside the window,
every decode step through the paged-attention kernel and the ``sscan_decode``
kernel (on a TPU), and the layers above layer 17 run over no more than one
position a prompt. The limits' two readings each:
``traffic/reason-longgen-backlog-sat.json``, taken by
``controls/phi4flash_limits.py``.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict

from benchmark import harness, metrics, traffic_gen
from benchmark.configs import phi4flash_reference
from benchmark.drivers.serve import COUNTERS, HISTOGRAMS, SPANS, drive
from benchmark.drivers.serve_dots3 import _program_gauges, _rel_err
from benchmark.drivers.serve_falconh1 import _left_out, _longest_iterations
from benchmark.drivers.serve_qwen3next import warmup_shapes
# a program without the model cannot run the cell: fail here, before any
# device is claimed
from byteps_tpu.models.phi4_flash import (
    Phi4FlashConfig,
    at_depth,
    layer_kinds,
    phi4_flash_block_init,
    phi4_flash_head_init,
)

P4_COUNTERS = COUNTERS + ("serve.kv.decode_keys_read.full",
                          "serve.kv.decode_keys_read.window",
                          "serve.attn.prefill_pairs.full",
                          "serve.attn.prefill_pairs.window",
                          "serve.sscan.decode_rows",
                          "serve.sscan.prefill_tokens",
                          "sambay.cross_positions",
                          "serve.cache.window_blocks_released",
                          "serve.state.resets.admit",
                          "serve.state.resets.preempt",
                          "serve.decode_steps_paged_attn",
                          "sscan.decode_kernel", "sscan.decode_twin")
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
                           for k in HISTOGRAMS},
            "counters": {k: snap["counters"].get(k, 0)
                         for k in P4_COUNTERS}}


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
    return Phi4FlashConfig(**kw)


def make_params(cfg, seed: int):
    """The bf16 tree, on the device: a jitted call for the head and one for
    each KIND of layer (layers of a kind are of one shape; an attention
    layer's constant of its depth is laid on outside)."""
    import jax

    keys = jax.random.split(jax.random.PRNGKey(seed), 1 + cfg.n_layers)
    tree = jax.jit(functools.partial(phi4_flash_head_init, cfg=cfg))(keys[0])
    init = {kind: jax.jit(functools.partial(phi4_flash_block_init, cfg=cfg,
                                            kind=kind))
            for kind in set(layer_kinds(cfg))}
    tree["blocks"] = [at_depth(init[kind](keys[1 + li]), li)
                      for li, kind in enumerate(layer_kinds(cfg))]
    return jax.block_until_ready(tree)


def take_running(sched, cfg, long_min: int, rng):
    """One request that was decoding when the window closed, a prompt of
    ``long_min`` or more, drawn by the seed, and **what the timed programs
    left in the three pools for it**: its slot's state and convolution tail
    of every scan layer, the window layers' live rows (positions ``[live_from,
    cached)``) through its window table, and layer 17's k and v rows of every
    cached position through its block table as it stands. What the device had
    picked and the host had not read is read first, so that ``emitted`` names
    every token the state has seen but the last. None where no such request
    runs."""
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
    slot, blocks = int(row[0, 0]), row[0, 1:1 + -(-n // bs)]
    lo = max(0, n - (cfg.window - 1))
    wtable = cache._wtables[run.req.rid]
    wblocks = np.asarray([wtable[b] for b in range(lo // bs, -(-n // bs))])

    def full_rows(pool_a):
        a = np.asarray(pool_a[0, blocks]).astype(np.float32)
        return a.reshape(-1, a.shape[-1])[:n]

    return {"rid": run.req.rid, "prompt": np.asarray(run.req.prompt),
            "emitted": np.asarray(run.emitted, np.int32), "cached": n,
            "slot": slot, "live_from": lo,
            # the pool's (N, d_inner) as the reference's (d_inner, N)
            "S": np.asarray(pool.s[:, slot]).transpose(0, 2, 1),
            "tail": np.asarray(pool.conv[:, slot]).astype(np.float32)
            .reshape(pool.s.shape[0], cfg.conv_kernel - 1, -1),
            "k": full_rows(pool.k), "v": full_rows(pool.v),
            "wk": _window_rows(pool.wk, wblocks, lo, n, bs),
            "wv": _window_rows(pool.wv, wblocks, lo, n, bs)}


def _window_rows(pool_a, wblocks, lo: int, n: int, bs: int):
    """Rows ``[lo, n)`` of every window layer: ``(layers, n - lo, width)``
    f32, from the physical blocks of logical blocks ``lo // bs ..``."""
    import numpy as np

    a = np.asarray(pool_a[:, wblocks]).astype(np.float32)
    a = a.reshape(a.shape[0], -1, a.shape[-1])
    return a[:, lo - lo // bs * bs:][:, :n - lo]


def pool_errors(cfg, taken, layers, tail_shift: int = 0) -> Dict:
    """What the pools held of one request (:func:`take_running`) against the
    reference after as many positions: the size of the difference over the
    size of the reference's. ``state_err``: layer 0's recurrent state;
    ``deep_state_err``: the worst of the other scan layers, whose inputs
    already differ by what bf16 did to the layers before; ``tail_err``: the
    worst convolution tail; ``window_row_err``: the worst window layer's k
    beside v over its live rows; ``full_row_err``: layer 17's k beside v over
    every cached position. ``tail_shift``: the slot's tail held to the
    reference's that many positions EARLY — a tail one token stale, for the
    limits' second reading."""
    import numpy as np

    n, lo = taken["cached"], taken["live_from"]
    kinds = layer_kinds(cfg)
    state, tail, window, full = [], [], [], None
    for li, (kind, layer) in enumerate(zip(kinds, layers)):
        if kind == "mamba":
            i = len(state)
            state.append(_rel_err(taken["S"][i], layer["S"]))
            want = np.asarray(layer["tail"], np.float32)
            got = taken["tail"][i]
            if tail_shift:
                want, got = want[:-tail_shift], got[tail_shift:]
            tail.append(_rel_err(got, want))
        elif kind == "window":
            i = len(window)
            window.append(_rel_err(
                np.concatenate([taken["wk"][i], taken["wv"][i]], -1),
                np.concatenate([np.asarray(layer[k][lo:n], np.float32)
                                for k in ("k", "v")], -1)))
        elif kind == "full":
            full = _rel_err(
                np.concatenate([taken["k"], taken["v"]], -1),
                np.concatenate([np.asarray(layer[k][:n], np.float32)
                                for k in ("k", "v")], -1))
    return {"state_err": state[0], "deep_state_err": max(state[1:]),
            "tail_err": max(tail), "window_row_err": max(window),
            "full_row_err": full,
            "state_errs_by_layer": state, "tail_errs_by_layer": tail,
            "window_row_errs_by_layer": window}


def serve(h) -> Dict:
    """The run up to the comparison: weights, scheduler, warm-up, the
    window. Returns what the window showed, the completed requests, the
    request taken from the pools at the window's close, and the weights (the
    pools are gone: the reference's f32 blocks need the room)."""
    import jax
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

    # the three pools' occupancy and the batch's mean context, sampled where
    # the loop reads the queue: between two steps, host integers
    waiting, tokens = _program_gauges()
    held = []

    def waiting_and_sample():
        running = [r.cache_len for r in sched._running
                   if r.state == "decode"]
        held.append((time.monotonic(), sched.cache.blocks_in_use,
                     sched.cache.window_blocks_in_use,
                     sched.cache.slots_in_use,
                     sum(running) / len(running) if running else 0.0))
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
    t0, t1 = seen["start"]["t"], seen["end"]["t"]
    inside = [s[1:] for s in held if t0 <= s[0] <= t1]

    def mean(i):
        return float(np.mean([s[i] for s in inside])) if inside else None

    # the batch's mean context by 15 s of the run, the ramp included
    first = held[0][0] if held else t0
    by_slice = {}
    for s in held:
        by_slice.setdefault(int((s[0] - first) // 15), []).append(s[4])
    return {"cfg": cfg, "params": params, "spec": spec, "seen": seen,
            "peak": peak, "taken": taken, "warm": warm,
            "at_trace": marked.at_trace,
            "blocks_in_use_mean": mean(0),
            "window_blocks_in_use_mean": mean(1),
            "state_slots_in_use_mean": mean(2),
            "context_mean": mean(3),
            "context_mean_by_15s": [round(float(np.mean(v)), 1)
                                    for _, v in sorted(by_slice.items())],
            "state_slots": sched.cache.state_slots,
            "pool_blocks": sched.cache.pool_blocks,
            "window_pool_blocks": sched.cache.window_blocks,
            "pool_bytes": int(sum(
                a.nbytes for a in sched.cache.state if a is not None)),
            "weight_bytes": int(sum(
                a.nbytes for a in jax.tree_util.tree_leaves(params))),
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
    state kept in bf16, m taken after the gate, lambda_init of the wrong
    layer, the sub-norm left out, another window, the cross layers on zeroed
    k/v of their own, layer 17's rows of the chunks that stopped made from a
    stale x — ``stale_full_kv="last_chunk"``: every position before the
    prompt's last chunk — a rotary applied) and ``tail_shift`` (a tail one
    token stale) for the limits' second readings
    (``benchmark/controls/phi4flash_limits.py``): never set in a run that
    decides ``correct``."""
    import jax.numpy as jnp
    import numpy as np

    cfg, params, spec, taken = st["cfg"], st["params"], st["spec"], st["taken"]
    results = st["results"]
    by_rid = {r.rid: r for r in st["seen"]["reqs"]}
    chunk = int(h.config["assumed"]["serve"]["prefill_chunk"])
    hp = {k: v for k, v in dataclasses.asdict(cfg).items() if k != "dtype"}
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
    gaps, seconds, pool = [], [], {}
    for rid, prompt, emitted, held in sample:
        t0 = time.monotonic()
        full = np.concatenate([prompt, emitted])
        n = len(prompt)
        toks = np.zeros(-(-len(full) // pad) * pad, np.int32)
        toks[:len(full)] = full
        hp_r = dict(hp, **(over or {}))
        if hp_r.get("stale_full_kv") == "last_chunk":
            hp_r["stale_full_kv"] = (n - 1) // chunk * chunk
        # the first, a middle and the last generated position: the logits
        # that picked emitted[j] are those of position n - 1 + j
        at = sorted({0, len(emitted) // 2, len(emitted) - 1})
        logits, layers = phi4flash_reference.forward(
            params, jnp.asarray(toks), hp_r,
            state_at=held["cached"] if held is not None else 0,
            rows=[n - 1 + j for j in at], qb=qb)
        logits = np.asarray(logits, np.float32)
        gaps += [float(logits[i].max() - logits[i, emitted[j]])
                 for i, j in enumerate(at)]
        if held is not None:
            pool = pool_errors(cfg, held, layers, tail_shift)
            pool["stale_tail_err"] = pool_errors(cfg, held, layers,
                                                 1)["tail_err"]
        del logits, layers
        seconds.append(time.monotonic() - t0)
    return {"checked_requests": [s[0] for s in sample],
            "checked_prompt_lens": [len(s[1]) for s in sample],
            "checked_emitted": [len(s[2]) for s in sample],
            "long_prompt_checked": taken is not None,
            "max_logit_gap": max(gaps) if gaps else None,
            "mean_logit_gap": float(np.mean(gaps)) if gaps else None,
            "logit_gaps": gaps,
            **pool, "check_seconds": seconds,
            **{k: spec[k] for k in LIMITS.values()}}


#: a number of :func:`check` -> the key of the traffic file that limits it
LIMITS = {"max_logit_gap": "logit_tolerance",
          "mean_logit_gap": "mean_logit_gap_max",
          "state_err": "state_err_max",
          "deep_state_err": "deep_state_err_max",
          "tail_err": "tail_err_max",
          "window_row_err": "window_row_err_max",
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
    prefill_tokens = moved("counters", "serve.prefill_tokens")
    cross_positions = moved("counters", "sambay.cross_positions")
    released = moved("counters", "serve.cache.window_blocks_released")
    elapsed = end["t"] - start["t"]
    occupancy = end["histograms"]["serve.batch_occupancy"]["sum"] \
        - start["histograms"]["serve.batch_occupancy"]["sum"]
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
    if released <= 0:
        failed.append("window_blocks_released")
    # the layers above layer 17 run over ONE position a prompt, and a prompt
    # is ``prompt.min`` tokens at least: more means that a chunk which reads
    # nothing out did not stop (1.0 a token if none does)
    if cross_positions * int(st["spec"]["prompt"]["min"]) > prefill_tokens:
        failed.append("cross_positions")
    # on the chip the kernel paths are the only ones timed: the gathered
    # attention twin and the gather-update-scatter state twin are not
    if h.device.get("platform") == "tpu":
        if through_kernel != decode_steps:
            failed.append("decode_steps_paged_attn")
        if end["counters"]["sscan.decode_twin"] \
                or not end["counters"]["sscan.decode_kernel"]:
            failed.append("sscan_decode_kernel")
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
        "requests_completed": completed, "elapsed_s": elapsed,
        "prefill_tokens": prefill_tokens,
        "window_blocks_released": released,
        "cross_positions_per_prompt_token":
            cross_positions / prefill_tokens if prefill_tokens else None,
        "queued_min_in_window": seen["queued_min"],
        "notes": {**chk, "not_correct_by": failed,
                  "requests": len(seen["reqs"]),
                  "completed_in_window": completed,
                  "requests_per_s": completed / elapsed if elapsed else None,
                  "tokens_in_window": tokens,
                  "decode_steps_in_window": decode_steps,
                  "decode_steps_paged_attn": through_kernel,
                  "batch_occupancy_mean":
                      occupancy / decode_steps if decode_steps else None,
                  "context_mean": st["context_mean"],
                  "context_mean_by_15s": st["context_mean_by_15s"],
                  "blocks_in_use_mean": st["blocks_in_use_mean"],
                  "window_blocks_in_use_mean":
                      st["window_blocks_in_use_mean"],
                  "state_slots_in_use_mean": st["state_slots_in_use_mean"],
                  "state_slots": st["state_slots"],
                  "pool_blocks": st["pool_blocks"],
                  "window_pool_blocks": st["window_pool_blocks"],
                  "weight_bytes": st["weight_bytes"],
                  "pool_bytes": st["pool_bytes"],
                  "cross_positions_in_window": cross_positions,
                  "window_blocks_released": released,
                  "state_resets":
                      moved("counters", "serve.state.resets.admit")
                      + moved("counters", "serve.state.resets.preempt"),
                  "sscan_decode_kernel_traces":
                      end["counters"]["sscan.decode_kernel"],
                  "sscan_decode_twin_traces":
                      end["counters"]["sscan.decode_twin"],
                  "leaked_blocks": st["leaked"],
                  "leaked_slots": st["leaked_slots"],
                  "running_at_close": st["running_at_close"],
                  "preempted": moved("counters", "serve.preempted"),
                  "prefill_tokens_in_window": prefill_tokens,
                  "queued_min_in_window": seen["queued_min"],
                  "refills": seen["refills"],
                  "refill_ms_total": seen["refill_ms_total"],
                  "tokens_by_slice": seen["tokens_by_slice"],
                  "warmup_shapes": st["warm"],
                  "cache_dir": h.cache_dir},
    }
    observed["notes"]["compiles_in_window"] = h.compiles
    observed["notes"]["longest_iterations"] = _longest_iterations(h, observed)
    if h.traced:
        observed["notes"].update(_left_out(h, observed))
    return observed
