"""The Falcon-H1 serving cell: ``Scheduler`` with a ``FalconH1Config`` under
the loop of ``drivers/serve.py`` (``drive``, imported as it stands), with what
is this model's carried here: one bf16 weight tree made on the device, the
warm-up rule of ``serve_qwen3next`` (imported), readings of the program's
``serve.kv.*`` / ``serve.attn.*`` / ``serve.ssd.*`` / ``serve.state.*`` series
at the window's ends and at the start of the trace, the k/v pool's and the
slot pool's occupancy sampled between steps, and the model's own reference.
(The loop around them is ``serve_qwen3next``'s, copied: ``ROADMAP.md`` C13.)

``correct``, decided outside the window on what the timed path produced,
against one reference forward (``configs/falconh1_reference.py``, f32, token
by token through every layer's mixer) over prompt + emitted tokens for each of
two requests. The long one is drawn by the seed among the requests of
``check_long_prompt_min`` tokens or more that were DECODING WHEN THE WINDOW
CLOSED, with what it has emitted so far, because its slot and its pages are
still in the pools: **the f32 state and the convolution tail the timed
programs left in its slot, of all four layers, are read back and held to the
reference's after as many positions** — ``state_err`` on layer 0 (its input is
the embedding, so the number is the program's own arithmetic),
``deep_state_err`` the worst of the other layers, ``tail_err`` the worst
convolution tail — and the k and v rows of all four layers are read back
through its block table (``row_err``). The short one is a completed request of
``check_short_prompt_max`` or less. For both: at the first, a middle and the
last generated position the reference's largest logit less its logit of the
served token (``logit_tolerance``). And: no failed request, no leaked block
and no leaked slot, every ``max_new`` met, the queue never empty inside the
window, every decode step through the paged-attention kernel and the
``ssd_decode`` kernel (on a TPU). The limits' two readings each:
``traffic/assist-chat-backlog-sat.json``, taken by
``controls/falconh1_limits.py``.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict

from benchmark import harness, metrics, traffic_gen
from benchmark.configs import falconh1_reference
from benchmark.drivers.serve import COUNTERS, HISTOGRAMS, SPANS, drive
from benchmark.drivers.serve_dots3 import _program_gauges, _rel_err
from benchmark.drivers.serve_qwen3next import warmup_shapes
# a program without the model cannot run the cell: fail here, before any
# device is claimed
from byteps_tpu.models.falcon_h1 import (
    FalconH1Config,
    falcon_h1_block_init,
    falcon_h1_head_init,
)

FH_COUNTERS = COUNTERS + ("serve.kv.decode_keys_read.full",
                          "serve.attn.prefill_pairs.full",
                          "serve.ssd.decode_rows",
                          "serve.ssd.prefill_tokens",
                          "serve.state.resets.admit",
                          "serve.state.resets.preempt",
                          "serve.decode_steps_paged_attn",
                          "ssd.decode_kernel", "ssd.decode_twin")
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
                         for k in FH_COUNTERS}}


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
    return FalconH1Config(**kw)


def make_params(cfg, seed: int):
    """The bf16 tree, on the device: a jitted call for the head and one for
    a layer (every layer is of one shape)."""
    import jax

    keys = jax.random.split(jax.random.PRNGKey(seed), 1 + cfg.n_layers)
    tree = jax.jit(functools.partial(falcon_h1_head_init, cfg=cfg))(keys[0])
    init = jax.jit(functools.partial(falcon_h1_block_init, cfg=cfg))
    tree["blocks"] = [init(keys[1 + li]) for li in range(cfg.n_layers)]
    return jax.block_until_ready(tree)


def take_running(sched, cfg, long_min: int, rng):
    """One request that was decoding when the window closed, a prompt of
    ``long_min`` or more, drawn by the seed, and **what the timed programs
    left in the pools for it**: its slot's state and convolution tail of
    every layer, and per layer the k and v rows of its ``cached`` positions
    read through its block table as it stands. What the device had picked and
    the host had not read is read first, so that ``emitted`` names every
    token the state has seen but the last. None where no such request
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


def pool_errors(taken, layers, tail_shift: int = 0) -> Dict:
    """What the pools held of one request (:func:`take_running`) against the
    reference after as many positions: the size of the difference over the
    size of the reference's. ``state_err``: layer 0's recurrent state;
    ``deep_state_err``: the worst of the other layers, whose inputs already
    differ by what bf16 did to the layers before; ``tail_err``: the worst
    convolution tail; ``row_err``: the worst layer's k beside v over every
    cached position. ``tail_shift``: the slot's tail held to the reference's
    that many positions EARLY — a tail one token stale, for the limits'
    second reading."""
    import numpy as np

    n = taken["cached"]
    state, tail, row = [], [], []
    for li, layer in enumerate(layers):
        state.append(_rel_err(taken["S"][li], layer["S"]))
        want = np.asarray(layer["tail"], np.float32)
        got = taken["tail"][li]
        if tail_shift:
            # the reference's rows for positions n - 3 - shift ..: its last
            # rows moved down, the slot's first rows dropped
            want, got = want[:-tail_shift], got[tail_shift:]
        tail.append(_rel_err(got, want))
        row.append(_rel_err(
            np.concatenate([taken["k"][li], taken["v"][li]], -1),
            np.concatenate([np.asarray(layer[k][:n], np.float32)
                            for k in ("k", "v")], -1)))
    return {"state_err": state[0],
            "deep_state_err": max(state[1:]) if state[1:] else 0.0,
            "tail_err": max(tail), "row_err": max(row),
            "state_errs_by_layer": state, "tail_errs_by_layer": tail,
            "row_errs_by_layer": row}


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
            "blocks_in_use_mean":
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
    state kept in bf16, the decay after the update, no ``dt_bias``, the other
    group's B and C, one norm group, a multiplier or a branch left out) and
    ``tail_shift`` (a tail one token stale) for the limits' second readings
    (``benchmark/controls/falconh1_limits.py``): never set in a run that
    decides ``correct``."""
    import jax.numpy as jnp
    import numpy as np

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
    gaps, seconds, shares = [], [], None
    pool = {}
    for rid, prompt, emitted, held in sample:
        t0 = time.monotonic()
        full = np.concatenate([prompt, emitted])
        n = len(prompt)
        toks = np.zeros(-(-len(full) // pad) * pad, np.int32)
        toks[:len(full)] = full
        # the first, a middle and the last generated position: the logits
        # that picked emitted[j] are those of position n - 1 + j
        at = sorted({0, len(emitted) // 2, len(emitted) - 1})
        logits, layers = falconh1_reference.forward(
            params, jnp.asarray(toks), hp,
            state_at=held["cached"] if held is not None else 0,
            rows=[n - 1 + j for j in at], qb=qb)
        logits = np.asarray(logits, np.float32)
        gaps += [float(logits[i].max() - logits[i, emitted[j]])
                 for i, j in enumerate(at)]
        if held is not None:
            pool = pool_errors(held, layers, tail_shift)
            pool["stale_tail_err"] = pool_errors(held, layers, 1)["tail_err"]
            # each branch's share of the residual it joins, by layer
            shares = np.asarray(jnp.stack(
                [layer["shares"] for layer in layers])).round(4).tolist()
        del logits, layers
        seconds.append(time.monotonic() - t0)
    return {"checked_requests": [s[0] for s in sample],
            "checked_prompt_lens": [len(s[1]) for s in sample],
            "checked_emitted": [len(s[2]) for s in sample],
            "long_prompt_checked": taken is not None,
            "max_logit_gap": max(gaps) if gaps else None,
            "logit_gaps": gaps,
            "branch_shares_ssm_attn_mlp_by_layer": shares,
            **pool, "check_seconds": seconds,
            **{k: spec[k] for k in LIMITS.values()}}


#: a number of :func:`check` -> the key of the traffic file that limits it
LIMITS = {"max_logit_gap": "logit_tolerance",
          "state_err": "state_err_max",
          "deep_state_err": "deep_state_err_max",
          "tail_err": "tail_err_max",
          "row_err": "row_err_max"}


def over_limit(chk: Dict) -> list:
    """The numbers of one :func:`check` that are missing or over their
    limits: empty is what ``correct`` needs of the comparison."""
    return [k for k, lim in LIMITS.items()
            if chk.get(k) is None or chk[k] > chk[lim]]


def _left_out(h, observed) -> Dict:
    """What the per-layer list has no place for (it holds 128 of 128), read
    by the readers that would read it, for the notes of a traced run: the
    device step of an iteration that carried a final chunk alone, and a
    decode step's elementwise and matmul time."""
    from benchmark.readers import program_span_ms, trace_ms_in_device_steps

    cut = dict(kind="decode", anchor="serve.iteration",
               trace_anchor="serve.step")
    return {
        "final_chunk_step_ms": program_span_ms.read(
            h, observed, spans=["serve.device_step.chunk"]),
        "decode_step_elementwise_ms": trace_ms_in_device_steps.read(
            h, observed, categories=["loop fusion"], **cut),
        "decode_step_matmul_ms": trace_ms_in_device_steps.read(
            h, observed, categories=["convolution fusion", "convolution"],
            **cut)}


def _longest_iterations(h, observed, n: int = 3) -> list:
    """The ``n`` longest ``serve.iteration`` spans of the window, from the
    program's own ring: seconds into the window, milliseconds, and the spans
    under each by name — where a stalled run lost its time."""
    from benchmark.readers import program_span_ms

    entries, w = program_span_ms.ring(), program_span_ms.window(h, observed)
    if not entries or w is None:
        return []
    inside = [e for e in entries if e[1] >= w[0] and e[1] + e[2] <= w[1]]
    its = sorted((e for e in inside if e[0] == "serve.iteration"),
                 key=lambda e: e[1])
    top = sorted(its, key=lambda e: -e[2])[:n]
    # the longest pause BETWEEN two iterations (the driver's own loop)
    gap = max(((b[1] - a[1] - a[2], a[1] + a[2] - w[0])
               for a, b in zip(its, its[1:])), default=(0.0, 0.0))
    out = [{"longest_gap_between_iterations_ms": round(1e3 * gap[0], 2),
            "at_s": round(gap[1], 3)}]
    for it in top:
        under: Dict[str, float] = {}
        for e in inside:
            if e[4] == it[3]:
                under[e[0]] = under.get(e[0], 0.0) + 1e3 * e[2]
        out.append({"at_s": round(it[1] - w[0], 3), "ms": round(1e3 * it[2], 2),
                    "under_ms": {k: round(v, 2) for k, v in under.items()}})
    return out


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
    # on the chip the kernel paths are the only ones timed: the gathered
    # attention twin and the gather-update-scatter state twin are not
    if h.device.get("platform") == "tpu":
        if through_kernel != decode_steps:
            failed.append("decode_steps_paged_attn")
        if end["counters"]["ssd.decode_twin"] \
                or not end["counters"]["ssd.decode_kernel"]:
            failed.append("ssd_decode_kernel")
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
        "prefill_tokens": moved("counters", "serve.prefill_tokens"),
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
                  "blocks_in_use_mean": st["blocks_in_use_mean"],
                  "state_slots_in_use_mean": st["state_slots_in_use_mean"],
                  "state_slots": st["state_slots"],
                  "state_resets":
                      moved("counters", "serve.state.resets.admit")
                      + moved("counters", "serve.state.resets.preempt"),
                  "ssd_decode_kernel_traces":
                      end["counters"]["ssd.decode_kernel"],
                  "ssd_decode_twin_traces":
                      end["counters"]["ssd.decode_twin"],
                  "leaked_blocks": st["leaked"],
                  "leaked_slots": st["leaked_slots"],
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
    observed["notes"]["compiles_in_window"] = h.compiles
    observed["notes"]["longest_iterations"] = _longest_iterations(h, observed)
    if h.traced:
        observed["notes"].update(_left_out(h, observed))
    return observed
