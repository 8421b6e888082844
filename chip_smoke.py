#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py            # one TPU chip: train, trace, serve, hybrid
    python chip_smoke.py --chips 4  # four chips: the dp=4 step and what it
                                    # is compared with, and nothing else
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse  # tiny sizes, no chip

Drives the main path once through the entry points a user calls, at
GPT-2-medium's published widths and depth (random weights from ``--seed``),
and checks what comes out by the repo's own means. Every phase prints JSON
lines; the LAST line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
and is printed only when every check of every phase held. Any failure —
no TPU, a phase that raises, a false check — is a non-zero exit without
that line. ``--rehearse`` shrinks the sizes, skips the platform assertion
and never prints the ``ok`` line.

A chip belongs to one process at a time, so this file is two programs. The
parent never imports jax: it runs the phases one after another as child
processes of this same file (``--phase``), each of which holds the chip
alone, reads its configuration from its own environment the way a user's
process would, and shares the one compile cache. For the hybrid phase the
parent also starts the C++ summation server (a process that never imports
jax) and sees it exit.

Phases (one chip): ``train`` — bps.init + make_gpt_train_step, raw leg
against a plain jax.numpy + optax step, then an onebit+EF leg; ``trace`` —
two steps under BYTEPS_TRACE_ON=1 and the dumped chrome trace;
``serve`` — Scheduler.serve against solo make_generate_fn; ``hybrid`` —
eager bps.push_pull through the REDUCE→COPYD2H→PUSH→PULL→COPYH2D
pipeline and a localhost server. Four chips: ``dp4``.

Tolerances, all from bf16's 8-bit significand (2**-8 relative per rounded
value), are the module constants below.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CSRC = os.path.join(HERE, "byteps_tpu", "server", "csrc")

ONE_CHIP_PHASES = ("train", "trace", "serve", "hybrid")

# The framework step (flash kernels, chunked CE) against the plain step
# (jnp attention, dense CE) on the same weights and batch. The loss is a
# mean over B*S = 8192 token losses of magnitude ln(50304) = 10.8, each
# carrying its own bf16 rounding of relative size 2**-8 (0.04 absolute):
# independent errors average down to 0.04 / sqrt(8192) = 5e-4, and the
# bound is four of those. (Measured on the chip, PR 21: 9e-5.)
LOSS_TOL = 2e-3
# After optimizer updates the two trajectories no longer share weights
# exactly (adam's first steps move every weight by ~lr whatever the
# gradient's size, so a gradient that rounds to the other sign moves its
# weight the other way): later steps get 2.5x the step-0 bound.
# (Measured at step 1, PR 21: 1.2e-4.)
TRAJ_TOL = 5e-3
# Greedy tokens from the paged server and from solo generate may part at
# a near-tie: the two run different attention code on the chip (ISSUE 21,
# ROADMAP A5). At the first differing position both tokens must be within
# this of the best logit of a plain float32-accumulated reference
# forward. Logits here are O(1) (|h| ~ 32, |wte row| ~ 0.64, random
# directions), and 24 layers of bf16 rounding leave ~1e-2 on them; 2**-4
# is the stated bound, anything larger fails. (Measured, PR 21: one
# request of eight parts, at a gap of 0.018.)
LOGIT_TOL = 2 ** -4

HYBRID_ROOT_PORT = 23711          # server 0 listens on root + 1


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: CHECK FAILED: {what}")


# --------------------------------------------------------------------------
# parent: no jax in this half
# --------------------------------------------------------------------------
def run_phase(phase: str, args, extra_env=None) -> list:
    """Run one phase as a child that holds the chip alone. Its stdout is
    passed through line by line; returns its JSON records. A non-zero exit
    ends the run."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--seed", str(args.seed)]
    if args.rehearse:
        cmd.append("--rehearse")
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra_env or {})
    records = []
    t0 = time.monotonic()
    with subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                          text=True) as proc:
        for line in proc.stdout:
            print(line, end="", flush=True)
            if line.startswith("{"):
                records.append(json.loads(line))
    if proc.returncode != 0:
        raise SystemExit(
            f"chip_smoke: phase {phase!r} failed (exit {proc.returncode})")
    emit(phase=phase, wall_s=round(time.monotonic() - t0, 1), done=True)
    return records


def run_hybrid(args) -> list:
    """The hybrid phase and its server. On the chip the server library is
    built from the committed sources first (a copied tree may carry a
    library from another commit with fresh-looking times); a rehearsal
    shares the loaded library with the tests and lets make decide."""
    if not args.rehearse:
        subprocess.run(["make", "-C", CSRC, "clean"], check=True,
                       stdout=subprocess.DEVNULL)
    port = HYBRID_ROOT_PORT + 1
    server = subprocess.Popen(
        [sys.executable, "-c",
         "from byteps_tpu.server import start_server\n"
         "from byteps_tpu.server.native import load_lib\n"
         f"start_server(port={port}, num_workers=1, engine_threads=4, "
         "async_mode=False)\n"
         "print('listening', flush=True)\n"
         "load_lib().bps_server_wait()\n"],
        env={**os.environ, "PYTHONPATH": HERE}, stdout=subprocess.PIPE,
        text=True)
    try:
        t0 = time.monotonic()
        line = server.stdout.readline()     # blocks through the build
        check(line.strip() == "listening",
              f"summation server did not start (said {line!r})")
        emit(phase="hybrid", server_build_and_start_s=round(
            time.monotonic() - t0, 1))
        records = run_phase("hybrid", args, {
            "BYTEPS_FORCE_DISTRIBUTED": "1",
            "DMLC_NUM_WORKER": "1", "DMLC_NUM_SERVER": "1",
            "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": str(HYBRID_ROOT_PORT),
        })
        # the worker's bps.shutdown() said goodbye: the server stops itself
        rc = server.wait(timeout=60)
        check(rc == 0, f"summation server exited with {rc}")
        emit(phase="hybrid", server_exited_after_shutdown=True)
        return records
    finally:
        if server.poll() is None:
            server.kill()
            server.wait()


def parent_main(args) -> int:
    phases = ("dp4",) if args.chips == 4 else ONE_CHIP_PHASES
    device = None
    for phase in phases:
        if phase == "hybrid":
            records = run_hybrid(args)
        elif phase == "trace":
            records = run_phase(phase, args, {
                "BYTEPS_TRACE_ON": "1",
                "BYTEPS_TRACE_DIR": os.path.join(HERE, "traces")})
        else:
            records = run_phase(phase, args)
        seen = [r["device"] for r in records if "device" in r]
        check(bool(seen), f"phase {phase} reported no device")
        check(device in (None, seen[0]),
              f"phase {phase} ran on {seen[0]}, earlier phases on {device}")
        device = seen[0]
    emit(phases=list(phases), rehearse=args.rehearse, seed=args.seed,
         claim=None)
    if args.rehearse:
        return 0
    check(device["platform"] == "tpu" and device["count"] == args.chips,
          f"ran on {device}, wanted {args.chips} tpu device(s)")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


# --------------------------------------------------------------------------
# children: one process, one chip (or four)
# --------------------------------------------------------------------------
def child_setup(args, want_devices: int):
    """Import jax, check the platform, place the compile cache. Returns the
    device record the parent copies into the last line."""
    import jax

    from byteps_tpu.common.compile_cache import enable_compile_cache

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not args.rehearse and device["platform"] != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, but jax found platform "
            f"{device['platform']!r} ({device['kind']}, {device['count']} "
            "device(s)); run it through the chip tool, or rehearse with "
            "JAX_PLATFORMS=cpu python chip_smoke.py --rehearse")
    check(device["count"] == want_devices,
          f"phase needs {want_devices} device(s), jax found "
          f"{device['count']} (a rehearsal of --chips 4 wants XLA_FLAGS="
          "--xla_force_host_platform_device_count=4)")
    emit(phase=args.phase, device=device,
         compile_cache=enable_compile_cache())
    return device


def model_config(args):
    """GPT-2-medium at its published widths and depth (the bf16 ``gpt2m``
    shape); GPTConfig.tiny() in a rehearsal. Returns (cfg, B, S). S = 1024
    and B = 8: the largest power of two at which the TPU compiler's
    memory_analysis() puts EVERY leg's step under the chip's 16 GB — raw
    11.97 GiB, onebit+EF 13.44 GiB; at B=16 raw is 14.12 GiB and onebit+EF,
    another 3.6 GiB per 4 rows, no longer fits (compiled for a described
    v5e, PR 21)."""
    from byteps_tpu.models import GPTConfig

    if args.rehearse:
        return GPTConfig.tiny(), 4, 32
    return GPTConfig.gpt2_medium(), 8, 1024


@contextlib.contextmanager
def plain_jnp():
    """Trace what is built inside with every op on its jax.numpy twin —
    the reference side of a comparison must not share the kernels under
    test. (The switch is read at trace time, so the first call of a
    jitted reference happens inside.)"""
    old = os.environ.get("BYTEPS_KERNEL_BACKEND")
    os.environ["BYTEPS_KERNEL_BACKEND"] = "jnp"
    try:
        yield
    finally:
        if old is None:
            del os.environ["BYTEPS_KERNEL_BACKEND"]
        else:
            os.environ["BYTEPS_KERNEL_BACKEND"] = old


def host_batches(seed: int, cfg, batch: int, seq: int, steps: int):
    """``steps`` synthetic (tokens, targets) batches, a pure function of
    the seed — both sides of every comparison are fed from here."""
    import jax
    import numpy as np

    from byteps_tpu.models.train import synthetic_batch

    rng = jax.random.PRNGKey(seed)
    return [tuple(np.asarray(a) for a in synthetic_batch(
        jax.random.fold_in(rng, i), cfg, batch, seq)) for i in range(steps)]


def host_params(seed: int, cfg):
    """gpt_init weights from the seed, kept on the HOST: each leg's
    factory places its own copy, and a spare 1.4 GB set on the device
    would come out of the batch's room."""
    import jax
    import numpy as np

    from byteps_tpu.models import gpt_init

    return jax.tree.map(np.asarray,
                        gpt_init(jax.random.PRNGKey(seed), cfg))


def n_pallas_calls(compiled_text: str) -> int:
    return compiled_text.count('custom_call_target="tpu_custom_call"')


def peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats()     # None on the CPU backend
    return None if stats is None else stats["peak_bytes_in_use"]


def train_leg(phase: str, label: str, cfg, mesh, params0, batches,
              **factory_kw):
    """One leg of a train phase, through the entry points a user calls:
    make_gpt_train_step on ``mesh`` from the host weights, the step
    compiled ahead of time (its text is what the caller's kernel and
    collective checks read; the jit call that follows finds it in the
    compile cache), then every batch through PrefetchLoader, each step
    ended by block_until_ready. Returns (losses, compiled text, params,
    opt_state, the first placed tokens)."""
    import jax
    import optax

    from byteps_tpu.data import PrefetchLoader
    from byteps_tpu.models.train import make_gpt_train_step

    t0 = time.perf_counter()
    step, params, opt_state, bsh = make_gpt_train_step(
        cfg, mesh, optax.adamw(1e-3), init_params=params0, **factory_kw)
    tokens, targets = (jax.device_put(a, bsh) for a in batches[0])
    text = step.lower(params, opt_state, tokens, targets).compile().as_text()
    emit(phase=phase, leg=label, pallas_calls=n_pallas_calls(text),
         compile_s=round(time.perf_counter() - t0, 1))
    losses = []
    with PrefetchLoader(iter(batches), bsh, depth=2) as loader:
        for i, (tok, tgt) in enumerate(loader):
            t0 = time.perf_counter()
            loss, params, opt_state = step(params, opt_state, tok, tgt)
            jax.block_until_ready((loss, params, opt_state))
            ms = (time.perf_counter() - t0) * 1e3
            losses.append(float(loss))
            emit(phase=phase, leg=label, step=i, loss=losses[-1],
                 wall_ms=round(ms, 2))
    check(all(math.isfinite(x) for x in losses), f"{label}: losses finite")
    return losses, text, params, opt_state, tokens


def gold_losses(cfg, params0, batches, n_steps: int):
    """The plain side: jax.jit + jax.numpy attention + dense softmax CE +
    optax.adamw, no framework (the plain gold step, with the jnp twins
    forced and blocks rematerialized so the S*S score arrays of 24 layers
    need not live at once). Returns the first ``n_steps`` losses."""
    import functools

    import jax
    import optax

    from byteps_tpu.models import gpt_loss

    tx = optax.adamw(1e-3)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def gold_step(p, s, tok, tgt):
        loss, g = jax.value_and_grad(lambda p_: gpt_loss(
            p_, tok, tgt, cfg, chunked_ce=False, remat=True))(p)
        u, s = tx.update(g, s, p)
        return loss, optax.apply_updates(p, u), s

    p = jax.device_put(params0)
    s = tx.init(p)
    out = []
    with plain_jnp():
        for tok, tgt in batches[:n_steps]:
            loss, p, s = gold_step(p, s, tok, tgt)
            out.append(float(loss))
    return out


def phase_train(args) -> None:
    import jax
    import jax.numpy as jnp

    import byteps_tpu
    import byteps_tpu.jax as bps
    from byteps_tpu.jax.optimizer import _chunk_bounds
    from byteps_tpu.parallel import MeshAxes, make_mesh

    device = child_setup(args, 1)
    on_tpu = device["platform"] == "tpu"
    cfg, B, S = model_config(args)
    n_steps = 5
    bps.init()
    mesh = make_mesh(MeshAxes(), devices=jax.devices()[:1])
    batches = host_batches(args.seed, cfg, B, S, n_steps)
    params0 = host_params(args.seed, cfg)
    n_params = sum(x.size for x in jax.tree.leaves(params0))
    emit(phase="train", config=dict(
        vocab=cfg.vocab_size, d_model=cfg.d_model, n_layers=cfg.n_layers,
        n_heads=cfg.n_heads, d_ff=cfg.d_ff, dtype=jnp.dtype(cfg.dtype).name,
        B=B, S=S, params=int(n_params)), seed=args.seed)

    t0 = time.perf_counter()
    gold = gold_losses(cfg, params0, batches, 2)
    emit(phase="train", leg="gold", losses=gold,
         wall_s=round(time.perf_counter() - t0, 1))

    # --- raw leg: the default fused DistributedOptimizer + chunked CE ----
    losses, text, _, _, _ = train_leg("train", "raw", cfg, mesh, params0,
                                      batches)
    flash_calls = 2 * cfg.n_layers      # fwd + the one-pass bwd, every layer
    if on_tpu:
        check(n_pallas_calls(text) == flash_calls,
              f"raw step holds {n_pallas_calls(text)} Pallas calls, "
              f"expected {flash_calls} (flash fwd + 1 bwd per layer)")
    ln_v = math.log(cfg.vocab_size)
    check(abs(losses[0] - ln_v) <= 0.05 * ln_v,
          f"step-0 loss {losses[0]} within 5% of ln(vocab) {ln_v:.4f}")
    check(abs(losses[0] - gold[0]) <= LOSS_TOL,
          f"step-0 loss {losses[0]} equals the plain step's {gold[0]} "
          f"to {LOSS_TOL}")
    check(abs(losses[1] - gold[1]) <= TRAJ_TOL,
          f"step-1 loss {losses[1]} follows the plain step's {gold[1]} "
          f"to {TRAJ_TOL}")
    emit(phase="train", leg="raw", losses=losses,
         step0_minus_gold=losses[0] - gold[0],
         step1_minus_gold=losses[1] - gold[1], peak_bytes=peak_bytes())

    # --- onebit + error feedback: the Pallas codec kernels, compiled -----
    comp = {"compressor": "onebit", "ef": "vanilla"}
    c_losses, text, _, opt_state, _ = train_leg(
        "train", "onebit", cfg, mesh, params0, batches[:3],
        compression_params=comp)
    n_chunks = len(_chunk_bounds(
        n_params, byteps_tpu.get_config().partition_bytes // 4))
    if on_tpu:
        want = flash_calls + 2 * n_chunks   # + pack and unpack per chunk
        check(n_pallas_calls(text) == want,
              f"onebit step holds {n_pallas_calls(text)} Pallas calls, "
              f"expected {want} (flash + 2 x {n_chunks} partitions)")
    # a one-device mesh must not drop compression silently: error feedback
    # leaves a residual only where a codec ran
    check(opt_state.ef is not None
          and float(jnp.abs(opt_state.ef).sum()) > 0.0,
          "onebit leg: the error-feedback residual is non-zero")
    check(abs(c_losses[0] - losses[0]) <= 1e-3,
          f"onebit step-0 loss {c_losses[0]} is the raw one {losses[0]} "
          "(the loss precedes the update)")
    # ... and the eager compressed collective behind bps.init(): the
    # ici.* counters are host-dispatch counts, which the fused step by
    # design never touches (comm/ici.py _account_wire)
    x = jax.random.normal(jax.random.PRNGKey(args.seed + 1),
                          (1, (4 << 20) if on_tpu else (1 << 16)))
    out = bps.push_pull(x, average=False, name="smoke.eager",
                        compression_params=comp)
    scale = float(jnp.abs(x).mean())
    check(bool(jnp.all(jnp.abs(out) > 0))
          and abs(float(jnp.abs(out).mean()) - scale) <= 1e-3 * scale,
          "eager onebit push_pull returns sign(x) * mean|x|")
    counters = byteps_tpu.metrics_snapshot()["metrics"]["counters"]
    check(counters.get("ici.compressed_allreduce_dispatch", 0) > 0,
          "ici.compressed_allreduce_dispatch > 0 after a compressed "
          "push_pull")
    emit(phase="train", leg="onebit", losses=c_losses,
         ef_abs_sum=float(jnp.abs(opt_state.ef).sum()),
         compressed_allreduce_dispatch=counters[
             "ici.compressed_allreduce_dispatch"],
         peak_bytes=peak_bytes())
    bps.shutdown()


def phase_trace(args) -> None:
    """Two steps of the train step with BYTEPS_TRACE_ON=1 in the
    environment, as a user would set it: the fused path's in-program
    marker (jax.debug.callback) is the only step marker there is, so the
    dumped chrome trace shows whether host callbacks work on this chip."""
    import jax
    import optax

    import byteps_tpu.jax as bps
    from byteps_tpu.common.tracing import get_tracer
    from byteps_tpu.models import gpt_init
    from byteps_tpu.models.train import make_gpt_train_step
    from byteps_tpu.parallel import MeshAxes, make_mesh

    child_setup(args, 1)
    cfg, B, S = model_config(args)
    # widths uncut, depth cut to two layers: the marker does not depend
    # on depth, and a third 24-layer compile would buy nothing
    cfg = dataclasses.replace(cfg, n_layers=2)
    bps.init()
    mesh = make_mesh(MeshAxes(), devices=jax.devices()[:1])
    t0 = time.perf_counter()
    step, params, opt_state, bsh = make_gpt_train_step(
        cfg, mesh, optax.adamw(1e-3),
        init_params=gpt_init(jax.random.PRNGKey(args.seed), cfg))
    for tok, tgt in host_batches(args.seed, cfg, B, S, 2):
        loss, params, opt_state = step(
            params, opt_state, jax.device_put(tok, bsh),
            jax.device_put(tgt, bsh))
        jax.block_until_ready(loss)
    jax.effects_barrier()
    path = get_tracer().dump()
    check(path is not None, "BYTEPS_TRACE_ON=1 dumped a chrome trace")
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    marks = sorted(e["name"] for e in events
                   if e["tid"] == "FUSED_PUSHPULL")
    check(marks == ["step1", "step2"],
          f"the trace holds the two in-program step markers, got {marks}")
    emit(phase="trace", markers=marks, trace=path, loss=float(loss),
         wall_s=round(time.perf_counter() - t0, 1))
    bps.shutdown()


def parting(served, solo, next_token_logits):
    """None where the two token arrays are equal. Else the first position
    where they part, the two tokens, and how far each one's logit lies
    below the best in ``next_token_logits(common context)`` — a greedy
    pick may legitimately flip between two near-equal logits, and after
    that the continuations differ by right."""
    import numpy as np

    check(served.shape == solo.shape,
          f"token counts differ: {served.shape} vs {solo.shape}")
    diff = np.flatnonzero(served != solo)
    if diff.size == 0:
        return None
    at = int(diff[0])
    lg = next_token_logits(solo[:at])
    return dict(position=at, served=int(served[at]), solo=int(solo[at]),
                logit_gaps=[float(lg.max() - lg[int(t)])
                            for t in (served[at], solo[at])])


def phase_serve(args) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import byteps_tpu
    from byteps_tpu.models import gpt_forward, gpt_init
    from byteps_tpu.models.generate import make_generate_fn
    from byteps_tpu.serve import Request, Scheduler, SpecPolicy

    device = child_setup(args, 1)
    on_tpu = device["platform"] == "tpu"
    cfg, _, _ = model_config(args)
    if args.rehearse:
        lengths, max_new = (8, 12, 16, 24), 8
    else:
        lengths, max_new = (64, 128, 256, 512), 32
    params = gpt_init(jax.random.PRNGKey(args.seed), cfg)
    rs = np.random.RandomState(args.seed)
    prompts = [rs.randint(0, cfg.vocab_size, n).astype(np.int32)
               for n in lengths + lengths]
    reqs = [Request(rid=i, prompt=p, max_new=max_new,
                    spec=SpecPolicy("lookup") if i == 3 else None)
            for i, p in enumerate(prompts)]

    sched = Scheduler(params, cfg)
    t0 = time.perf_counter()
    results = sched.serve(reqs)
    serve_s = time.perf_counter() - t0
    check(len(results) == len(reqs), "every request completed")
    check(sched.cache.leaked_blocks() == 0, "no leaked KV blocks")
    snap = byteps_tpu.metrics_snapshot()
    json.dumps(snap)                         # raises unless JSON-safe
    counters = snap["metrics"]["counters"]
    check(counters.get("serve.completed") == len(reqs)
          and counters.get("serve.spec_rounds", 0) > 0,
          f"serve counters: {counters}")
    emit(phase="serve", requests=len(reqs), prompt_lengths=list(lengths),
         max_new=max_new, wall_s=round(serve_s, 1),
         note="includes compiling every chunk and table-width shape",
         spec_rounds=counters["serve.spec_rounds"])

    # --- against solo generate, on the same device -----------------------
    gen = make_generate_fn(cfg, max_new)
    t0 = time.perf_counter()
    if on_tpu:
        probe = jnp.asarray(prompts[0])[None]
        text = gen.lower(params, probe, jax.random.PRNGKey(0),
                         0.0).compile().as_text()
        # prefill: one flash forward per layer; decode: one flash-decode
        # per layer inside the scan body
        check(n_pallas_calls(text) == 2 * cfg.n_layers,
              f"solo generate holds {n_pallas_calls(text)} Pallas "
              f"calls, expected {2 * cfg.n_layers}")
    solo = [np.asarray(gen(params, jnp.asarray(p)[None],
                           jax.random.PRNGKey(0), 0.0))[0] for p in prompts]
    emit(phase="serve", solo_generate_s=round(time.perf_counter() - t0, 1))

    @jax.jit
    def ref_logits(p, ctx, last):
        # plain forward over the right-padded context (causal: padding
        # cannot reach position `last`); one shape, one compile
        return gpt_forward(p, ctx, cfg)[0, last]

    def next_token_logits(context):
        ctx = np.zeros((1, cfg.max_seq), np.int32)
        ctx[0, :len(context)] = context
        with plain_jnp():
            return np.asarray(ref_logits(params, jnp.asarray(ctx),
                                         len(context) - 1), np.float32)

    equal, near_ties = 0, []
    for i, r in enumerate(reqs):
        parted = parting(results[r.rid]["tokens"], solo[i],
                         next_token_logits)
        if parted is None:
            equal += 1
            continue
        near_ties.append(dict(request=i, **parted))
        check(max(parted["logit_gaps"]) <= LOGIT_TOL,
              f"request {i} parts from solo generate at position "
              f"{parted['position']} with reference logit gaps "
              f"{parted['logit_gaps']} > {LOGIT_TOL}")
    emit(phase="serve", token_equal_requests=equal,
         requests_parting_at_a_near_tie=near_ties, logit_tol=LOGIT_TOL,
         bit_equality_holds=not near_ties, peak_bytes=peak_bytes())


def phase_hybrid(args) -> None:
    import jax
    import jax.numpy as jnp

    import byteps_tpu
    import byteps_tpu.jax as bps

    device = child_setup(args, 1)
    cfg = byteps_tpu.get_config()
    check(cfg.is_distributed, "BYTEPS_FORCE_DISTRIBUTED reached the config")
    bps.init()
    # 16 MB of f32 on the device = four partitions of the default 4 MB,
    # each through REDUCE -> COPYD2H -> COMPRESS -> PUSH -> PULL ->
    # DECOMPRESS -> COPYH2D (a rehearsal sends one)
    n = (1 if args.rehearse else 4) * cfg.partition_bytes // 4
    x = jax.random.normal(jax.random.PRNGKey(args.seed), (1, n), jnp.float32)
    t0 = time.perf_counter()
    out = jax.block_until_ready(
        bps.push_pull(x, average=False, name="smoke.hybrid"))
    ms = (time.perf_counter() - t0) * 1e3
    check(out.shape == (n,) and bool(jnp.array_equal(out, x[0])),
          "one worker: the pulled sum is the pushed array, bit for bit")
    where = {d.platform for d in out.devices()}
    check(where == {device["platform"]}, f"result lives on {where}")
    pushed = bps._state.psworker.bytes_pushed
    check(pushed == 4 * n, f"{pushed} bytes pushed, expected {4 * n}")
    emit(phase="hybrid", bytes=4 * n, bytes_pushed=pushed,
         partitions=-(-4 * n // cfg.partition_bytes),
         wall_ms=round(ms, 1), result_platform=sorted(where))
    bps.shutdown()


def phase_dp4(args) -> None:
    """Four chips, one process: the same GPT-2-medium step data-parallel
    over dp=4 at 4x the one-chip batch — (a) raw aggregation, (b)
    onebit+EF on the default staged tier — against the same global batch
    on a one-device mesh (8 accumulated microbatches: 4x the activations
    do not fit one chip, and 4 microbatches compile to 15.3 GiB)."""
    import jax
    import jax.numpy as jnp

    import byteps_tpu
    import byteps_tpu.jax as bps
    from byteps_tpu.parallel import MeshAxes, make_mesh

    child_setup(args, 4)
    check(byteps_tpu.get_config().ici_tier == "staged",
          "the default ICI tier is staged")
    cfg, B, S = model_config(args)
    n_steps = 3
    bps.init()
    batches = host_batches(args.seed, cfg, 4 * B, S, n_steps)
    params0 = host_params(args.seed, cfg)
    mesh4 = make_mesh(MeshAxes(dp=4))
    mesh1 = make_mesh(MeshAxes(), devices=jax.devices()[:1])

    def leg(label, mesh, want_text=(), **kw):
        losses, text, params, opt_state, tokens = train_leg(
            "dp4", label, cfg, mesh, params0, batches, **kw)
        for needle in want_text:
            check(needle in text, f"{label}: compiled step has {needle}")
        # the batch and, where the Partitioner says so, the state really
        # sit on as many distinct devices as the mesh has
        n_dev = mesh.devices.size
        for name, arr in (("batch", tokens), ("wte", params["wte"]),
                          ("ef", opt_state.ef)):
            if arr is None:
                continue
            devs = {s.device for s in arr.addressable_shards}
            check(len(devs) == n_dev,
                  f"{label}: {name} sits on {len(devs)} devices, "
                  f"expected {n_dev}")
        check(tokens.addressable_shards[0].data.shape[0] == 4 * B // n_dev,
              f"{label}: each device holds {4 * B // n_dev} batch rows")
        if kw.get("compression_params"):
            ef = opt_state.ef
            check(ef.addressable_shards[0].data.size * n_dev == ef.size
                  and float(jnp.abs(ef).sum()) > 0,
                  f"{label}: EF residual is dp-sharded and non-zero")
        return losses

    one = leg("one_device_accum8", mesh1, accum_steps=8)
    raw = leg("dp4_raw", mesh4, want_text=("all-reduce",))
    comp = leg("dp4_onebit_staged", mesh4, want_text=("all-to-all",),
               compression_params={"compressor": "onebit", "ef": "vanilla"})
    check(abs(raw[0] - one[0]) <= LOSS_TOL,
          f"dp4 step-0 loss {raw[0]} equals one device's {one[0]}")
    check(max(abs(a - b) for a, b in zip(raw, one)) <= TRAJ_TOL,
          f"dp4 trajectory {raw} follows one device's {one}")
    check(abs(comp[0] - raw[0]) <= 1e-3,
          f"onebit step-0 loss {comp[0]} is the raw one {raw[0]}")
    emit(phase="dp4", one_device=one, dp4_raw=raw, dp4_onebit=comp,
         global_batch=4 * B, peak_bytes=peak_bytes())
    bps.shutdown()


PHASES = {"train": phase_train, "trace": phase_trace, "serve": phase_serve,
          "hybrid": phase_hybrid, "dp4": phase_dp4}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="weights, batches and prompts are made from it")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip phase")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, any platform, never prints ok")
    ap.add_argument("--phase", choices=sorted(PHASES),
                    help="(internal) run one phase in this process")
    args = ap.parse_args()
    if args.phase is None:
        return parent_main(args)
    if args.phase == "trace":
        check(os.environ.get("BYTEPS_TRACE_ON") == "1",
              "the trace phase is started with BYTEPS_TRACE_ON=1")
    PHASES[args.phase](args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
