"""Training cells: factory -> PrefetchLoader -> step ended by
block_until_ready (the pattern of ``chip_smoke.py``'s ``train_leg``), steps
back to back until the clock passes ``--seconds``."""

from __future__ import annotations

import math
import time
from typing import Dict

from benchmark import metrics, traffic_gen
from benchmark.configs import gpt2_reference

SPANS = ("train.step", "train.wait_input")


def _optimizer(spec: Dict):
    import optax

    if spec["name"] != "adamw":
        raise ValueError(f"unknown optimizer {spec['name']!r}")
    return optax.adamw(spec["learning_rate"])


def run(h) -> Dict:
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    import byteps_tpu.jax as bps
    from byteps_tpu.data import PrefetchLoader
    from byteps_tpu.models import gpt_init
    from byteps_tpu.models.train import make_gpt_train_step
    from byteps_tpu.parallel import MeshAxes, make_mesh

    t = h.traffic
    cfg = h.gpt_config()
    dp = int(t["mesh"]["dp"])
    if dp != h.chips:
        raise SystemExit(f"benchmark: mix {t['name']!r} is laid out for "
                         f"dp={dp}, the cell has {h.chips} chip(s)")
    if t["aggregation"] != "raw":
        raise ValueError(f"unknown aggregation {t['aggregation']!r}")
    B, S = int(t["batch_per_chip"]) * dp, int(t["seq"])
    vocab = int(h.config["source_vocab_size"])
    bps.init()
    devices = jax.devices()[:dp]
    mesh = make_mesh(MeshAxes(dp=dp), devices=devices)
    # weights on the device, one jitted call from the seed
    init = jax.jit(functools.partial(gpt_init, cfg=cfg))
    step, params, opt_state, bsh = make_gpt_train_step(
        cfg, mesh, _optimizer(t["optimizer"]),
        init_params=init(jax.random.PRNGKey(h.seed)))

    losses, step_ms = [], []
    n_warm = int(t["warmup_steps"])
    with PrefetchLoader(traffic_gen.train_batches(h.seed, vocab, B, S),
                        bsh, depth=int(t["prefetch_depth"])) as loader:
        for _ in range(n_warm):      # the step compiles on its second call too
            tok, tgt = next(loader)
            loss, params, opt_state = step(params, opt_state, tok, tgt)
            jax.block_until_ready((loss, params, opt_state))
            losses.append(loss)
        t0 = last = h.open_window()
        while True:
            with h.span("train.wait_input"):
                tok, tgt = next(loader)
            with h.span("train.step"):
                loss, params, opt_state = step(params, opt_state, tok, tgt)
                jax.block_until_ready((loss, params, opt_state))
            now = time.monotonic()
            step_ms.append((now - last) * 1e3)
            last = now
            losses.append(loss)
            if now - t0 >= h.seconds:
                break
            if h.trace_due(t0, now):
                h.start_trace()
        t_end = last
        h.close_window()
    peak = h.memory_peak_bytes()
    h.reduce_trace(SPANS)
    losses = [float(x) for x in losses]
    n_bad = sum(not math.isfinite(x) for x in losses)

    # outside the window: the first batch's loss against the plain
    # reference (f32, forward only), one sequence at a time so that one
    # small program serves any batch
    del params, opt_state
    params0 = init(jax.random.PRNGKey(h.seed))
    tok, tgt = next(traffic_gen.train_batches(h.seed, vocab, B, S))
    ref = jax.jit(functools.partial(
        gpt2_reference.mean_nll, n_heads=cfg.n_heads, eps=cfg.norm_eps))
    ref_loss = float(np.mean([float(ref(
        params0, jnp.asarray(tok[i:i + 1]), jnp.asarray(tgt[i:i + 1])))
        for i in range(B)]))
    tol = float(t["loss_tolerance"])
    bps.shutdown()
    steps = len(step_ms)
    return {
        "correct": n_bad == 0 and abs(losses[0] - ref_loss) <= tol,
        "attempted": steps, "failed": n_bad,
        "end_to_end": {"train_tokens_per_s": metrics.train_tokens_per_s(
            steps, B * S, t0, t_end, h.chips)},
        "memory_peak_bytes": peak,
        "step_ms": step_ms, "steps": steps, "elapsed_s": t_end - t0,
        "batch": B, "seq": S,
        "notes": {"first_loss": losses[0], "reference_loss": ref_loss,
                  "loss_tolerance": tol, "last_loss": losses[-1],
                  "steps": steps, "cache_dir": h.cache_dir},
    }
