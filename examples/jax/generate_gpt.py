"""Autoregressive sampling from a (toy) GPT checkpoint with a KV cache.

Usage::

    python examples/jax/generate_gpt.py [--steps 32] [--temperature 0.8]

Companion to train_mnist_jax.py on the inference side (the reference has
no decode path — its examples stop at training): builds tiny random
weights, prefills a prompt, and samples with the jitted cached decoder
(`byteps_tpu.models.generate`). Swap in orbax-restored params for real
checkpoints (see checkpoint_resume.py).
"""

import argparse
import time

import jax
import jax.numpy as jnp

from byteps_tpu.common.compile_cache import enable_compile_cache
from byteps_tpu.models import GPTConfig, gpt_init, make_generate_fn


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--top-k", type=int, default=None)
    ap.add_argument("--top-p", type=float, default=None)
    ap.add_argument("--rope", action="store_true",
                    help="rotary position embeddings instead of wpe")
    ap.add_argument("--kv-heads", type=int, default=None,
                    help="GQA: kv heads in the cache (default = all)")
    args = ap.parse_args()
    enable_compile_cache()

    import dataclasses

    cfg = GPTConfig.tiny()
    if args.rope:
        cfg = dataclasses.replace(cfg, pos_embedding="rope")
    if args.kv_heads is not None:
        cfg = dataclasses.replace(cfg, n_kv_heads=args.kv_heads)
    params = gpt_init(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (args.batch, 8), 0,
                                cfg.vocab_size)
    gen = make_generate_fn(cfg, max_new=args.steps, top_k=args.top_k,
                           top_p=args.top_p)

    t0 = time.perf_counter()
    out = gen(params, prompt, jax.random.PRNGKey(2), args.temperature)
    out.block_until_ready()
    compile_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    out = gen(params, prompt, jax.random.PRNGKey(3), args.temperature)
    out.block_until_ready()
    run_s = time.perf_counter() - t0

    toks = args.batch * args.steps
    print(f"generated {toks} tokens: compile {compile_s:.1f}s, "
          f"run {run_s*1e3:.1f} ms ({toks/run_s:.0f} tok/s)")
    print("sequences:")
    for row in out.tolist():
        print(" ", row)


if __name__ == "__main__":
    main()
