"""Train a GPT across every parallelism composition the framework ships.

    --mode dense  : dp x sp x tp (ring attention + Megatron tp + BytePS dp)
    --mode pp     : pp x dp GPipe pipeline (microbatched, ppermute shifts)
    --mode moe    : dp x ep Switch MoE (all_to_all expert dispatch)

Runs on a TPU slice or virtual CPU devices:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/jax/train_gpt_parallel.py --mode pp
"""

import argparse

import jax
import optax

from byteps_tpu.common.compile_cache import enable_compile_cache
from byteps_tpu.data import PrefetchLoader
from byteps_tpu.models import GPTConfig, MoEGPTConfig
from byteps_tpu.models.train import (
    make_gpt_moe_train_step,
    make_gpt_pp_train_step,
    make_gpt_train_step,
    synthetic_batch,
)
from byteps_tpu.parallel import MeshAxes, factor_devices, make_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["dense", "pp", "moe"],
                    default="dense")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--n-micro", type=int, default=2)
    ap.add_argument("--compressor", choices=["none", "onebit", "topk"],
                    default="none",
                    help="compressed dp aggregation — composes with every "
                    "mesh axis (tp/sp/pp/ep) since round 4")
    args = ap.parse_args()
    enable_compile_cache()

    comp = (None if args.compressor == "none"
            else {"compressor": args.compressor, "ef": "vanilla"})
    n = len(jax.devices())
    tx = optax.adamw(1e-3)
    if args.mode == "dense":
        cfg = GPTConfig.tiny()
        mesh = make_mesh(factor_devices(n))
        make = lambda: make_gpt_train_step(  # noqa: E731
            cfg, mesh, tx, compression_params=comp)
    elif args.mode == "pp":
        cfg = GPTConfig.tiny()
        pp = 2
        mesh = make_mesh(MeshAxes(pp=pp, dp=n // pp))
        make = lambda: make_gpt_pp_train_step(  # noqa: E731
            cfg, mesh, tx, n_micro=args.n_micro, compression_params=comp)
    else:
        cfg = MoEGPTConfig.tiny()
        ep = 2
        mesh = make_mesh(MeshAxes(dp=n // ep, ep=ep))
        make = lambda: make_gpt_moe_train_step(  # noqa: E731
            cfg, mesh, tx, compression_params=comp)
    # guard BEFORE the factory: on a dp-less mesh _make_tx would silently
    # drop compression after all the expensive setup
    if comp is not None and "dp" not in mesh.axis_names:
        raise SystemExit(
            f"--compressor {args.compressor} needs a dp axis to compress "
            f"over, but this mesh is {dict(mesh.shape)} — compression "
            "rides the dp gradient aggregation (use more devices or a "
            "mode whose factorization keeps dp > 1)")
    step, params, opt_state, bsh = make()
    print(f"mode={args.mode} mesh={dict(mesh.shape)} "
          f"compressor={args.compressor}", flush=True)

    def host_batches():
        for i in range(args.steps):
            yield synthetic_batch(
                jax.random.PRNGKey(i), cfg, args.batch_size, args.seq
            )

    # PrefetchLoader device_puts batch t+1 on a background thread while
    # batch t trains (byteps_tpu/data: the framework's input pipeline)
    with PrefetchLoader(host_batches(), bsh, depth=2) as loader:
        for i, (tokens, targets) in enumerate(loader):
            loss, params, opt_state = step(params, opt_state, tokens, targets)
            if i % 5 == 0 or i == args.steps - 1:
                print(f"step {i}: loss={float(loss):.4f}", flush=True)


if __name__ == "__main__":
    main()
