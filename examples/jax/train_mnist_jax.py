"""MNIST training with byteps_tpu.jax — the BASELINE north star's
``byteps/jax`` adapter in the reference MNIST example's shape (reference:
example/pytorch/train_mnist_byteps.py, transposed to jax/optax).

Runs on a TPU slice or on virtual CPU devices:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/jax/train_mnist_jax.py
"""

import argparse

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import byteps_tpu.jax as bps
from byteps_tpu.common.compile_cache import enable_compile_cache
from byteps_tpu.parallel import MeshAxes, make_mesh
from byteps_tpu.parallel.sharding import opt_state_specs


def mlp_init(rng):
    k1, k2, k3 = jax.random.split(rng, 3)
    s = 0.05
    return {
        "w1": jax.random.normal(k1, (784, 128)) * s, "b1": jnp.zeros(128),
        "w2": jax.random.normal(k2, (128, 64)) * s, "b2": jnp.zeros(64),
        "w3": jax.random.normal(k3, (64, 10)) * s, "b3": jnp.zeros(10),
    }


def mlp_loss(params, x, y):
    h = jax.nn.relu(x @ params["w1"] + params["b1"])
    h = jax.nn.relu(h @ params["w2"] + params["b2"])
    logits = h @ params["w3"] + params["b3"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(logp, y[:, None], axis=1).mean()


def synthetic_mnist(rng, n):
    teacher = jax.random.normal(jax.random.PRNGKey(1234), (784, 10))
    x = jax.random.normal(rng, (n, 784))
    y = (x @ teacher).argmax(1)
    return x, y


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch-size", type=int, default=512)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--compressor", type=str, default="")
    args = ap.parse_args()
    enable_compile_cache()

    n_dev = len(jax.devices())
    mesh = make_mesh(MeshAxes(dp=n_dev))
    bps.init(mesh=mesh)
    comp = {"compressor": args.compressor, "ef": "vanilla"} \
        if args.compressor else None

    def make_tx(pb=None):
        return bps.DistributedOptimizer(
            optax.sgd(args.lr, momentum=0.9), compression_params=comp,
            num_devices=n_dev, partition_bytes=pb,
        )

    tx = make_tx()
    params = mlp_init(jax.random.PRNGKey(0))
    opt_state = tx.init(params)
    pspecs = jax.tree.map(lambda _: P(), params)
    ospecs = opt_state_specs(opt_state, params, pspecs)
    if opt_state.ef is not None:
        ospecs = ospecs._replace(ef=P("dp"))
    if opt_state.momentum is not None:
        ospecs = ospecs._replace(momentum=P("dp"))

    def build_step(pb):
        tx = make_tx(pb)

        def per_device(params, opt_state, x, y):
            loss, grads = jax.value_and_grad(mlp_loss)(params, x, y)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return jax.lax.pmean(loss, "dp"), params, opt_state

        return jax.jit(jax.shard_map(
            per_device, mesh=mesh,
            in_specs=(pspecs, ospecs, P("dp"), P("dp")),
            out_specs=(P(), pspecs, ospecs),
            check_vma=False,
        ), donate_argnums=(0, 1))

    # BYTEPS_AUTO_TUNE=1: online partition-size search, retracing the step
    # as the tuner moves (ByteScheduler's tuner on the fused path)
    if bps.auto_tune_enabled():
        step = bps.AutoTunedStep(build_step, bps.default_partition_bytes())
    else:
        step = build_step(None)

    bsh = NamedSharding(mesh, P("dp"))
    for i in range(args.steps):
        x, y = synthetic_mnist(jax.random.PRNGKey(i + 1), args.batch_size)
        x, y = jax.device_put(x, bsh), jax.device_put(y, bsh)
        loss, params, opt_state = step(params, opt_state, x, y)
        if i % 10 == 0 or i == args.steps - 1:
            print(f"step {i}: loss={float(loss):.4f}", flush=True)
    if bps.auto_tune_enabled():
        print(
            f"tuner: converged={step.tuner.converged} "
            f"partition={step.partition_bytes >> 10}KB "
            f"retraces={step.retraces}", flush=True,
        )
    x, y = synthetic_mnist(jax.random.PRNGKey(999), 2048)
    h = jax.nn.relu(x @ params["w1"] + params["b1"])
    h = jax.nn.relu(h @ params["w2"] + params["b2"])
    acc = float(((h @ params["w3"] + params["b3"]).argmax(1) == y).mean())
    print(f"final synthetic-MNIST accuracy: {acc:.3f}", flush=True)
    bps.shutdown()


if __name__ == "__main__":
    main()
