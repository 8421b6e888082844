"""Checkpoint/resume with the byteps_tpu checkpoint subsystem.

Reference behavior (SURVEY §5.4): checkpointing belongs to the host
framework; BytePS contributes ``broadcast_parameters`` /
``broadcast_optimizer_state`` so rank 0's restored state reaches every
worker. Here: ``byteps_tpu.checkpoint.Checkpointer`` writes step-numbered
sharded checkpoints (hybrid multi-pod mode gates the write to pod 0 via
``should_save``), and on resume ``broadcast_parameters`` synchronizes the
restored pytree across pods — same division of labor, sharded-aware.
"""

import argparse
import os
import shutil

import jax
import jax.numpy as jnp
import optax

import byteps_tpu.jax as bps
from byteps_tpu.checkpoint import Checkpointer
from byteps_tpu.common.compile_cache import enable_compile_cache
from byteps_tpu.models import GPTConfig
from byteps_tpu.models.train import make_gpt_train_step, synthetic_batch
from byteps_tpu.parallel import MeshAxes, make_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt-dir", default="/tmp/byteps_tpu_ckpt")
    ap.add_argument("--steps", type=int, default=6)
    args = ap.parse_args()
    enable_compile_cache()

    n = len(jax.devices())
    mesh = make_mesh(MeshAxes(dp=n))
    bps.init(mesh=mesh)
    cfg = GPTConfig.tiny()
    step, params, opt_state, bsh = make_gpt_train_step(
        cfg, mesh, optax.adam(1e-3)
    )
    tokens, targets = synthetic_batch(jax.random.PRNGKey(0), cfg, 2 * n, 32)
    tokens = jax.device_put(tokens, bsh)
    targets = jax.device_put(targets, bsh)

    # Two multi-host regimes, two recipes:
    #  - global mesh (BYTEPS_JAX_DISTRIBUTED=1): arrays are globally
    #    sharded, so save/restore are COLLECTIVE — every process
    #    participates (shared filesystem required), no broadcast needed.
    #  - hybrid PS pods: independent jax worlds — pod 0 writes, everyone
    #    receives the restored values via broadcast_parameters.
    collective = jax.process_count() > 1
    writer = collective or bps.rank() == 0
    # a demo trains from scratch every run — clear stale steps so orbax's
    # monotone step numbering starts fresh (real resume jobs keep the dir)
    if jax.process_index() == 0 and bps.rank() == 0 \
            and os.path.isdir(args.ckpt_dir):
        shutil.rmtree(args.ckpt_dir)
    ckpt = Checkpointer(args.ckpt_dir, max_to_keep=2, should_save=writer)

    for i in range(args.steps):
        loss, params, opt_state = step(params, opt_state, tokens, targets)
        ckpt.save(i + 1, {"params": params})
    ckpt.wait()
    print(f"trained {args.steps} steps, loss={float(loss):.4f}; "
          f"checkpoints kept: {ckpt.all_steps() if writer else 'n/a'}")

    # resume: collective restore on a global mesh; otherwise the
    # reference's rank-0 recipe — the writer pod restores (its ckpt dir
    # need not be shared) and every other pod receives the values
    # through broadcast_parameters
    if writer:
        restored = ckpt.restore({"params": params})["params"]
    else:
        restored = jax.tree.map(jnp.zeros_like, params)
    if not collective and bps.size() > bps.pod_size():
        stacked = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (bps.pod_size(),) + x.shape),
            restored,
        )
        synced = bps.broadcast_parameters(stacked, root_rank=0)
        restored = synced
    leaves_match = all(
        bool(jnp.allclose(a, b))
        for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(params))
    )
    print(f"restored checkpoint matches live params: {leaves_match}")
    bps.shutdown()


if __name__ == "__main__":
    main()
