"""ZeRO-1 sharded optimizer state (no reference analog — the reference
keeps full optimizer replicas per worker; SURVEY §2.7 sync DP).

Correctness lever: adam/adamw are elementwise in the aggregated gradient,
so the segment-sharded update must reproduce the replicated update
exactly (modulo fp32 collective summation order) — the zero_1 step is
pinned trajectory-for-trajectory to the baseline step on every supported
mesh, weight decay included (the params-segment path).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from byteps_tpu.models import GPTConfig
from byteps_tpu.models.train import (
    make_gpt_pp_train_step,
    make_gpt_train_step,
    synthetic_batch,
)
from byteps_tpu.parallel import MeshAxes, make_mesh

CFG = GPTConfig.tiny()


def _run(made, tokens, targets, steps=6):
    step, params, opt_state, bsh = made
    tok = jax.device_put(tokens, bsh)
    tgt = jax.device_put(targets, bsh)
    losses = []
    for _ in range(steps):
        loss, params, opt_state = step(params, opt_state, tok, tgt)
        losses.append(float(loss))
    return losses, opt_state


@pytest.mark.slow
def test_zero1_matches_replicated_adamw():
    """Elementwise inner transform ⇒ segment update ≡ replicated update."""
    tokens, targets = synthetic_batch(jax.random.PRNGKey(0), CFG, 8, 32)
    mesh = make_mesh(MeshAxes(dp=4), devices=jax.devices()[:4])
    tx = optax.adamw(1e-2, weight_decay=1e-2)
    base, _ = _run(make_gpt_train_step(CFG, mesh, tx), tokens, targets)
    zero, zstate = _run(make_gpt_train_step(CFG, mesh, tx, zero_1=True),
                        tokens, targets)
    np.testing.assert_allclose(zero, base, rtol=2e-4, atol=2e-4)
    # moments live on dp-sharded flat vectors, one segment per worker
    mu = zstate.inner[0].mu
    assert mu.ndim == 1 and mu.shape[0] % 4 == 0
    assert mu.sharding.spec == P("dp")


@pytest.mark.slow
def test_zero1_composes_with_compression():
    tokens, targets = synthetic_batch(jax.random.PRNGKey(1), CFG, 8, 32)
    mesh = make_mesh(MeshAxes(dp=4), devices=jax.devices()[:4])
    step, params, opt_state, bsh = make_gpt_train_step(
        CFG, mesh, optax.adam(1e-2), zero_1=True,
        compression_params={"compressor": "onebit", "ef": "vanilla"},
    )
    losses, opt_state = _run((step, params, opt_state, bsh), tokens, targets,
                             steps=10)
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses
    assert float(jnp.abs(opt_state.ef).max()) > 0.0


@pytest.mark.slow
def test_zero1_on_pipeline_mesh_matches_baseline():
    tokens, targets = synthetic_batch(jax.random.PRNGKey(2), CFG, 8, 32)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("pp", "dp"))
    tx = optax.adamw(1e-2, weight_decay=1e-2)
    base, _ = _run(make_gpt_pp_train_step(CFG, mesh, tx), tokens, targets)
    zero, zstate = _run(
        make_gpt_pp_train_step(CFG, mesh, tx, zero_1=True), tokens, targets)
    np.testing.assert_allclose(zero, base, rtol=2e-4, atol=2e-4)
    # per-(stage, dp worker) segments: (n_pp, n_dp * seg)
    mu = zstate.inner[0].mu
    assert mu.ndim == 2 and mu.shape[0] == 2
    assert mu.sharding.spec == P("pp", "dp")


@pytest.mark.slow
def test_zero1_topk_identity_matches_uncompressed_zero():
    """Compressed ZeRO with the identity compressor equals plain ZeRO."""
    tokens, targets = synthetic_batch(jax.random.PRNGKey(3), CFG, 8, 32)
    mesh = make_mesh(MeshAxes(dp=4), devices=jax.devices()[:4])
    tx = optax.adam(1e-2)
    base, _ = _run(make_gpt_train_step(CFG, mesh, tx, zero_1=True),
                   tokens, targets)
    comp, _ = _run(make_gpt_train_step(
        CFG, mesh, tx, zero_1=True,
        compression_params={"compressor": "topk", "k": 1.0}),
        tokens, targets)
    np.testing.assert_allclose(comp, base, rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_accum_steps_matches_full_batch():
    """accum_steps=2 over a batch ≡ the full-batch step (mean-of-means
    with equal microbatches; adam sees identical grads)."""
    tokens, targets = synthetic_batch(jax.random.PRNGKey(4), CFG, 8, 32)
    mesh = make_mesh(MeshAxes(dp=2), devices=jax.devices()[:2])
    tx = optax.adam(1e-2)
    base, _ = _run(make_gpt_train_step(CFG, mesh, tx), tokens, targets)
    acc, _ = _run(make_gpt_train_step(CFG, mesh, tx, accum_steps=2),
                  tokens, targets)
    np.testing.assert_allclose(acc, base, rtol=2e-4, atol=2e-4)


def test_accum_steps_on_one_device_mesh_matches_full_batch():
    """The one-device mesh has every axis at size 1: the batch spec still
    names (slice_, dp) while the params are not cast to them, so the
    accumulator's scan carry has to type the loss by the batch's axes
    too (a TypeError under check_vma before PR 21 — chip_smoke's
    one-device reference for the four-chip step is this path)."""
    tokens, targets = synthetic_batch(jax.random.PRNGKey(4), CFG, 8, 32)
    mesh = make_mesh(MeshAxes(), devices=jax.devices()[:1])
    tx = optax.adam(1e-2)
    base, _ = _run(make_gpt_train_step(CFG, mesh, tx), tokens, targets)
    acc, _ = _run(make_gpt_train_step(CFG, mesh, tx, accum_steps=4),
                  tokens, targets)
    np.testing.assert_allclose(acc, base, rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_accum_steps_with_zero_and_compression():
    tokens, targets = synthetic_batch(jax.random.PRNGKey(5), CFG, 8, 32)
    mesh = make_mesh(MeshAxes(dp=4), devices=jax.devices()[:4])
    step, params, opt_state, bsh = make_gpt_train_step(
        CFG, mesh, optax.adam(1e-2), zero_1=True, accum_steps=2,
        compression_params={"compressor": "onebit", "ef": "vanilla"},
    )
    losses, _ = _run((step, params, opt_state, bsh), tokens, targets,
                     steps=10)
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


@pytest.mark.slow
def test_bert_zero1_matches_replicated():
    from byteps_tpu.models import BertConfig
    from byteps_tpu.models.train import (
        make_bert_train_step,
        synthetic_mlm_batch,
    )

    bcfg = BertConfig.tiny()
    tokens, targets, mask = synthetic_mlm_batch(
        jax.random.PRNGKey(6), bcfg, 8, 32)
    mesh = make_mesh(MeshAxes(dp=4), devices=jax.devices()[:4])
    tx = optax.adamw(1e-2, weight_decay=1e-2)

    def run(made):
        step, params, opt_state, bsh = made
        tok = jax.device_put(tokens, bsh)
        tgt = jax.device_put(targets, bsh)
        m = jax.device_put(mask, bsh)
        losses = []
        for _ in range(6):
            loss, params, opt_state = step(params, opt_state, tok, tgt, m)
            losses.append(float(loss))
        return losses

    base = run(make_bert_train_step(bcfg, mesh, tx))
    zero = run(make_bert_train_step(bcfg, mesh, tx, zero_1=True))
    np.testing.assert_allclose(zero, base, rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_accum_steps_on_tp_mesh_matches_full_batch():
    """accum composes with the VMA (tp) path — carry widening + the
    post-scan resym/collapse keep grads and loss exact."""
    tokens, targets = synthetic_batch(jax.random.PRNGKey(7), CFG, 8, 32)
    mesh = make_mesh(MeshAxes(dp=2, tp=2), devices=jax.devices()[:4])
    tx = optax.adam(1e-2)
    base, _ = _run(make_gpt_train_step(CFG, mesh, tx), tokens, targets)
    acc, _ = _run(make_gpt_train_step(CFG, mesh, tx, accum_steps=2),
                  tokens, targets)
    np.testing.assert_allclose(acc, base, rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_bert_accum_weighted_matches_full_batch():
    """Masked-mean loss: microbatch mask counts differ, so the
    accumulation must weight by count to reproduce the full-batch step."""
    from byteps_tpu.models import BertConfig
    from byteps_tpu.models.train import (
        make_bert_train_step,
        synthetic_mlm_batch,
    )

    bcfg = BertConfig.tiny()
    tokens, targets, mask = synthetic_mlm_batch(
        jax.random.PRNGKey(8), bcfg, 8, 32)
    mesh = make_mesh(MeshAxes(dp=2), devices=jax.devices()[:2])
    tx = optax.adam(1e-2)

    def run(made):
        step, params, opt_state, bsh = made
        args = [jax.device_put(a, bsh) for a in (tokens, targets, mask)]
        losses = []
        for _ in range(6):
            loss, params, opt_state = step(params, opt_state, *args)
            losses.append(float(loss))
        return losses

    base = run(make_bert_train_step(bcfg, mesh, tx))
    acc = run(make_bert_train_step(bcfg, mesh, tx, accum_steps=2))
    np.testing.assert_allclose(acc, base, rtol=2e-4, atol=2e-4)


def test_zero1_without_dp_axis_raises():
    mesh = Mesh(np.array(jax.devices()[:2]), ("pp",))
    with pytest.raises(ValueError, match="dp mesh axis"):
        make_gpt_pp_train_step(CFG, mesh, optax.adam(1e-2), zero_1=True)


@pytest.mark.slow
def test_resnet_zero1_matches_replicated():
    from byteps_tpu.models import ResNetConfig
    from byteps_tpu.models.train import make_resnet_train_step

    rcfg = ResNetConfig.tiny()
    mesh = make_mesh(MeshAxes(dp=4), devices=jax.devices()[:4])
    tx = optax.adamw(1e-2, weight_decay=1e-2)
    imgs = jax.random.normal(jax.random.PRNGKey(9), (8, 16, 16, 3))
    labels = jax.random.randint(jax.random.PRNGKey(10), (8,), 0,
                                rcfg.num_classes)

    def run(made):
        step, params, opt_state, bn, bsh = made
        im = jax.device_put(imgs, bsh)
        lb = jax.device_put(labels, bsh)
        losses = []
        for _ in range(6):
            loss, params, opt_state, bn = step(params, opt_state, bn, im, lb)
            losses.append(float(loss))
        return losses

    base = run(make_resnet_train_step(rcfg, mesh, tx))
    zero = run(make_resnet_train_step(rcfg, mesh, tx, zero_1=True))
    np.testing.assert_allclose(zero, base, rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_bert_accum_on_sp_mesh_matches_full_batch():
    """sp-sharded masks: accumulation weights must be the sp-global count."""
    from byteps_tpu.models import BertConfig
    from byteps_tpu.models.train import (
        make_bert_train_step,
        synthetic_mlm_batch,
    )

    bcfg = BertConfig.tiny()
    tokens, targets, mask = synthetic_mlm_batch(
        jax.random.PRNGKey(11), bcfg, 8, 32)
    mesh = make_mesh(MeshAxes(dp=2, sp=2), devices=jax.devices()[:4])
    tx = optax.adam(1e-2)

    def run(made):
        step, params, opt_state, bsh = made
        args = [jax.device_put(a, bsh) for a in (tokens, targets, mask)]
        losses = []
        for _ in range(6):
            loss, params, opt_state = step(params, opt_state, *args)
            losses.append(float(loss))
        return losses

    base = run(make_bert_train_step(bcfg, mesh, tx))
    acc = run(make_bert_train_step(bcfg, mesh, tx, accum_steps=2))
    np.testing.assert_allclose(acc, base, rtol=2e-4, atol=2e-4)
