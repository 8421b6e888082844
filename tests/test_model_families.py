"""BERT and ResNet families: sharded train steps vs single-device golds
(same pattern as tests/test_models.py for GPT)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from byteps_tpu.models import (
    BertConfig,
    ResNetConfig,
    bert_forward,
    bert_init,
    bert_mlm_loss,
    resnet_init,
    resnet_loss,
)
from byteps_tpu.models.bert import bert_param_specs
from byteps_tpu.models.train import (
    make_bert_train_step,
    make_resnet_train_step,
    synthetic_mlm_batch,
)
from byteps_tpu.parallel import MeshAxes, make_mesh

BCFG = BertConfig.tiny()
RCFG = ResNetConfig.tiny()


@pytest.fixture(scope="module")
def mesh_dst():
    return make_mesh(MeshAxes(dp=2, tp=2, sp=2))


@pytest.fixture(scope="module")
def mesh_dp():
    return make_mesh(MeshAxes(dp=8))


def test_bert_sharded_forward_matches_single_device(mesh_dst):
    params = bert_init(jax.random.PRNGKey(0), BCFG)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                BCFG.vocab_size)
    want = bert_forward(params, tokens, BCFG)
    pspecs = bert_param_specs(BCFG, "tp")
    got = jax.jit(
        jax.shard_map(
            lambda p, t: bert_forward(p, t, BCFG, tp_axis="tp",
                                      sp_axis="sp"),
            mesh=mesh_dst,
            in_specs=(pspecs, P("dp", "sp")),
            out_specs=P("dp", "sp"),
            check_vma=False,
        )
    )(params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-4, atol=5e-4)


@pytest.mark.slow
def test_bert_train_step_matches_single_device(mesh_dst):
    tokens, targets, mask = synthetic_mlm_batch(
        jax.random.PRNGKey(2), BCFG, 4, 32
    )
    step, params, opt_state, bsh = make_bert_train_step(
        BCFG, mesh_dst, optax.adam(1e-2)
    )
    tok = jax.device_put(tokens, bsh)
    tgt = jax.device_put(targets, bsh)
    msk = jax.device_put(mask, bsh)

    gold_params = bert_init(jax.random.PRNGKey(0), BCFG)
    gold_tx = optax.adam(1e-2)
    gold_state = gold_tx.init(gold_params)

    @jax.jit
    def gold_step(p, s, tok, tgt, msk):
        # DP semantics: mean over dp replicas of per-replica masked means
        # (NOT the global masked mean — shards have unequal mask counts,
        # same averaging property as reference push_pull average=True)
        def loss_fn(p_):
            l0 = bert_mlm_loss(p_, tok[:2], tgt[:2], msk[:2], BCFG)
            l1 = bert_mlm_loss(p_, tok[2:], tgt[2:], msk[2:], BCFG)
            return (l0 + l1) / 2
        loss, g = jax.value_and_grad(loss_fn)(p)
        u, s = gold_tx.update(g, s, p)
        return loss, optax.apply_updates(p, u), s

    for _ in range(3):
        loss, params, opt_state = step(params, opt_state, tok, tgt, msk)
        gl, gold_params, gold_state = gold_step(
            gold_params, gold_state, tokens, targets, mask
        )
        np.testing.assert_allclose(float(loss), float(gl),
                                   rtol=2e-4, atol=2e-4)


def test_bert_mlm_loss_ignores_unmasked_positions():
    params = bert_init(jax.random.PRNGKey(0), BCFG)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 16), 0,
                                BCFG.vocab_size)
    targets = tokens
    mask = jnp.zeros((2, 16), jnp.int32).at[:, :4].set(1)
    # corrupting an unmasked target must not change the loss
    l1 = bert_mlm_loss(params, tokens, targets, mask, BCFG)
    l2 = bert_mlm_loss(params, tokens,
                       targets.at[:, 10].set(0), mask, BCFG)
    assert float(l1) == pytest.approx(float(l2))


@pytest.mark.slow
def test_resnet_train_step_matches_single_device(mesh_dp):
    rng = jax.random.PRNGKey(4)
    images = jax.random.normal(rng, (16, 16, 16, 3), jnp.float32)
    labels = jax.random.randint(jax.random.PRNGKey(5), (16,), 0,
                                RCFG.num_classes)
    step, params, opt_state, bn_state, bsh = make_resnet_train_step(
        RCFG, mesh_dp, optax.sgd(0.1)
    )
    img = jax.device_put(images, bsh)
    lbl = jax.device_put(labels, bsh)

    gold_params, gold_bn = resnet_init(jax.random.PRNGKey(0), RCFG)
    gold_tx = optax.sgd(0.1)
    gold_state = gold_tx.init(gold_params)

    @jax.jit
    def gold_step(p, s, bn, img, lbl):
        (loss, new_bn), g = jax.value_and_grad(
            lambda p_: resnet_loss(p_, bn, img, lbl, RCFG), has_aux=True
        )(p)
        u, s = gold_tx.update(g, s, p)
        return loss, optax.apply_updates(p, u), s, new_bn

    for _ in range(3):
        loss, params, opt_state, bn_state = step(
            params, opt_state, bn_state, img, lbl
        )
        gl, gold_params, gold_state, gold_bn = gold_step(
            gold_params, gold_state, gold_bn, images, labels
        )
        np.testing.assert_allclose(float(loss), float(gl),
                                   rtol=2e-4, atol=2e-4)
    # BN running stats synced identically
    for a, b in zip(jax.tree.leaves(bn_state), jax.tree.leaves(gold_bn)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


@pytest.mark.slow
def test_resnet_loss_decreases(mesh_dp):
    images = jax.random.normal(jax.random.PRNGKey(6), (16, 16, 16, 3))
    labels = jax.random.randint(jax.random.PRNGKey(7), (16,), 0,
                                RCFG.num_classes)
    step, params, opt_state, bn_state, bsh = make_resnet_train_step(
        RCFG, mesh_dp, optax.sgd(0.5)
    )
    img = jax.device_put(images, bsh)
    lbl = jax.device_put(labels, bsh)
    losses = []
    for _ in range(6):
        loss, params, opt_state, bn_state = step(
            params, opt_state, bn_state, img, lbl
        )
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


@pytest.mark.slow
def test_bert_compressed_dp_training(mesh_dp):
    tokens, targets, mask = synthetic_mlm_batch(
        jax.random.PRNGKey(8), BCFG, 8, 16
    )
    step, params, opt_state, bsh = make_bert_train_step(
        BCFG, mesh_dp, optax.adam(1e-2),
        compression_params={"compressor": "onebit", "ef": "vanilla"},
    )
    tok = jax.device_put(tokens, bsh)
    tgt = jax.device_put(targets, bsh)
    msk = jax.device_put(mask, bsh)
    losses = []
    for _ in range(8):
        loss, params, opt_state = step(params, opt_state, tok, tgt, msk)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses

def test_falcon_h1_family_keeps_pages_and_a_slot_in_every_layer():
    """``serve_family(FalconH1Config)``: both pools are ``n_layers`` deep —
    row ``i`` of the k/v pool and row ``i`` of the state pool are layer
    ``i``'s — the state pool holds one slot a request the scheduler admits
    at once and scratch, and every layer of the plan is hybrid."""
    from byteps_tpu.models.falcon_h1 import FalconH1Config
    from byteps_tpu.serve.families import (
        HybridKVFamily, RecurrentKVFamily, admitted_at_once, serve_family)

    cfg = FalconH1Config.tiny(n_layers=3)
    family = serve_family(cfg)
    assert isinstance(family, HybridKVFamily)
    assert family.REFUSED is RecurrentKVFamily.REFUSED
    lay = family.layout(None, cfg, block_size=4, pool_blocks=9, max_batch=8,
                        prefill_chunk=8, quant=False)
    slots = 1 + admitted_at_once(8)
    assert lay.state_slots == slots == 11 and lay.kv_heads == cfg.n_kv_heads
    assert lay.window is None and lay.window_blocks == 0
    pool = lay.state
    assert pool.k.shape == pool.v.shape == (
        3, 9, 4, cfg.n_kv_heads * cfg.head_dim)
    assert pool.s.shape == (3, slots, cfg.ssm_heads, cfg.ssm_state,
                            cfg.ssm_head_dim) and pool.s.dtype == jnp.float32
    assert pool.conv.shape == (3, slots, 3 * cfg.conv_channels)
    assert pool.wk is None and pool.k_scale is None
    plan = family.plan(cfg)
    assert [k.index for k in plan.kinds] == [0, 1, 2]
    assert all(k.hybrid and not k.state and k.window is None
               for k in plan.kinds)
    assert plan.last_logits and plan.embed_scale == cfg.embedding_multiplier
    assert plan.logit_scale == cfg.lm_head_multiplier
