"""Ring attention and tp primitive numerics vs single-device goldens."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from byteps_tpu.parallel import (
    MeshAxes,
    factor_devices,
    make_mesh,
    plain_attention,
    ring_attention,
)


@pytest.fixture(scope="module")
def sp_mesh():
    return make_mesh(MeshAxes(sp=4), devices=jax.devices()[:4])


def _rand_qkv(rng, B=2, S=16, H=2, D=8):
    ks = jax.random.split(rng, 3)
    return tuple(jax.random.normal(k, (B, S, H, D), jnp.float32) for k in ks)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_plain(sp_mesh, causal):
    q, k, v = _rand_qkv(jax.random.PRNGKey(0))
    want = plain_attention(q, k, v, causal=causal)

    got = jax.jit(
        jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, "sp", causal=causal),
            mesh=sp_mesh,
            in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
            out_specs=P(None, "sp"),
            check_vma=False,
        )
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_grads_match_plain(sp_mesh):
    q, k, v = _rand_qkv(jax.random.PRNGKey(1))

    def gold(q, k, v):
        return (plain_attention(q, k, v) ** 2).sum()

    want = jax.grad(gold, argnums=(0, 1, 2))(q, k, v)

    def local(q, k, v):
        # psum → an sp-unvarying scalar; under check_vma=True its transpose
        # seeds ONE cotangent (not one per device), so the grads are exactly
        # those of the global objective. (With check_vma=False psum
        # transposes to psum and grads come out n×.)
        o = ring_attention(q, k, v, "sp")
        return jax.lax.psum((o ** 2).sum(), "sp")

    def sharded_grads(q, k, v):
        g = jax.grad(local, argnums=(0, 1, 2))(q, k, v)
        return g  # each sp block's grad is local to its q/k/v block

    got = jax.jit(
        jax.shard_map(
            sharded_grads, mesh=sp_mesh,
            in_specs=(P(None, "sp"),) * 3,
            out_specs=(P(None, "sp"),) * 3,
        )
    )(q, k, v)
    for g_got, g_want in zip(got, want):
        np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_want),
                                   rtol=2e-4, atol=2e-4)


def test_factor_devices():
    assert factor_devices(8) == MeshAxes(dp=2, tp=2, sp=2)
    assert factor_devices(4) == MeshAxes(dp=1, tp=2, sp=2)
    assert factor_devices(2) == MeshAxes(dp=1, tp=2, sp=1)
    assert factor_devices(1) == MeshAxes(dp=1, tp=1, sp=1)
    assert factor_devices(6) == MeshAxes(dp=3, tp=2, sp=1)
    for n in (1, 2, 4, 6, 8):
        assert factor_devices(n).total == n


def test_make_mesh_axis_order():
    m = make_mesh(MeshAxes(dp=2, tp=2, sp=2))
    assert m.axis_names == ("dp", "sp", "tp")
    assert m.shape["dp"] == 2
