"""The three forms of the SSD (Mamba-2) rule of ``ops/ssd.py``, each held to
the token-by-token one: the chunked form from zero and from a carried state
at chunk lengths that are and are not whole sub-chunks, the decode kernel
(interpreted) and its jnp twin over a slot pool, rows on the scratch slot
among them. f32 everywhere; the tolerances are sums of a few hundred f32
products of unit-scale terms (1e-5 absolute on outputs of size ~10). At
sub-chunks of 128 the chunked form exponentiates differences of a running
log-decay that reaches -250 here, of which f32 keeps 2e-5 absolute: a decay
is good to 2e-5 relative and a sum of 128 such terms to a few 1e-4."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.common.metrics import get_registry
from byteps_tpu.ops import ssd


def _inputs(T, H=4, G=2, N=16, P=128, seed=0, zero_state=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (T, H)) - 2.0)
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.7))
    B = jax.random.normal(ks[3], (T, G, N))
    C = jax.random.normal(ks[4], (T, G, N))
    D = jax.random.normal(ks[5], (H,))
    S = jnp.zeros((H, N, P)) if zero_state \
        else 0.5 * jax.random.normal(ks[6], (H, N, P))
    return x, dt, A, B, C, D, S


@pytest.mark.parametrize("T,sub,cut,zero", [
    (256, 128, None, True),    # whole published sub-chunks, from zero
    (64, 16, None, False),     # whole sub-chunks, from a carried state
    (37, 8, None, False),      # a tail that is padded
    (300, 128, None, True),    # not a multiple of 128
    (40, 8, 24, True),         # two calls: the state carried across a chunk
    (23, 64, 7, False),        # shorter than one sub-chunk, carried mid-way
    (5, 4, 1, True),
], ids=["whole128_zero", "whole_carried", "padded", "t300_sub128",
        "two_chunks", "short", "one_then_four"])
def test_chunked_rule_equals_the_recurrent_one(T, sub, cut, zero):
    x, dt, A, B, C, D, S = _inputs(T, zero_state=zero)
    y_want, S_want = ssd.ssd_recurrent(x, dt, A, B, C, D, S)
    if cut is None:
        y, S1 = ssd.ssd_chunk_fwd(x, dt, A, B, C, D, S, sub)
    else:
        y1, S1 = ssd.ssd_chunk_fwd(x[:cut], dt[:cut], A, B[:cut], C[:cut],
                                   D, S, sub)
        y2, S1 = ssd.ssd_chunk_fwd(x[cut:], dt[cut:], A, B[cut:], C[cut:],
                                   D, S1, sub)
        y = jnp.concatenate([y1, y2])
    atol = 5e-4 if sub == 128 else 2e-5
    np.testing.assert_allclose(y, y_want, atol=atol, rtol=1e-5)
    np.testing.assert_allclose(S1, S_want, atol=atol, rtol=1e-5)


def test_a_group_reads_its_own_b_and_c():
    """Heads 0-1 are group 0's, heads 2-3 group 1's: changing group 1's B and
    C moves heads 2-3 alone."""
    x, dt, A, B, C, D, S = _inputs(9)
    y0, S0 = ssd.ssd_recurrent(x, dt, A, B, C, D, S)
    y1, S1 = ssd.ssd_recurrent(x, dt, A, B.at[:, 1].mul(2.0),
                               C.at[:, 1].mul(-1.0), D, S)
    np.testing.assert_array_equal(y0[:, :2], y1[:, :2])
    np.testing.assert_array_equal(S0[:2], S1[:2])
    assert float(jnp.abs(y0[:, 2:] - y1[:, 2:]).max()) > 0.1


@pytest.mark.parametrize("H,G,N", [(4, 2, 16), (32, 2, 32), (6, 3, 8)],
                         ids=["h4_g2", "h32_two_steps_a_group", "h6_g3"])
def test_decode_kernel_updates_the_slots_in_place_like_its_twin(H, G, N):
    """The Pallas kernel (interpreted) against the jnp twin and the
    recurrent rule: rows at scattered slots of layer 1, two of them the
    scratch slot; no other slot and no other layer moves."""
    R, L, slots_n = 5, 2, 7
    x, dt, A, B, C, D, _ = _inputs(R, H=H, G=G, N=N)
    pool = jax.random.normal(jax.random.PRNGKey(9), (L, slots_n, H, N, 128))
    slots = jnp.asarray([3, 1, 0, 6, 0], jnp.int32)
    y_t, p_t = ssd.ssd_decode_jnp(x, dt, A, B, C, D, pool, 1, slots)
    y_k, p_k = ssd._decode(x * dt[..., None], jnp.exp(dt * A), B, C, pool, 1,
                           slots, True)
    y_k = y_k + D[:, None] * x
    live = np.asarray([0, 1, 3])
    np.testing.assert_allclose(y_k[live], y_t[live], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(p_k[:, 1:], p_t[:, 1:], atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(p_k[0], pool[0])
    np.testing.assert_array_equal(p_k[1, [2, 4, 5]], pool[1, [2, 4, 5]])
    for r in live:
        y_r, S_r = ssd.ssd_recurrent(x[r:r + 1], dt[r:r + 1], A, B[r:r + 1],
                                     C[r:r + 1], D, pool[1, slots[r]])
        np.testing.assert_allclose(y_k[r], y_r[0], atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(p_k[1, slots[r]], S_r, atol=1e-5,
                                   rtol=1e-5)


@pytest.mark.parametrize("backend,counter", [
    ("pallas", "ssd.decode_kernel"), ("jnp", "ssd.decode_twin")])
def test_decode_dispatch_counts_which_form_was_traced(monkeypatch, backend,
                                                      counter):
    monkeypatch.setenv("BYTEPS_KERNEL_BACKEND", backend)
    x, dt, A, B, C, D, _ = _inputs(2)
    pool = jnp.zeros((1, 3, 4, 16, 128), jnp.float32)
    c = get_registry().counter(counter)
    before = c.value()
    slots = jnp.asarray([1, 2], jnp.int32)
    y, pool = ssd.ssd_decode(x, dt, A, B, C, D, pool, 0, slots)
    assert c.value() == before + 1
    want, _ = ssd.ssd_decode_jnp(x, dt, A, B, C, D, jnp.zeros_like(pool), 0,
                                 slots)
    np.testing.assert_allclose(y, want, atol=1e-5)


def test_decode_kernel_refuses_what_it_cannot_tile():
    assert ssd.decode_unsupported_reason(32, 2, 256, 128, jnp.float32) is None
    assert "float32" in ssd.decode_unsupported_reason(32, 2, 256, 128,
                                                      jnp.bfloat16)
    assert "tiles" in ssd.decode_unsupported_reason(4, 2, 32, 16, jnp.float32)
    assert "blocks" in ssd.decode_unsupported_reason(24, 2, 256, 128,
                                                     jnp.float32)
