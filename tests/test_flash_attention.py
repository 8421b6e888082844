"""Flash attention kernels vs the jnp golden (interpret mode on CPU).

Covers the fwd/bwd Pallas kernels, the global-offset causal masking, the
logsumexp merge, and the flash ring-attention path under shard_map —
mirroring the reference's compressor-vs-golden test style
(SURVEY §4: every kernel has a dense-math twin asserted bit-close).
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from byteps_tpu.ops.flash_attention import (
    _NEG,
    attention_jnp,
    flash_attention,
    flash_attention_lse,
    merge_attention,
    supported,
)
from byteps_tpu.parallel import (
    MeshAxes,
    make_mesh,
    ring_attention,
)

# (the package re-exports a function named like the module)
fa = importlib.import_module("byteps_tpu.ops.flash_attention")


@pytest.fixture(autouse=True)
def _force_pallas(monkeypatch):
    monkeypatch.setenv("BYTEPS_KERNEL_BACKEND", "pallas")


def _rand_qkv(rng, B=2, S=64, H=2, D=16, dtype=jnp.float32):
    ks = jax.random.split(rng, 3)
    return tuple(jax.random.normal(k, (B, S, H, D), dtype) for k in ks)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape", [(2, 64, 2, 16), (1, 128, 3, 32)])
def test_forward_matches_golden(shape, causal):
    B, S, H, D = shape
    q, k, v = _rand_qkv(jax.random.PRNGKey(0), B, S, H, D)
    got = flash_attention(q, k, v, causal=causal)
    want = attention_jnp(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_grads_match_golden(causal):
    q, k, v = _rand_qkv(jax.random.PRNGKey(1))

    def loss(attn):
        return lambda q, k, v: (attn(q, k, v, causal=causal) ** 2).sum()

    got = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(attention_jnp), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-4)


def test_bf16_forward_close_to_f32_golden():
    q, k, v = _rand_qkv(jax.random.PRNGKey(2), dtype=jnp.bfloat16)
    got = flash_attention(q, k, v)
    assert got.dtype == jnp.bfloat16
    want = attention_jnp(q.astype(jnp.float32), k.astype(jnp.float32),
                         v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got, dtype=np.float32),
                               np.asarray(want), rtol=2e-2, atol=2e-2)


def test_global_offsets_mask_against_manual_golden():
    """q block at global rows 32.., k block at global cols 16..: the kernel
    must mask exactly where (32 + i) < (16 + j)."""
    B, Sq, Sk, H, D = 1, 32, 64, 2, 16
    rng = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(rng[0], (B, Sq, H, D), jnp.float32)
    k = jax.random.normal(rng[1], (B, Sk, H, D), jnp.float32)
    v = jax.random.normal(rng[2], (B, Sk, H, D), jnp.float32)
    q_off, k_off = 32, 16

    o, lse = flash_attention_lse(q, k, v, q_off, k_off, causal=True)

    scale = 1.0 / (D ** 0.5)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    mask = (q_off + jnp.arange(Sq))[:, None] >= (k_off + jnp.arange(Sk))
    s = jnp.where(mask[None, None], s, _NEG)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # lse golden: logsumexp of live scores per row
    want_lse = jax.nn.logsumexp(s, axis=-1).transpose(0, 2, 1)  # (B, Sq, H)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               rtol=2e-5, atol=2e-5)


def test_fully_masked_rows_are_neutral():
    """k block strictly in the future → o = 0, lse = −1e30 (merge-neutral)."""
    B, S, H, D = 1, 16, 1, 8
    rng = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(rng[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(rng[1], (B, S, H, D), jnp.float32)
    v = jax.random.normal(rng[2], (B, S, H, D), jnp.float32)
    o, lse = flash_attention_lse(q, k, v, 0, 1000, causal=True)
    assert np.all(np.asarray(o) == 0.0)
    assert np.all(np.asarray(lse) <= _NEG / 2)


def test_merge_reconstructs_split_attention():
    """Attention over [K_a ; K_b] == merge(attn(K_a), attn(K_b))."""
    B, S, H, D = 2, 64, 2, 16
    rng = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(rng[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(rng[1], (B, 2 * S, H, D), jnp.float32)
    v = jax.random.normal(rng[2], (B, 2 * S, H, D), jnp.float32)

    o_a, lse_a = flash_attention_lse(q, k[:, :S], v[:, :S], 0, 0,
                                     causal=False)
    o_b, lse_b = flash_attention_lse(q, k[:, S:], v[:, S:], 0, 0,
                                     causal=False)
    o, _ = merge_attention(o_a, lse_a, o_b, lse_b)
    want = attention_jnp(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.fixture(scope="module")
def sp_mesh():
    return make_mesh(MeshAxes(sp=4), devices=jax.devices()[:4])


@pytest.mark.parametrize("causal", [True, False])
def test_ring_flash_matches_golden(sp_mesh, causal):
    # S_loc = 16 ≥ the kernel's min block → the flash ring path engages
    q, k, v = _rand_qkv(jax.random.PRNGKey(6), S=64)
    want = attention_jnp(q, k, v, causal=causal)
    got = jax.jit(
        jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, "sp", causal=causal),
            mesh=sp_mesh,
            in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"),
            check_vma=False,
        )
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_flash_grads_match_golden(sp_mesh):
    q, k, v = _rand_qkv(jax.random.PRNGKey(7), S=64)

    def gold(q, k, v):
        return (attention_jnp(q, k, v) ** 2).sum()

    want = jax.grad(gold, argnums=(0, 1, 2))(q, k, v)

    # Per-device loss WITHOUT psum: the global objective is the sum of
    # per-device losses, and the ppermute transpose already routes each
    # device's k/v cotangent contributions around the ring — so local
    # grads == global grads, with no vma requirement. (check_vma=True +
    # interpret-mode pallas is a known jax gap; its own error message
    # recommends check_vma=False.)
    def local(q, k, v):
        o = ring_attention(q, k, v, "sp")
        return (o.astype(jnp.float32) ** 2).sum()

    got = jax.jit(
        jax.shard_map(
            jax.grad(local, argnums=(0, 1, 2)), mesh=sp_mesh,
            in_specs=(P(None, "sp"),) * 3,
            out_specs=(P(None, "sp"),) * 3,
            check_vma=False,
        )
    )(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-4)


def test_supported_shapes():
    assert supported(64, 64, 16)
    assert supported(128, 256, 64)
    assert not supported(100, 64, 16)   # S not tileable
    assert not supported(64, 64, 512)   # head_dim beyond VMEM budget


@pytest.mark.slow
def test_zigzag_ring_matches_golden_both_backends(sp_mesh, monkeypatch):
    from byteps_tpu.parallel import (
        zigzag_inverse,
        zigzag_permutation,
        zigzag_ring_attention,
    )

    n = 4
    q, k, v = _rand_qkv(jax.random.PRNGKey(8), S=64)
    perm = np.asarray(zigzag_permutation(64, n))
    inv = np.asarray(zigzag_inverse(64, n))
    for backend in ("pallas", "jnp"):
        monkeypatch.setenv("BYTEPS_KERNEL_BACKEND", backend)
        for causal in (True, False):
            want = attention_jnp(q, k, v, causal=causal)
            got_z = jax.jit(
                jax.shard_map(
                    lambda a, b, c: zigzag_ring_attention(
                        a, b, c, "sp", causal=causal),
                    mesh=sp_mesh, in_specs=(P(None, "sp"),) * 3,
                    out_specs=P(None, "sp"), check_vma=False,
                )
            )(q[:, perm], k[:, perm], v[:, perm])
            np.testing.assert_allclose(
                np.asarray(got_z)[:, inv], np.asarray(want),
                rtol=2e-5, atol=2e-5, err_msg=f"{backend} causal={causal}")


@pytest.mark.slow
def test_zigzag_ring_grads_match_golden(sp_mesh):
    from byteps_tpu.parallel import (
        zigzag_inverse,
        zigzag_permutation,
        zigzag_ring_attention,
    )

    n = 4
    q, k, v = _rand_qkv(jax.random.PRNGKey(9), S=64)
    perm = np.asarray(zigzag_permutation(64, n))
    inv = np.asarray(zigzag_inverse(64, n))

    def gold(q, k, v):
        return (attention_jnp(q, k, v) ** 2).sum()

    want = jax.grad(gold, argnums=(0, 1, 2))(q, k, v)

    def local(qz, kz, vz):
        o = zigzag_ring_attention(qz, kz, vz, "sp")
        return (o.astype(jnp.float32) ** 2).sum()

    got = jax.jit(
        jax.shard_map(
            jax.grad(local, argnums=(0, 1, 2)), mesh=sp_mesh,
            in_specs=(P(None, "sp"),) * 3,
            out_specs=(P(None, "sp"),) * 3,
            check_vma=False,
        )
    )(q[:, perm], k[:, perm], v[:, perm])
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g)[:, inv], np.asarray(w),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.slow
def test_gqa_kernel_matches_grouped_jnp(causal):
    """Native GQA kernels (narrow k/v via grid-index maps) vs the grouped
    jnp golden — fwd and all grads, dk/dv summed over the group."""
    from byteps_tpu.ops.flash_attention import attention_lse_jnp

    B, S, H, Hkv, D = 2, 64, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(30), 4)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32)
    g = jax.random.normal(ks[3], (B, S, H, D), jnp.float32)

    o, lse = flash_attention_lse(q, k, v, 0, 0, causal=causal)
    ow, lw = attention_lse_jnp(q, k, v, 0, 0, causal=causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ow),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lw),
                               rtol=2e-5, atol=2e-5)

    def loss(fn):
        return lambda q, k, v: (
            fn(q, k, v, 0, 0, causal=causal)[0] * g).sum()

    got = jax.grad(loss(flash_attention_lse), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(attention_lse_jnp), argnums=(0, 1, 2))(q, k, v)
    for gg, ww in zip(got, want):
        assert gg.shape == ww.shape
        np.testing.assert_allclose(np.asarray(gg), np.asarray(ww),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_mqa_extreme_kernel(causal):
    """Hkv=1 (multi-query): every query head reads one kv row."""
    from byteps_tpu.ops.flash_attention import attention_lse_jnp

    B, S, H, D = 1, 32, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(31), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, 1, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, 1, D), jnp.float32)
    o, _ = flash_attention_lse(q, k, v, 0, 0, causal=causal)
    ow, _ = attention_lse_jnp(q, k, v, 0, 0, causal=causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ow),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# block-choice / VMEM-budget pins (VERDICT r5 #5): the round-5 retune's
# 1.75× came entirely from these tile choices — a silent edit to
# _FWD_PREFER/_BWD_PREFER or the walk-down must fail HERE, not resurface
# as 22 TFLOP/s in a bench three rounds later.
# ---------------------------------------------------------------------------
def _vmem_cost(bq, bk, D, itemsize, n_inter):
    """The same live-set model _train_blocks budgets against."""
    inter = n_inter * bq * bk * 4
    io = 2 * 2 * (2 * bq + 2 * bk) * D * itemsize
    scratch = (bq + 2 * bk) * D * 4
    return inter + io + scratch


def test_train_blocks_retuned_gpt2m_tiles():
    """The measured-optimal tiles on the retune shapes (v5e, bf16, D=64):
    forward whole-sequence k-tiles at S=1024, backward 512s."""
    from byteps_tpu.ops.flash_attention import (
        _BWD_PREFER, _FWD_PREFER, _train_blocks)

    assert _train_blocks(1024, 1024, 64, 2, _FWD_PREFER, n_inter=2) == \
        (1024, 1024)
    assert _train_blocks(1024, 1024, 64, 2, _BWD_PREFER, n_inter=4) == \
        (512, 512)
    # flagship S=512: both paths take whole-sequence tiles
    assert _train_blocks(512, 512, 64, 2, _FWD_PREFER, n_inter=2) == \
        (512, 512)
    assert _train_blocks(512, 512, 64, 2, _BWD_PREFER, n_inter=4) == \
        (512, 512)
    # what the backward runs is _bwd_plan's: the same tiles, and at these
    # shapes the one-pass kernel under Mosaic's default limit
    from byteps_tpu.ops.flash_attention import _VMEM_BUDGET, _bwd_plan

    for S in (1024, 512):
        bq, bk, need = _bwd_plan(S, S, 64, 64, 2, 1)
        assert (bq, bk) == (512, 512) and need <= _VMEM_BUDGET


@pytest.mark.parametrize("itemsize,D,n_inter", [
    (4, 64, 2), (4, 64, 4),            # f32 activations
    (2, 256, 2), (2, 256, 4),          # max head_dim
    (4, 256, 4),                       # both at once (worst case)
])
def test_train_blocks_walkdown_respects_vmem_budget(itemsize, D, n_inter):
    """f32 / wide-head shapes must degrade to smaller tiles that FIT the
    budget instead of shipping the bf16-measured 1024s to Mosaic."""
    from byteps_tpu.ops.flash_attention import (
        _FWD_PREFER, _VMEM_BUDGET, _train_blocks)

    bq, bk = _train_blocks(1024, 1024, D, itemsize, _FWD_PREFER,
                           n_inter=n_inter)
    assert 1024 % bq == 0 and 1024 % bk == 0
    assert _vmem_cost(bq, bk, D, itemsize, n_inter) <= _VMEM_BUDGET
    # the (greedy) walk-down must not collapse to pipeline-overhead
    # territory on these shapes — 256² was the measured 22 TFLOP/s
    # regime the retune escaped, and every shape here still fits ≥256
    assert min(bq, bk) >= 256


def test_train_blocks_none_contract():
    """Indivisible sequence lengths return None (the documented
    jnp-fallback signal), never raise."""
    from byteps_tpu.ops.flash_attention import _FWD_PREFER, _train_blocks

    assert _train_blocks(1023, 1024, 64, 2, _FWD_PREFER) is None
    assert _train_blocks(1024, 7, 64, 2, _FWD_PREFER) is None


def test_train_blocks_env_override(monkeypatch):
    """BYTEPS_FLASH_BLOCK prepends experiment tiles (still
    budget-checked)."""
    from byteps_tpu.ops.flash_attention import _FWD_PREFER, _train_blocks

    monkeypatch.setenv("BYTEPS_FLASH_BLOCK", "256")
    assert _train_blocks(1024, 1024, 64, 2, _FWD_PREFER, n_inter=2) == \
        (256, 256)


# ---------------------------------------------------------------------------
# the one-pass backward (PR 42): no cell's `correct` reads a gradient, so
# these hold dq, dk and dv to the f32 jnp golden, case by case
# ---------------------------------------------------------------------------
def _form_counts():
    from byteps_tpu.common.metrics import get_registry

    reg = get_registry()
    return (reg.counter("flash.bwd_fused").value(),
            reg.counter("flash.bwd_split").value())


@pytest.fixture
def fresh_traces(monkeypatch):
    """The tile preference and the form are read while ``_fwd`` / ``_bwd``
    trace: a case that sets either must not meet another case's trace."""
    def fresh(block=None, cap=None):
        if block is not None:
            monkeypatch.setenv("BYTEPS_FLASH_BLOCK", str(block))
        if cap is not None:
            monkeypatch.setattr(fa, "_FUSED_VMEM_CAP", cap)
        fa._fwd.clear_cache()
        fa._bwd.clear_cache()
    fresh()
    yield fresh
    fa._fwd.clear_cache()
    fa._bwd.clear_cache()


def _vjp_inputs(B, Sq, Sk, H, Hkv, D, Dv, seed=40):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (B, Sq, H, D), jnp.float32),
            jax.random.normal(ks[1], (B, Sk, Hkv, D), jnp.float32),
            jax.random.normal(ks[2], (B, Sk, Hkv, Dv), jnp.float32),
            # cotangents of o and of lse, the second on every row
            jax.random.normal(ks[3], (B, Sq, H, Dv), jnp.float32),
            jax.random.normal(ks[4], (B, Sq, H), jnp.float32))


def _grads(fn, q, k, v, do, dlse, qoff, koff, causal):
    _, vjp = jax.vjp(
        lambda q, k, v: fn(q, k, v, qoff, koff, causal=causal), q, k, v)
    return vjp((do, dlse))


# id: (B, Sq, Sk, H, Hkv, D, Dv, causal, qoff, koff, tile, fused)
_BWD_CASES = {
    "causal": (2, 64, 64, 2, 2, 16, 16, True, 0, 0, None, True),
    "not_causal": (2, 64, 64, 2, 2, 16, 16, False, 0, 0, None, True),
    # v narrower than q/k (24/16 standing for 192/128), two blocks each way:
    # the resident dq is added to at two row offsets, dk/dv over two q blocks
    "dv_narrower_2x2_blocks": (1, 256, 256, 2, 2, 24, 16, True, 0, 0, 128,
                               True),
    "not_causal_3_q_2_kv_blocks": (1, 384, 256, 2, 2, 16, 16, False, 0, 0,
                                   128, True),
    # ring attention's offsets: q ahead of k (an all-live block beside the
    # diagonal), k ahead of q (rows 0..63 have no live key at all), and k
    # wholly in the future (every tile skipped: all three gradients zero)
    "qoff_ahead": (1, 256, 256, 2, 2, 16, 16, True, 128, 0, 128, True),
    "koff_ahead_masked_rows": (1, 256, 256, 2, 2, 16, 16, True, 0, 64, 128,
                               True),
    "koff_all_future": (1, 256, 256, 1, 1, 16, 16, True, 0, 1000, 128, True),
    # what must keep the two kernels: a grouped-query shape, and a q tile
    # that is neither whole lanes nor the whole sequence (192 = 3 x 64)
    "gqa_keeps_two_kernels": (2, 64, 64, 4, 2, 16, 16, True, 0, 0, None,
                              False),
    "narrow_q_tile_keeps_two_kernels": (1, 192, 192, 2, 2, 16, 16, True, 0,
                                        0, None, False),
}


@pytest.mark.parametrize("case", _BWD_CASES.values(), ids=_BWD_CASES.keys())
def test_backward_matches_f32_golden(fresh_traces, case):
    from byteps_tpu.ops.flash_attention import attention_lse_jnp

    B, Sq, Sk, H, Hkv, D, Dv, causal, qoff, koff, tile, fused = case
    fresh_traces(block=tile)
    args = _vjp_inputs(B, Sq, Sk, H, Hkv, D, Dv)
    before = _form_counts()
    got = _grads(flash_attention_lse, *args, qoff, koff, causal)
    after = _form_counts()
    assert (after[0] - before[0], after[1] - before[1]) == \
        ((1, 0) if fused else (0, 1))
    want = _grads(attention_lse_jnp, *args, qoff, koff, causal)
    for name, g, w in zip(("dq", "dk", "dv"), got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4,
                                   atol=2e-4, err_msg=name)
    if koff >= Sq + qoff:
        assert all(not np.asarray(g).any() for g in got)


@pytest.mark.parametrize("name", ["causal", "not_causal",
                                  "dv_narrower_2x2_blocks",
                                  "not_causal_3_q_2_kv_blocks",
                                  "koff_ahead_masked_rows"])
def test_one_pass_equals_two_kernels(fresh_traces, name):
    """One input through both forms: the same products over the same tiles,
    so equal to f32 rounding (the sum over k blocks is taken in another
    order for dq, and Δ − dlse is taken before the tile, not inside it)."""
    B, Sq, Sk, H, Hkv, D, Dv, causal, qoff, koff, tile, _ = _BWD_CASES[name]
    args = _vjp_inputs(B, Sq, Sk, H, Hkv, D, Dv, seed=41)
    fresh_traces(block=tile)
    before = _form_counts()
    one = _grads(flash_attention_lse, *args, qoff, koff, causal)
    fresh_traces(cap=0)                 # no live set fits: the two kernels
    two = _grads(flash_attention_lse, *args, qoff, koff, causal)
    after = _form_counts()
    assert (after[0] - before[0], after[1] - before[1]) == (1, 1)
    for g, w in zip(one, two):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5,
                                   atol=1e-5)


def test_one_pass_bf16_close_to_f32_golden(fresh_traces):
    q, k, v, do, dlse = _vjp_inputs(1, 256, 256, 2, 2, 24, 16)
    fresh_traces(block=128)
    lo = [x.astype(jnp.bfloat16) for x in (q, k, v, do)]
    got = _grads(flash_attention_lse, *lo, dlse, 0, 0, True)
    from byteps_tpu.ops.flash_attention import attention_lse_jnp

    want = _grads(attention_lse_jnp,
                  *[x.astype(jnp.float32) for x in lo], dlse, 0, 0, True)
    for g, w in zip(got, want):
        assert g.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(g, dtype=np.float32),
                                   np.asarray(w), rtol=5e-2, atol=5e-2)


# (Sq, Sk, D, Dv, itemsize, group) -> the form, and where the live set lies
@pytest.mark.parametrize("shape,form", [
    ((1024, 1024, 64, 64, 2, 1), "fused_default_limit"),     # gpt2m-train
    ((4096, 4096, 192, 128, 2, 1), "fused_stated_limit"),    # JoyAI
    ((65536, 65536, 128, 128, 2, 1), "split"),   # dq cannot stay: 67 MB
    ((1024, 1024, 64, 64, 2, 4), "split"),       # grouped-query
    ((1023, 1024, 64, 64, 2, 1), "none"),
])
def test_bwd_plan_from_shapes_alone(shape, form):
    from byteps_tpu.ops.flash_attention import (
        _FUSED_VMEM_CAP, _VMEM_BUDGET, _bwd_plan)

    plan = _bwd_plan(*shape)
    if form == "none":
        assert plan is None
        return
    bq, bk, need = plan
    assert (bq, bk) == (512, 512)
    if form == "split":
        assert need is None
    elif form == "fused_default_limit":
        assert need <= _VMEM_BUDGET
    else:
        assert _VMEM_BUDGET < need <= _FUSED_VMEM_CAP


def test_backward_form_is_counted_once_a_trace(fresh_traces):
    args = _vjp_inputs(1, 64, 64, 2, 2, 16, 16)
    before = _form_counts()
    for _ in range(3):                  # one trace, three calls
        _grads(flash_attention_lse, *args, 0, 0, True)
    assert _form_counts() == (before[0] + 1, before[1])
