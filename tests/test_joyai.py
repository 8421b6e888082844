"""JoyAI-LLM-Flash for training (models/joyai.py, parallel/moe.py's dropless
path, ops/grouped_matmul.py, the flash kernels at a value width of their
own) against the plain reference (benchmark/configs/joyai_reference.py), at
tiny sizes on the CPU: d 64, 4 heads of 24/8/16, q_lora 48, kv_lora 32, 16
experts top-4, 1 dense + 2 expert layers + MTP. Everything runs in float32
here (the program's jnp twins at XLA:CPU's exact f32 products), so the
tolerances are f32 rounding over a few hundred sums, not bf16's."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byteps_tpu.models.joyai import (
    BUFFER_KEYS, STEP_STATS, JoyAIConfig, joyai_init, joyai_loss)
from byteps_tpu.parallel.moe import moe_dropless_init, moe_ffn_dropless

_REF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark", "configs", "joyai_reference.py")
_spec = importlib.util.spec_from_file_location("joyai_reference", _REF)
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

CFG = JoyAIConfig.tiny()


def _ref_kw(cfg):
    return dict(n_heads=cfg.n_heads, nope=cfg.qk_nope_dim,
                rope=cfg.qk_rope_dim, v_dim=cfg.v_head_dim,
                theta=cfg.rope_base, eps=cfg.norm_eps, top_k=cfg.top_k,
                scale=cfg.routed_scaling, first_expert=cfg.first_expert)


def _batch(cfg, B=2, S=16, seed=1):
    toks = np.random.default_rng(seed).integers(0, cfg.vocab_size,
                                                (B, S + 1)).astype(np.int32)
    return jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])


def test_reference_imports_nothing_from_the_program():
    with open(_REF) as f:
        assert "byteps_tpu" not in f.read().split('"""', 2)[2]


@pytest.mark.parametrize("remat", [False, True])
def test_loss_and_every_gradient_leaf_match_the_reference(remat):
    params = joyai_init(jax.random.PRNGKey(3), CFG)
    tok, tgt = _batch(CFG)

    def mine(p):
        return joyai_loss(p, tok, tgt, CFG, remat=remat)[0]

    def theirs(p):
        return ref.loss(p, tok, tgt, mtp_weight=CFG.mtp_loss_weight,
                        **_ref_kw(CFG))

    (l1, g1), (l2, g2) = (jax.value_and_grad(f)(params)
                          for f in (mine, theirs))
    # f32 on both sides; sums of a few hundred terms of O(1e-2): 1e-5
    # absolute on a loss of ~5, and on gradients 2e-5 of the leaf's largest
    # entry plus 1e-7. A bf16 router moves picks (loss by ~1e-3) and a
    # dropped pair moves that token's row of every expert gradient by O(1)
    # of its size.
    assert abs(float(l1) - float(l2)) < 1e-5
    flat1 = jax.tree_util.tree_leaves_with_path(g1)
    flat2 = jax.tree.leaves(g2)
    assert len(flat1) == len(flat2)
    for (path, a), b in zip(flat1, flat2):
        tol = 2e-5 * float(jnp.abs(b).max()) + 1e-7
        assert float(jnp.abs(a - b).max()) <= tol, jax.tree_util.keystr(path)
    # the bias steers picks and takes no gradient
    for p in g1["blocks"][1:]:
        assert float(jnp.abs(p["moe"]["router_bias"]).max()) == 0.0


def test_stats_count_pairs_and_split_the_loss():
    params = joyai_init(jax.random.PRNGKey(3), CFG)
    tok, tgt = _batch(CFG)
    loss, (stats, loads) = joyai_loss(params, tok, tgt, CFG)
    here, total, load, main, mtp = (float(v) for v in stats)
    T, moe_layers = tok.size, CFG.n_layers - CFG.first_k_dense + CFG.n_mtp
    assert len(stats) == len(STEP_STATS)
    assert total == T * CFG.top_k * moe_layers
    assert here == total            # every expert is held
    assert load >= 1.0
    assert abs(float(loss) - (main + CFG.mtp_loss_weight * mtp)) < 1e-6
    r_main, r_mtp = ref.losses(params, tok, tgt, **_ref_kw(CFG))
    assert abs(main - float(r_main)) < 1e-5
    assert abs(mtp - float(r_mtp)) < 1e-5
    # one row of picks per expert layer, over ALL routed experts
    assert loads.shape == (moe_layers, CFG.n_routed_experts)
    assert float(loads.sum()) == total


def _layer(seed=0, T=24, d=32, ff=16, E=16, held=16, bias_std=0.0):
    p = moe_dropless_init(jax.random.PRNGKey(seed), d, ff, E, held,
                          std=0.2, bias_std=bias_std)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (T, d), jnp.float32)
    return x, p


def test_bias_changes_the_picks_and_weights_still_come_from_scores():
    x, p = _layer()
    base = moe_ffn_dropless(x, p, 4, 2.5, row_tile=8)[0]
    # large enough on two experts to put them in every token's picks
    p2 = dict(p, router_bias=p["router_bias"].at[jnp.array([3, 11])].set(5.0))
    y = moe_ffn_dropless(x, p2, 4, 2.5, row_tile=8)[0]
    want = ref.moe_layer(x, p2, top_k=4, scale=2.5)
    assert float(jnp.abs(y - base).max()) > 1e-2          # the picks moved
    np.testing.assert_allclose(y, want, atol=2e-5, rtol=2e-5)
    # had the weights been taken from s + b, they would be dominated by 5.0
    s = jax.nn.sigmoid(x @ p2["wg"])
    _, idx = jax.lax.top_k(s + p2["router_bias"], 4)
    assert bool(jnp.all(jnp.any(idx == 3, -1) & jnp.any(idx == 11, -1)))


def _towering(x, p):
    # the router's column 5 towers over the rest: every token's first pick
    # is expert 5
    return jnp.abs(x), dict(p, wg=p["wg"].at[:, 5].set(3.0))


def test_every_token_to_one_held_expert_drops_nothing():
    # all T tokens pick expert 5 first, T pairs in one group — a capacity
    # would overflow
    x, p = _towering(*_layer(T=40))
    y, stats, load = moe_ffn_dropless(x, p, 4, 2.5, row_tile=8)
    want = ref.moe_layer(x, p, top_k=4, scale=2.5)
    np.testing.assert_allclose(y, want, atol=2e-5, rtol=2e-5)
    assert float(stats[0]) == float(stats[1]) == 40 * 4
    assert float(load[5]) == 40 and float(load.sum()) == 40 * 4
    assert float(stats[2]) >= 4.0      # 40 of 160 pairs on one of 16


def test_shares_sum_to_the_uncut_layer():
    """The parts that all E/experts_held shares give, with the shared expert
    counted once, add up to the uncut reference's layer output."""
    cfg = JoyAIConfig.tiny(n_layers=2)        # 1 dense + 1 expert layer
    full = joyai_init(jax.random.PRNGKey(5), cfg)["blocks"][1]
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 12, cfg.d_model))
    held = 4
    total = jnp.zeros_like(x)
    pairs = 0.0
    for first in range(0, cfg.n_routed_experts, held):
        share = dict(full["moe"], **{k: full["moe"][k][first:first + held]
                                     for k in ("w1", "w3", "w2")})
        y, stats, _ = moe_ffn_dropless(
            x, share, cfg.top_k, cfg.routed_scaling, first_expert=first,
            row_tile=8)
        total = total + y
        pairs += float(stats[0])
    sh = full["shared"]
    shared = (jax.nn.silu(x @ sh["w1"]) * (x @ sh["w3"])) @ sh["w2"]
    want = (ref.moe_layer(x, full["moe"], top_k=cfg.top_k,
                          scale=cfg.routed_scaling)
            + ref._swiglu(x, sh["w1"], sh["w3"], sh["w2"]))
    np.testing.assert_allclose(total + shared, want, atol=2e-5, rtol=2e-5)
    assert pairs == x.shape[0] * x.shape[1] * cfg.top_k   # each pair once


def test_a_share_matches_the_reference_given_the_same_share():
    cfg = JoyAIConfig.tiny(experts_held=4, first_expert=8)
    params = joyai_init(jax.random.PRNGKey(7), cfg)
    tok, tgt = _batch(cfg)
    loss, (stats, _) = joyai_loss(params, tok, tgt, cfg)
    want = ref.loss(params, tok, tgt, mtp_weight=cfg.mtp_loss_weight,
                    **_ref_kw(cfg))
    assert abs(float(loss) - float(want)) < 1e-5
    # the rows the kernels ran over are the pairs the reference routes to
    # the held experts, one for one; with those routed elsewhere, all T*k
    held = ref.forward(params, tok, tgt, **_ref_kw(cfg))[2]
    assert 0 < float(stats[0]) == float(held) < float(stats[1])
    assert float(stats[1]) == tok.size * cfg.top_k * 3


@pytest.mark.parametrize("router_dtype,same", [(jnp.float32, True),
                                               (jnp.bfloat16, False)])
def test_router_picks_are_the_references_and_a_bf16_router_differs(
        router_dtype, same):
    """What the benchmark's ``correct`` asks at the published widths: on the
    reference's own router input the program's router picks what the
    reference picks, token for token; rounded to bf16 it does not."""
    from byteps_tpu.parallel.moe import sigmoid_topk_route

    cfg = JoyAIConfig.tiny(n_routed_experts=64, experts_held=64, top_k=8,
                           max_seq=128)
    params = joyai_init(jax.random.PRNGKey(13), cfg)
    tok, tgt = _batch(cfg, B=4, S=128)
    h, idx = ref.forward(params, tok, tgt, **_ref_kw(cfg))[3]
    moe = params["blocks"][cfg.first_k_dense]["moe"]
    mine, _ = sigmoid_topk_route(
        h.reshape(-1, cfg.d_model).astype(router_dtype).astype(jnp.float32),
        moe["wg"].astype(router_dtype), moe["router_bias"], cfg.top_k,
        cfg.routed_scaling)
    differ = int(jnp.sum(jnp.any(
        jnp.sort(mine, -1) != jnp.sort(idx.reshape(mine.shape), -1), -1)))
    assert (differ == 0) if same else (differ > 0)


# -- kernels, interpreted ---------------------------------------------------
@pytest.fixture
def pallas(monkeypatch):
    monkeypatch.setenv("BYTEPS_KERNEL_BACKEND", "pallas")


@pytest.fixture
def small_buffers(monkeypatch):
    """THE RULE admits a training step's buffer (``ROW_KERNEL_TILES`` tiles);
    the tests' buffers are six tiles, so the test says they count."""
    from byteps_tpu.parallel import moe

    monkeypatch.setattr(moe, "ROW_KERNEL_TILES", 1)


def test_flash_kernels_with_a_value_width_of_their_own(pallas):
    """MLA's shapes in miniature (q/k 48 wide, v 32): forward and the three
    gradients against attention_jnp."""
    from byteps_tpu.ops.flash_attention import attention_jnp, flash_attention

    k = jax.random.split(jax.random.PRNGKey(0), 4)
    B, S, H, D, Dv = 2, 64, 4, 48, 32
    q = jax.random.normal(k[0], (B, S, H, D))
    kk = jax.random.normal(k[1], (B, S, H, D))
    v = jax.random.normal(k[2], (B, S, H, Dv))
    w = jax.random.normal(k[3], (B, S, H, Dv))

    def scalar(fn):
        return lambda q, k, v: (fn(q, k, v, causal=True) * w).sum()

    o = flash_attention(q, kk, v)
    assert o.shape == (B, S, H, Dv)
    np.testing.assert_allclose(o, attention_jnp(q, kk, v), atol=2e-6)
    got = jax.grad(scalar(flash_attention), (0, 1, 2))(q, kk, v)
    want = jax.grad(scalar(attention_jnp), (0, 1, 2))(q, kk, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-5)


def _gmm_module():
    # (the package re-exports a function of the module's name)
    import importlib
    return importlib.import_module("byteps_tpu.ops.grouped_matmul")


def _check_against_twin(sizes, K, N, key, rhs_scale=1.0, atol=1e-5):
    """Forward, ``dlhs`` and ``drhs`` of the interpreted kernels against the
    twin for five groups of ``sizes`` rows (row tile 8) in a buffer of 64;
    rows past the last group exactly zero."""
    gm = _gmm_module()
    k = jax.random.split(jax.random.PRNGKey(key), 3)
    tm, G, M = 8, 5, 64
    gs = jnp.asarray(sizes, jnp.int32)
    lhs = jax.random.normal(k[0], (M, K))
    rhs = jax.random.normal(k[1], (G, K, N)) * rhs_scale
    w = jax.random.normal(k[2], (M, N))

    def scalar(fn):
        return lambda a, b: (fn(a, b) * w).sum()

    def kern(a, b):
        return gm.grouped_matmul(a, b, gs, tm)

    def twin(a, b):
        return gm.grouped_matmul_jnp(a, b, gs)

    out = jax.jit(kern)(lhs, rhs)
    np.testing.assert_allclose(out, twin(lhs, rhs), atol=atol)
    got = jax.jit(jax.grad(scalar(kern), (0, 1)))(lhs, rhs)
    want = jax.grad(scalar(twin), (0, 1))(lhs, rhs)
    for past in (out, got[0]):
        assert float(jnp.abs(past[sum(sizes):]).max(initial=0.0)) == 0.0
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=atol)


@pytest.mark.parametrize("sizes", [[16, 0, 8, 24, 0], [0, 0, 0, 0, 0],
                                   [8, 8, 8, 8, 8], [0, 56, 0, 0, 8]])
def test_grouped_product_against_its_twin(pallas, sizes):
    _check_against_twin(sizes, 16, 24, key=1)


@pytest.mark.parametrize("budget", [None, 800 << 10], ids=["whole", "split"])
@pytest.mark.parametrize("K,N", [(384, 640), (640, 384)])
def test_grouped_product_at_widths_off_a_power_of_two(
        pallas, monkeypatch, K, N, budget):
    """Widths that are multiples of 128 and no power of two, groups with
    empty and multi-tile members: whole-axis blocks under the module's own
    budget, and under one of 800 KB a split contraction axis (384 x 640:
    blocks of 128 x 640) or a split output axis (640 x 384: 640 x 128) and
    ``moe_gmm_dw`` in five walks."""
    gm = _gmm_module()
    if budget is not None:
        monkeypatch.setattr(gm, "_VMEM_BUDGET", budget)
    rows, dw = gm._rows_blocks(8, K, N, 4), gm._dw_blocks(8, K, N, 4)
    if budget is None:
        assert rows == dw == (K, N)
    else:
        assert rows == ((128, 640) if K == 384 else (640, 128)), rows
        assert dw == ((384, 128) if K == 384 else (128, 384)), dw
    _check_against_twin([16, 0, 8, 24, 0], K, N, key=2,
                        rhs_scale=K ** -0.5, atol=2e-5)


# every cell's expert matrices (Mellum2, JoyAI, dots3: gate/up and down) at
# the cells' row tile, and the tests' own tiny one
_CELL_SHAPES = [(256, 2304, 896), (256, 896, 2304), (256, 2048, 768),
                (256, 768, 2048), (256, 5120, 1536), (256, 1536, 5120),
                (8, 16, 24)]


@pytest.mark.parametrize("itemsize", [2, 4], ids=["bf16", "f32"])
@pytest.mark.parametrize("tm,C,O", _CELL_SHAPES,
                         ids=["x".join(map(str, s[1:])) for s in _CELL_SHAPES])
def test_grouped_product_blocks_from_shapes_alone(tm, C, O, itemsize):
    """The chooser is a function of ``(tm, C, out_dim, itemsize)``: each
    block divides its axis and is a multiple of 128 or the whole axis, the
    set the pipeline holds is within the stated budget (itself under
    Mosaic's scoped default, so no call of a cell asks for more), and an
    expert matrix that fits is taken whole: Mellum2's 2304 x 896 in bf16 is
    one grid step a row tile, JoyAI's 2048 x 768 too; dots3's 5120 x 1536
    (15.7 MB) splits its contraction axis and keeps the output axis."""
    gm = _gmm_module()
    assert gm._VMEM_BUDGET < gm._SCOPED_VMEM
    for blocks, nbytes in ((gm._rows_blocks, gm._rows_bytes),
                           (gm._dw_blocks, gm._dw_bytes)):
        a, b = blocks(tm, C, O, itemsize)
        for blk, axis in ((a, C), (b, O)):
            assert axis % blk == 0 and (blk % 128 == 0 or blk == axis), (
                blk, axis)
        assert nbytes(tm, a, b, itemsize) <= gm._VMEM_BUDGET
    rows = gm._rows_blocks(tm, C, O, itemsize)
    if itemsize == 2 and C * O <= 2304 * 896:
        assert rows == (C, O)
    if (itemsize, C, O) == (2, 5120, 1536):
        assert rows[1] == O and 1 < C // rows[0] <= 5
    assert gm._compiler_params(("arbitrary",), gm._rows_bytes(
        tm, *rows, itemsize)).vmem_limit_bytes is None
    # never more grid steps a row tile than the halving walk gave
    old = [next((t for t in (1024, 512, 256, 128) if n % t == 0), n)
           for n in (C, O)]
    assert (C // rows[0]) * (O // rows[1]) <= (C // old[0]) * (O // old[1])


def test_grouped_product_over_the_budget_asks_for_its_vmem():
    """Widths off the lanes have one legal block, the whole axis: a set over
    the budget asks Mosaic for its bytes and the headroom the budget
    leaves, a number derived from the blocks."""
    gm = _gmm_module()
    assert gm._rows_blocks(256, 3000, 1000, 2) == (3000, 1000)
    need = gm._rows_bytes(256, 3000, 1000, 2)
    assert gm._compiler_params(("arbitrary",), need).vmem_limit_bytes \
        == need + gm._SCOPED_VMEM - gm._VMEM_BUDGET > gm._SCOPED_VMEM


def test_dropless_layer_through_the_kernels(pallas):
    x, p = _layer(bias_std=0.01)

    def f(x, p):
        y = moe_ffn_dropless(x, p, 4, 2.5, row_tile=8)[0]
        return (y * y).sum()

    def g(x, p):
        return (ref.moe_layer(x, p, top_k=4, scale=2.5) ** 2).sum()

    (l1, g1), (l2, g2) = (jax.value_and_grad(fn, (0, 1))(x, p)
                          for fn in (f, g))
    assert abs(float(l1) - float(l2)) < 1e-4 * abs(float(l2))
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(a, b, atol=2e-4 * float(
            jnp.abs(b).max()) + 1e-7)


# -- the row moves as kernels over the live prefix (ops/moe_rows.py) ---------
def _share(dtype, T=256, d=256, ff=128, E=32, held=2, bias=()):
    """A 1/16 share: experts 4 and 5 of 32 held; ``bias`` = (expert, value)
    pairs laid on the router's correction bias."""
    p = moe_dropless_init(jax.random.PRNGKey(0), d, ff, E, held, std=0.05,
                          bias_std=0.01)
    for e, v in bias:
        p["router_bias"] = p["router_bias"].at[e].set(v)
    p = {k: v.astype(dtype) if v.ndim == 3 else v for k, v in p.items()}
    return jax.random.normal(jax.random.PRNGKey(1), (T, d), dtype), p


def _layer_and_grads(x, p, tile=None):
    def f(x, p):
        y, stats, _ = moe_ffn_dropless(x, p, 4, 2.5, first_expert=4,
                                       row_tile=tile)
        return (y.astype(jnp.float32) ** 2).sum(), (y, stats)

    (_, (y, stats)), g = jax.value_and_grad(f, (0, 1), has_aux=True)(x, p)
    return y, stats, g


def _row_move_counts():
    from byteps_tpu.common.metrics import get_registry

    reg = get_registry()
    return tuple(reg.counter(f"moe.row_move.{path}").value()
                 for path in ("kernel", "gather"))


# (id, dtype, bias on the router, tile, pairs held here, path)
_ROW_MOVE_CASES = [
    # one held expert takes a pair of (nearly) every token, the other its
    # even share: a group of more than one tile beside one that ends mid-tile
    ("collapsed_router_bf16", jnp.bfloat16, ((5, 10.0),), None, "many",
     "kernel"),
    ("collapsed_router_f32", jnp.float32, ((5, 10.0),), None, "many",
     "kernel"),
    ("group_ends_mid_tile_bf16", jnp.bfloat16, (), None, "some", "kernel"),
    ("held_expert_with_no_pair_f32", jnp.float32, ((4, -10.0),), None,
     "some", "kernel"),
    ("no_pair_held_bf16", jnp.bfloat16, ((4, -10.0), (5, -10.0)), None,
     "none", "kernel"),
    # THE RULE asks for the 256-row tile: a buffer laid out by a smaller one
    # (a decode step's) keeps the gathers, share or not
    ("tile_16_keeps_the_gathers_bf16", jnp.bfloat16, (), 16, "some",
     "gather"),
]


@pytest.mark.parametrize("dtype,bias,tile,pairs,path",
                         [c[1:] for c in _ROW_MOVE_CASES],
                         ids=[c[0] for c in _ROW_MOVE_CASES])
def test_row_kernels_are_the_gathers_bit_for_bit(pallas, small_buffers,
                                                 monkeypatch, dtype, bias,
                                                 tile, pairs, path):
    """Under an expert share the four row moves run as kernels
    (``moe_rows_*``, interpreted here); ``y`` and every gradient — ``dx``,
    the router's (which carries ``dweight``), ``dw1``/``dw2``/``dw3`` — are
    the ``jnp.take`` path's, bit for bit."""
    from byteps_tpu.ops import moe_rows

    x, p = _share(dtype, bias=bias)
    before = _row_move_counts()
    y, stats, g = _layer_and_grads(x, p, tile)
    took = tuple(b - a for a, b in zip(before, _row_move_counts()))
    assert took == ((1, 0) if path == "kernel" else (0, 1))
    here = int(stats[0])
    assert {"many": here > 256, "some": 0 < here < 256,
            "none": here == 0}[pairs], here
    if pairs == "some" and not bias:
        assert here % 256 != 0 and here % 16 != 0      # a group ends mid-tile
    monkeypatch.setattr(moe_rows, "rows_supported", lambda *a: False)
    y0, stats0, g0 = _layer_and_grads(x, p, tile)
    np.testing.assert_array_equal(stats, stats0)
    np.testing.assert_array_equal(y, y0)
    assert pairs == "none" or float(jnp.abs(y0.astype(jnp.float32)).max()) > 0
    for (path_, a), b in zip(jax.tree_util.tree_leaves_with_path(g),
                             jax.tree.leaves(g0)):
        np.testing.assert_array_equal(a, b, err_msg=str(path_))
    assert float(jnp.abs(g0[1]["wg"]).max()) > 0 or pairs == "none"


def test_nothing_reads_a_dead_tile_of_the_row_buffer(pallas, small_buffers,
                                                     monkeypatch):
    """The kernels leave the tiles past the live ones unwritten. Poisoned:
    every operand and every result of the three grouped products — ``xs``,
    ``gate``, ``up``, the SwiGLU's product, ``ys`` — and, on the way back,
    each of their cotangents (``dys``, ``dxs``) holds NaN on its dead
    tiles, and ``y``, ``stats`` and every gradient are what they were."""
    import importlib

    # (the package re-exports a function named like the module)
    gm = importlib.import_module("byteps_tpu.ops.grouped_matmul")

    @jax.custom_vjp
    def poison(a, live):
        rows = jax.lax.broadcasted_iota(jnp.int32, (a.shape[0], 1), 0)
        return jnp.where(rows < live, a, jnp.nan)

    poison.defvjp(lambda a, live: (poison(a, live), live),
                  lambda live, da: (poison(da, live), None))
    real = gm.grouped_matmul

    def poisoned(lhs, rhs, sizes, tm):
        live = jnp.sum(sizes)
        return poison(real(poison(lhs, live), rhs, sizes, tm), live)

    x, p = _share(jnp.bfloat16, bias=((5, 10.0),))
    want = _layer_and_grads(x, p)
    monkeypatch.setattr(gm, "grouped_matmul", poisoned)
    before = _row_move_counts()
    got = _layer_and_grads(x, p)
    assert _row_move_counts()[0] == before[0] + 1
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("held,T,path", [
    (2, 4096, "kernel"), (32, 4096, "gather"), (2, 2048, "gather")],
    ids=["a_share", "all_experts_held", "a_share_in_a_chunk"])
def test_row_kernels_only_under_an_expert_share(pallas, held, T, path):
    """THE RULE: with every routed expert held the buffer is dense and the
    layer's program holds no ``moe_rows_*`` call (SDAR's and Mellum2's
    programs are the parent's); under a share it holds them from a buffer of
    ``ROW_KERNEL_TILES`` tiles (4,096 tokens x top-16: 256 + 2), and a serve
    chunk's 2,048 tokens keep the gathers. The counters say which path a
    trace took."""
    p = moe_dropless_init(jax.random.PRNGKey(0), 128, 64, 32, held)
    x = jnp.zeros((T, 128), jnp.float32)
    before = _row_move_counts()
    text = str(jax.make_jaxpr(jax.grad(lambda x: moe_ffn_dropless(
        x, p, 16, 2.5, row_tile=256)[0].sum()))(x))
    took = tuple(b - a for a, b in zip(before, _row_move_counts()))
    assert took == ((1, 0) if path == "kernel" else (0, 1))
    for name in ("moe_rows_pack", "moe_rows_to_buffer", "moe_rows_to_tokens",
                 "moe_rows_dweight"):
        assert (name in text) == (path == "kernel"), name
    assert "moe_gmm_fwd" in text


def test_row_kernels_wait_for_the_pallas_backend():
    """Off the TPU (the jnp twins) the layer keeps its gathers."""
    x, p = _share(jnp.float32)
    before = _row_move_counts()
    moe_ffn_dropless(x, p, 4, 2.5, first_expert=4)
    assert _row_move_counts() == (before[0], before[1] + 1)


# -- the row tile follows from the pairs a program holds ----------------------
# (program, tokens, top-k, experts held, operand bytes, tile): the four cells'
# decode steps, 2,048-token chunks and 1,024-token tail chunks, JoyAI's
# training step, and a decode step in f32
_TILE_CASES = [
    ("mellum2_decode", 24, 8, 64, 2, 16),
    ("qwen3next_decode", 128, 10, 64, 2, 32),
    ("dots3_decode", 16, 8, 32, 2, 16),
    ("mellum2_chunk", 2048, 8, 64, 2, 256),
    ("qwen3next_chunk", 2048, 10, 64, 2, 256),
    ("dots3_chunk", 2048, 8, 32, 2, 256),
    ("mellum2_tail", 1024, 8, 64, 2, 128),
    ("qwen3next_tail", 1024, 10, 64, 2, 256),
    ("dots3_tail", 1024, 8, 32, 2, 256),
    ("joyai_step", 4 * 4096, 8, 16, 2, 256),
    ("decode_f32", 24, 8, 64, 4, 8),
    ("one_row_f32", 1, 2, 64, 4, 8),
]


@pytest.mark.parametrize("T,k,held,itemsize,want",
                         [c[1:] for c in _TILE_CASES],
                         ids=[c[0] for c in _TILE_CASES])
def test_row_tile_from_the_program_shapes(T, k, held, itemsize, want):
    """A pure function of what the layer sees while tracing: an expert's
    even share of the pairs, up to a power of two, between Mosaic's least
    row block for the operand type and ``ROW_TILE`` — so 256, the program
    as it was, wherever that share is 256 rows or more."""
    from byteps_tpu.ops.grouped_matmul import ROW_TILE, min_row_tile
    from byteps_tpu.parallel.moe import dropless_row_tile

    tm = dropless_row_tile(T * k, held, itemsize)
    assert tm == want
    assert tm & (tm - 1) == 0 and min_row_tile(itemsize) <= tm <= ROW_TILE
    even = -(-T * k // held)
    assert tm == ROW_TILE if even >= ROW_TILE else even <= tm


@pytest.mark.parametrize("backend", ["pallas", "jnp"])
@pytest.mark.parametrize("top_k,tower,tile",
                         [(4, False, 16), (4, True, 16), (1, True, 8)],
                         ids=["spread", "first_pick_on_one", "all_on_one"])
def test_dropless_layer_at_the_derived_tile(monkeypatch, backend, top_k,
                                            tower, tile):
    """No ``row_tile=``: the layer derives its tile (f32, 40 tokens over 16
    experts: 16 rows at top-4, 8 at top-1) and gives what 256-row tiles and
    the reference give, forward and gradients, through the interpreted
    kernels and through the twin — also when every pair lands on ONE held
    expert (top-1, a towering router column: 40 rows, five tiles, where the
    even share is three rows), and the buffer still holds them all: nothing
    lost a row."""
    from byteps_tpu.parallel.moe import dropless_row_tile

    monkeypatch.setenv("BYTEPS_KERNEL_BACKEND", backend)
    x, p = _layer(T=40, bias_std=0.01)
    if tower:
        x, p = _towering(x, p)
    assert dropless_row_tile(40 * top_k, 16, 4) == tile

    def run(row_tile):
        def f(x, p):
            y, stats, _ = moe_ffn_dropless(x, p, top_k, 2.5,
                                           row_tile=row_tile)
            return (y * y).sum(), (y, stats)
        return jax.value_and_grad(f, (0, 1), has_aux=True)(x, p)

    ((_, (y1, st1)), g1), ((_, (y2, st2)), g2) = run(None), run(256)
    want = ref.moe_layer(x, p, top_k=top_k, scale=2.5)
    np.testing.assert_allclose(y1, want, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(y1, y2, atol=2e-6, rtol=2e-6)
    np.testing.assert_array_equal(st1, st2)
    assert float(st1[0]) == float(st1[1]) == 40 * top_k
    if tower and top_k == 1:
        assert float(st1[2]) == 16.0             # one expert has them all
    g_ref = jax.grad(lambda x, p: (ref.moe_layer(
        x, p, top_k=top_k, scale=2.5) ** 2).sum(), (0, 1))(x, p)
    for a, b, c in zip(*map(jax.tree.leaves, (g1, g2, g_ref))):
        tol = 2e-4 * float(jnp.abs(c).max()) + 1e-7
        np.testing.assert_allclose(a, c, atol=tol)
        np.testing.assert_allclose(a, b, atol=tol)


def test_a_trace_counts_its_row_tile_once():
    """``moe.row_tile.<tm>`` is incremented where the tile is chosen, while
    tracing: once a trace of the layer, not once a call; the gauge holds
    the last traced buffer's rows."""
    from byteps_tpu.common.metrics import get_registry

    x, p = _layer(T=40)
    reg = get_registry()
    before = {tm: reg.counter(f"moe.row_tile.{tm}").value()
              for tm in (16, 256)}
    f = jax.jit(lambda x, p: moe_ffn_dropless(x, p, 4, 2.5)[0])
    f(x, p)
    f(x + 1.0, p)                                # the compiled program again
    assert reg.counter("moe.row_tile.16").value() == before[16] + 1
    assert reg.counter("moe.row_tile.256").value() == before[256]
    assert reg.gauge("moe.row_buffer_rows").value() == (10 + 16) * 16
    jax.jit(lambda x, p: moe_ffn_dropless(x, p, 4, 2.5, row_tile=256)[0])(
        x, p)
    assert reg.counter("moe.row_tile.256").value() == before[256] + 1
    assert reg.gauge("moe.row_buffer_rows").value() == (1 + 16) * 256


# -- the row plan: compare-and-count, one scatter, groups by the tile ---------
# (program, pairs, experts held, tile): the cells' decode steps, Mellum2's
# tail chunk and a cut of JoyAI's step (8,192 of its 131,072 pairs)
_PLAN_SHAPES = [
    ("dots3_decode", 128, 32, 16),
    ("mellum2_decode", 192, 64, 16),
    ("qwen3next_decode", 1280, 64, 32),
    ("sdar_decode", 4096, 128, 32),
    ("mellum2_tail", 8192, 64, 128),
    ("joyai_step", 8192, 16, 256),
]
_PLAN_LOADS = ["even", "skewed", "some_empty", "some_not_held", "all_on_one"]


def _plan_load(load, pairs, held, seed=0):
    """Each pair's expert ``0 .. held - 1`` (``held``: not held here)."""
    rng = np.random.default_rng(seed)
    if load == "even":
        local = rng.permutation(np.arange(pairs) % held)
    elif load == "skewed":
        p = 1.0 / np.arange(1, held + 1) ** 2
        local = rng.choice(held, pairs, p=p / p.sum())
    elif load == "some_empty":
        local = rng.choice(np.arange(held)[1::3], pairs)
    elif load == "some_not_held":
        local = np.minimum(rng.integers(0, 2 * held, pairs), held)
    else:
        local = np.full(pairs, held // 3)
    return local.astype(np.int32)


def _np_row_plan(local, held, tm):
    """The plan by a stable sort and a loop over the groups, in NumPy."""
    P = len(local)
    n_rows = (-(-P // tm) + held) * tm
    order = np.argsort(local, kind="stable")
    pair_row, row_pair = np.full(P, n_rows), np.full(n_rows, P)
    row = 0
    for g in range(held):
        pairs = order[local[order] == g]
        pair_row[pairs] = row + np.arange(len(pairs))
        row_pair[row:row + len(pairs)] = pairs
        row += -(-len(pairs) // tm) * tm
    return pair_row, row_pair


_plan_cases = pytest.mark.parametrize(
    "pairs,held,tm,load",
    [c[1:] + (load,) for c in _PLAN_SHAPES for load in _PLAN_LOADS],
    ids=[f"{c[0]}-{load}" for c in _PLAN_SHAPES for load in _PLAN_LOADS])


@_plan_cases
def test_row_plan_is_the_plain_plan(pairs, held, tm, load):
    """``pair_row`` and ``row_pair`` integer for integer what a stable sort
    by expert and a walk over the groups give: which pair gets which row is
    part of the result (it fixes the order of ``_combine``'s f32 sums)."""
    from byteps_tpu.parallel.moe import _row_plan

    local = _plan_load(load, pairs, held)
    pair_row, row_pair, counts, padded = jax.jit(
        _row_plan, static_argnums=(1, 2))(jnp.asarray(local), held, tm)
    want_pair_row, want_row_pair = _np_row_plan(local, held, tm)
    np.testing.assert_array_equal(pair_row, want_pair_row)
    np.testing.assert_array_equal(row_pair, want_row_pair)
    np.testing.assert_array_equal(counts, np.bincount(local, minlength=held +
                                                      1)[:held])
    np.testing.assert_array_equal(padded, -(-np.asarray(counts) // tm) * tm)


@_plan_cases
def test_tile_groups_against_searchsorted(pairs, held, tm, load):
    """The kernels' tile -> group map, by compare-and-count, is the search's:
    every tile's group (the last group past the live tiles) and the number
    of live tiles."""
    from byteps_tpu.ops.grouped_matmul import _tile_groups

    counts = np.bincount(_plan_load(load, pairs, held),
                         minlength=held + 1)[:held]
    padded = (-(-counts // tm) * tm).astype(np.int32)
    n_tiles = -(-pairs // tm) + held
    tile_group, n_live = _tile_groups(jnp.asarray(padded), tm, n_tiles)
    ends = np.cumsum(padded) // tm
    np.testing.assert_array_equal(tile_group, np.minimum(
        np.searchsorted(ends, np.arange(n_tiles), side="right"), held - 1))
    assert tile_group.dtype == jnp.int32 and n_live.shape == (1,)
    assert int(n_live[0]) == ends[-1] == padded.sum() // tm


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "grad"])
@pytest.mark.parametrize("T,tile", [(24, 8), (1024, 256)],
                         ids=["decode", "chunk"])
def test_dropless_layer_lowers_to_no_loop(T, tile, grad):
    """The lowered layer holds no ``while`` (a ``searchsorted`` is one, of
    dependent scalar gathers), no sort of the pairs and ONE gather or
    scatter of integers: the scatter that inverts ``pair_row``. The rest of
    its index arithmetic is compares, sums and broadcasts."""
    import re

    from byteps_tpu.parallel.moe import dropless_row_tile

    x, p = _layer(T=T)
    assert dropless_row_tile(T * 4, 16, 4) == tile

    def f(x, p):
        y = moe_ffn_dropless(x, p, 4, 2.5)[0]
        return (y * y).sum()

    text = jax.jit(jax.grad(f, (0, 1)) if grad else f).lower(x, p).as_text()
    assert "while" not in text
    # every gather and scatter with the type of its result (a scatter's
    # follows its region, lines below)
    index_ops = [m.group(1) for m in re.finditer(
        r'"stablehlo\.(gather|scatter)"(?s:.*?)-> tensor<[0-9x]*x(\w+)>', text)
        if m.group(2) == "i32"]
    assert index_ops == ["scatter"]
    assert "stablehlo.sort" not in text


def test_a_trace_sets_the_tiles_its_plan_was_built_over():
    """The gauge ``moe.row_plan_tiles``: the buffer's rows over the tile,
    set while tracing beside ``moe.row_buffer_rows``."""
    from byteps_tpu.common.metrics import get_registry

    x, p = _layer(T=40)
    reg = get_registry()
    jax.jit(lambda x, p: moe_ffn_dropless(x, p, 4, 2.5)[0])(x, p)
    assert reg.gauge("moe.row_plan_tiles").value() == 10 + 16
    assert reg.gauge("moe.row_buffer_rows").value() == (10 + 16) * 16
    jax.jit(lambda x, p: moe_ffn_dropless(x, p, 4, 2.5, row_tile=256)[0])(
        x, p)
    assert reg.gauge("moe.row_plan_tiles").value() == 1 + 16


# -- the normal train step --------------------------------------------------
def test_trains_through_make_gpt_moe_train_step():
    from byteps_tpu.common.metrics import get_registry
    from byteps_tpu.models.train import make_gpt_moe_train_step
    from byteps_tpu.parallel import MeshAxes, make_mesh

    mesh = make_mesh(MeshAxes(dp=2), devices=jax.devices()[:2])
    init = joyai_init(jax.random.PRNGKey(11), CFG)
    bias0 = [np.asarray(b["moe"]["router_bias"]) for b in init["blocks"][1:]]
    # weight decay on: a bias the optimizer saw would shrink
    step, params, opt_state, bsh = make_gpt_moe_train_step(
        CFG, mesh, optax.adamw(1e-2, weight_decay=0.1), remat=True,
        init_params=init)
    tok, tgt = _batch(CFG, B=4)
    want = ref.loss(init, tok, tgt, mtp_weight=CFG.mtp_loss_weight,
                    **_ref_kw(CFG))
    tok, tgt = (jax.device_put(a, bsh) for a in (tok, tgt))
    losses = []
    for _ in range(4):
        loss, params, opt_state = step(params, opt_state, tok, tgt)
        losses.append(float(loss))
    assert abs(losses[0] - float(want)) < 1e-5
    assert losses[-1] < losses[0] - 0.5
    for b, b0 in zip(params["blocks"][1:], bias0):
        np.testing.assert_array_equal(np.asarray(b["moe"]["router_bias"]), b0)
    # no optimizer state for a buffer: adam's moments have the leaves of
    # the tree less its buffers
    n_buf = sum(1 for path, _ in jax.tree_util.tree_leaves_with_path(params)
                if getattr(path[-1], "key", None) in BUFFER_KEYS)
    n_params = len(jax.tree.leaves(params))
    mu = [s for s in jax.tree.leaves(
        opt_state.inner, is_leaf=lambda s: hasattr(s, "mu"))
        if hasattr(s, "mu")][0].mu
    assert n_buf == 3 and len(jax.tree.leaves(mu)) == n_params - n_buf
    # the stats of every step, one observation each, no earlier than a
    # step later
    step.flush_stats()
    hist = get_registry().snapshot()["histograms"]
    T = tok.size // 2                   # one device's tokens: a pmean
    assert hist["moe.pairs_total"]["count"] == 4
    assert hist["moe.pairs_total"]["sum"] == 4 * T * CFG.top_k * 3
    terms = (hist["train.loss_main"]["sum"]
             + CFG.mtp_loss_weight * hist["train.loss_mtp"]["sum"])
    assert abs(terms / 4 - np.mean(losses)) < 1e-4


def test_bias_update_is_the_references_rule_through_the_train_step():
    """``router_bias_update_rate`` > 0: after a step every expert layer's
    bias is the reference's ``bias_update`` of the bias before it, from the
    loads of BOTH ranks' tokens; the picks of the second step then follow
    the moved bias."""
    from byteps_tpu.models.train import make_gpt_moe_train_step
    from byteps_tpu.parallel import MeshAxes, make_mesh

    rate = 0.05
    cfg = dataclasses.replace(CFG, router_bias_update_rate=rate)
    mesh = make_mesh(MeshAxes(dp=2), devices=jax.devices()[:2])
    init = joyai_init(jax.random.PRNGKey(13), cfg)
    step, params, opt_state, bsh = make_gpt_moe_train_step(
        cfg, mesh, optax.sgd(0.0), remat=True, init_params=init)
    tok, tgt = _batch(cfg, B=4)
    picks = ref.forward(init, tok, tgt, all_picks=True, **_ref_kw(cfg))
    layers = [b["moe"] for b in init["blocks"][1:]] + [
        init["mtp"]["block"]["moe"]]
    # before the step: it donates the tree it was given
    before = [np.asarray(m["router_bias"]) for m in layers]
    want = [np.asarray(ref.bias_update(m["router_bias"], idx, rate))
            for m, idx in zip(layers, picks)]
    _, params, opt_state = step(params, opt_state,
                                *(jax.device_put(a, bsh) for a in (tok, tgt)))
    got = [b["moe"]["router_bias"] for b in params["blocks"][1:]] + [
        params["mtp"]["block"]["moe"]["router_bias"]]
    moved = 0
    for g, w, b0 in zip(got, want, before):
        np.testing.assert_array_equal(np.asarray(g), w)
        moved += int(np.sum(np.asarray(g) != b0))
    assert moved > len(layers) * cfg.n_routed_experts // 2


def test_bias_update_unloads_an_overloaded_expert():
    # expert 5 starts in every token's picks; each balancing step lowers
    # its bias by the rate until tokens leave it, and the idle experts rise
    from byteps_tpu.parallel.moe import noaux_bias_step

    x, p = _layer(T=40)
    p = dict(p, router_bias=p["router_bias"].at[5].set(1.0))
    loads = []
    for _ in range(24):
        _, _, load = moe_ffn_dropless(x, p, 4, 2.5, row_tile=8)
        loads.append(float(load[5]))
        p = dict(p, router_bias=noaux_bias_step(p["router_bias"], load, 0.05))
    assert loads[0] == 40 and loads[-1] < 20
    assert float(load.sum()) == 40 * 4           # still top-4 of every token
    np.testing.assert_allclose(
        moe_ffn_dropless(x, p, 4, 2.5, row_tile=8)[0],
        ref.moe_layer(x, p, top_k=4, scale=2.5), atol=2e-5, rtol=2e-5)


def test_auto_tune_is_refused_not_ignored(monkeypatch):
    from byteps_tpu.common import config as bps_config
    from byteps_tpu.models.train import make_gpt_moe_train_step
    from byteps_tpu.parallel import MeshAxes, make_mesh

    monkeypatch.setenv("BYTEPS_AUTO_TUNE", "1")
    bps_config.reset_config()
    try:
        with pytest.raises(ValueError, match="BYTEPS_AUTO_TUNE"):
            make_gpt_moe_train_step(
                CFG, make_mesh(MeshAxes(dp=2), devices=jax.devices()[:2]),
                optax.adamw(1e-2))
    finally:
        monkeypatch.delenv("BYTEPS_AUTO_TUNE")
        bps_config.reset_config()


def test_unknown_axes_are_refused():
    params = joyai_init(jax.random.PRNGKey(3), CFG)
    tok, tgt = _batch(CFG)
    with pytest.raises(NotImplementedError, match="ep axis"):
        joyai_loss(params, tok, tgt, CFG, ep_axis="ep")
