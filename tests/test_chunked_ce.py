"""Chunked (fused readout+CE) vs the dense head_dot+log_softmax golden.

The pins behind ops/chunked_ce.py's numerics claims:

* single-device, single-vocab-chunk, ONE row block → BIT-EXACT with the
  dense chain (same op order: max, exp-shift, sum, log). With several
  row blocks the pin is a few ulp of the logit magnitude: jax 0.9.0's
  CPU backend picks its GEMM blocking from the row count, so an 8-row
  block's ``h @ head`` differs from the same rows of the full product
  by up to 1 ulp (measured: 1.9e-6 at |logit| ≈ 19), which is the whole
  difference — see ``_assert_rowblock_close``;
* vocab sub-chunking / the tp vocab-parallel combine → f32-roundoff
  tolerance (the sum-exp association order changes);
* gradients (recompute-in-backward custom VJP) → f32-roundoff tolerance
  vs plain AD through the dense chain;
* the full train-step factories (dp, dp×tp, pp×dp; tied and untied
  readout; remat) agree between ``chunked_ce=True`` and the
  ``chunked_ce=False`` escape hatch — loss AND one optimizer step's
  updated params (i.e. the assembled gradients).

This file is tier-1: every CI pass pins the fused path against the
golden at CPU shapes.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byteps_tpu.models import GPTConfig
from byteps_tpu.ops.chunked_ce import chunked_ce_nll, dense_ce_nll

# f32 roundoff through the blockwise sum-exp / chunk-GEMM accumulation:
# a few ulps at the ~1-magnitude values these tiny configs produce
RTOL, ATOL = 1e-5, 1e-6


def _rand(seed, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, dtype)


def _assert_rowblock_close(got, want, h, head, bias=None):
    """Row-blocked chunked CE vs dense: equal to within 4 ulp AT THE
    LOGIT MAGNITUDE. Not `==` any more: the row-block GEMM and the full
    GEMM accumulate in different orders on this CPU backend (module
    docstring), so each logit — hence each nll, a difference of two
    logit-sized terms — may move by an ulp or two of max|logit|
    (measured 2). A real defect (a leaked pad row, a wrong target) is
    orders of magnitude larger."""
    logits = jnp.einsum("...d,dv->...v", h.astype(jnp.float32), head)
    if bias is not None:
        logits = logits + bias
    tol = 4 * np.spacing(np.float32(jnp.abs(logits).max()))
    assert np.abs(np.asarray(got) - np.asarray(want)).max() <= tol


@pytest.fixture(scope="module")
def hht():
    d, V = 24, 96
    h = _rand(0, (3, 17, d))
    head = _rand(1, (d, V))
    tgt = jax.random.randint(jax.random.PRNGKey(2), (3, 17), 0, V)
    bias = _rand(3, (V,))
    return h, head, tgt, bias


# 64 >= the 51 rows: one row block, the GEMM is the dense one → `==`;
# 8: seven row blocks → the ulp bound (reason in _assert_rowblock_close)
@pytest.mark.parametrize("row_block", [64, 8])
def test_fwd_bit_exact_dense(hht, row_block):
    h, head, tgt, _ = hht
    got = jax.jit(lambda h, hd: chunked_ce_nll(
        h, hd, tgt, row_block=row_block))(h, head)
    want = jax.jit(lambda h, hd: dense_ce_nll(h, hd, tgt))(h, head)
    if row_block >= tgt.size:
        assert (np.asarray(got) == np.asarray(want)).all()
    else:
        _assert_rowblock_close(got, want, h, head)


@pytest.mark.parametrize("row_block", [64, 8])
def test_fwd_bit_exact_with_bias(hht, row_block):
    h, head, tgt, bias = hht
    got = chunked_ce_nll(h, head, tgt, bias=bias, row_block=row_block)
    want = dense_ce_nll(h, head, tgt, bias=bias)
    if row_block >= tgt.size:
        assert (np.asarray(got) == np.asarray(want)).all()
    else:
        _assert_rowblock_close(got, want, h, head, bias)


def test_grads_match_dense(hht):
    h, head, tgt, bias = hht

    def lc(h, hd, b):
        return chunked_ce_nll(h, hd, tgt, bias=b, row_block=8).mean()

    def ld(h, hd, b):
        return dense_ce_nll(h, hd, tgt, bias=b).mean()

    got = jax.jit(jax.grad(lc, argnums=(0, 1, 2)))(h, head, bias)
    want = jax.jit(jax.grad(ld, argnums=(0, 1, 2)))(h, head, bias)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=RTOL, atol=ATOL)


def test_vocab_chunked_online_accumulation(hht):
    """vocab_block < V exercises the online max/sum-exp path — tolerance,
    not bit-exact (the association order changes)."""
    h, head, tgt, bias = hht
    got = chunked_ce_nll(h, head, tgt, bias=bias, row_block=8,
                         vocab_block=32)
    want = dense_ce_nll(h, head, tgt, bias=bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=RTOL, atol=ATOL)
    gc = jax.grad(lambda h_: chunked_ce_nll(
        h_, head, tgt, bias=bias, row_block=8, vocab_block=32).mean())(h)
    gd = jax.grad(lambda h_: dense_ce_nll(
        h_, head, tgt, bias=bias).mean())(h)
    np.testing.assert_allclose(np.asarray(gc), np.asarray(gd),
                               rtol=RTOL, atol=ATOL)


def test_ragged_row_blocks(hht):
    """N not divisible by row_block: the pad rows must not leak into
    values or gradients."""
    h, head, tgt, _ = hht          # N = 51 rows, row_block 16 → pad 13
    got = chunked_ce_nll(h, head, tgt, row_block=16)
    want = dense_ce_nll(h, head, tgt)
    _assert_rowblock_close(got, want, h, head)
    gc = jax.grad(lambda hd: chunked_ce_nll(h, hd, tgt,
                                            row_block=16).sum())(head)
    gd = jax.grad(lambda hd: dense_ce_nll(h, hd, tgt).sum())(head)
    np.testing.assert_allclose(np.asarray(gc), np.asarray(gd),
                               rtol=RTOL, atol=ATOL)


def test_bf16_activations(hht):
    """The head_dot dtype contract: bf16 operands, f32 accumulation —
    chunked and dense agree at bf16 exactly as they do at f32."""
    h, head, tgt, _ = hht
    hb = h.astype(jnp.bfloat16)
    got = chunked_ce_nll(hb, head, tgt, row_block=8)
    want = dense_ce_nll(hb, head, tgt)
    assert got.dtype == jnp.float32
    _assert_rowblock_close(got, want, hb, head)
    gc = jax.grad(lambda h_: chunked_ce_nll(h_, head, tgt,
                                            row_block=8).mean())(hb)
    gd = jax.grad(lambda h_: dense_ce_nll(h_, head, tgt).mean())(hb)
    assert gc.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(gc, np.float32),
                               np.asarray(gd, np.float32),
                               rtol=2e-2, atol=1e-4)   # bf16 cotangents


def test_tp_vocab_parallel(hht):
    """shard_map tp=4: V/4 logits per device, stats combined over tp —
    values and grads match the single-device dense golden."""
    from jax.sharding import PartitionSpec as P

    h, head, tgt, bias = hht
    mesh = jax.make_mesh((4,), ("tp",))

    def per_dev(h, hd, b):
        return chunked_ce_nll(h, hd, tgt, bias=b, tp_axis="tp",
                              row_block=8)

    got = jax.jit(jax.shard_map(
        per_dev, mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
        check_vma=True))(h, head, bias)
    want = dense_ce_nll(h, head, tgt, bias=bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=RTOL, atol=ATOL)

    def grads(h, hd, b):
        return jax.grad(
            lambda *a: per_dev(*a).mean(), argnums=(0, 1, 2))(h, hd, b)

    got_g = jax.jit(jax.shard_map(
        grads, mesh=mesh, in_specs=(P(), P(), P()),
        out_specs=(P(), P(), P()), check_vma=True))(h, head, bias)
    want_g = jax.grad(
        lambda *a: dense_ce_nll(a[0], a[1], tgt, bias=a[2]).mean(),
        argnums=(0, 1, 2))(h, head, bias)
    for g, w in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=RTOL, atol=ATOL)


def test_tp_indivisible_vocab_falls_back(hht):
    """V=96 doesn't divide tp=5? Use a V that doesn't divide the axis:
    the op must fall back to replicated full-vocab compute, still exact."""
    from jax.sharding import PartitionSpec as P

    h, _, _, _ = hht
    d = h.shape[-1]
    V = 66                          # not divisible by 4
    head = _rand(7, (d, V))
    tgt = jax.random.randint(jax.random.PRNGKey(8), h.shape[:-1], 0, V)
    mesh = jax.make_mesh((4,), ("tp",))
    got = jax.jit(jax.shard_map(
        lambda h_, hd: chunked_ce_nll(h_, hd, tgt, tp_axis="tp",
                                      row_block=8),
        mesh=mesh, in_specs=(P(), P()), out_specs=P(),
        check_vma=True))(h, head)
    want = dense_ce_nll(h, head, tgt)
    _assert_rowblock_close(got, want, h, head)


def test_shape_validation(hht):
    h, head, tgt, bias = hht
    with pytest.raises(ValueError):
        chunked_ce_nll(h, head, tgt[:, :-1])
    with pytest.raises(ValueError):
        chunked_ce_nll(h, head.T, tgt)
    with pytest.raises(ValueError):
        chunked_ce_nll(h, head, tgt, bias=bias[:-1])


# ---------------------------------------------------------------------------
# the backward's scan axis: a plan from the shapes (`_bwd_axis`), and the
# vocab cut against the dense chain's gradients
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape,want", [
    # the two measured training shapes, one on each side of the rule:
    # GPT-2-medium carries dh (8192·1024·25 elements a pass, not
    # 1024·50304·32), JoyAI keeps its rows (16384·16 > 16256·16)
    ((8192, 1024, 50304, None, None), ("vocab", 2048)),
    ((16384, 2048, 16256, None, None), ("rows", 1024)),
    ((32768, 1024, 6288, None, None), ("rows", 2048)),   # gpt2m under tp=8
    # a plane that fits one block is the row cut's single, un-scanned one
    ((51, 24, 96, None, None), ("rows", 51)),
    ((51, 24, 96, 64, None), ("rows", 64)),
    ((51, 24, 96, 64, 32), ("rows", 64)),
    # a row block of a few rows affords no 128-lane vocab block: the
    # cases this file had before the vocab cut still run the row loop
    ((51, 24, 96, 8, None), ("rows", 8)),
    ((51, 24, 96, 16, None), ("rows", 16)),
    ((51, 24, 96, 8, 32), ("rows", 8)),
    ((51, 24, 24, 8, None), ("rows", 8)),               # …under tp=4
    # one that affords whole lanes takes the vocab cut where N < v_loc
    ((8192, 1024, 50304, 512, None), ("vocab", 3072)),
    # `vocab_block` alone names the vocab cut's block, taken as it is
    ((51, 24, 96, None, 32), ("vocab", 32)),
    ((51, 24, 96, None, 40), ("vocab", 40)),
    ((26, 24, 48, None, 16), ("vocab", 16)),
    ((200, 24, 96, None, 32), ("rows", 200)),           # N ≥ v_loc: rows
], ids=lambda v: "-".join(map(str, v)))
def test_bwd_axis_plan(shape, want):
    from byteps_tpu.ops.chunked_ce import _bwd_axis

    assert _bwd_axis(*shape) == want


def _lowered_scopes(fn, *args):
    txt = jax.jit(fn).lower(*args).as_text(debug_info=True)
    return set(re.findall(r"readout_ce\.bwd_\w+", txt))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("with_bias", [False, True], ids=["nobias", "bias"])
@pytest.mark.parametrize("n_rows", [51, 53])
@pytest.mark.parametrize("vocab_block", [32, 40, 64],
                         ids=["3_equal", "2_and_tail_16", "1_and_tail_32"])
def test_vocab_major_grads_match_dense(hht, vocab_block, n_rows, with_bias,
                                       dtype):
    """The vocab cut's dh / dhead / dbias against plain AD through the
    dense chain, as `test_grads_match_dense` holds the row cut: equal
    blocks, a ragged tail (V = 96 is no multiple of 40 or 64), a row count
    that no block divides (51 = 3·17; 53 is prime: nothing pads rows)."""
    h, head, tgt, bias = hht
    h = h.reshape(-1, h.shape[-1])
    tgt = tgt.reshape(-1)
    if n_rows > h.shape[0]:
        h = jnp.concatenate([h, _rand(4, (n_rows - h.shape[0], h.shape[1]))])
        tgt = jnp.concatenate([tgt, tgt[:n_rows - tgt.shape[0]]])
    h = h.astype(dtype)
    b = bias if with_bias else None

    def lc(h, hd, b):
        return chunked_ce_nll(h, hd, tgt, bias=b,
                              vocab_block=vocab_block).mean()

    def ld(h, hd, b):
        return dense_ce_nll(h, hd, tgt, bias=b).mean()

    argnums = (0, 1, 2) if with_bias else (0, 1)
    assert _lowered_scopes(jax.grad(lc, argnums=argnums), h, head, b) == {
        "readout_ce.bwd_vocab"}
    got = jax.jit(jax.grad(lc, argnums=argnums))(h, head, b)
    want = jax.jit(jax.grad(ld, argnums=argnums))(h, head, b)
    assert got[0].dtype == dtype and got[1].dtype == jnp.float32
    # bf16: dz is rounded to the activation type before both products,
    # as the row cut rounds it (`test_bf16_activations`' tolerance)
    rtol, atol = (RTOL, ATOL) if dtype == jnp.float32 else (2e-2, 1e-4)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   rtol=rtol, atol=atol)


def test_row_major_cases_keep_the_row_loop(hht):
    """`row_block=8` (this file's lever since before the vocab cut) still
    lowers the row loop, and says so in its scope."""
    h, head, tgt, _ = hht
    assert _lowered_scopes(
        jax.grad(lambda h_: chunked_ce_nll(h_, head, tgt,
                                           row_block=8).mean()), h) == {
        "readout_ce.bwd_rows"}


@pytest.mark.parametrize("check_vma", [True, False], ids=["vma", "novma"])
@pytest.mark.parametrize("vocab_block", [16, 20],
                         ids=["3_equal", "2_and_tail_8"])
def test_vocab_major_tp_vocab_parallel(hht, vocab_block, check_vma):
    """shard_map dp=2 × tp=2: each device scans ITS 48 columns in vocab
    blocks over its 26 rows; dh takes its psum over tp after the loop and
    dhead is scattered and summed, as in the row cut."""
    from jax.sharding import PartitionSpec as P

    _, head, _, bias = hht
    d, V = head.shape
    h = _rand(5, (4, 13, d))
    tgt = jax.random.randint(jax.random.PRNGKey(6), (4, 13), 0, V)
    mesh = jax.make_mesh((2, 2), ("dp", "tp"))

    def per_dev(h, hd, b, t):
        def loss(h, hd, b):
            return chunked_ce_nll(h, hd, t, bias=b, tp_axis="tp",
                                  vocab_block=vocab_block).sum() / tgt.size
        dh, dhd, db = jax.grad(loss, argnums=(0, 1, 2))(h, hd, b)
        if not check_vma:
            # no vma to read: the op sums over tp alone, the caller over
            # the axes its activations are split on (models/train.py)
            dhd, db = jax.lax.psum((dhd, db), "dp")
        return dh, dhd, db

    f = jax.shard_map(
        per_dev, mesh=mesh, in_specs=(P("dp"), P(), P(), P("dp")),
        out_specs=(P("dp"), P(), P()), check_vma=check_vma)
    assert _lowered_scopes(f, h, head, bias, tgt) == {"readout_ce.bwd_vocab"}
    got = jax.jit(f)(h, head, bias, tgt)
    want = jax.grad(
        lambda *a: dense_ce_nll(a[0], a[1], tgt, bias=a[2]).mean(),
        argnums=(0, 1, 2))(h, head, bias)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=RTOL, atol=ATOL)


# ---------------------------------------------------------------------------
# factory-level parity: chunked_ce=True vs the False escape hatch across
# the parallel compositions the acceptance matrix names
# ---------------------------------------------------------------------------
def _run_two_steps(make, mesh_axes, cfg, **kw):
    from byteps_tpu.models.train import synthetic_batch
    from byteps_tpu.parallel import MeshAxes, make_mesh

    n = int(np.prod([v for v in mesh_axes.values()]))
    mesh = make_mesh(MeshAxes(**mesh_axes), devices=jax.devices()[:n])
    out = {}
    for chunked in (True, False):
        step, params, opt_state, bsh = make(
            cfg, mesh, optax.sgd(0.1), chunked_ce=chunked, **kw)
        tokens, targets = synthetic_batch(jax.random.PRNGKey(0), cfg, 4, 32)
        tokens = jax.device_put(tokens, bsh)
        targets = jax.device_put(targets, bsh)
        loss, params, opt_state = step(params, opt_state, tokens, targets)
        out[chunked] = (float(loss), jax.device_get(params))
    loss_c, params_c = out[True]
    loss_d, params_d = out[False]
    np.testing.assert_allclose(loss_c, loss_d, rtol=RTOL, atol=ATOL)
    flat_c, _ = jax.tree_util.tree_flatten(params_c)
    flat_d, _ = jax.tree_util.tree_flatten(params_d)
    for c, d_ in zip(flat_c, flat_d):
        # params after one sgd step = init − lr·grad: pins the gradients
        np.testing.assert_allclose(np.asarray(c), np.asarray(d_),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
@pytest.mark.parametrize("mesh_axes", [dict(dp=2), dict(dp=2, tp=2)],
                         ids=["dp", "dpxtp"])
def test_gpt_factory_parity(mesh_axes, tied):
    from byteps_tpu.models.train import make_gpt_train_step

    cfg = (GPTConfig.tiny() if tied
           else dataclasses.replace(GPTConfig.tiny(), tied_readout=False))
    _run_two_steps(make_gpt_train_step, mesh_axes, cfg)


def test_gpt_factory_vocab_parallel_opt_in():
    """chunked_ce='vocab_parallel' on a dp×tp mesh: the tp vocab split's
    loss and one-step params still match the dense path at f32 roundoff
    (the split is opt-in BECAUSE this roundoff drifts multi-step
    trajectories off the dp-only pins — gpt_loss docstring)."""
    from byteps_tpu.models.train import make_gpt_train_step, synthetic_batch
    from byteps_tpu.parallel import MeshAxes, make_mesh

    cfg = GPTConfig.tiny()
    mesh = make_mesh(MeshAxes(dp=2, tp=2), devices=jax.devices()[:4])
    out = {}
    for mode in ("vocab_parallel", False):
        step, params, opt_state, bsh = make_gpt_train_step(
            cfg, mesh, optax.sgd(0.1), chunked_ce=mode)
        tokens, targets = synthetic_batch(jax.random.PRNGKey(0), cfg, 4, 32)
        tokens = jax.device_put(tokens, bsh)
        targets = jax.device_put(targets, bsh)
        loss, params, _ = step(params, opt_state, tokens, targets)
        out[mode] = (float(loss), jax.device_get(params))
    np.testing.assert_allclose(out["vocab_parallel"][0], out[False][0],
                               rtol=RTOL, atol=ATOL)
    for c, d_ in zip(jax.tree_util.tree_flatten(out["vocab_parallel"][1])[0],
                     jax.tree_util.tree_flatten(out[False][1])[0]):
        np.testing.assert_allclose(np.asarray(c), np.asarray(d_),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_gpt_pp_factory_parity(tied):
    from byteps_tpu.models.train import make_gpt_pp_train_step

    cfg = (GPTConfig.tiny() if tied
           else dataclasses.replace(GPTConfig.tiny(), tied_readout=False))
    _run_two_steps(make_gpt_pp_train_step, dict(pp=2, dp=2), cfg,
                   n_micro=2)


def test_gpt_factory_parity_remat():
    from byteps_tpu.models.train import make_gpt_train_step

    _run_two_steps(make_gpt_train_step, dict(dp=2), GPTConfig.tiny(),
                   remat=True)


def test_bert_factory_parity():
    from byteps_tpu.models.bert import BertConfig
    from byteps_tpu.models.train import (
        make_bert_train_step, synthetic_mlm_batch)
    from byteps_tpu.parallel import MeshAxes, make_mesh

    cfg = BertConfig.tiny()
    mesh = make_mesh(MeshAxes(dp=2), devices=jax.devices()[:2])
    out = {}
    for chunked in (True, False):
        step, params, opt_state, bsh = make_bert_train_step(
            cfg, mesh, optax.sgd(0.1), chunked_ce=chunked)
        batch = synthetic_mlm_batch(jax.random.PRNGKey(0), cfg, 4, 32)
        batch = tuple(jax.device_put(a, bsh) for a in batch)
        loss, params, _ = step(params, opt_state, *batch)
        out[chunked] = (float(loss), jax.device_get(params))
    np.testing.assert_allclose(out[True][0], out[False][0],
                               rtol=RTOL, atol=ATOL)
    for c, d_ in zip(jax.tree_util.tree_flatten(out[True][1])[0],
                     jax.tree_util.tree_flatten(out[False][1])[0]):
        np.testing.assert_allclose(np.asarray(c), np.asarray(d_),
                                   rtol=1e-4, atol=1e-5)


def test_t5_loss_parity():
    from byteps_tpu.models.t5 import T5Config, t5_init, t5_loss
    from byteps_tpu.models import synthetic_seq2seq_batch

    cfg = T5Config.tiny()
    params = t5_init(jax.random.PRNGKey(0), cfg)
    src, ti, to = synthetic_seq2seq_batch(jax.random.PRNGKey(1), cfg, 2,
                                          32, 32)
    lc = t5_loss(params, src, ti, to, cfg, chunked_ce=True)
    ld = t5_loss(params, src, ti, to, cfg, chunked_ce=False)
    assert float(lc) == float(ld)   # single device → bit-exact
    gc = jax.grad(lambda p: t5_loss(p, src, ti, to, cfg,
                                    chunked_ce=True))(params)
    gd = jax.grad(lambda p: t5_loss(p, src, ti, to, cfg,
                                    chunked_ce=False))(params)
    for c, d_ in zip(jax.tree_util.tree_flatten(gc)[0],
                     jax.tree_util.tree_flatten(gd)[0]):
        np.testing.assert_allclose(np.asarray(c), np.asarray(d_),
                                   rtol=RTOL, atol=ATOL)


def test_moe_loss_parity():
    from byteps_tpu.models.moe_gpt import (
        MoEGPTConfig, moe_gpt_init, moe_gpt_loss)
    from byteps_tpu.models.train import synthetic_batch

    cfg = MoEGPTConfig.tiny()
    params = moe_gpt_init(jax.random.PRNGKey(0), cfg)
    tokens, targets = synthetic_batch(jax.random.PRNGKey(1), cfg, 4, 32)
    lc = moe_gpt_loss(params, tokens, targets, cfg, chunked_ce=True)
    ld = moe_gpt_loss(params, tokens, targets, cfg, chunked_ce=False)
    assert float(lc) == float(ld)


# ---------------------------------------------------------------------------
# the vocab cut inside whole train steps. No factory takes a block size (the
# plan reads shapes alone), so the test shrinks the elements a block may hold
# until the tiny configurations scan several 128-column blocks
# ---------------------------------------------------------------------------
@pytest.fixture
def axes_taken(monkeypatch):
    from byteps_tpu.ops import chunked_ce as ce

    taken = []
    plan = ce._bwd_axis

    def spy(*a):
        taken.append(plan(*a))
        return taken[-1]

    monkeypatch.setattr(ce, "_bwd_axis", spy)
    return taken


@pytest.mark.parametrize("case", ["dp", "dp_untied", "remat", "pp"])
def test_gpt_factory_parity_vocab_major(monkeypatch, axes_taken, case):
    """Under `jax.checkpoint` and in the pipeline factory the VJP is traced
    apart from its forward; the carries' vma must close there too."""
    from byteps_tpu.models.train import (
        make_gpt_pp_train_step, make_gpt_train_step)
    from byteps_tpu.ops import chunked_ce as ce

    # V = 256 against 64 rows a device: two blocks of 128 columns
    monkeypatch.setattr(ce, "_BLOCK_ELEMS", 64 * 128)
    cfg = GPTConfig.tiny()
    if case == "dp_untied":
        cfg = dataclasses.replace(cfg, tied_readout=False)
    if case == "pp":
        _run_two_steps(make_gpt_pp_train_step, dict(pp=2, dp=2), cfg,
                       n_micro=2)
    else:
        _run_two_steps(make_gpt_train_step, dict(dp=2), cfg,
                       remat=case == "remat")
    assert axes_taken and set(axes_taken) == {("vocab", 128)}
