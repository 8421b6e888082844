"""Paged decode attention kernel vs its jnp twin (interpret mode on CPU).

The contract (ops/paged_attention.py): for one query per row at fill
level ``length[r]``, reading the pool IN PLACE through the block table,
the kernel must reproduce what the packed decode step computed before it
— ``_gather_view`` (a dense view, zero at and past the fill level) +
``attention_lse_jnp`` with per-row offsets — on every width bucket, with
mixed lengths in one batch, padded rows, GQA, shared prefix blocks, and
whatever garbage a recycled block holds. Quantised pools keep the twin,
and say so. End to end, a Scheduler under the Pallas backend emits the
tokens it emits under jnp, and ``serve.decode_steps_paged_attn`` counts
its decode steps in the first and none in the second.
"""

import dataclasses
import logging

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from byteps_tpu.common.metrics import get_registry
from byteps_tpu.models import GPTConfig, gpt_init
from byteps_tpu.ops import backend
from byteps_tpu.ops.flash_attention import attention_lse_jnp
from byteps_tpu.ops.paged_attention import (
    paged_attention_decode,
    unsupported_reason,
)
from byteps_tpu.serve import Request, Scheduler
from byteps_tpu.serve.paged_cache import (
    _gather_view,
    decode_uses_paged_attn,
    make_paged_decode_fn,
    make_paged_prefill_fn,
)

BS = 16          # block size (a whole bf16 tile of rows)
LAYER = 1        # of the pool's two: the kernel picks it, not the caller


def _pool(rng, NB, Hkv, D, dtype):
    """K and V pools (2, NB, BS, Hkv*D) of unit normals."""
    shape = (2, NB, BS, Hkv * D)
    return (jnp.asarray(rng.standard_normal(shape), dtype),
            jnp.asarray(rng.standard_normal(shape), dtype))


def _tables(rng, lens, W, NB):
    """Each row's live blocks drawn without replacement from 1..NB-1,
    the dead tail on scratch block 0 (as ``table_row`` builds them)."""
    tables = np.zeros((len(lens), W), np.int32)
    free = list(rng.permutation(np.arange(1, NB)))
    for r, n in enumerate(lens):
        for b in range(-(-n // BS)):
            tables[r, b] = free.pop()
    return tables


def _twin(q, k, v, tables, lens, D):
    lens = jnp.asarray(lens, jnp.int32)
    kk = _gather_view(k[LAYER], None, jnp.asarray(tables), lens, q.dtype, D)
    vv = _gather_view(v[LAYER], None, jnp.asarray(tables), lens, q.dtype, D)
    o, _ = attention_lse_jnp(q[:, None], kk, vv, lens - 1, 0, causal=True)
    return np.asarray(o[:, 0], np.float32)


def _kernel(q, k, v, tables, lens):
    o = paged_attention_decode(q, k, v, jnp.asarray(tables),
                               jnp.asarray(lens, jnp.int32), LAYER)
    assert o.dtype == q.dtype and o.shape == q.shape
    return np.asarray(o, np.float32)


def _check(lens, W, H=4, Hkv=4, D=32, dtype=jnp.float32, seed=0,
           tables=None):
    rng = np.random.default_rng(seed)
    NB = 1 + sum(-(-n // BS) for n in lens) + 3
    k, v = _pool(rng, NB, Hkv, D, dtype)
    q = jnp.asarray(rng.standard_normal((len(lens), H, D)), dtype)
    if tables is None:
        tables = _tables(rng, lens, W, NB)
    got = _kernel(q, k, v, tables, lens)
    want = _twin(q, k, v, tables, lens, D)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    return q, k, v, tables


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_mixed_lengths_in_one_batch(dtype):
    """1, 15, 16, 17 keys and a full table side by side: the block
    boundary from both sides, and rows that end chunks apart."""
    _check([1, 15, 16, 17, 4 * BS], W=4, dtype=jnp.dtype(dtype).type)


def test_padded_rows_on_the_scratch_block():
    """The scheduler pads the batch to max_batch rows at pos 0 with an
    all-scratch table: they cost one block and disturb nobody."""
    lens = [40, 1, 1, 23, 1]
    rng = np.random.default_rng(3)
    tables = _tables(rng, lens, 4, 16)
    tables[[1, 2, 4]] = 0
    _check(lens, W=4, tables=tables, seed=3)


@pytest.mark.parametrize("W", [1, 2, 4, 8, 16, 32, 64])
def test_every_width_bucket(W):
    """The step is traced once per power-of-two table width; a row may
    fill its table or one block of it. W=16 and up hold more than one
    128-key chunk, so the double buffer turns over inside a row."""
    full = W * BS
    _check([full, 1, max(1, full - 5), min(full, BS + 1)], W=W, seed=W)


@pytest.mark.parametrize("g", [2, 4])
def test_gqa_rides_the_kv_heads_block(g):
    _check([5, 33, 64, 1], W=4, H=4 * g, Hkv=4, D=32, seed=g)


def test_rows_sharing_prefix_blocks():
    """Two requests whose tables alias the same physical blocks (the
    radix prefix cache): read-only sharing, each with its own tail."""
    lens = [3 * BS + 4, 3 * BS + 9, 2 * BS]
    rng = np.random.default_rng(5)
    tables = _tables(rng, lens, 4, 16)
    tables[1, :3] = tables[0, :3]
    tables[2, :2] = tables[0, :2]
    _check(lens, W=4, tables=tables, seed=5)


@pytest.mark.parametrize("poison", [np.nan, np.inf])
def test_poison_past_the_fill_level_never_reaches_the_output(poison):
    """``_gather_view`` zeroed what lies at or past the fill level; the
    kernel masks. NaN or inf in a partly filled block's tail, in every
    unreferenced block and in the scratch block must leave the output
    finite and what it was."""
    lens = [1, 15, 17, 2 * BS, 150]
    q, k, v, tables = _check(lens, W=16, seed=7)
    clean = _kernel(q, k, v, tables, lens)
    live = np.zeros(k.shape[1:3], bool)                  # (NB, BS)
    for r, n in enumerate(lens):
        for p in range(n):
            live[tables[r, p // BS], p % BS] = True
    bad = jnp.asarray(~live)[None, :, :, None]
    kp = jnp.where(bad, poison, k)
    vp = jnp.where(bad, -poison, v)
    got = _kernel(q, kp, vp, tables, lens)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, clean)


def test_layer_is_picked_inside_the_kernel():
    """The whole pool is the operand; the other layer's contents must
    not matter."""
    lens = [20, 7]
    q, k, v, tables = _check(lens, W=2, seed=11)
    got = _kernel(q, k.at[0].set(99.0), v.at[0].set(-99.0), tables, lens)
    np.testing.assert_array_equal(got, _kernel(q, k, v, tables, lens))


def test_quantised_pool_takes_its_announced_fallback(monkeypatch, caplog):
    """int8 pools keep the gathered view: the dispatcher says so once
    through note_fallback, and the kernel refuses them if called."""
    monkeypatch.setenv("BYTEPS_KERNEL_BACKEND", "pallas")
    backend.note_fallback.cache_clear()
    cfg = dataclasses.replace(GPTConfig.tiny(), d_model=128)
    assert decode_uses_paged_attn(cfg, 8, cfg.kv_heads, quant=False)
    logger = logging.getLogger("byteps_tpu.ops")   # its root does not
    logger.addHandler(caplog.handler)              # propagate to pytest's
    try:
        assert not decode_uses_paged_attn(cfg, 8, cfg.kv_heads, quant=True)
        assert not decode_uses_paged_attn(cfg, 8, cfg.kv_heads, quant=True)
    finally:
        logger.removeHandler(caplog.handler)
    said = [r.getMessage() for r in caplog.records
            if "paged_attn_decode" in r.getMessage()]
    assert len(said) == 1 and "jnp twin" in said[0]
    rng = np.random.default_rng(0)
    k8 = jnp.zeros((2, 4, 32, 128), jnp.int8)
    q = jnp.asarray(rng.standard_normal((1, 4, 32)), jnp.float32)
    with pytest.raises(ValueError, match="int8"):
        paged_attention_decode(q, k8, k8, jnp.zeros((1, 2), jnp.int32),
                               jnp.ones((1,), jnp.int32), 0)


def test_shapes_the_kernel_does_not_take(monkeypatch):
    """A block is DMA'd as it lies, so it must be whole tiles: rows a
    multiple of the dtype's sublane count, a row a multiple of 128
    lanes. Off Pallas the question is never asked."""
    assert unsupported_reason(16, 20, 64, jnp.bfloat16) is None
    assert unsupported_reason(8, 4, 32, jnp.float32) is None
    assert "block_size" in unsupported_reason(8, 20, 64, jnp.bfloat16)
    assert "block_size" in unsupported_reason(4, 4, 32, jnp.float32)
    assert "128" in unsupported_reason(16, 4, 16, jnp.float32)
    assert "int8" in unsupported_reason(32, 20, 64, jnp.int8)
    cfg = dataclasses.replace(GPTConfig.tiny(), d_model=128)
    monkeypatch.setenv("BYTEPS_KERNEL_BACKEND", "jnp")
    assert not decode_uses_paged_attn(cfg, 8, cfg.kv_heads, quant=False)


def test_scheduler_tokens_and_counter_across_backends(monkeypatch):
    """End to end: the same requests through a small Scheduler under
    BYTEPS_KERNEL_BACKEND=pallas (the kernel, interpreted) and under jnp
    (the twin) give the same greedy tokens; the counter counts every
    decode step of the first and none of the second."""
    cfg = dataclasses.replace(GPTConfig.tiny(), d_model=128)
    params = gpt_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 11, 17)]
    reg = get_registry()
    steps = reg.histogram("serve.batch_occupancy")
    paged = reg.counter("serve.decode_steps_paged_attn")

    def serve(kernel_backend):
        monkeypatch.setenv("BYTEPS_KERNEL_BACKEND", kernel_backend)
        # the factories trace once per process: a backend switch inside
        # one is a test's doing, so the test drops their programs
        make_paged_decode_fn.cache_clear()
        make_paged_prefill_fn.cache_clear()
        s0, p0 = steps.count(), paged.value()
        sched = Scheduler(params, cfg, max_batch=4, block_size=8,
                          pool_blocks=33, prefill_chunk=8)
        out = sched.serve([Request(rid=f"r{i}", prompt=p, max_new=6 + i)
                           for i, p in enumerate(prompts)])
        assert sched.cache.leaked_blocks() == 0
        return ({r: np.asarray(o["tokens"]) for r, o in out.items()},
                steps.count() - s0, paged.value() - p0)

    try:
        got, n_steps, n_paged = serve("pallas")
        assert n_steps > 0 and n_paged == n_steps
        want, n_steps, n_paged = serve("jnp")
        assert n_steps > 0 and n_paged == 0
    finally:
        make_paged_decode_fn.cache_clear()
        make_paged_prefill_fn.cache_clear()
    assert got.keys() == want.keys()
    for rid in want:
        np.testing.assert_array_equal(got[rid], want[rid])
