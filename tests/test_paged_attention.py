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
from byteps_tpu.ops import backend, paged_attention
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


def _pool(rng, NB, Hkv, D, dtype, bs=BS):
    """K and V pools (2, NB, bs, Hkv*D) of unit normals."""
    shape = (2, NB, bs, Hkv * D)
    return (jnp.asarray(rng.standard_normal(shape), dtype),
            jnp.asarray(rng.standard_normal(shape), dtype))


def _tables(rng, lens, W, NB, bs=BS):
    """Each row's live blocks drawn without replacement from 1..NB-1,
    the dead tail on scratch block 0 (as ``table_row`` builds them)."""
    tables = np.zeros((len(lens), W), np.int32)
    free = list(rng.permutation(np.arange(1, NB)))
    for r, n in enumerate(lens):
        for b in range(-(-n // bs)):
            tables[r, b] = free.pop()
    return tables


def _twin(q, k, v, tables, lens, D):
    lens = jnp.asarray(lens, jnp.int32)
    kk = _gather_view(k[LAYER], None, jnp.asarray(tables), lens, q.dtype, D)
    vv = _gather_view(v[LAYER], None, jnp.asarray(tables), lens, q.dtype, D)
    o, _ = attention_lse_jnp(q[:, None], kk, vv, lens - 1, 0, causal=True)
    return np.asarray(o[:, 0], np.float32)


def _kernel(q, k, v, tables, lens, first=None):
    o = paged_attention_decode(
        q, k, v, jnp.asarray(tables), jnp.asarray(lens, jnp.int32), LAYER,
        first=None if first is None else jnp.asarray(first))
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
    fill its table or one block of it. W=64 holds more than one 512-key
    chunk, so the double buffer turns over inside a row."""
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


# ---- the two layouts of a chunk's products, chunks of several pages ---------
# the cells' shapes in small, (H, Hkv, D, nq): Qwen3-Next's, Mellum2's,
# Falcon-H1's (5 query heads a k/v head, padded to 8 rows), SDAR's block of 4
# queries a row; and the tier-1 shape, whose heads are narrower than a lane
# tile and keep the block-diagonal layout
SHAPES = [(16, 2, 256, 1), (32, 4, 128, 1), (20, 4, 128, 1), (32, 4, 128, 4),
          (4, 4, 32, 1)]
CHUNK = paged_attention._CHUNK_TOKENS      # keys a chunk, a table wide enough


def _dense(q, k, v, tables, lens, first=None):
    """Plain f32 softmax over each row's live keys ``[first, length)``,
    gathered through its table; q ``(R, [nq,] H, D)``."""
    R, D = q.shape[0], q.shape[-1]
    Hkv = k.shape[-1] // D
    qg = np.asarray(q, np.float32).reshape(R, -1, Hkv, q.shape[-2] // Hkv, D)
    kk, vv = (np.asarray(a[LAYER], np.float32)[tables].reshape(
        R, -1, Hkv, D) for a in (k, v))
    at = np.arange(kk.shape[1])[None]
    live = at < np.asarray(lens)[:, None]
    if first is not None:
        live &= at >= np.asarray(first)[:, None]
    s = np.einsum("rnhgd,rthd->rnhgt", qg, kk) / np.sqrt(D)
    s = np.where(live[:, None, None, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    o = np.einsum("rnhgt,rthd->rnhgd", p,
                  np.where(live[..., None, None], vv, 0.0))
    return o.reshape(q.shape)


def _case(shape, lens, W, bs=BS, seed=0, window=None, dtype=jnp.float32):
    """q, pools, tables and ``first`` of one batch at a cell's shape."""
    H, Hkv, D, nq = shape
    rng = np.random.default_rng(seed)
    NB = 1 + sum(-(-n // bs) for n in lens) + 3
    k, v = _pool(rng, NB, Hkv, D, dtype, bs)
    q = jnp.asarray(rng.standard_normal(
        (len(lens),) + ((nq,) if nq > 1 else ()) + (H, D)), dtype)
    tables = _tables(rng, lens, W, NB, bs)
    first = None if window is None else np.maximum(
        np.asarray(lens) - window, 0).astype(np.int32)
    return q, k, v, tables, first


# where a row can end, in keys: inside the first page of a chunk (the first
# chunk's and the second's), on a page boundary, on a chunk boundary (one
# chunk and two), and one key past one
ENDS = [5, CHUNK + 5, CHUNK + 3 * BS, CHUNK, 2 * CHUNK, CHUNK + 1]


@pytest.mark.parametrize("windowed", [False, True],
                         ids=["from_key_0", "first"])
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "h%d_kv%d_d%d_q%d" % s)
def test_cells_shapes_equal_the_twin_wherever_a_row_ends(shape, windowed):
    """Every cell's shape in small, in the layout its head size gives it,
    with chunks of ``CHUNK`` keys (32 pages here): rows that end inside a
    chunk's first page, on a page boundary, on a chunk boundary; with a
    window, rows whose first key lies in the same chunk as the last, a chunk
    before it, and on a chunk boundary."""
    W = 2 * CHUNK // BS
    q, k, v, tables, first = _case(shape, ENDS, W, seed=shape[0],
                                   window=CHUNK - 40 if windowed else None)
    got = _kernel(q, k, v, tables, ENDS, first)
    np.testing.assert_allclose(got, _dense(q, k, v, tables, ENDS, first),
                               rtol=2e-5, atol=2e-5)
    if not windowed and shape[3] == 1:       # and the step's own twin
        np.testing.assert_allclose(got, _twin(q, k, v, tables, ENDS,
                                              shape[2]), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("windowed", [False, True],
                         ids=["from_key_0", "first"])
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "h%d_kv%d_d%d_q%d" % s)
def test_cells_shapes_with_a_table_narrower_than_one_chunk(shape, windowed):
    """A table of 8 pages holds 128 keys: the chunk is the table, never
    wider. And the cells' own pages, 128 rows: a chunk is 4 of them, a
    table of 2 is a chunk of 2."""
    lens = [1, BS, 5 * BS + 3, 8 * BS]
    q, k, v, tables, first = _case(shape, lens, 8, seed=shape[1],
                                   window=40 if windowed else None)
    np.testing.assert_allclose(_kernel(q, k, v, tables, lens, first),
                               _dense(q, k, v, tables, lens, first),
                               rtol=2e-5, atol=2e-5)
    lens = [130, 256, 1, 77]
    q, k, v, tables, first = _case(shape, lens, 2, bs=128, seed=shape[2],
                                   window=100 if windowed else None)
    np.testing.assert_allclose(_kernel(q, k, v, tables, lens, first),
                               _dense(q, k, v, tables, lens, first),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shape", SHAPES[1:4],
                         ids=lambda s: "h%d_kv%d_d%d_q%d" % s)
def test_the_two_forms_agree_on_a_shape_both_take(monkeypatch, shape):
    """Heads of 128 take either layout; the rule gives them the grouped
    one. Held to the diagonal one here: the same softmax over the same
    keys, the products laid out differently."""
    lens = [CHUNK + 37, 3, 2 * CHUNK, CHUNK - BS]
    q, k, v, tables, first = _case(shape, lens, 2 * CHUNK // BS, seed=9,
                                   window=CHUNK)
    reg = get_registry()
    plans = [reg.counter(f"paged_attn.plan.{form}.{CHUNK}")
             for form in ("grouped", "diagonal")]
    before = [c.value() for c in plans]
    grouped = [_kernel(q, k, v, tables, lens, f) for f in (None, first)]
    monkeypatch.setattr(paged_attention, "_form", lambda head_dim: "diagonal")
    diagonal = [_kernel(q, k, v, tables, lens, f) for f in (None, first)]
    assert [c.value() - b for c, b in zip(plans, before)] == [2, 2]
    for g, d in zip(grouped, diagonal):
        np.testing.assert_allclose(g, d, rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("windowed", [False, True],
                         ids=["from_key_0", "first"])
@pytest.mark.parametrize("poison", [np.nan, np.inf])
@pytest.mark.parametrize("shape", [SHAPES[3], SHAPES[2], SHAPES[4]],
                         ids=["grouped_q4", "grouped_padded", "diagonal"])
def test_poison_inside_a_wide_chunk_never_reaches_the_output(
        shape, poison, windowed):
    """Chunks of 32 pages: NaN or inf in every row of the pool that is not
    a live key — the tail of a partly filled page, the rows of a window
    layer's first page that lie below its first key, every block no table
    names — and every dead entry of a table pointed at a poisoned block of
    its own, INSIDE a chunk whose other pages are fetched (and, under a
    window, before the first page that is). The output stays finite and bit
    for bit what it was: a dead page is never fetched, and the one tile a
    row ends in is cleaned in the buffer."""
    lens = [1, 15, 17, CHUNK + 3, 2 * CHUNK - 2 * BS - 1, CHUNK // 2]
    W = 2 * CHUNK // BS
    q, k, v, tables, first = _case(shape, lens, W, seed=13,
                                   window=3 * BS + 5 if windowed else None)
    clean = _kernel(q, k, v, tables, lens, first)
    np.testing.assert_allclose(clean, _dense(q, k, v, tables, lens, first),
                               rtol=2e-5, atol=2e-5)
    NB = k.shape[1]
    live = np.zeros((NB, BS), bool)
    for r, n in enumerate(lens):
        for p in range(0 if first is None else int(first[r]), n):
            live[tables[r, p // BS], p % BS] = True
    dead_blocks = [b for b in range(NB) if not live[b].any()]
    tables = tables.copy()
    for r, n in enumerate(lens):                 # dead entries: poisoned blocks
        lo = 0 if first is None else int(first[r]) // BS
        for b in range(W):
            if not lo <= b < -(-n // BS):
                tables[r, b] = dead_blocks[(r + b) % len(dead_blocks)]
    bad = jnp.asarray(~live)[None, :, :, None]
    got = _kernel(q, jnp.where(bad, poison, k), jnp.where(bad, -poison, v),
               tables, lens, first)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, clean)


def test_the_plan_follows_from_the_shapes_and_is_counted_once_a_trace():
    """``paged_attn.plan.<form>.<keys a chunk>`` counts a layer's trace, not
    its calls: ``grouped`` at heads of 128 and 256, ``diagonal`` at 32 and
    64; the keys of a chunk are whole pages, never more than the table
    holds, and four buffers of them fit their budget — where one page
    does not, ``unsupported_reason`` refuses the pool."""
    reg = get_registry()

    def plans():
        return reg.snapshot("paged_attn.plan.")["counters"]

    for (H, Hkv, D, nq), form, W, keys in [
            ((16, 2, 256, 1), "grouped", 64, CHUNK),
            ((32, 4, 128, 4), "grouped", 4, 4 * BS),
            ((4, 4, 32, 1), "diagonal", 64, CHUNK),
            ((4, 2, 64, 1), "diagonal", 16, 16 * BS)]:
        lens = [W * BS, 3]
        q, k, v, tables, _ = _case((H, Hkv, D, nq), lens, W, seed=D)
        step = jax.jit(lambda q, k, v, t, n: paged_attention_decode(
            q, k, v, t, n, LAYER))
        before = plans()
        args = (q, k, v, jnp.asarray(tables), jnp.asarray(lens, jnp.int32))
        step(*args)
        np.testing.assert_allclose(
            np.asarray(step(*args)), _dense(q, k, v, tables, lens),
            rtol=2e-5, atol=2e-5)
        after = plans()
        moved = {n: c - before.get(n, 0) for n, c in after.items()
                 if c != before.get(n, 0)}
        assert moved == {f"paged_attn.plan.{form}.{keys}": 1}, moved
    budget = paged_attention._BUFFER_BUDGET
    for bs in (8, 16, 48, 128, 256, 1024):
        for W in (1, 2, 3, 7, 64, 1024):
            for row_bytes in (256, 1024, 2560, 16384):
                ppb = paged_attention._pages_per_chunk(W, bs, row_bytes)
                if ppb == 0:         # not even one page: the pool is refused
                    assert 4 * bs * row_bytes > budget
                    assert "fit" in unsupported_reason(
                        bs, row_bytes // 256, 128, jnp.bfloat16)
                    continue
                assert 1 <= ppb <= W
                assert 4 * ppb * bs * row_bytes <= budget
                assert ppb * bs <= max(CHUNK, bs)
                # from one lane tile of scores up, whole tiles
                assert ppb * bs < 128 or (ppb * bs) % (128 if 128 % bs == 0
                                                       else bs) == 0
    assert "fit" in unsupported_reason(2048, 8, 128, jnp.bfloat16)
    assert unsupported_reason(128, 4, 128, jnp.bfloat16) is None


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
