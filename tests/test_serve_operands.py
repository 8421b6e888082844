"""The serve tier's prepared operand tree (serve/paged_cache.py::
serve_operands, PERF.md §6 PR 33): at bf16 compute on an f32 tree the
matmul operands are cast ONCE, at ``Scheduler.__init__``, and not in every
decode step and prefill chunk. The bar is the serve tier's: BIT-equality
with the caller's tree, in the programs' logits and pools and in the tokens
a scheduler emits; plus what is shared by reference, and what the gauges
say."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from byteps_tpu.common.metrics import get_registry
from byteps_tpu.common.tracing import get_tracer
from byteps_tpu.models import GPTConfig, gpt_init
from byteps_tpu.models.generate import make_generate_fn
from byteps_tpu.models.lora import lora_init
from byteps_tpu.serve import AdapterPool, Request, Scheduler
from byteps_tpu.serve.paged_cache import (
    _PROJECTED,
    PagedKVCache,
    make_paged_decode_fn,
    make_paged_prefill_fn,
    serve_operands,
)

_TINY = dict(vocab_size=256, max_seq=64, d_model=64, n_heads=4, n_layers=2,
             d_ff=128, dtype=jnp.bfloat16)
CFGS = {
    # GPT-2: tied readout (lm_head is made from wte.T), biases, learned wpe
    "gpt2": GPTConfig(**_TINY),
    # llama: untied lm_head, w3, no biases, no wpe, GQA
    "llama": GPTConfig.llama(n_kv_heads=2, **_TINY),
}
BS, C = 8, 8
_POOLS = pytest.mark.parametrize("quant", [False, True],
                                 ids=["bf16_pool", "int8_pool"])


@pytest.fixture(scope="module", params=list(CFGS))
def model(request):
    cfg = CFGS[request.param]
    params = gpt_init(jax.random.PRNGKey(0), cfg)
    return cfg, params, serve_operands(params, cfg)


def _pool(cfg, quant):
    return PagedKVCache(cfg, block_size=BS, pool_blocks=4, max_batch=2,
                        quant=quant).state


def _same(a, b):
    a, b = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(a) == len(b) and all(
        x.dtype == y.dtype and bool(jnp.array_equal(x, y))
        for x, y in zip(a, b))


# ---- (a) the two programs: same logits, same pools, bit for bit -------------
@_POOLS
@pytest.mark.parametrize("readout", [True, False],
                         ids=["readout", "no_readout"])
def test_prefill_chunk_same_bits_on_prepared_tree(model, readout, quant):
    cfg, params, operands = model
    chunk = make_paged_prefill_fn(cfg, BS, C, None, readout)
    toks = jnp.arange(3, 3 + C, dtype=jnp.int32)[None]
    table = jnp.asarray([1, 2], jnp.int32)
    want = chunk(params, _pool(cfg, quant), toks, jnp.int32(0), table)
    got = chunk(operands, _pool(cfg, quant), toks, jnp.int32(0), table)
    assert (want[0] is None) == (not readout)
    assert _same(want, got)


@_POOLS
def test_decode_step_same_bits_on_prepared_tree(model, quant):
    cfg, params, operands = model
    chunk = make_paged_prefill_fn(cfg, BS, C, None, False)
    step = make_paged_decode_fn(cfg, BS)
    toks = jnp.arange(3, 3 + C, dtype=jnp.int32)[None]
    table = jnp.asarray([1, 2], jnp.int32)
    # row 0 decodes behind a prefilled chunk, row 1 is a padded slot
    args = (jnp.asarray([7, 0], jnp.int32), jnp.asarray([C, 0], jnp.int32),
            jnp.asarray([[1, 2], [0, 0]], jnp.int32))
    out = []
    for tree in (params, operands):
        _, pool = chunk(tree, _pool(cfg, quant), toks, jnp.int32(0), table)
        out.append(step(tree, pool, *args))
    assert out[0][0].dtype == jnp.float32
    assert _same(out[0], out[1])


# ---- (b) a scheduler on the prepared tree emits the solo run's tokens -------
def _adapter_pool(cfg):
    pool = AdapterPool(cfg, n_slots=3, rank_bucket=4, targets=("wq", "wv"))
    ad = lora_init(jax.random.PRNGKey(10), cfg, 2, ("wq", "wv"))
    for bi, blk in enumerate(ad["blocks"]):
        for t in blk:       # a nonzero b: the adapter changes the tokens
            blk[t]["b"] = 0.02 * jax.random.normal(
                jax.random.PRNGKey(100 + bi), blk[t]["b"].shape)
    pool.register("a0", ad, scale=1.5)
    return pool


@_POOLS
def test_scheduler_tokens_match_solo_on_callers_tree(model, quant):
    """Mixed requests through one replica — one carries a LoRA adapter, the
    pool is too small for all of them so one is preempted and resumed — are
    the tokens of solo ``make_generate_fn`` on the caller's f32 tree (which
    casts per step)."""
    cfg, params, _ = model
    rng = np.random.default_rng(13)
    apool = _adapter_pool(cfg)
    shapes = [(14, 10, None), (14, 10, None), (5, 6, "a0"), (9, 7, None)]
    reqs = [Request(rid=f"r{i}", max_new=m, adapter=a, tenant=f"t{i}",
                    prompt=rng.integers(0, cfg.vocab_size, n).astype(np.int32))
            for i, (n, m, a) in enumerate(shapes)]
    sched = Scheduler(params, cfg, max_batch=3, prefill_chunk=8, block_size=4,
                      pool_blocks=1 + 12, quant_cache=quant,
                      adapter_pool=apool)
    assert sched.params is params and sched._operands is not params
    res = sched.serve(list(reqs))
    assert sum(res[r.rid]["preemptions"] for r in reqs) > 0
    for r in reqs:
        tree = params if r.adapter is None else apool.graft(params, r.adapter)
        solo = make_generate_fn(cfg, r.max_new, quant_cache=quant)(
            tree, jnp.asarray(r.prompt)[None], jax.random.PRNGKey(0), 0.0)
        np.testing.assert_array_equal(res[r.rid]["tokens"],
                                      np.asarray(solo)[0], err_msg=r.rid)
    assert sched.cache.leaked_blocks() == 0 and apool.leaked_slots() == 0


def test_graft_follows_the_base_it_is_given(model):
    """The adapter pool's cached graft is per base: the scheduler's prepared
    operands and a caller's own tree each get their leaves, and both share
    the adapter's thin leaves."""
    cfg, params, operands = model
    apool = _adapter_pool(cfg)
    on_ops = apool.graft(operands, "a0")
    assert apool.graft(operands, "a0") is on_ops
    on_f32 = apool.graft(params, "a0")
    b_ops, b_f32 = on_ops["blocks"][0], on_f32["blocks"][0]
    assert b_ops["wq"] is operands["blocks"][0]["wq"]
    assert b_f32["wq"] is params["blocks"][0]["wq"]
    assert b_ops["lora"] is b_f32["lora"]
    assert "lora" not in operands["blocks"][0]


# ---- (c) what is cast, and what is the caller's leaf ------------------------
def test_only_the_matmul_operands_are_new_leaves(model):
    cfg, params, operands = model
    assert operands["lm_head"].shape == (cfg.d_model, cfg.vocab_size)
    assert operands["lm_head"].dtype == cfg.dtype
    head = params["lm_head"] if "lm_head" in params else params["wte"].T
    assert jnp.array_equal(operands["lm_head"], head.astype(cfg.dtype))
    for k in set(params) - {"blocks", "lm_head"}:       # wte, wpe, lnf_*
        assert operands[k] is params[k], k
    for mine, theirs in zip(operands["blocks"], params["blocks"]):
        assert set(mine) == set(theirs)
        for k, w in theirs.items():
            if k in _PROJECTED:
                assert mine[k].dtype == cfg.dtype and mine[k].shape == w.shape
                assert jnp.array_equal(mine[k], w.astype(cfg.dtype)), k
            else:                                       # norm gains, biases
                assert mine[k] is w, k
    assert all(w.dtype == jnp.float32
               for w in jax.tree_util.tree_leaves(params))


@pytest.mark.parametrize("case", ["f32_compute", "tree_already_bf16",
                                  "prepared_twice"])
def test_nothing_to_cast_returns_the_callers_tree(model, case):
    cfg, params, operands = model
    if case == "f32_compute":
        cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    elif case == "tree_already_bf16":
        params = jax.tree_util.tree_map(
            lambda w: w.astype(jnp.bfloat16), params)
    else:                      # a prepared tree: its operands are in place
        params = operands
    assert serve_operands(params, cfg) is params


def test_a_sharded_leaf_keeps_its_sharding():
    cfg = CFGS["gpt2"]
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    params = gpt_init(jax.random.PRNGKey(0), cfg)
    col, row = NamedSharding(mesh, P(None, "tp")), NamedSharding(mesh, P("tp"))
    blk = dict(params["blocks"][0])
    blk["w1"] = jax.device_put(blk["w1"], col)
    blk["w2"] = jax.device_put(blk["w2"], row)
    params = {**params, "blocks": [blk] + params["blocks"][1:]}
    mine = serve_operands(params, cfg)["blocks"][0]
    assert mine["w1"].sharding.is_equivalent_to(col, 2)
    assert mine["w2"].sharding.is_equivalent_to(row, 2)
    assert mine["w1"].dtype == cfg.dtype


# ---- (d) the span and the gauges read what the tree holds -------------------
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_gauges_read_what_the_tree_holds(model, dtype):
    cfg, params, _ = model
    cfg = dataclasses.replace(cfg, dtype=dtype)
    sched = Scheduler(params, cfg, max_batch=2, block_size=BS, pool_blocks=8)
    theirs = {id(w) for w in jax.tree_util.tree_leaves(params)}
    new = [w for w in jax.tree_util.tree_leaves(sched._operands)
           if id(w) not in theirs]
    per_block = sum(k in params["blocks"][0] for k in _PROJECTED)
    want = (per_block * cfg.n_layers + 1) if dtype == jnp.bfloat16 else 0
    assert len(new) == want
    gauges = get_registry().snapshot("serve.operand")["gauges"]
    assert gauges["serve.operand_leaves_cast"]["value"] == want
    assert gauges["serve.operand_bytes"]["value"] == sum(
        w.size * 2 for w in new)
    assert [s[0] for s in get_tracer().spans()].count(
        "serve.prepare_operands") == 1
