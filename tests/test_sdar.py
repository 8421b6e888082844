"""SDAR at tiny sizes, every mechanism live: blocks of 4 positions under a
block-causal mask, 2 kv heads under 4 query heads, norms on q and k, 8
experts top-2 all held, requests that ask 1, 2 or 4 denoising passes a block.
The dense model, the serve tier's two programs told the block, the two
kernels taught it and the scheduler's block state, each held to the plain
reference (``benchmark/configs/sdar_reference.py``) or to its jnp twin.

No expert is cut (``n_experts`` held = routed, the whole vocabulary), so the
model-configs guide's "the shares add up to the uncut layer" test has nothing
to add up here: the one share IS the layer, and the forward test below holds
it to the reference."""

import ast
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.configs import sdar_reference as ref
from byteps_tpu.common.metrics import get_registry
from byteps_tpu.models.sdar import (
    SDARConfig,
    fix_positions,
    param_count,
    sdar_apply,
    sdar_init,
)
from byteps_tpu.ops.flash_attention import flash_attention_block_causal
from byteps_tpu.ops.paged_attention import paged_attention_decode
from byteps_tpu.parallel.moe import softmax_topk_route
from byteps_tpu.serve import Request, Scheduler, SpecPolicy
from byteps_tpu.serve.families import BlockDiffusionFamily, serve_family
from byteps_tpu.serve.paged_cache import (
    PagedKVCache,
    make_paged_decode_fn,
    make_paged_prefill_fn,
)

CFG = SDARConfig.tiny()
B = CFG.block_length
BS, CHUNK = 8, 8
S_REF = 48      # every reference forward runs at this length: one compile
#: (prompt length, max_new, denoising passes): prompts with and without a
#: remainder mod B, one shorter than a block, ``max_new`` that ends mid-block,
#: every tier in one batch, several chunks
SHAPES = [(10, 7, 2), (16, 8, 4), (3, 5, 1), (21, 9, 4), (8, 4, 2),
          (13, 6, 1)]


def _hp(cfg=CFG, **over):
    hp = {k: v for k, v in dataclasses.asdict(cfg).items() if k != "dtype"}
    return dict(hp, **over)


HP = _hp()


@pytest.fixture(scope="module")
def params():
    return sdar_init(jax.random.PRNGKey(0), CFG)


def _padded(tokens, n=S_REF):
    """Under a block-causal mask a block does not see the blocks after it."""
    out = np.zeros(n, np.int32)
    out[:len(tokens)] = tokens
    return jnp.asarray(out)


def _requests(seed=0, shapes=SHAPES, eos=None):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, max_new=m, denoise_steps=t,
                    eos_id=(eos or {}).get(i),
                    prompt=rng.integers(0, CFG.mask_id, n).astype(np.int32))
            for i, (n, m, t) in enumerate(shapes)]


def _sched(params, **kw):
    kw = dict(dict(max_batch=3, block_size=BS, pool_blocks=40,
                   prefill_chunk=CHUNK), **kw)
    return Scheduler(params, CFG, **kw)


@pytest.fixture(scope="module")
def generated(params):
    """What the reference generates for SHAPES, with no ``eos_id``."""
    return [ref.generate(params, r.prompt, r.max_new, HP, r.denoise_steps,
                         pad_to=S_REF) for r in _requests()]


@pytest.fixture(scope="module")
def eos(generated):
    """For requests 1 and 3 a token the reference generates INSIDE a block
    (not as a block's last): the request ends there."""
    out = {}
    for i in (1, 3):
        toks = generated[i][0]
        given = SHAPES[i][0] % B
        inside = [j for j, t in enumerate(toks)
                  if (given + j) % B != B - 1 and j + 1 < len(toks)
                  and t not in toks[:j]]
        assert inside, toks
        out[i] = int(toks[inside[-1]])
    return out


@pytest.fixture(scope="module")
def served(params, eos):
    """SHAPES through one Scheduler, three rows a step in mixed phases, chunks
    of 8; request 0 is preempted once, by force, in the middle of its second
    block (two passes issued, one block committed)."""
    reg = get_registry()
    before = {k: reg.counter(k).value() for k in (
        "serve.decode_steps_overlapped", "serve.pipeline_drains.idle",
        "serve.preempted", "serve.block.row_passes",
        "serve.block.commit_row_passes", "serve.block.commits",
        "serve.block.positions_fixed", "serve.kv.block_rows_rewritten")}
    sched = _sched(params)
    reqs = _requests(eos=eos)
    for r in reqs:
        sched.submit(r)
    forced = False
    while not sched.finished:
        sched.step()
        run = sched._runs.get(0)
        if (not forced and run is not None and run.blk is not None
                and run.blk.issued == 2 and run.emitted):
            sched._drain_in_flight("preempt")
            sched._preempt(run)
            forced = True
    assert forced
    results = sched.results
    sched.flush_stats()
    moved = {k: reg.counter(k).value() - v for k, v in before.items()}
    return reqs, results, sched, moved


def test_reference_imports_nothing_from_the_program():
    tree = ast.parse(open(ref.__file__).read())
    names = [n.module or "" for n in ast.walk(tree)
             if isinstance(n, ast.ImportFrom)]
    names += [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
              for a in n.names]
    assert names and not [n for n in names if n.startswith("byteps_tpu")]


def test_model_forward_equals_the_reference(params):
    """Logits of the dense forward, f32 against f32, and the router's picks
    on the reference's own router input, layer by layer."""
    toks = np.random.default_rng(1).integers(0, CFG.mask_id, S_REF)
    got = sdar_apply(params, jnp.asarray(toks)[None], CFG)[0]
    want, layers = ref.forward(params, jnp.asarray(toks), HP, qb=16)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    for p, layer in zip(params["blocks"], layers):
        idx, w = softmax_topk_route(layer["router_input"], p["moe"]["wg"],
                                    k=CFG.top_k)
        np.testing.assert_array_equal(np.sort(idx, -1),
                                      np.sort(layer["router_picks"], -1))
        np.testing.assert_allclose(w.sum(-1), 1.0, atol=1e-6)


def test_a_causal_forward_is_told_apart(params):
    """The mask is the mechanism: a reference that masks causally moves the
    logits by far more than the served path may differ by (1e-4)."""
    toks = jnp.asarray(np.random.default_rng(2).integers(0, CFG.mask_id,
                                                         S_REF))
    want, _ = ref.forward(params, toks, HP)
    off, _ = ref.forward(params, toks, _hp(causal=True))
    assert float(jnp.abs(off - want).max()) > 20 * 1e-4


def test_later_blocks_do_not_move_earlier_rows(params):
    """What lets a forward be given its prefix's rows: the k, v and logits of
    whole earlier blocks are the same whatever follows them."""
    toks = np.random.default_rng(3).integers(0, CFG.mask_id, S_REF)
    whole, layers = ref.forward(params, jnp.asarray(toks), HP)
    tail, _ = ref.forward(
        params, jnp.asarray(toks[32:]), HP, start=32,
        prefix=[(layer["k"][:32], layer["v"][:32]) for layer in layers])
    np.testing.assert_allclose(tail, whole[32:], atol=1e-5, rtol=1e-5)


def test_the_parameter_count_of_the_cell_is_the_issues():
    """Six published layers and the head: 6 x 623,120,640 + 622,331,904."""
    assert param_count(SDARConfig(n_layers=6)) == 4_361_055_744


@pytest.mark.parametrize("n_fix", [0, 1, 2, 4])
def test_the_devices_pick_is_the_references_rule(n_fix):
    """``fix_positions`` against the reference's numpy ``fix``: rows with open
    positions in different places, a tie (the earlier position wins), the
    mask token never picked though its logit is the largest."""
    rng = np.random.default_rng(n_fix)
    mask = CFG.mask_id
    logits = rng.normal(size=(5, B, CFG.vocab_size)).astype(np.float32)
    logits[..., mask] = 9.0
    logits[1, 2] = logits[1, 0]                       # a tie in confidence
    toks = rng.integers(0, mask, (5, B)).astype(np.int32)
    toks[0, :], toks[1, [0, 2, 3]], toks[2, 1:], toks[3, 3] = \
        mask, mask, mask, mask                        # row 4: none open
    at = np.where(toks == mask, 0, 7).astype(np.int32)
    got_t, got_at = fix_positions(
        jnp.asarray(logits), jnp.asarray(toks), jnp.asarray(at),
        jnp.full(5, n_fix, jnp.int32), jnp.full(5, 3, jnp.int32), mask)
    for r in range(5):
        want, fixed, _ = ref.fix(logits[r], toks[r], n_fix, mask)
        np.testing.assert_array_equal(got_t[r], want)
        want_at = at[r].copy()
        want_at[fixed] = 3
        np.testing.assert_array_equal(got_at[r], want_at)
        assert mask not in want[fixed]


# -- the scheduler's block state ---------------------------------------------
@pytest.mark.parametrize("i", range(len(SHAPES)))
def test_scheduler_serves_what_the_reference_generates(params, served, eos,
                                                       i):
    """Tokens and the pass each was fixed at, for every request of the mixed
    batch: three tiers, remainders mod B, a prompt shorter than a block,
    ``max_new`` inside a block, ``eos_id`` inside a block (requests 1 and 3),
    and the run preempted inside a block (0: its block is denoised again from
    the recomputed prefix, to the same tokens at the same passes)."""
    reqs, results, _, _ = served
    r = reqs[i]
    toks, at, _ = ref.generate(params, r.prompt, r.max_new, HP,
                               r.denoise_steps, eos_id=eos.get(i),
                               pad_to=S_REF)
    np.testing.assert_array_equal(results[i]["emitted"], toks)
    np.testing.assert_array_equal(results[i]["fixed_at"], at)
    if i in eos:
        assert toks[-1] == eos[i] and len(toks) < r.max_new
        assert (SHAPES[i][0] + len(toks)) % B != 0      # inside a block
    else:
        assert len(toks) == r.max_new


def test_a_run_was_preempted_and_nothing_leaked(served):
    _, results, sched, moved = served
    assert moved["serve.preempted"] == 1
    assert results[0]["preemptions"] == 1
    assert sched.cache.leaked_blocks() == 0 and sched.cache.blocks_in_use == 0


def test_the_pipeline_stays_on_across_block_boundaries(params):
    """A step is issued behind the unread one at every phase of a block: with
    room for everything, the only unread step read with nothing behind it is
    the last (every request's last commit)."""
    reg = get_registry()
    names = ("serve.decode_steps_overlapped", "serve.pipeline_drains.idle",
             "serve.pipeline_drains")
    before = {k: reg.counter(k).value() for k in names}
    steps0 = reg.histogram("serve.batch_occupancy").count()
    sched = _sched(params)
    sched.serve(_requests(shapes=[(16, 16, 4), (12, 16, 2), (9, 16, 1)]))
    moved = {k: reg.counter(k).value() - before[k] for k in names}
    steps = reg.histogram("serve.batch_occupancy").count() - steps0
    # 4 blocks of 5 passes, the longest of the three requests
    assert steps == 20
    assert moved["serve.decode_steps_overlapped"] == steps - 1
    assert moved["serve.pipeline_drains"] == 1
    assert moved["serve.pipeline_drains.idle"] == 1


def test_the_block_counters_follow_the_schedule(served):
    """What the host counts without reading a token: row-passes by the static
    schedule. Every commit read is a commit issued (no row was dropped but
    behind an ``eos_id``), a denoising row-pass rewrites B rows a layer."""
    _, results, _, moved = served
    rows, commits = moved["serve.block.row_passes"], \
        moved["serve.block.commit_row_passes"]
    assert rows > commits > 0
    assert moved["serve.block.commits"] <= commits
    assert moved["serve.kv.block_rows_rewritten"] == \
        (rows - commits) * B * CFG.n_layers
    # without the preempted run's repeats: every generated token was a
    # position some pass fixed
    assert moved["serve.block.positions_fixed"] >= sum(
        len(r["emitted"]) for r in results.values())


def test_one_latency_observation_a_token_and_a_gap_a_block(params):
    reg = get_registry()
    hists = {k: reg.histogram(k) for k in (
        "serve.ttft_ms", "serve.token_ms", "serve.block_ms",
        "serve.block.passes")}
    before = {k: h.count() for k, h in hists.items()}
    sched = _sched(params)
    out = sched.serve(_requests(shapes=[(10, 10, 4), (8, 8, 2)]))
    moved = {k: h.count() - before[k] for k, h in hists.items()}
    assert moved["serve.ttft_ms"] == 2
    assert moved["serve.token_ms"] == 10 + 8 - 2
    # blocks committed: 2 + 4 + 4 tokens and 4 + 4; a gap each but the first
    assert moved["serve.block.passes"] == 5
    assert moved["serve.block_ms"] == 3
    assert [len(out[i]["token_s"]) for i in (0, 1)] == [10, 8]


# -- the two programs told the block -----------------------------------------
def _programs(params, prompt, blocks, rows=2):
    """The family's two programs, driven by hand: ``prompt`` (whole blocks)
    in chunks, then each of ``blocks`` fed three times — the first and second
    time with other tokens in it (denoising passes whose rows the next pass
    must overwrite), then as given. ``rows`` requests hold the same tokens.
    Returns the last pass's logits ``(len(blocks), B, V)`` and the cache."""
    fam = serve_family(CFG)
    plan = fam.plan(CFG)
    cache = PagedKVCache(
        CFG, block_size=BS, pool_blocks=40, max_batch=rows,
        layout=lambda bs_, nb_: fam.layout(
            params, CFG, block_size=bs_, pool_blocks=nb_, max_batch=rows,
            prefill_chunk=CHUNK, quant=False))
    step = make_paged_decode_fn(CFG, BS, plan=plan)
    n = len(prompt)
    for rid in range(rows):
        cache.register(rid)
        cache.ensure(rid, n + B * len(blocks))
        for lo in range(0, n, CHUNK):
            C = min(CHUNK, n - lo)
            chunk = make_paged_prefill_fn(CFG, BS, C, with_readout=False,
                                          plan=plan)
            _, cache.state = chunk(params, cache.state,
                                   prompt[None, lo:lo + C], np.int32(lo),
                                   cache.table_row(rid, 8))
    tables = np.stack([cache.table_row(rid, 8) for rid in range(rows)])
    out, pos = [], n
    for blk in blocks:
        for noise in (17, 5, 0):
            fed = np.where(np.arange(B) % 2 == 0, (blk + noise) % CFG.mask_id,
                           blk if noise == 0 else CFG.mask_id)
            logits, cache.state = step(
                params, cache.state, np.tile(fed.astype(np.int32), (rows, 1)),
                np.full(rows, pos, np.int32), tables)
        np.testing.assert_array_equal(logits[0], logits[rows - 1])
        out.append(np.asarray(logits[0]))
        pos += B
    return np.stack(out), cache


def test_programs_logits_and_rows_equal_the_reference(params):
    """The logits themselves, f32 against f32 (1e-4), of three blocks decoded
    behind a 20-token prompt (three chunks, the last half a page), and the k
    and v rows the pool holds of every committed position of every layer —
    after denoising passes wrote other rows at the same places. A causal
    reference and a reference fed a denoising pass's tokens are off by far
    more."""
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, CFG.mask_id, 20).astype(np.int32)
    blocks = rng.integers(0, CFG.mask_id, (3, B)).astype(np.int32)
    got, cache = _programs(params, prompt, blocks)
    full = np.concatenate([prompt, blocks.reshape(-1)])
    want, layers = ref.forward(params, _padded(full), HP)
    np.testing.assert_allclose(
        got.reshape(-1, CFG.vocab_size), np.asarray(want)[20:32], atol=1e-4,
        rtol=1e-4)
    table = cache.table_row(0)[:4]
    for li, layer in enumerate(layers):
        for name, pool_a in (("k", cache.state.k), ("v", cache.state.v)):
            rows = np.asarray(pool_a[li, table]).reshape(-1, 64)[:32]
            np.testing.assert_allclose(rows, layer[name][:32], atol=1e-5,
                                       rtol=1e-5)
    off, _ = ref.forward(params, _padded(full), _hp(causal=True))
    assert np.abs(got.reshape(-1, CFG.vocab_size)
                  - np.asarray(off)[20:32]).max() > 20 * 1e-4
    stale = full.copy()
    stale[20:32:2] = (stale[20:32:2] + 5) % CFG.mask_id    # the second pass's
    _, old = ref.forward(params, _padded(stale), HP)
    rows = np.asarray(cache.state.k[0, table]).reshape(-1, 64)[:32]
    assert np.abs(rows - old[0]["k"][:32]).max() > 1e-2


def test_what_the_programs_count_is_what_the_shapes_say(params):
    """``pool.stats`` of a chunk and of a decode step: visible pairs under the
    block-causal mask (a query sees to the end of its block), keys a row's
    block of queries reads (the fill level and the block)."""
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, CFG.mask_id, 16).astype(np.int32)
    _, cache = _programs(params, prompt, np.zeros((1, B), np.int32), rows=3)
    from byteps_tpu.serve.paged_cache import STATS

    s = dict(zip(STATS, np.asarray(cache.state.stats).tolist()))
    assert s["serve.kv.decode_keys_read.full"] == 3 * (16 + B) * CFG.n_layers
    assert s["moe.pairs_here"] == 3 * B * CFG.top_k * CFG.n_layers
    chunk = make_paged_prefill_fn(CFG, BS, 8, with_readout=False,
                                  plan=serve_family(CFG).plan(CFG))
    _, cache.state = chunk(params, cache.state, prompt[None, 8:16],
                           np.int32(8), cache.table_row(0, 8))
    s = dict(zip(STATS, np.asarray(cache.state.stats).tolist()))
    assert s["serve.attn.prefill_pairs.full"] == \
        (12 * 4 + 16 * 4) * CFG.n_layers


# -- what the family refuses --------------------------------------------------
@pytest.mark.parametrize("feature,kw", [
    ("prefix_cache", dict(prefix_cache=True)),
    ("quant_cache", dict(quant_cache=True)),
    ("role", dict(role="prefill")),
    ("role", dict(role="decode")),
    ("tp_axis", dict(tp_axis="tp")),
    ("adapter_pool", dict(adapter_pool=object())),
], ids=["prefix_cache", "int8_pool", "role_prefill", "role_decode", "tp",
        "lora"])
def test_what_a_rewritten_page_does_not_carry_is_refused(params, feature, kw):
    assert feature in BlockDiffusionFamily.REFUSED
    with pytest.raises(NotImplementedError, match=feature):
        _sched(params, max_batch=2, pool_blocks=16, **kw)


@pytest.mark.parametrize("feature,kw", [
    ("speculation", dict(spec=SpecPolicy("lookup", spec_len=2))),
    ("temperature", dict(temperature=0.7)),
    ("denoise_steps", dict(denoise_steps=3)),
], ids=["speculation", "temperature", "passes_that_do_not_divide_a_block"])
def test_what_a_request_may_not_ask_is_refused_at_submit(params, feature, kw):
    sched = _sched(params, max_batch=2, pool_blocks=16)
    with pytest.raises(NotImplementedError, match=feature):
        sched.submit(Request(rid=0, prompt=np.zeros(4, np.int32), max_new=2,
                             **kw))


def test_pages_and_chunks_are_whole_blocks(params):
    with pytest.raises(ValueError, match="whole blocks"):
        _sched(params, block_size=6)
    with pytest.raises(ValueError, match="whole blocks"):
        _sched(params, prefill_chunk=6)


# -- kernels against their twins (interpret mode) ----------------------------
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
def test_paged_decode_with_a_block_of_queries_against_its_twin(monkeypatch,
                                                               dtype, tol):
    """32 query heads on 4 kv heads of 128, blocks of 16, 4 queries a row: the
    ``4 x 8`` query rows of a k/v head meet that head's columns, the batch in
    4 grid steps. Rows of one block, rows many pages long, a padded row, the
    scratch block poisoned; every query of a row sees all of the row's
    keys."""
    monkeypatch.setenv("BYTEPS_KERNEL_BACKEND", "pallas")
    rng = np.random.default_rng(7)
    H, Hkv, D, bs, nq, W, NB = 32, 4, 128, 16, 4, 16, 48
    lens = np.array([4, 40, 44, 56, 132, 248, 4, 16], np.int32)
    shape = (3, NB, bs, Hkv * D)
    k = np.asarray(rng.standard_normal(shape), np.float32)
    v = np.asarray(rng.standard_normal(shape), np.float32)
    k[:, 0], v[:, 0] = np.nan, np.inf
    tables = np.zeros((len(lens), W), np.int32)
    free = list(rng.permutation(np.arange(1, NB)))
    for r, n in enumerate(lens):
        for b in range(-(-n // bs)):
            tables[r, b] = free.pop()
    k, v = jnp.asarray(k, dtype), jnp.asarray(v, dtype)
    q = jnp.asarray(rng.standard_normal((len(lens), nq, H, D)), dtype)
    got = paged_attention_decode(q, k, v, jnp.asarray(tables),
                                 jnp.asarray(lens), 1)
    assert got.dtype == q.dtype and got.shape == q.shape
    kf, vf, qf = (np.asarray(a, np.float32) for a in (k, v, q))
    for r, n in enumerate(lens):
        at = np.arange(n)
        kk = kf[1, tables[r, at // bs], at % bs].reshape(-1, Hkv, D)
        vv = vf[1, tables[r, at // bs], at % bs].reshape(-1, Hkv, D)
        for j in range(nq):
            for h in (0, 7, 8, 31):
                s = kk[:, h // 8] @ qf[r, j, h] / np.sqrt(D)
                p = np.exp(s - s.max())
                np.testing.assert_allclose(
                    np.asarray(got[r, j, h], np.float32),
                    (p / p.sum()) @ vv[:, h // 8], atol=tol, rtol=tol)
    # a batch the block does not divide goes in one grid step, as one query a
    # row does
    odd = paged_attention_decode(q[:3], k, v, jnp.asarray(tables[:3]),
                                 jnp.asarray(lens[:3]), 1)
    np.testing.assert_allclose(np.asarray(odd, np.float32),
                               np.asarray(got[:3], np.float32), atol=tol)
    one = paged_attention_decode(q[:, 2], k, v, jnp.asarray(tables),
                                 jnp.asarray(lens), 1)
    np.testing.assert_allclose(np.asarray(one, np.float32),
                               np.asarray(got[:, 2], np.float32), atol=tol)


@pytest.mark.parametrize("backend", ["pallas", "jnp"])
@pytest.mark.parametrize("pos0,S", [(0, 64), (64, 64), (1024, 1024)])
def test_flash_with_a_block_causal_mask_and_fewer_kv_heads(monkeypatch,
                                                           backend, pos0, S):
    """8 query heads on 2 kv heads: a chunk of ``S`` queries at ``pos0`` over
    the ``pos0 + S`` keys of the gathered view (at 1024 the key tiles before
    the chunk are interior, the diagonal ones masked by the block)."""
    if backend == "pallas" and S > 64 and jax.default_backend() == "cpu":
        S = 256                               # interpret mode: keep it short
        pos0 = 256
    monkeypatch.setenv("BYTEPS_KERNEL_BACKEND", backend)
    rng = np.random.default_rng(pos0)
    H, Hkv, D = 8, 2, 64
    q = jnp.asarray(rng.normal(size=(1, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, pos0 + S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, pos0 + S, Hkv, D)), jnp.float32)
    got = flash_attention_block_causal(q, k, v, pos0, 0, 4)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, H // Hkv, 2)) \
        / np.sqrt(D)
    rows = (pos0 + np.arange(S)[:, None]) // 4
    cols = np.arange(pos0 + S)[None, :] // 4
    want = jnp.einsum("bhqk,bkhd->bqhd",
                      jax.nn.softmax(jnp.where(cols <= rows, s, -1e30), -1),
                      jnp.repeat(v, H // Hkv, 2))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_scheduler_tokens_and_counter_across_backends(monkeypatch):
    """Shapes the kernels take (2 kv heads of 64, pages of 8, chunks of 16):
    the same requests under ``BYTEPS_KERNEL_BACKEND=pallas`` (both kernels,
    interpreted) and under jnp (both twins) give the same tokens fixed at the
    same passes, and ``serve.decode_steps_paged_attn`` counts every decode
    step of the first and none of the second."""
    cfg = SDARConfig.tiny(n_heads=4, n_kv_heads=2, head_dim=64, max_seq=128)
    params = sdar_init(jax.random.PRNGKey(1), cfg)
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, cfg.mask_id, n).astype(np.int32)
               for n in (40, 21)]
    reg = get_registry()
    steps = reg.histogram("serve.batch_occupancy")
    paged = reg.counter("serve.decode_steps_paged_attn")

    def serve(kernel_backend):
        monkeypatch.setenv("BYTEPS_KERNEL_BACKEND", kernel_backend)
        make_paged_decode_fn.cache_clear()
        make_paged_prefill_fn.cache_clear()
        s0, p0 = steps.count(), paged.value()
        sched = Scheduler(params, cfg, max_batch=2, block_size=8,
                          pool_blocks=33, prefill_chunk=16)
        out = sched.serve([Request(rid=f"r{i}", prompt=p, max_new=6 + i,
                                   denoise_steps=(4, 2)[i])
                           for i, p in enumerate(prompts)])
        assert sched.cache.leaked_blocks() == 0
        return ({r: (np.asarray(o["emitted"]), np.asarray(o["fixed_at"]))
                 for r, o in out.items()},
                steps.count() - s0, paged.value() - p0)

    try:
        got, n_steps, n_paged = serve("pallas")
        assert n_steps > 0 and n_paged == n_steps
        want, n_steps, n_paged = serve("jnp")
        assert n_steps > 0 and n_paged == 0
    finally:
        make_paged_decode_fn.cache_clear()
        make_paged_prefill_fn.cache_clear()
    for rid in want:
        np.testing.assert_array_equal(got[rid][0], want[rid][0])
        np.testing.assert_array_equal(got[rid][1], want[rid][1])


# -- the benchmark's read-back and replay -------------------------------------
@pytest.fixture(scope="module")
def taken(params):
    """``drivers/serve_sdar.py::take_running``: three requests served until
    the 20-token one has committed two blocks and is inside its third, then
    its pages as the programs left them (an open block's rows lie past the
    committed ones and are not read)."""
    from benchmark.drivers.serve_sdar import take_running

    sched = _sched(params)
    for r in _requests(shapes=[(20, 16, 4), (16, 8, 2), (8, 8, 1)]):
        sched.submit(r)
    while not any(r.state == "decode" and len(r.req.prompt) == 20
                  and len(r.emitted) >= 8 and r.blk is not None
                  and r.blk.issued >= 2 for r in sched._running):
        sched.step()
    got = take_running(sched, 20, np.random.default_rng(0))
    assert got["cached"] == 20 + len(got["emitted"]) >= 28
    assert got["k"].shape == (CFG.n_layers, got["cached"], 64)
    assert len(got["fixed_at"]) == len(got["emitted"])
    return got


def _reference_rows(params, taken, **over):
    full = np.concatenate([taken["prompt"], taken["emitted"]])
    if over.pop("stale_pass", False):
        # each block as its last denoising pass fed it
        at = taken["fixed_at"]
        last = np.maximum.reduceat(at, np.arange(0, len(at), B)).repeat(B)
        full[20:] = np.where(at == last[:len(at)], CFG.mask_id,
                             taken["emitted"])
    return ref.forward(params, _padded(full), _hp(**over),
                       logits_from=None)[1]


@pytest.mark.parametrize("over,name,low,high", [
    ({}, "kv_row_err", 0.0, 1e-5), ({}, "deep_row_err", 0.0, 1e-5),
    ({"causal": True}, "deep_row_err", 1e-2, 2.0),
    ({"stale_pass": True}, "kv_row_err", 5e-2, 2.0),
    ({"compute_dtype": "bfloat16"}, "kv_row_err", 5e-4, 1e-2),
    ({"cache_round": "bfloat16"}, "kv_row_err", 5e-4, 4e-3)],
    ids=["as_served_layer0", "as_served_deep", "causal_prefill",
         "a_denoising_passs_rows", "bf16_products", "bf16_cache"])
def test_pool_rows_are_the_references_cache(params, taken, over, name, low,
                                            high):
    """What the timed programs leave in the pool is what the reference says
    a cache holds, layer by layer over every committed position; a reference
    that masks causally, one whose blocks hold a denoising pass's tokens, and
    one computed or cached in bf16 each read far from it (what the chip's
    limits are set against: ``controls/sdar_limits.py``)."""
    from benchmark.drivers.serve_sdar import pool_errors

    err = pool_errors(taken, _reference_rows(params, taken, **dict(over)))
    assert low <= err[name] <= high, err


def test_the_sampler_replayed_from_the_record_agrees_by_value(params, taken):
    """``drivers/serve_sdar.py::replay``: every pass of every block rebuilt
    from ``fixed_at``; at f32 the served token is the reference's argmax at
    each position the pass fixed, and the positions the program fixed are as
    confident as the ones the reference would fix."""
    from benchmark.drivers.serve_sdar import replay

    layers = _reference_rows(params, taken)
    n_blocks = len(taken["emitted"]) // B
    gaps, conf = replay(params, HP, taken["prompt"], taken["emitted"],
                        taken["fixed_at"], layers, range(n_blocks))
    assert len(gaps) == n_blocks * B and len(conf) == n_blocks * 4
    assert max(gaps) <= 1e-4 and max(conf) <= 1e-5
    # a record that says another order of fixing is told apart by value
    # wherever the confidences differ
    wrong = taken["fixed_at"].copy()
    wrong[:B] = wrong[:B][::-1]
    _, conf = replay(params, HP, taken["prompt"], taken["emitted"], wrong,
                     layers, [0])
    assert max(conf) > 1e-5
