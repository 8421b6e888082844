"""DeepSeek-V3.2-Exp at tiny sizes, every mechanism live: an ``index_topk``
(12) shorter than the sequences on EVERY layer, a YaRN ramp inside the rotary
pairs at positions past the original context, 16 experts in 4 groups of which
2 stay, a share of the experts held. The model, the serve tier's absorbed
form over latent pages WITH the prefix index on (adoption at admission and
mid-prefill, copy-on-write, eviction), the group-limited router and the
rotation, each held to the plain reference
(``benchmark/configs/deepseek_v32_reference.py``) or to hand-computed
values."""

import ast
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.configs import deepseek_v32_reference as ref
from byteps_tpu.common.metrics import get_registry
from byteps_tpu.models import deepseek_v32 as dsv
from byteps_tpu.models.deepseek_v32 import (
    DeepSeekV32Config,
    dsv32_apply,
    dsv32_init,
)
from byteps_tpu.models.dots3 import FULL, Dots3Config
from byteps_tpu.parallel.moe import (
    moe_ffn_dropless,
    sigmoid_group_topk_route,
    sigmoid_topk_route,
)
from byteps_tpu.serve import Request, Scheduler
from byteps_tpu.serve.families import LatentFamily, serve_family

CFG = DeepSeekV32Config.tiny(experts_held=8, first_expert=4)
HP = dataclasses.asdict(CFG)
QB = 4
S_REF = 48      # every reference forward runs at this length: one compile
BS = 4          # block size
DOC = 24        # a document: six whole blocks


@pytest.fixture(scope="module")
def params():
    return dsv32_init(jax.random.PRNGKey(0), CFG)


def _padded(tokens):
    """A causal model's earlier positions do not see what follows them."""
    out = np.zeros(S_REF, np.int32)
    out[:len(tokens)] = tokens
    return jnp.asarray(out)


def _counter(name):
    return get_registry().counter(name).value()


def test_the_reference_imports_nothing_from_the_program():
    tree = ast.parse(open(ref.__file__).read())
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names] + [n.module for n in ast.walk(tree)
                                  if isinstance(n, ast.ImportFrom)]
    assert not [n for n in names if n and n.startswith("byteps_tpu")]


def test_full_forward_is_the_references(params):
    toks = np.random.default_rng(1).integers(0, CFG.vocab_size, S_REF)
    mine = np.asarray(dsv32_apply(params, jnp.asarray(toks)[None], CFG))[0]
    want, lo, layers = ref.forward(params, jnp.asarray(toks), HP, qb=QB)
    assert lo == 0
    np.testing.assert_allclose(mine, np.asarray(want), atol=2e-5)
    # every layer selects: fewer keys than positions from index_topk on
    sel = np.asarray(layers[-1]["selected"])
    assert sel.shape == (S_REF, CFG.index_topk)
    assert (sel[-1] >= 0).all() and (sel[3] >= 0).sum() == 4


# --------------------------------------------------------------------------
# served through the Scheduler with the prefix index ON
# --------------------------------------------------------------------------
def _asks(seed=0):
    """One document asked five times: 0 a first ask; 1 and 2 admitted with
    it, before anyone committed (they adopt MID-PREFILL); 3 submitted after
    0 finished (it adopts at ADMISSION); 4 diverges inside a block of the
    document (a partial hit: copy-on-write)."""
    rng = np.random.default_rng(seed)
    doc = rng.integers(0, CFG.vocab_size, DOC).astype(np.int32)

    def ask(i, n_doc, n_q, new):
        q = rng.integers(0, CFG.vocab_size, n_q).astype(np.int32)
        q[0] = (doc[n_doc] + 1 + i) % CFG.vocab_size if n_doc < DOC \
            else i                     # no chance match past the hit
        return Request(rid=i, max_new=new,
                       prompt=np.concatenate([doc[:n_doc], q]))

    return [ask(0, DOC, 5, 6), ask(1, DOC, 9, 5), ask(2, DOC, 6, 7),
            ask(3, DOC, 12, 5), ask(4, DOC - 2, 7, 6)]


@pytest.fixture(scope="module")
def served(params):
    before = {k: _counter(k) for k in (
        "serve.prefix_hits", "serve.prefix_saved_tokens",
        "serve.prefix.cow_blocks", "serve.prefill_tokens")}
    sched = Scheduler(params, CFG, max_batch=3, block_size=BS,
                      pool_blocks=64, prefill_chunk=8, prefix_cache=True)
    reqs = _asks()
    for r in reqs[:3]:
        sched.submit(r)
    while not sched.finished:
        sched.step()
    held = {}
    for r in reqs[3:]:
        sched.submit(r)
        while not sched.finished:
            sched.step()
            run = next((x for x in sched._running
                        if x.req.rid == r.rid and x.state == "decode"), None)
            if run is not None and r.rid not in held:
                # what the pool holds of it, through its table, while it runs
                n = run.cache_len
                row = sched.cache.table_row(r.rid)[:-(-n // BS)]
                pool = sched.cache.state
                held[r.rid] = (n, np.asarray(pool.kv[:, row]).reshape(
                    CFG.n_layers, -1, pool.kv.shape[-1])[:, :n],
                    np.asarray(pool.ki[:, row]).reshape(
                        CFG.n_layers, -1, pool.ki.shape[-1])[:, :n])
    sched.flush_stats()
    moved = {k: _counter(k) - v for k, v in before.items()}
    return reqs, sched.results, sched, moved, held


@pytest.mark.parametrize("rid", range(5))
def test_served_tokens_are_the_references(served, params, rid):
    """Prefill then decode through the latent cache; requests 1-4 from
    ADOPTED pages (they wrote none of the document's)."""
    reqs, results, _, _, _ = served
    r, emitted = reqs[rid], np.asarray(results[rid]["emitted"])
    assert len(emitted) == r.max_new
    n = len(r.prompt)
    logits, lo, _ = ref.forward(
        params, _padded(np.concatenate([r.prompt, emitted])), HP, qb=QB,
        keep={})
    rows = np.asarray(logits)[n - 1 - lo:n - 1 - lo + len(emitted)]
    gap = rows.max(-1) - rows[np.arange(len(emitted)), emitted]
    assert gap.max() <= 1e-4, gap


def test_hits_compute_only_what_they_do_not_share(served):
    reqs, _, sched, moved, _ = served
    # 1 and 2 jump to the document's end mid-prefill, 3 adopts it whole at
    # admission, 4 adopts five blocks and a part of the sixth
    assert moved["serve.prefix_hits"] == 4
    assert moved["serve.prefix_saved_tokens"] == 3 * DOC + (DOC - 2)
    assert moved["serve.prefix.cow_blocks"] == 1
    prompts = sum(len(r.prompt) for r in reqs)
    assert moved["serve.prefill_tokens"] == prompts - (3 * DOC + DOC - 2)
    assert sched.cache.leaked_blocks() == 0
    sched.cache.check_refcounts()
    assert sched.cache.prefix_blocks >= DOC // BS


@pytest.mark.parametrize("rid", [3, 4])
def test_adopted_pages_hold_the_references_cache(served, params, rid):
    """The rows and indexer keys a hit request READS through its table are
    the reference's cache of its whole prompt, every layer."""
    reqs, results, _, _, held = served
    n, kv, ki = held[rid]
    toks = np.concatenate([reqs[rid].prompt, results[rid]["emitted"]])
    _, _, layers = ref.forward(params, _padded(toks), HP, qb=QB,
                               keep={"cache": None})
    a = CFG.dims()
    for li in range(CFG.n_layers):
        c = layers[li]["cache"]
        want = np.concatenate([c["c_kv"], c["k_rope"]], -1)[:n]
        np.testing.assert_allclose(kv[li][:, :a.row], want, atol=2e-5)
        np.testing.assert_allclose(ki[li], np.asarray(c["ki"])[:n],
                                   atol=2e-5)
        assert not kv[li][:, a.row:].any()        # the lane padding


def test_eviction_on_a_latent_pool_keeps_tokens_exact(params):
    """A pool too small for two documents' idle pages: the second
    document's asks evict the first's, and a third ask of the first
    recomputes it — same tokens as with room."""
    rng = np.random.default_rng(7)
    docs = [rng.integers(0, CFG.vocab_size, DOC).astype(np.int32)
            for _ in range(2)]

    def ask(i, d):
        return Request(rid=i, max_new=4, prompt=np.concatenate(
            [docs[d], np.asarray([i, 3, 5], np.int32)]))

    order = [ask(0, 0), ask(1, 1), ask(2, 1), ask(3, 0)]

    def run(pool_blocks):
        sched = Scheduler(params, CFG, max_batch=2, block_size=BS,
                          pool_blocks=pool_blocks, prefill_chunk=8,
                          prefix_cache=True)
        out = {}
        for r in order:               # one at a time: idle pages pile up
            out.update(sched.serve([r]))
        assert sched.cache.leaked_blocks() == 0
        sched.cache.check_refcounts()
        return {k: list(v["emitted"]) for k, v in out.items()}

    evicted = _counter("serve.prefix_evictions")
    tight = run(1 + 8 + 4)            # one request's 8 blocks and a few more
    assert _counter("serve.prefix_evictions") > evicted
    assert tight == run(64)


def test_copy_on_write_copies_rows_and_keys(params):
    """A shared latent page is copied leaf by leaf: rows AND indexer
    keys."""
    sched = Scheduler(params, CFG, max_batch=2, block_size=BS,
                      pool_blocks=32, prefill_chunk=8, prefix_cache=True)
    cache = sched.cache
    cache.register("a")
    cache.ensure("a", 2 * BS)
    a0 = cache._tables["a"][0]
    st = cache.state
    cache.state = st._replace(kv=st.kv.at[:, a0].set(1.5),
                              ki=st.ki.at[:, a0].set(-2.5))
    cache.commit_prefix("a", np.arange(2 * BS, dtype=np.int32), 2 * BS)
    cache.register("b")
    blocks, n = cache.match_prefix(np.arange(BS, dtype=np.int32))
    assert n == BS and blocks == [a0]
    cache.adopt_prefix("b", blocks)
    assert cache.ensure_writable("b", 0, BS) == 1
    b0 = cache._tables["b"][0]
    assert b0 != a0
    assert (np.asarray(cache.state.kv[:, b0]) == 1.5).all()
    assert (np.asarray(cache.state.ki[:, b0]) == -2.5).all()
    cache.release("a"), cache.release("b")
    cache.drop_prefix_cache()
    assert cache.leaked_blocks() == 0


def test_a_latent_configuration_shares_prefixes_unless_it_has_windows(params):
    assert serve_family(CFG).shares_prefixes
    assert not LatentFamily(Dots3Config.tiny()).shares_prefixes
    assert LatentFamily(Dots3Config.tiny(
        layer_types=(FULL,) * 5)).shares_prefixes
    with pytest.raises(NotImplementedError, match="int8 pool"):
        Scheduler(params, CFG, block_size=BS, pool_blocks=16,
                  quant_cache=True)


# --------------------------------------------------------------------------
# the router
# --------------------------------------------------------------------------
def _sorted_route(x, wg, bias, k, scale, n_group, topk_group):
    """The group limit by sorting, numpy, of equal scores the lower index
    first."""
    s = 1.0 / (1.0 + np.exp(-(x.astype(np.float64) @ wg.astype(np.float64))))
    s = s.astype(np.float32)
    c = s + bias[None, :]
    T, E = c.shape
    per = E // n_group
    idx = np.zeros((T, k), np.int64)
    for t in range(T):
        g = c[t].reshape(n_group, per)
        score = np.sort(g, -1)[:, -2:].sum(-1)
        kept = np.argsort(-score, kind="stable")[:topk_group]
        masked = np.full(E, -np.inf, np.float32)
        for j in kept:
            masked[j * per:(j + 1) * per] = c[t, j * per:(j + 1) * per]
        idx[t] = np.argsort(-masked, kind="stable")[:k]
    picked = np.take_along_axis(s, idx, -1)
    return idx, scale * picked / picked.sum(-1, keepdims=True)


@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "ties"])
def test_group_limited_route_is_the_sorted_one(ties):
    rng = np.random.default_rng(3)
    T, d, E = 64, 16, 32
    x = rng.standard_normal((T, d)).astype(np.float32)
    wg = (rng.standard_normal((d, E)) * 0.5).astype(np.float32)
    bias = (rng.standard_normal(E) * 0.01).astype(np.float32)
    if ties:
        # whole groups of equal experts, and equal groups: every choice is
        # between equals somewhere
        wg = np.repeat(wg[:, :8], 4, axis=1)
        wg[:, 16:] = wg[:, :16]
        bias = np.zeros(E, np.float32)
    idx, w = sigmoid_group_topk_route(jnp.asarray(x), jnp.asarray(wg),
                                      jnp.asarray(bias), 6, 2.5, 8, 3)
    want_idx, want_w = _sorted_route(x, wg, bias, 6, 2.5, 8, 3)
    np.testing.assert_array_equal(np.sort(np.asarray(idx), -1),
                                  np.sort(want_idx, -1))
    np.testing.assert_allclose(np.sort(np.asarray(w), -1),
                               np.sort(want_w, -1), rtol=1e-5)
    groups = np.asarray(idx) // 4
    assert max(len(set(g)) for g in groups) <= 3


def test_one_group_is_the_route_without_a_limit():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)
    wg = jnp.asarray(rng.standard_normal((16, 24)) * 0.5, jnp.float32)
    bias = jnp.asarray(rng.standard_normal(24) * 0.01, jnp.float32)
    a = sigmoid_group_topk_route(x, wg, bias, 4, 2.5, 1, 1)
    b = sigmoid_topk_route(x, wg, bias, 4, 2.5)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))


def test_the_references_route_is_the_programs(params):
    moe = params["blocks"][CFG.first_k_dense]["moe"]
    h = jnp.asarray(np.random.default_rng(5).standard_normal(
        (40, CFG.d_model)), jnp.float32)
    idx, w = sigmoid_group_topk_route(
        h, moe["wg"], moe["router_bias"], CFG.top_k, CFG.routed_scaling,
        CFG.n_group, CFG.topk_group)
    ridx, rw = ref.route(h, moe, HP)
    np.testing.assert_array_equal(np.sort(np.asarray(idx), -1),
                                  np.sort(np.asarray(ridx), -1))
    np.testing.assert_allclose(np.sort(np.asarray(w), -1),
                               np.sort(np.asarray(rw), -1), rtol=1e-5)
    # the limit binds: without it some token picks from a third group
    free, _ = ref.route(h, moe, dict(HP, group_limit=False))
    per = CFG.n_routed_experts // CFG.n_group
    assert max(len(set(g)) for g in np.asarray(free) // per) > CFG.topk_group


def test_the_shares_of_all_ranks_add_up_to_the_uncut_layer():
    """ep = 4 over 16 experts: every rank routes over all 16 and computes
    its own 4; their sum and the shared expert ONCE are the uncut
    reference's layer."""
    whole = DeepSeekV32Config.tiny()
    full = dsv32_init(jax.random.PRNGKey(2), whole)
    p = full["blocks"][whole.first_k_dense]
    h = jnp.asarray(np.random.default_rng(6).standard_normal(
        (24, whole.d_model)), jnp.float32)
    total, pairs = jnp.zeros_like(h), 0.0
    for rank in range(4):
        lo = rank * 4
        moe = dict(p["moe"], **{k: p["moe"][k][lo:lo + 4]
                                for k in ("w1", "w3", "w2")})
        y, stats, _ = moe_ffn_dropless(
            h, moe, whole.top_k, whole.routed_scaling, lo,
            route="sigmoid_bias_groups",
            group_limit=(whole.n_group, whole.topk_group))
        total, pairs = total + y, pairs + float(stats[0])
        assert 1.0 <= float(stats[3]) <= whole.topk_group
    hp = dataclasses.asdict(whole)
    want, _ = ref._moe(h, p["moe"], hp)
    assert pairs == h.shape[0] * whole.top_k       # no pair twice, none lost
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-6)
    np.testing.assert_allclose(
        np.asarray(total + dsv._mlp(h, p["shared"], None, use_bias=False)),
        np.asarray(want + ref._swiglu(h, p["shared"])), atol=2e-6)


# --------------------------------------------------------------------------
# the rotation
# --------------------------------------------------------------------------
def test_yarn_frequencies_and_the_softmax_factor_by_hand():
    cfg = DeepSeekV32Config()
    rf = dsv.rope_freqs(cfg)
    inv = np.asarray(rf.inv_freq)
    plain = 10000.0 ** (-2.0 * np.arange(32) / 64)
    # d(32) = 64 ln(4096 / (64 pi)) / (2 ln 1e4) = 10.47; d(1) = 22.51
    assert math.floor(64 * math.log(4096 / (2 * math.pi * 32))
                      / (2 * math.log(1e4))) == 10
    assert math.ceil(64 * math.log(4096 / (2 * math.pi))
                     / (2 * math.log(1e4))) == 23
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-12)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-12)
    np.testing.assert_allclose(
        inv[16], plain[16] * (1 - 6 / 13) + plain[16] / 40 * (6 / 13),
        rtol=1e-12)
    assert rf.factor == 1.0
    assert abs(dsv.softmax_mscale(cfg) - (0.1 * math.log(40) + 1) ** 2) < 1e-12
    assert abs(dsv.softmax_mscale(cfg) - 1.8738542) < 1e-6
    ref_inv, ref_factor = ref.rope_inv_freq(dataclasses.asdict(cfg))
    np.testing.assert_allclose(ref_inv, inv, rtol=1e-12)
    assert ref_factor == 1.0
    # the tiny configuration's ramp is live too, and m² is not 1
    tiny = np.asarray(dsv.rope_freqs(CFG).inv_freq)
    assert 0.1 / 4 < tiny[1] < 0.1 and dsv.softmax_mscale(CFG) > 1.2


def test_the_softmax_factor_is_in_the_served_logits(params):
    """A reference without m² is not the program's: the factor is live at
    tiny sizes."""
    toks = np.random.default_rng(8).integers(0, CFG.vocab_size, S_REF)
    mine = np.asarray(dsv32_apply(params, jnp.asarray(toks)[None], CFG))[0]
    off, _, _ = ref.forward(params, jnp.asarray(toks),
                            dict(HP, softmax_mscale=False), qb=QB, keep={})
    assert np.abs(mine - np.asarray(off)).max() > 1e-3
