"""Phi-4-mini-flash (SambaY with differential attention) at tiny sizes, every
mechanism live: eight layers — three Mamba-1 layers (state 8 x 64, 4
convolution taps with bias), two window-attention layers (6 keys), ONE
full-attention layer, a Gated Memory Unit fed the last Mamba layer's scan
output and a cross-attention layer that reads the full layer's keys — all
attention differential (8 query heads on 4 k/v heads of 8, paired). The dense
model, the serve tier's two programs over FOUR cache behaviours at once (a
global pool one layer deep, a window pool, a slot pool, and layers that own
nothing), the chunk that stops after the full layer and the final chunk that
runs the layers above it on one position, each held to the plain reference
(``benchmark/configs/phi4flash_reference.py``). Everything is f32: the
tolerances (a few 1e-5 on logits of unit scale) are what the order of f32 sums
moves between the chunked rule and the token-by-token one."""

import ast
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.configs import phi4flash_reference as ref
from byteps_tpu.common.metrics import get_registry
from byteps_tpu.models import phi4_flash
from byteps_tpu.models.mellum2 import dense_attend
from byteps_tpu.models.phi4_flash import (
    Phi4FlashConfig,
    layer_kinds,
    param_count,
    phi4_flash_apply,
    phi4_flash_init,
)
from byteps_tpu.ops import selective_scan as sscan
from byteps_tpu.serve import Request, Scheduler
from byteps_tpu.serve import families
from byteps_tpu.serve.families import SharedKVFamily, serve_family
from byteps_tpu.serve.paged_cache import (
    STATS_SAMBAY,
    PagedKVCache,
    _cacheless_tail,
    one_kind_plan,
)

CFG = Phi4FlashConfig.tiny()
KINDS = layer_kinds(CFG)
BS, CHUNK, QB = 4, 8, 4
S_REF = 48      # every reference forward runs at this length: one compile
#: (prompt length, max_new): four chunks and a tail that is no whole
#: sub-chunk, mid-block ends, one prompt shorter than the convolution; all but
#: the short one pass the window, so window blocks are given back
SHAPES = [(37, 6), (22, 9), (2, 12), (19, 7)]
TOL = 5e-5


def _hp(cfg=CFG, **over):
    hp = {k: v for k, v in dataclasses.asdict(cfg).items() if k != "dtype"}
    return dict(hp, **over)


@pytest.fixture(scope="module")
def params():
    return phi4_flash_init(jax.random.PRNGKey(0), CFG)


def _requests(seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, max_new=new,
                    prompt=rng.integers(0, CFG.vocab_size, n)
                    .astype(np.int32))
            for i, (n, new) in enumerate(SHAPES)]


def _scheduler(params, **kw):
    kw = dict(dict(max_batch=3, block_size=BS, pool_blocks=65,
                   prefill_chunk=CHUNK), **kw)
    return Scheduler(params, CFG, **kw)


@pytest.fixture(scope="module")
def served(params):
    reg = get_registry()
    released = reg.counter("serve.cache.window_blocks_released").value()
    sched = _scheduler(params)
    results = sched.serve(_requests())
    sched.flush_stats()
    return sched, results, \
        reg.counter("serve.cache.window_blocks_released").value() - released


def _ref_forward(params, tokens, state_at=0, **over):
    toks = np.zeros(S_REF, np.int32)
    toks[:len(tokens)] = tokens
    return ref.forward(params, jnp.asarray(toks), _hp(**over), state_at,
                       qb=QB)


def test_reference_imports_nothing_from_the_program():
    tree = ast.parse(open(ref.__file__).read())
    names = [n.module or "" for n in ast.walk(tree)
             if isinstance(n, ast.ImportFrom)] \
        + [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
           for a in n.names]
    assert not [n for n in names if n.startswith(("byteps_tpu", "benchmark"))]


def test_the_published_sizes_and_their_parameter_count():
    full = Phi4FlashConfig()
    kinds = layer_kinds(full)
    assert [kinds.count(k) for k in ("mamba", "window", "full", "gmu",
                                     "cross")] == [9, 8, 1, 7, 7]
    assert kinds[16] == "mamba" and kinds[17] == "full" \
        and kinds[18] == "gmu" and kinds[31] == "cross"
    # the pool's head is a PAIR: 20 x 64 is 10 x 128
    assert (full.kv_heads, full.head_dim) == (10, 128)
    assert full.state_bytes() == 16 * 5120 * 4 + 3 * 5120 * 2
    mlp, mamba = 2560 * 20480 + 10240 * 2560, 41_241_600
    attn, cross, gmu = 19_668_864, 13_112_704, 26_214_400
    assert mlp == 78_643_200
    assert mamba == (2560 * 10240 + 5 * 5120 + 5120 * 192 + 160 * 5120
                     + 5120 + 16 * 5120 + 5120 + 5120 * 2560)
    assert attn == (2560 * 2560 + 2560) * 2 + (2560 * 1280 + 1280) * 2 \
        + 4 * 64 + 128
    assert param_count(full) == 3_852_562_944 == (
        32 * mlp + 9 * mamba + 9 * attn + 7 * cross + 7 * gmu
        + 32 * 4 * 2560 + 2 * 2560 + 200064 * 2560)


def test_lambda_init_is_the_layers_depth(params):
    for li, (p, kind) in enumerate(zip(params["blocks"], KINDS)):
        if kind in ("window", "full", "cross"):
            assert float(p["lambda_init"]) == pytest.approx(
                0.8 - 0.6 * np.exp(-0.3 * li))
        else:
            assert "lambda_init" not in p


# ---- the model against the reference -------------------------------------------
@pytest.mark.parametrize("recurrent", [True, False],
                         ids=["recurrent", "chunked"])
def test_model_forward_equals_the_reference(params, recurrent):
    toks = np.random.default_rng(3).integers(0, CFG.vocab_size, S_REF)
    got = phi4_flash_apply(params, jnp.asarray(toks)[None], CFG, recurrent)
    want, _ = _ref_forward(params, toks)
    assert float(jnp.std(want)) > 0.3          # logits that tell tokens apart
    np.testing.assert_allclose(got[0], want, atol=TOL)


@pytest.mark.parametrize("over,why", [
    (dict(m_after_gate=True), "m taken after the gate"),
    (dict(lambda_depth_shift=1), "lambda_init of the wrong layer"),
    (dict(sub_norm=False), "the sub-norm left out"),
    (dict(window_keys=5), "a window one key short"),
    (dict(window_keys=7), "a window one key long"),
    (dict(cross_own_kv=True), "cross layers on their own (absent) k/v"),
    (dict(stale_full_kv=16), "the full layer's rows from a stale x"),
    (dict(rope_base=10000.0), "a rotary applied"),
])
def test_a_reference_off_by_design_is_told_apart(params, over, why):
    toks = np.random.default_rng(3).integers(0, CFG.vocab_size, S_REF)
    got = phi4_flash_apply(params, jnp.asarray(toks)[None], CFG)[0]
    right, _ = _ref_forward(params, toks)
    wrong, _ = _ref_forward(params, toks, **over)
    near = float(jnp.max(jnp.abs(got - right)))
    far = float(jnp.max(jnp.abs(got - wrong)))
    assert far > 50 * max(near, 1e-6), (why, near, far)


def test_padded_heads_give_the_direct_differential_attention(params):
    """The model's attention (queries padded to a pair's width against pairs
    of k/v heads, through a stock GQA ``attend``) against the reference's
    direct formula: two softmaxes a pair, subtracted, sub-normed."""
    li = KINDS.index("full")
    p = params["blocks"][li]
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 12, CFG.d_model))
    got, _ = phi4_flash.diff_attn_half(CFG, x, p, CFG.head_dim, None,
                                       dense_attend(None))
    h = ref._ln(x[0], p["ln1_g"], p["ln1_b"], CFG.norm_eps)
    k, v = ref.kv_rows(p, h, _hp())
    want = x[0] + ref.diff_attention(p, h, k, v, jnp.float32(li), None,
                                     _hp(), 4)
    np.testing.assert_allclose(got[0], want, atol=2e-5)


# ---- the selective scan's three forms ------------------------------------------
def _scan_inputs(T, N=8, Dn=128, seed=0, zero_state=False):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    u = jax.random.normal(k[0], (T, Dn))
    delta = jax.nn.softplus(jax.random.normal(k[1], (T, Dn)) - 1.0)
    A = -jnp.exp(jax.random.uniform(k[2], (N, Dn), minval=-1.0, maxval=2.5))
    B, C = jax.random.normal(k[3], (T, N)), jax.random.normal(k[4], (T, N))
    S = jnp.zeros((N, Dn)) if zero_state \
        else jax.random.normal(k[5], (N, Dn))
    return u, delta, A, B, C, jnp.linspace(0.5, 1.5, Dn), S


@pytest.mark.parametrize("T,sub,cut,zero", [
    (32, 8, None, True), (32, 8, None, False), (21, 8, None, False),
    (24, 4, 16, False), (5, 8, None, False)],
    ids=["zero_state", "from_a_slot", "ragged", "two_chunks", "short"])
def test_chunked_scan_equals_the_recurrent_one(T, sub, cut, zero):
    """Incl. a chunk that starts from a non-zero slot, a length that is no
    whole sub-chunk, and two chunks in a row (the second from the first's
    state)."""
    u, delta, A, B, C, D, S = _scan_inputs(T, zero_state=zero)
    want_y, want_S = sscan.sscan_recurrent(u, delta, A, B, C, D, S)
    if cut is None:
        y, S1 = sscan.sscan_chunk_fwd(u, delta, A, B, C, D, S, sub)
    else:
        y0, S0 = sscan.sscan_chunk_fwd(u[:cut], delta[:cut], A, B[:cut],
                                       C[:cut], D, S, sub)
        y1, S1 = sscan.sscan_chunk_fwd(u[cut:], delta[cut:], A, B[cut:],
                                       C[cut:], D, S0, sub)
        y = jnp.concatenate([y0, y1])
    np.testing.assert_allclose(y, want_y, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(S1, want_S, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("N,Dn", [(8, 128), (16, 256)], ids=["8x128",
                                                             "16x256"])
def test_decode_kernel_updates_the_slots_in_place_like_its_twin(N, Dn):
    """The kernel (interpreted) against the twin AND the recurrent rule:
    three rows at scattered slots of layer 1, one of them the scratch slot;
    every other slot and layer untouched."""
    R, L, slots_n = 3, 2, 5
    u, delta, A, B, C, D, _ = _scan_inputs(R, N, Dn, seed=1)
    pool = jax.random.normal(jax.random.PRNGKey(2), (L, slots_n, N, Dn))
    slots = jnp.asarray([3, 0, 1], jnp.int32)
    want_y, want_pool = sscan.sscan_decode_jnp(u, delta, A, B, C, D, pool,
                                               1, slots)
    y, got = sscan._decode(u, delta, A, B, C, pool + 0.0, jnp.int32(1),
                           slots, True)
    np.testing.assert_allclose(y + D * u, want_y, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(got, want_pool, atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(got[0], pool[0])
    np.testing.assert_array_equal(got[1, [2, 4]], pool[1, [2, 4]])
    for r, slot in enumerate([3, 0, 1]):
        yr, Sr = sscan.sscan_recurrent(u[r:r + 1], delta[r:r + 1], A,
                                       B[r:r + 1], C[r:r + 1], D,
                                       pool[1, slot])
        np.testing.assert_allclose(want_y[r], yr[0], atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(want_pool[1, slot], Sr, atol=1e-5)


@pytest.mark.parametrize("backend,counter", [
    ("pallas", "sscan.decode_kernel"), ("jnp", "sscan.decode_twin")])
def test_decode_dispatch_counts_which_form_was_traced(monkeypatch, backend,
                                                      counter):
    monkeypatch.setenv("BYTEPS_KERNEL_BACKEND", backend)
    u, delta, A, B, C, D, _ = _scan_inputs(2, seed=3)
    pool = jnp.zeros((1, 3, 8, 128))
    c = get_registry().counter(counter)
    before = c.value()
    y, pool = sscan.sscan_decode(u, delta, A, B, C, D, pool, 0,
                                 jnp.asarray([1, 2], jnp.int32))
    assert c.value() == before + 1
    want, _ = sscan.sscan_decode_jnp(u, delta, A, B, C, D,
                                     jnp.zeros((1, 3, 8, 128)), 0,
                                     jnp.asarray([1, 2], jnp.int32))
    np.testing.assert_allclose(y, want, atol=1e-5)


def test_decode_kernel_refuses_what_it_cannot_tile():
    assert sscan.decode_unsupported_reason(16, 5120, jnp.float32) is None
    assert "float32" in sscan.decode_unsupported_reason(16, 5120,
                                                        jnp.bfloat16)
    assert "tiles" in sscan.decode_unsupported_reason(8, 64, jnp.float32)
    assert "tiles" in sscan.decode_unsupported_reason(4, 128, jnp.float32)


# ---- served through the scheduler ----------------------------------------------
@pytest.mark.parametrize("i", range(len(SHAPES)),
                         ids=[f"p{n}_n{m}" for n, m in SHAPES])
def test_scheduler_prefill_then_decode_equals_the_reference(params, served,
                                                            i):
    """Chunked prefill (8-token chunks of 4-token sub-chunks, every chunk but
    a request's last ENDING after the full layer) into pages, window pages
    and a slot, then packed decode steps whose rows change as requests of
    unequal length finish: at every generated position the served token's
    logit is the reference's largest, of one full forward — every layer on
    every position — over prompt + emitted."""
    _, results, _ = served
    tokens = results[i]["tokens"]
    n, new = SHAPES[i]
    assert len(tokens) == n + new
    logits, _ = _ref_forward(params, tokens)
    rows = np.asarray(logits)[n - 1:n - 1 + new]
    gap = rows.max(-1) - rows[np.arange(new), tokens[n:]]
    assert gap.max() < 1e-4, gap


def test_slots_blocks_and_window_blocks_all_come_back(served):
    sched, _, released = served
    cache = sched.cache
    assert cache.state_slots == 1 + 3 + 1          # scratch + admitted at once
    assert cache.window == CFG.window
    assert cache.slots_in_use == 0 and cache.blocks_in_use == 0
    assert cache.window_blocks_in_use == 0
    assert cache.leaked_slots() == 0 and cache.leaked_blocks() == 0
    # window blocks were handed back WHILE the requests ran (past 6 keys)
    assert released > 0
    pool = cache.state
    assert pool.k.shape[0] == 1                    # ONE layer's pages
    assert pool.wk.shape[0] == KINDS.count("window")
    assert pool.s.shape[:1] == (KINDS.count("mamba"),)
    assert pool.s.shape[2:] == (CFG.ssm_state, CFG.d_inner)


def test_cross_positions_are_one_a_prompt(params):
    """The layers above the full one ran over ONE position a request's
    prompt, whatever its length: the chunks that read nothing out ended
    before them."""
    reg = get_registry()
    before = reg.counter("sambay.cross_positions").value()
    tokens = reg.counter("serve.prefill_tokens").value()
    sched = _scheduler(params)
    sched.serve(_requests(seed=9))
    sched.flush_stats()
    assert reg.counter("sambay.cross_positions").value() - before \
        == len(SHAPES)
    assert reg.counter("serve.prefill_tokens").value() - tokens \
        == sum(n for n, _ in SHAPES)


def _programs(params, max_batch=3):
    family = serve_family(CFG)
    cache = PagedKVCache(
        CFG, block_size=BS, pool_blocks=65, max_batch=max_batch,
        layout=lambda bs, nb: family.layout(
            params, CFG, block_size=bs, pool_blocks=nb, max_batch=max_batch,
            prefill_chunk=CHUNK, quant=False))
    return family, cache


def _prefill(family, cache, params, rid, prompt, tail_params=None):
    """Chunk by chunk as the scheduler does: only the LAST chunk reads out.
    ``tail_params``: the tree the chunks that read nothing out are given."""
    cache.register(rid)
    cache.ensure(rid, len(prompt) + 8)
    logits = None
    for lo in range(0, len(prompt), CHUNK):
        toks = prompt[lo:lo + CHUNK]
        final = lo + CHUNK >= len(prompt)
        cache.ensure_window(rid, lo + len(toks))
        fn = family.prefill_fn(CFG, BS, len(toks), None, final)
        logits, cache.state = fn(
            params if final or tail_params is None else tail_params,
            cache.state, toks[None], np.int32(lo), cache.table_row(rid, 16))
        cache.release_behind(rid, lo + len(toks))
        if final:
            # a chunk of this family reads out ONE position: its last
            assert logits.shape == (1, 1, CFG.vocab_size)
        else:
            assert logits is None
    return logits[0, -1]


def _window_rows(cache, rid, pool_a, wi, lo, n):
    """Rows ``[lo, n)`` of window layer ``wi`` through the window table."""
    wt = cache._wtables[rid]
    return np.stack([np.asarray(pool_a[wi, wt[t // BS], t % BS])
                     for t in range(lo, n)])


def test_programs_logits_and_pool_contents_equal_the_reference(params):
    """The two programs called as the scheduler calls them: the last chunk's
    one row of logits, then three packed decode steps in which the two
    requests CHANGE ROWS (and a row holds no request), every logit against
    the reference; then what the pools hold — the state and the convolution's
    tail of every Mamba layer in the slot, the LIVE rows of every window
    layer, the full layer's k and v over every position — against the
    reference's after as many positions."""
    family, cache = _programs(params)
    assert isinstance(family, SharedKVFamily)
    rng = np.random.default_rng(5)
    seqs = {"a": list(rng.integers(0, CFG.vocab_size, 21)),
            "b": list(rng.integers(0, CFG.vocab_size, 10))}
    for rid, seq in seqs.items():
        last = _prefill(family, cache, params, rid, np.asarray(seq, np.int32))
        want, _ = _ref_forward(params, seq)
        np.testing.assert_allclose(last, want[len(seq) - 1], atol=TOL)
        seq.append(int(jnp.argmax(last)))
    assert cache.slot_of("a") != cache.slot_of("b") and cache.slot_of("a") > 0
    step = family.decode_fn(CFG, BS, None, None)
    for order in (["a", "b", None], [None, "a", "b"], ["b", None, "a"]):
        toks, pos = np.zeros(3, np.int32), np.zeros(3, np.int32)
        tables = np.zeros((3, 2, 17), np.int32)
        for r, rid in enumerate(order):
            if rid is not None:
                toks[r], pos[r] = seqs[rid][-1], len(seqs[rid]) - 1
                cache.ensure_window(rid, len(seqs[rid]))
                tables[r] = cache.table_row(rid, 16)
        logits, cache.state = step(params, cache.state, toks, pos, tables)
        for r, rid in enumerate(order):
            if rid is not None:
                cache.release_behind(rid, len(seqs[rid]))
                want, _ = _ref_forward(params, seqs[rid])
                np.testing.assert_allclose(
                    logits[r], want[len(seqs[rid]) - 1], atol=TOL)
                seqs[rid].append(int(jnp.argmax(logits[r])))
    K, pool = CFG.conv_kernel, cache.state
    for rid, seq in seqs.items():
        n = len(seq) - 1               # the last token was picked, not fed
        _, layers = _ref_forward(params, seq, state_at=n)
        slot = cache.slot_of(rid)
        row = cache.table_row(rid)
        assert row.shape == (2, 1 + cache.blocks_per_req)
        assert row[0, 0] == slot and row[1, 0] == 0
        seen = {"mamba": 0, "window": 0}
        for li, kind in enumerate(KINDS):
            if kind == "mamba":
                i = seen["mamba"]
                np.testing.assert_allclose(pool.s[i, slot].T,
                                           layers[li]["S"], atol=2e-5)
                np.testing.assert_allclose(
                    pool.conv[i, slot].reshape(K - 1, -1),
                    layers[li]["tail"], atol=2e-5)
            elif kind == "window":
                lo = max(0, n - (CFG.window - 1))
                for name, pool_a in (("k", pool.wk), ("v", pool.wv)):
                    np.testing.assert_allclose(
                        _window_rows(cache, rid, pool_a, seen["window"], lo,
                                     n), layers[li][name][lo:n], atol=2e-5)
            elif kind == "full":
                blocks = row[0, 1:1 + -(-n // BS)]
                for name, pool_a in (("k", pool.k), ("v", pool.v)):
                    rows = np.asarray(pool_a[0, blocks])
                    np.testing.assert_allclose(
                        rows.reshape(-1, rows.shape[-1])[:n],
                        layers[li][name][:n], atol=2e-5)
            else:
                assert layers[li] == {}
            if kind in seen:
                seen[kind] += 1


def test_a_chunk_that_reads_nothing_out_never_touches_the_cross_decoder(
        params):
    """The same prompt prefilled with every layer above the full one made NaN
    in the chunks that are not its last gives the last-position logits of the
    dense forward: those chunks end after the full layer."""
    family, cache = _programs(params)
    n_self = KINDS.index("full") + 1
    poisoned = dict(params, blocks=params["blocks"][:n_self] + [
        jax.tree_util.tree_map(lambda a: jnp.full_like(a, jnp.nan), p)
        for p in params["blocks"][n_self:]])
    prompt = np.random.default_rng(8).integers(0, CFG.vocab_size, 29)
    last = _prefill(family, cache, params, "x", prompt.astype(np.int32),
                    tail_params=poisoned)
    dense = phi4_flash_apply(params, jnp.asarray(prompt)[None], CFG)[0, -1]
    np.testing.assert_allclose(last, dense, atol=TOL)
    s = dict(zip(STATS_SAMBAY, np.asarray(cache.state.stats).tolist()))
    assert s["sambay.cross_positions"] == 1.0


def test_a_slots_last_owner_is_not_seen(params):
    """Nothing zeroes a slot at release: the next owner's first chunk starts
    from zero whatever was left there (here: NaN)."""
    family, cache = _programs(params)
    cache.state = cache.state._replace(
        s=jnp.full_like(cache.state.s, jnp.nan),
        conv=jnp.full_like(cache.state.conv, jnp.nan))
    prompt = np.random.default_rng(6).integers(0, CFG.vocab_size, 13)
    last = _prefill(family, cache, params, "x", prompt.astype(np.int32))
    want, _ = _ref_forward(params, prompt)
    np.testing.assert_allclose(last, want[12], atol=TOL)


def test_what_the_programs_count_is_what_the_shapes_say(params):
    family, cache = _programs(params)
    assert family.late_stats().names == STATS_SAMBAY
    n_m, n_w = KINDS.count("mamba"), KINDS.count("window")
    n_x = KINDS.count("cross")
    prompt = np.random.default_rng(7).integers(0, CFG.vocab_size, 16)
    cache.register("x")
    cache.ensure("x", 24)
    cache.ensure_window("x", 16)
    row = cache.table_row("x", 16)

    def stats():
        return dict(zip(STATS_SAMBAY, np.asarray(cache.state.stats).tolist()))

    for lo, final in ((0, False), (8, True)):
        fn = family.prefill_fn(CFG, BS, 8, None, final)
        _, cache.state = fn(params, cache.state, prompt[None, lo:lo + 8],
                            np.int32(lo), row)
        s = stats()
        assert s["serve.sscan.prefill_tokens"] == 8 * n_m
        assert s["sambay.cross_positions"] == float(final)
        # a final chunk: the full layer's 8 queries, and each reader's one
        # sees every key; a chunk that stops: the full layer's rows are
        # written and its queries, which feed nothing, are not run
        assert s["serve.attn.prefill_pairs.full"] == (
            sum(range(lo + 1, lo + 9)) + 16 * n_x if final else 0)
        assert s["serve.attn.prefill_pairs.window"] == n_w * sum(
            min(t + 1, CFG.window) for t in range(lo, lo + 8))
        assert s["moe.layers"] == 0        # a dense MLP: no expert series
    step = family.decode_fn(CFG, BS, None, None)
    tables = np.zeros((3, 2, 17), np.int32)
    cache.ensure_window("x", 17)
    tables[1] = cache.table_row("x", 16)
    _, cache.state = step(params, cache.state, np.zeros(3, np.int32),
                          np.asarray([0, 16, 0], np.int32), tables)
    s = stats()
    assert s["serve.sscan.decode_rows"] == 1 * n_m
    assert s["sambay.cross_positions"] == 0
    # the full layer and each reader of its pages: 17 keys
    assert s["serve.kv.decode_keys_read.full"] == 17 * (1 + n_x)
    assert s["serve.kv.decode_keys_read.window"] == CFG.window * n_w


def test_preemption_recomputes_to_the_same_logits(params):
    """Two requests of 12 + 12 tokens in a pool of 9 blocks: the younger is
    preempted mid-decode, its slot and its window blocks go back, and on
    resume it is recomputed from position 0; the tokens are those of the run
    with room, and so the reference's argmax."""
    def two(seed):
        rng = np.random.default_rng(seed)
        return [Request(rid=i, max_new=12, prompt=rng.integers(
            0, CFG.vocab_size, 12).astype(np.int32)) for i in range(2)]

    reg = get_registry()
    again = reg.counter("serve.state.resets.preempt")
    p0 = again.value()
    free = _scheduler(params, max_batch=2).serve(two(11))
    tight = _scheduler(params, max_batch=2, pool_blocks=10)
    got = tight.serve(two(11))
    preemptions = sum(r["preemptions"] for r in got.values())
    assert preemptions > 0 and again.value() - p0 == preemptions
    for rid in free:
        np.testing.assert_array_equal(got[rid]["tokens"],
                                      free[rid]["tokens"])
        logits, _ = _ref_forward(params, got[rid]["tokens"])
        rows = np.asarray(logits)[11:23]
        gap = rows.max(-1) - rows[np.arange(12), got[rid]["tokens"][12:]]
        assert gap.max() < 1e-4, gap
    assert tight.cache.slots_in_use == 0 and tight.cache.leaked_blocks() == 0
    assert tight.cache.window_blocks_in_use == 0


def test_the_decode_step_goes_through_both_kernels(params, monkeypatch):
    """On the Pallas backend (interpreted here) with shapes the kernels take
    — a k/v pair of 128, a state of 8 x 128 — a packed decode step attends
    through the paged-attention kernel (the full layer, the window layers by
    each row's first key, and the cross layer reading the full layer's pages)
    and updates the state through ``sscan_decode``, and serves the tokens of
    the jnp path."""
    cfg = Phi4FlashConfig.tiny(n_heads=4, n_kv_heads=2, d_head=64,
                               d_inner=128, window=6)
    p = phi4_flash_init(jax.random.PRNGKey(1), cfg)
    kw = dict(max_batch=2, block_size=8, pool_blocks=17, prefill_chunk=8)

    def reqs():
        return [Request(rid=0, max_new=3,
                        prompt=np.arange(11, dtype=np.int32))]

    want = Scheduler(p, cfg, **kw).serve(reqs())
    monkeypatch.setenv("BYTEPS_KERNEL_BACKEND", "pallas")
    reg = get_registry()
    before = reg.counter("sscan.decode_kernel").value()
    # (another configuration: the programs' factories are cached by it)
    sched = Scheduler(p, dataclasses.replace(cfg, max_seq=72), **kw)
    got = sched.serve(reqs())
    assert reg.counter("sscan.decode_kernel").value() > before
    assert sched._decode_paged_attn
    np.testing.assert_array_equal(got[0]["tokens"], want[0]["tokens"])


@pytest.mark.parametrize("feature,kw", [
    ("prefix_cache", dict(prefix_cache=True)),
    ("quant_cache", dict(quant_cache=True)),
    ("role", dict(role="prefill")),
    ("tp_axis", dict(tp_axis="tp")),
    ("adapter_pool", dict(adapter_pool=object())),
])
def test_what_three_pools_cannot_do_is_refused_by_name(params, feature, kw):
    with pytest.raises(NotImplementedError) as e:
        _scheduler(params, **kw)
    assert SharedKVFamily.REFUSED[feature] in str(e.value)
    assert feature in str(e.value) and "Phi4FlashConfig" in str(e.value)


def test_speculation_is_refused_at_submit(params):
    from byteps_tpu.serve.scheduler import SpecPolicy

    sched = _scheduler(params)
    with pytest.raises(NotImplementedError, match="rewind a recurrent"):
        sched.submit(Request(rid=0, prompt=np.zeros(4, np.int32), max_new=2,
                             spec=SpecPolicy(kind="lookup")))


# ---- the families that were there ----------------------------------------------
def _plans():
    from byteps_tpu.models.falcon_h1 import FalconH1Config
    from byteps_tpu.models.gpt import GPTConfig
    from byteps_tpu.models.mellum2 import Mellum2Config
    from byteps_tpu.models.qwen3_next import Qwen3NextConfig
    from byteps_tpu.models.sdar import SDARConfig

    return {"gpt": (one_kind_plan, GPTConfig.tiny(), (4,)),
            "mellum2": (families._windowed_plan, Mellum2Config.tiny(),
                        (2, 4)),
            "qwen3next": (families._recurrent_plan, Qwen3NextConfig.tiny(),
                          (5,)),
            "falconh1": (families._hybrid_plan, FalconH1Config.tiny(), (5,)),
            "sdar": (families._block_plan, SDARConfig.tiny(), (4,))}


@pytest.mark.parametrize("name", ["gpt", "mellum2", "qwen3next", "falconh1",
                                  "sdar"])
def test_the_existing_families_plans_are_what_they_were(name):
    """No reader, no fed layer, no tail that a chunk skips, no keyword the
    first half did not take — and a table row of the shape it had."""
    make, cfg, row_shape = _plans()[name]
    plan = make(cfg)
    assert plan.fed is None and not plan.attn_takes_kind
    assert not any(k.reader or k.fed for k in plan.kinds)
    assert _cacheless_tail(plan) == 0
    family = serve_family(cfg)
    params = {"blocks": [{"wk": np.zeros((1, cfg.kv_heads * cfg.head_dim))}]}
    cache = PagedKVCache(
        cfg, block_size=4, pool_blocks=9, max_batch=2,
        layout=lambda bs, nb: family.layout(
            params, cfg, block_size=bs, pool_blocks=nb, max_batch=2,
            prefill_chunk=8, quant=False))
    cache.register("r")
    cache.ensure("r", 8)
    assert cache.table_row("r", 4).shape == row_shape
