"""Mellum2 at tiny sizes, every mechanism live: a window (9) shorter than the
contexts, YaRN's ramp inside a 32-wide head, 2 kv heads under 4 query heads,
8 experts top-2 all held. The dense model, the serve tier's two programs over
k/v pages of two kinds, the windowed decode kernel and the cache's window
kind, each held to the plain reference
(``benchmark/configs/mellum2_reference.py``) or to its jnp twin.

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

from benchmark.configs import mellum2_reference as ref
from byteps_tpu.common.metrics import get_registry
from byteps_tpu.models.mellum2 import (
    FULL,
    SLIDING,
    Mellum2Config,
    mellum2_apply,
    mellum2_init,
    rope_freqs,
)
from byteps_tpu.ops.flash_attention import flash_attention_window
from byteps_tpu.ops.paged_attention import paged_attention_decode
from byteps_tpu.parallel.moe import softmax_topk_route
from byteps_tpu.serve import Request, Scheduler
from byteps_tpu.serve.families import WindowedKVFamily, window_pool_blocks
from byteps_tpu.serve.paged_cache import (
    PagedKVCache,
    make_paged_decode_fn,
    make_paged_prefill_fn,
)

CFG = Mellum2Config.tiny()
BS, CHUNK = 4, 8
QB = 4
S_REF = 48      # every reference forward runs at this length: one compile
#: (prompt length, max_new): 4 x the window, mid-block ends, three chunks and
#: more, different lengths in one batch, one shorter than the window
SHAPES = [(37, 6), (22, 9), (5, 12), (19, 7)]


def _hp(cfg=CFG, **over):
    hp = {k: (list(v) if isinstance(v, tuple) else v)
          for k, v in dataclasses.asdict(cfg).items() if k != "dtype"}
    return dict(hp, **over)


HP = _hp()


@pytest.fixture(scope="module")
def params():
    return mellum2_init(jax.random.PRNGKey(0), CFG)


def _padded(tokens, n=S_REF):
    """A causal model's earlier positions do not see what follows them."""
    out = np.zeros(n, np.int32)
    out[:len(tokens)] = tokens
    return jnp.asarray(out)


def _requests(seed=0, shapes=SHAPES):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, max_new=m,
                    prompt=rng.integers(0, CFG.vocab_size, n)
                    .astype(np.int32))
            for i, (n, m) in enumerate(shapes)]


def _sched(params, **kw):
    kw = dict(dict(max_batch=3, block_size=BS, pool_blocks=64,
                   prefill_chunk=CHUNK), **kw)
    return Scheduler(params, CFG, **kw)


@pytest.fixture(scope="module")
def served(params):
    """SHAPES through one Scheduler, three rows a decode step, chunks of 8:
    the requests, the results, the most window blocks any one request ever
    held while decoding and at all, and the scheduler."""
    sched = _sched(params)
    reqs = _requests()
    for r in reqs:
        sched.submit(r)
    decoding = at_all = 0
    while not sched.finished:
        sched.step()
        for run in sched._running:
            held = len(sched.cache._wtables.get(run.req.rid, ()))
            at_all = max(at_all, held)
            if run.state == "decode":
                decoding = max(decoding, held)
    sched.flush_stats()
    import byteps_tpu

    return (reqs, sched.results, (decoding, at_all), sched,
            byteps_tpu.metrics_snapshot()["metrics"])


def test_reference_imports_nothing_from_the_program():
    tree = ast.parse(open(ref.__file__).read())
    names = [n.module or "" for n in ast.walk(tree)
             if isinstance(n, ast.ImportFrom)]
    names += [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
              for a in n.names]
    assert names and not [n for n in names if n.startswith("byteps_tpu")]


def test_model_forward_equals_the_reference(params):
    toks = np.random.default_rng(1).integers(0, CFG.vocab_size, S_REF)
    with jax.default_matmul_precision("highest"):
        got = mellum2_apply(params, jnp.asarray(toks)[None], CFG)[0]
    want, lo, _ = ref.forward(params, jnp.asarray(toks), HP, qb=QB)
    assert lo == 0
    # f32 against f32: the orders of summation differ, nothing else
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    # the tail alone, through the plan of what each layer has to produce: the
    # last layer is full, so only its own queries are fewer
    tail, lo, layers = ref.forward(params, jnp.asarray(toks), HP, n_tail=4,
                                   qb=QB)
    assert lo == 44 and [l["out_lo"] for l in layers] == [0, 0, 0, 44]
    np.testing.assert_allclose(tail, want[lo:], atol=1e-6, rtol=1e-6)


# -- rotation ----------------------------------------------------------------
@pytest.mark.parametrize("i,want", [
    (10, 0.12868737343265052),          # below lo = 18: plain RoPE's
    (26, 0.0027043825167258223),        # on the ramp: 8/17 interpolated
    (40, 1.7140510979762956e-05),       # above hi = 35: 16 times slower
    (63, 1.5344629944572555e-07),       # the last pair
], ids=["below_lo", "on_the_ramp", "above_hi", "last"])
def test_yarn_frequencies_at_the_published_parameters(i, want):
    """``500000^(-2i/128)``, mixed with a sixteenth of itself by ``ramp =
    clip((i - 18) / 17, 0, 1)``: ``d(32) = 18.08`` and ``d(1) = 34.98`` with
    ``d(r) = 128 ln(8192 / (2 pi r)) / (2 ln 500000)``, computed by hand."""
    cfg = Mellum2Config()
    full, sliding = rope_freqs(cfg, FULL), rope_freqs(cfg, SLIDING)
    assert len(full.inv_freq) == 64
    np.testing.assert_allclose(full.inv_freq[i], want, rtol=1e-12)
    np.testing.assert_allclose(sliding.inv_freq[i], 500000.0 ** (-i / 64),
                               rtol=1e-12)
    assert full.factor == 1.2772588722239782 and sliding.factor == 1.0
    np.testing.assert_allclose(full.factor, 0.1 * np.log(16.0) + 1.0,
                               rtol=1e-12)
    # the reference computes its own, and agrees
    inv, f = ref.inv_freq_and_factor(_hp(cfg), "full")
    np.testing.assert_allclose(inv, full.inv_freq, rtol=1e-12)
    assert f == full.factor


def test_a_base_still_rotates_as_it_did():
    """``rope_rotate(x, pos, base)`` computes its frequencies as before; the
    same frequencies as data, factor 1, give the same rotation."""
    from byteps_tpu.models.gpt import RopeFreqs, rope_rotate

    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 6, 2, 8)),
                    jnp.float32)
    pos = jnp.arange(3, 9)
    plain = rope_rotate(x, pos, 10000.0)
    inv = tuple(float(v) for v in 1.0 / (
        10000.0 ** (np.arange(4, dtype=np.float32) / 4)))
    np.testing.assert_allclose(rope_rotate(x, pos, RopeFreqs(inv)), plain,
                               atol=1e-6)
    np.testing.assert_allclose(rope_rotate(x, pos, RopeFreqs(inv, 1.5)),
                               1.5 * plain, atol=1e-6)


# -- routing -----------------------------------------------------------------
def test_softmax_topk_weights_sum_to_one_and_match_the_reference(params):
    toks = np.random.default_rng(2).integers(0, CFG.vocab_size, S_REF)
    _, _, layers = ref.forward(params, jnp.asarray(toks), HP, qb=QB)
    for li in (0, 3):
        idx, w = softmax_topk_route(layers[li]["router_input"],
                                    params["blocks"][li]["moe"]["wg"],
                                    CFG.top_k)
        np.testing.assert_allclose(jnp.sum(w, -1), 1.0, atol=1e-6)
        np.testing.assert_array_equal(
            np.sort(idx, -1), np.sort(layers[li]["router_picks"], -1))
        want_idx, want_w = ref.route(
            layers[li]["router_input"], params["blocks"][li]["moe"]["wg"], HP)
        np.testing.assert_allclose(
            np.take_along_axis(np.asarray(w), np.argsort(idx, -1), -1),
            np.take_along_axis(np.asarray(want_w),
                               np.argsort(want_idx, -1), -1), atol=1e-6)


def test_one_dispatch_path_for_both_routing_rules():
    """The rule is an argument: the same tree (with a bias leaf) under both
    rules goes through the same dispatch and combine; softmax's weights sum to
    ``scale``, and a tree without a bias leaf serves it."""
    from byteps_tpu.parallel.moe import moe_dropless_init, moe_ffn_dropless

    p = moe_dropless_init(jax.random.PRNGKey(3), 16, 8, 8, 8)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(12, 16)),
                    jnp.float32)
    sig, st_sig, _ = moe_ffn_dropless(x, p, 2, 1.0, row_tile=8)
    bare = {k: v for k, v in p.items() if k != "router_bias"}
    soft, st_soft, load = moe_ffn_dropless(x, bare, 2, 1.0, row_tile=8,
                                           route="softmax")
    assert st_sig[0] == st_soft[0] == 24 and float(load.sum()) == 24
    assert float(jnp.abs(sig - soft).max()) > 0
    # by hand
    idx, w = softmax_topk_route(x, p["wg"], 2)
    want = sum(w[:, j, None] * (
        (jax.nn.silu(jnp.einsum("td,tdf->tf", x, p["w1"][idx[:, j]]))
         * jnp.einsum("td,tdf->tf", x, p["w3"][idx[:, j]]))[:, None, :]
        @ p["w2"][idx[:, j]])[:, 0] for j in range(2))
    np.testing.assert_allclose(soft, want, atol=1e-6)
    with pytest.raises(KeyError):
        moe_ffn_dropless(x, bare, 2, 1.0, row_tile=8)


# -- the served path ---------------------------------------------------------
@pytest.mark.parametrize("i", range(len(SHAPES)),
                         ids=[f"p{n}_n{m}" for n, m in SHAPES])
def test_scheduler_prefill_then_decode_equals_the_reference(params, served, i):
    """Chunked prefill, then packed decode beside other requests: at EVERY
    generated position the served token's logit, in the reference's one full
    forward over prompt + emitted, is the largest to within f32's summation
    order (1e-4 on logits of size ~1)."""
    reqs, results, _, _, _ = served
    r, emitted = reqs[i], np.asarray(results[i]["emitted"])
    assert len(emitted) == r.max_new
    full = np.concatenate([r.prompt, emitted])
    logits, _, _ = ref.forward(params, _padded(full), HP, qb=QB)
    rows = np.asarray(logits)[len(r.prompt) - 1:len(full) - 1]
    gap = rows.max(-1) - rows[np.arange(len(emitted)), emitted]
    assert gap.max() <= 1e-4, gap


def _programs(params, prompt, n_new, rows=2):
    """One request through the family's two programs and a two-kind cache, as
    the scheduler drives them (tables of two lines, blocks given back behind
    each chunk and step), ``rows`` copies of it decoding together: the logits
    of the final chunk's last position and of every decode step, row 0."""
    fam = WindowedKVFamily()
    cache = PagedKVCache(
        CFG, block_size=BS, pool_blocks=64, max_batch=rows,
        layout=lambda bs, nb: fam.layout(
            params, CFG, block_size=bs, pool_blocks=nb, max_batch=rows,
            prefill_chunk=CHUNK, quant=False))
    n, W = len(prompt), 16
    for rid in range(rows):
        cache.register(rid)
        cache.ensure(rid, n + n_new)
        done = 0
        while done < n:
            C = min(CHUNK, n - done)
            cache.ensure_window(rid, done + C)
            logits, cache.state = fam.prefill_fn(CFG, BS, C, None, True)(
                params, cache.state, prompt[None, done:done + C],
                np.int32(done), cache.table_row(rid, W))
            done += C
            cache.release_behind(rid, done)
    out = [np.asarray(logits[0, -1])]
    step = fam.decode_fn(CFG, BS, None, None)
    tok, pos = int(out[0].argmax()), n
    for _ in range(n_new - 1):
        for rid in range(rows):
            cache.ensure_window(rid, pos + 1)
        tables = np.stack([cache.table_row(rid, W) for rid in range(rows)])
        logits, cache.state = step(
            params, cache.state, np.full(rows, tok, np.int32),
            np.full(rows, pos, np.int32), tables)
        np.testing.assert_array_equal(logits[0], logits[rows - 1])
        out.append(np.asarray(logits[0]))
        tok, pos = int(out[-1].argmax()), pos + 1
        for rid in range(rows):
            cache.release_behind(rid, pos)
    per_req = -(-(CFG.window - 1) // BS) + 2
    assert all(len(t) <= per_req for t in cache._wtables.values())
    for rid in range(rows):
        cache.release(rid)
    assert cache.leaked_blocks() == 0
    return np.stack(out)


def test_programs_logits_equal_the_reference_and_a_window_off_by_one_does_not(
        params):
    """The logits themselves, f32 against f32 (2e-4: the flash twin, the
    gathered window and the reference sum in three orders): a 37-token prompt
    (4 windows, ends mid-block, five chunks) then 8 decode steps, two rows
    together. The same served logits against a reference whose window is one
    key longer or shorter are off by far more: the tolerance holds the
    window to the key."""
    prompt = np.random.default_rng(4).integers(0, CFG.vocab_size, 37) \
        .astype(np.int32)
    got = _programs(params, prompt, 9)
    full = np.concatenate([prompt, got.argmax(-1)[:-1].astype(np.int32)])
    worst = {}
    for window in (CFG.window - 1, CFG.window, CFG.window + 1):
        logits, _, _ = ref.forward(params, _padded(full),
                                   _hp(window=window), qb=QB)
        want = np.asarray(logits)[36:36 + len(got)]
        worst[window] = float(np.abs(got - want).max())
    assert worst[CFG.window] <= 2e-4, worst
    assert min(worst[CFG.window - 1], worst[CFG.window + 1]) > 20 * 2e-4, \
        worst


@pytest.mark.parametrize("over,why", [
    ({"yarn": False}, "full layers rotated as sliding ones"),
    ({"attention_factor": 1.0}, "YaRN's frequencies without its factor"),
    ({"renormalize": False}, "top-k weights as the softmax gave them"),
], ids=["no_yarn", "no_attention_factor", "no_renormalisation"])
def test_a_reference_off_by_design_is_told_apart(params, over, why):
    """What the chip's limits are set against (``controls/
    mellum2_limits.py``), at the tiny size: each departure moves the logits
    by several times what the served path may differ from the reference by
    (2e-4). Not by orders: at init std 0.02 the scores are small and the
    softmax nearly flat, so where a key sits moves little — which is why the
    chip's comparison holds the rotation by the cached k rows, not by logits
    (``test_pool_rows_are_the_references_cache``)."""
    toks = np.random.default_rng(5).integers(0, CFG.vocab_size, S_REF)
    want, _, _ = ref.forward(params, jnp.asarray(toks), HP, qb=QB)
    off, _, _ = ref.forward(params, jnp.asarray(toks), _hp(**over), qb=QB)
    assert float(jnp.abs(off - want).max()) > 5 * 2e-4, why


# -- the window kind of k/v page ---------------------------------------------
def test_window_blocks_are_bounded_by_the_window_and_none_leaks(served):
    """A request of four windows never holds more window blocks while it
    decodes than ``ceil((window - 1) / bs) + 2``, nor more than the chunk's
    beside them while it prefills; completion gives every block of both
    kinds back."""
    _, _, (decoding, at_all), sched, snap = served
    per_req = -(-(CFG.window - 1) // BS) + 2
    assert 0 < decoding <= per_req
    assert at_all <= per_req + CHUNK // BS
    assert per_req < -(-(37 + 6) // BS)          # fewer than the context's
    assert sched.cache.window_blocks == window_pool_blocks(
        CFG.window, BS, 3, CHUNK)
    assert sched.cache.leaked_blocks() == 0
    assert sched.cache.window_blocks_in_use == 0
    assert sched.cache.blocks_in_use == 0
    c, h = snap["counters"], snap["histograms"]
    assert c["serve.cache.window_blocks_released"] > 0
    # a layer: one full layer reads more keys than one of the three sliding
    assert c["serve.kv.decode_keys_read.full"] \
        > c["serve.kv.decode_keys_read.window"] / 3 > 0
    assert c["serve.attn.prefill_pairs.full"] > 0
    assert c["serve.attn.prefill_pairs.window"] > 0
    assert h["moe.experts_hit"]["count"] == h["moe.pairs_here"]["count"] > 0
    assert 1 <= h["moe.experts_hit"]["min"] \
        and h["moe.experts_hit"]["max"] <= CFG.n_experts


def test_what_the_programs_count_is_what_the_shapes_say(params):
    """One request alone: 22 prompt tokens in chunks of 8, then 4 decode
    steps. Pairs and keys by hand, a layer kind each (1 full, 3 sliding)."""
    reg = get_registry()
    names = ("serve.attn.prefill_pairs.full",
             "serve.attn.prefill_pairs.window",
             "serve.kv.decode_keys_read.full",
             "serve.kv.decode_keys_read.window")
    before = [reg.counter(n).value() for n in names]
    sched = _sched(params, max_batch=2)
    sched.serve(_requests(6, [(22, 5)]))
    sched.flush_stats()
    got = [reg.counter(n).value() - b for n, b in zip(names, before)]
    w = CFG.window
    assert got == [
        sum(t + 1 for t in range(22)),
        3 * sum(min(t + 1, w) for t in range(22)),
        sum(p + 1 for p in range(22, 26)),
        3 * sum(min(p + 1, w) for p in range(22, 26))]


def test_preemption_and_cancel_give_both_kinds_back(params):
    """Two requests of 12 + 12 tokens in a global pool of 9 blocks: the
    younger is preempted, gives back its blocks of both kinds and resumes by
    recompute — still the reference's tokens. Then two more are dropped
    mid-decode (a replica drained): nothing leaks."""
    reqs = _requests(11, [(12, 12), (12, 12)])
    sched = _sched(params, max_batch=2, pool_blocks=10)
    out = sched.serve(reqs)
    assert sum(r["preemptions"] for r in out.values()) > 0
    for r in reqs:
        emitted = np.asarray(out[r.rid]["emitted"])
        logits, _, _ = ref.forward(
            params, _padded(np.concatenate([r.prompt, emitted])), HP, qb=QB)
        rows = np.asarray(logits)[11:23]
        assert (rows.max(-1) - rows[np.arange(12), emitted]).max() <= 1e-4
    assert sched.cache.leaked_blocks() == 0
    assert sched.cache.window_blocks_in_use == 0
    sched = _sched(params, max_batch=2)
    for r in _requests(12, [(20, 30), (9, 30)]):
        sched.submit(r)
    while not (len(sched._running) == 2 and all(
            r.state == "decode" and len(r.emitted) > 3
            for r in sched._running)):
        sched.step()
    assert sched.cache.window_blocks_in_use > 0
    assert len(sched.drain_incomplete()) == 2
    assert sched.cache.leaked_blocks() == 0
    assert sched.cache.window_blocks_in_use == 0
    assert sched.cache.blocks_in_use == 0


@pytest.mark.parametrize("feature,kw", [
    ("prefix_cache", dict(prefix_cache=True)),
    ("quant_cache", dict(quant_cache=True)),
    ("role", dict(role="prefill")),
    ("role", dict(role="decode")),
    ("tp_axis", dict(tp_axis="tp")),
    ("adapter_pool", dict(adapter_pool=object())),
], ids=["prefix_cache", "int8_pool", "role_prefill", "role_decode", "tp",
        "lora"])
def test_what_two_kinds_of_page_do_not_carry_is_refused(params, feature, kw):
    assert feature in WindowedKVFamily.REFUSED
    with pytest.raises(NotImplementedError, match=feature):
        _sched(params, max_batch=2, pool_blocks=16, **kw)


def test_speculation_is_refused_at_submit(params):
    from byteps_tpu.serve import SpecPolicy

    sched = _sched(params, max_batch=2, pool_blocks=16)
    with pytest.raises(NotImplementedError, match="speculation"):
        sched.submit(Request(rid=0, prompt=np.zeros(4, np.int32), max_new=2,
                             spec=SpecPolicy("lookup", spec_len=2)))


def test_the_gpt_family_says_who_serves_experts():
    from byteps_tpu.models import MoEGPTConfig, moe_gpt_init

    cfg = MoEGPTConfig.tiny()
    with pytest.raises(NotImplementedError, match="Mellum2Config"):
        Scheduler(moe_gpt_init(jax.random.PRNGKey(0), cfg), cfg, max_batch=2,
                  block_size=4, pool_blocks=16, prefill_chunk=8)


# -- kernels against their twins (interpret mode) ----------------------------
def _windowed_twin(q, k_pool, v_pool, tables, lens, first, layer):
    """Row ``r``: softmax over the keys ``first[r] <= p < lens[r]``, read
    through the table, query head ``j`` on kv head ``j // G``."""
    R, H, D = q.shape
    bs, HD = k_pool.shape[2:]
    Hkv = HD // D
    out = np.zeros((R, H, D), np.float32)
    for r in range(R):
        at = np.arange(first[r], lens[r])
        kk = np.asarray(k_pool, np.float32)[layer, tables[r, at // bs],
                                            at % bs].reshape(-1, Hkv, D)
        vv = np.asarray(v_pool, np.float32)[layer, tables[r, at // bs],
                                            at % bs].reshape(-1, Hkv, D)
        for h in range(H):
            s = kk[:, h // (H // Hkv)] @ np.asarray(q, np.float32)[r, h] \
                / np.sqrt(D)
            p = np.exp(s - s.max())
            out[r, h] = (p / p.sum()) @ vv[:, h // (H // Hkv)]
    return out


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
def test_paged_decode_with_a_first_key_against_its_twin(monkeypatch, dtype,
                                                        tol):
    """32 query heads on 4 kv heads of 128, blocks of 16, a window of 40 keys:
    rows shorter than the window, rows several windows long whose released
    blocks read 0 in the table (the scratch block, poisoned here), a first
    key inside a block and on a block's edge, a padded row."""
    monkeypatch.setenv("BYTEPS_KERNEL_BACKEND", "pallas")
    rng = np.random.default_rng(7)
    H, Hkv, D, bs, window, W, NB = 32, 4, 128, 16, 40, 16, 40
    lens = np.array([7, 40, 41, 56, 133, 250, 1], np.int32)
    first = np.maximum(lens - window, 0).astype(np.int32)
    shape = (3, NB, bs, Hkv * D)
    k = np.asarray(rng.standard_normal(shape), np.float32)
    v = np.asarray(rng.standard_normal(shape), np.float32)
    k[:, 0], v[:, 0] = np.nan, np.inf        # what a released block reads
    tables = np.zeros((len(lens), W), np.int32)
    free = list(rng.permutation(np.arange(1, NB)))
    for r, n in enumerate(lens):
        for b in range(first[r] // bs, -(-n // bs)):   # the blocks still held
            tables[r, b] = free.pop()
    k, v = jnp.asarray(k, dtype), jnp.asarray(v, dtype)
    q = jnp.asarray(rng.standard_normal((len(lens), H, D)), dtype)
    got = paged_attention_decode(q, k, v, jnp.asarray(tables),
                                 jnp.asarray(lens), 1,
                                 first=jnp.asarray(first))
    assert got.dtype == q.dtype and got.shape == q.shape
    want = _windowed_twin(q, k, v, tables, lens, first, 1)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=tol,
                               rtol=tol)
    # first = 0 for every row is the kernel without a window
    plain = paged_attention_decode(q[:3], k, v, jnp.asarray(tables[:3]),
                                   jnp.asarray(lens[:3]), 1)
    zero = paged_attention_decode(q[:3], k, v, jnp.asarray(tables[:3]),
                                  jnp.asarray(lens[:3]), 1,
                                  first=jnp.zeros(3, jnp.int32))
    np.testing.assert_allclose(np.asarray(zero, np.float32),
                               np.asarray(plain, np.float32), atol=tol)


@pytest.mark.parametrize("backend", ["pallas", "jnp"])
@pytest.mark.parametrize("pos0", [0, 5, 40])
def test_flash_with_a_window_and_fewer_kv_heads(monkeypatch, backend, pos0):
    """8 query heads on 2 kv heads: the keys of 24 positions before the
    queries laid out first, a window of 17 (some of the 24 are behind it)."""
    monkeypatch.setenv("BYTEPS_KERNEL_BACKEND", backend)
    rng = np.random.default_rng(pos0)
    P, S, H, Hkv, D, window = 24, 64, 8, 2, 128, 17
    q = jnp.asarray(rng.normal(size=(1, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, S + P, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, S + P, Hkv, D)), jnp.float32)
    got = flash_attention_window(q, k, v, pos0, pos0 - P, window)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, H // Hkv, 2)) \
        / np.sqrt(D)
    rows = pos0 + np.arange(S)[:, None]
    cols = pos0 - P + np.arange(S + P)[None, :]
    ok = (cols <= rows) & (rows - cols < window) & (cols >= 0)
    want = jnp.einsum("bhqk,bkhd->bqhd",
                      jax.nn.softmax(jnp.where(ok, s, -1e30), -1),
                      jnp.repeat(v, H // Hkv, 2))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_scheduler_tokens_and_counter_across_backends(monkeypatch):
    """Shapes the kernels take (2 kv heads of 64, blocks of 8, a window of 17,
    chunks of 16): the same requests under ``BYTEPS_KERNEL_BACKEND=pallas``
    (every kernel, interpreted) and under jnp (every twin) give the same
    greedy tokens, and ``serve.decode_steps_paged_attn`` counts every decode
    step of the first and none of the second."""
    cfg = Mellum2Config.tiny(n_heads=4, n_kv_heads=2, head_dim=64, window=17,
                             max_seq=128)
    params = mellum2_init(jax.random.PRNGKey(1), cfg)
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
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
        out = sched.serve([Request(rid=f"r{i}", prompt=p, max_new=5 + i)
                           for i, p in enumerate(prompts)])
        assert sched.cache.leaked_blocks() == 0
        return ({r: np.asarray(o["emitted"]) for r, o in out.items()},
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
        np.testing.assert_array_equal(got[rid], want[rid])


# -- the benchmark's read-back -----------------------------------------------
@pytest.fixture(scope="module")
def taken(params):
    """``drivers/serve_mellum2.py::take_running``: SHAPES served until the
    37-token request has decoded three tokens, then its pages of both kinds
    as the programs left them."""
    from benchmark.drivers.serve_mellum2 import take_running

    sched = _sched(params)
    for r in _requests():
        sched.submit(r)
    while not any(r.state == "decode" and len(r.req.prompt) == 37
                  and len(r.emitted) >= 3 for r in sched._running):
        sched.step()
    got = take_running(sched, CFG, 37, np.random.default_rng(0))
    assert got["cached"] == 37 + len(got["emitted"])
    assert got["k"].shape[:2] == (1, got["cached"])
    assert got["wk"].shape[:2] == (3, CFG.window - 1) and got["w_lo"] > 28
    return got, np.concatenate([got["prompt"], got["emitted"]])


@pytest.mark.parametrize("over,low,high", [
    (None, 0.0, 1e-5), ({"cache_round": "bfloat16"}, 5e-4, 4e-3),
    ({"cache_round": "int8_rows"}, 2e-3, 1e-2),
    ({"yarn": False}, 0.3, 2.0)],
    ids=["as_served", "bf16", "int8", "no_yarn"])
def test_pool_rows_are_the_references_cache(params, taken, over, low, high):
    """What the timed programs leave in the pools is what the reference says
    a cache holds, layer by layer and kind by kind; a reference whose cache is
    a narrower type reads that type's rounding on layer 0, one whose full
    layers rotate without YaRN reads other rows altogether on the full layer
    and the same rows on layer 0."""
    from benchmark.drivers.serve_mellum2 import pool_errors

    got, full = taken
    _, _, layers = ref.forward(params, _padded(full), _hp(**(over or {})),
                               n_tail=S_REF - 36, qb=QB)
    err = pool_errors(CFG, got, layers)
    assert len(err["row_errs_by_layer"]) == CFG.n_layers
    if over == {"yarn": False}:
        assert err["kv_row_err"] <= 1e-5 and low <= err["full_row_err"] <= high
    else:
        assert low <= err["kv_row_err"] <= high, err
        assert low <= err["full_row_err"] <= max(20 * high, 1e-5), err
        assert low <= err["deep_row_err"] <= max(20 * high, 1e-5), err
    # pages one position stale are rows of another token altogether
    stale = pool_errors(CFG, got, layers, shift=1)
    assert min(stale["full_row_err"], stale["deep_row_err"]) > 0.5
