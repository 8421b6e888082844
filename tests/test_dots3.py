"""dots3-note-prev at tiny sizes, every mechanism live: a window (9) and an
``index_topk`` (12) both shorter than the sequences, heads of two counts,
16 experts top-4 with a share of them held. The model, the serve tier's
absorbed form over latent pages, the kernels and the cache's window kind,
each held to the plain reference (``benchmark/configs/dots3_reference.py``)
or to its jnp twin."""

import ast
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.configs import dots3_reference as ref
from byteps_tpu.common.metrics import get_registry
from byteps_tpu.models import dots3
from byteps_tpu.models.dots3 import (
    FULL,
    SLIDING,
    Dots3Config,
    dots3_apply,
    dots3_init,
)
from byteps_tpu.models.gpt import _rmsnorm
from byteps_tpu.ops import dsa_index
from byteps_tpu.ops.flash_attention import flash_attention_window
from byteps_tpu.parallel.moe import sigmoid_topk_route
from byteps_tpu.serve import Request, Scheduler
from byteps_tpu.serve.latent_step import _pick_rows, select_mask

CFG = Dots3Config.tiny(experts_held=8, first_expert=4)
HP = dataclasses.asdict(CFG)
QB = 4
#: (prompt length, max_new): past the window and past index_topk, of
#: different lengths in one batch, one short
SHAPES = [(28, 6), (8, 10), (20, 8), (40, 5)]


@pytest.fixture(scope="module")
def params():
    return dots3_init(jax.random.PRNGKey(0), CFG)


S_REF = 48      # every reference forward runs at this length: one compile


def _padded(tokens):
    """A causal model's earlier positions do not see what follows them."""
    out = np.zeros(S_REF, np.int32)
    out[:len(tokens)] = tokens
    return jnp.asarray(out)


def _requests(seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, max_new=m,
                    prompt=rng.integers(0, CFG.vocab_size, n)
                    .astype(np.int32))
            for i, (n, m) in enumerate(SHAPES)]


@pytest.fixture(scope="module")
def served(params):
    """SHAPES through one Scheduler, three rows a decode step, chunks of 8:
    the results, the most window blocks ever held, and the scheduler."""
    sched = Scheduler(params, CFG, max_batch=3, block_size=4, pool_blocks=64,
                      prefill_chunk=8)
    reqs = _requests()
    for r in reqs:
        sched.submit(r)
    peak = 0
    while not sched.finished:
        sched.step()
        peak = max(peak, sched.cache.window_blocks_in_use)
    sched.flush_stats()
    import byteps_tpu

    return (reqs, sched.results, peak, sched,
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
        got = dots3_apply(params, jnp.asarray(toks)[None], CFG)[0]
    want, lo, _ = ref.forward(params, jnp.asarray(toks), HP, qb=QB)
    assert lo == 0
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    # the tail alone, through the plan of what each layer has to produce
    tail, lo, layers = ref.forward(params, jnp.asarray(toks), HP, n_tail=4,
                                   qb=QB)
    # the three sliding layers work on one span of positions: one program
    assert lo == 44 and [l["out_lo"] for l in layers] == [0, 20, 20, 20, 20]
    np.testing.assert_allclose(tail, want[lo:], atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("i", range(len(SHAPES)),
                         ids=[f"p{n}_n{m}" for n, m in SHAPES])
def test_scheduler_prefill_then_decode_equals_the_reference(params, served, i):
    """Chunked prefill, then packed decode beside other requests: at the
    first, a middle and the last generated position the served token is the
    reference's argmax of one full forward over prompt + emitted."""
    reqs, results, _, _, _ = served
    r, emitted = reqs[i], results[i]["emitted"]
    assert len(emitted) == r.max_new
    full = np.concatenate([r.prompt, emitted])
    logits, _, _ = ref.forward(params, _padded(full), HP, qb=QB)
    logits = np.asarray(logits)
    for j in sorted({0, len(emitted) // 2, len(emitted) - 1}):
        row = logits[len(r.prompt) + j - 1]
        assert row.max() - row[emitted[j]] <= 1e-4, (j, row.max(),
                                                     row[emitted[j]])


@pytest.mark.parametrize("kind", [FULL, SLIDING])
def test_absorbed_and_materialised_attention_agree(params, kind):
    """One layer's attention two ways: ``attention_dense`` (k and v
    materialised, dense masks) and queries absorbed into the latent's width
    over each query's own rows."""
    li = CFG.layers_of(kind)[-1]
    p, a = params["blocks"][li], CFG.dims(kind)
    S = 24
    h = jax.random.normal(jax.random.PRNGKey(2), (1, S, CFG.d_model))
    pos = jnp.arange(S)
    with jax.default_matmul_precision("highest"):
        want = dots3.attention_dense(h, p, pos, CFG, kind)
        c_q, q, c_kv, k_rope = dots3.latents(h, p, pos, CFG, kind)
        rows_all = dots3.cache_row(c_kv, k_rope[:, :, 0], a)[0]
        if kind == FULL:
            qi, w = dots3.index_queries(c_q, h, p["idx"], pos, CFG)
            ki = dots3.index_keys(h, p["idx"], pos, CFG)
            sc = dsa_index.index_scores_jnp(qi[0], ki[0], w[0], 0)
            sel, valid = _pick_rows(sc, CFG.index_topk)
        else:
            sel = pos[:, None] - (a.window - 1) + jnp.arange(a.window)[None]
            valid, sel = sel >= 0, jnp.maximum(sel, 0)
        o = dots3.unabsorb_v(dots3.latent_attend(
            dots3.absorb_q(q[0], p, a), rows_all[sel], valid, a), p, a)
        got = dots3.headwise_gate(o[None], h, p)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-5)


def _program_selection(params, x_in, keys_from=None):
    """The serve path's picked sets for every position of ``x_in (S, d)``,
    the first full layer: twin scores, then ``top_k`` as the decode step
    picks and the sort-free mask as the chunk program does, which agree."""
    p = params["blocks"][0]
    S = x_in.shape[0]
    pos = jnp.arange(S)
    h = _rmsnorm(x_in[None], p["ln1_g"], eps=CFG.norm_eps)
    c_q = dots3.latents(h, p, pos, CFG, FULL)[0]
    qi, w = dots3.index_queries(c_q, h, p["idx"], pos, CFG)
    ki = dots3.index_keys(h if keys_from is None else keys_from, p["idx"],
                          pos, CFG)
    sc = dsa_index.index_scores_jnp(qi[0], ki[0], w[0], 0)
    sel, valid = _pick_rows(sc, CFG.index_topk)           # the decode step's
    picks = [set(np.asarray(s)[np.asarray(v)].tolist())
             for s, v in zip(sel, valid)]
    mask = np.asarray(select_mask(sc, CFG.index_topk))    # the chunk's
    assert picks == [set(np.flatnonzero(m).tolist()) for m in mask]
    return picks


def test_selected_set_is_the_references(params):
    toks = np.random.default_rng(3).integers(0, CFG.vocab_size, S_REF)
    _, _, layers = ref.forward(params, jnp.asarray(toks), HP, qb=QB)
    want = [set(r[r >= 0].tolist()) for r in np.asarray(layers[0]["selected"])]
    with jax.default_matmul_precision("highest"):
        got = _program_selection(params, layers[0]["input"])
    assert got == want
    assert len(want[-1]) == CFG.index_topk < S_REF      # a real selection


@pytest.mark.parametrize("fault", ["stale_cache", "window_off_by_one"])
def test_a_selection_off_by_design_fails(params, fault):
    """What the comparison is for: indexer keys one position stale pick
    other sets, and a window one key short moves the logits past the
    tolerance the scheduler test holds."""
    toks = np.random.default_rng(3).integers(0, CFG.vocab_size, S_REF)
    logits, _, layers = ref.forward(params, jnp.asarray(toks), HP, qb=QB)
    if fault == "stale_cache":
        want = [set(r[r >= 0].tolist())
                for r in np.asarray(layers[0]["selected"])]
        x = layers[0]["input"]
        stale = _rmsnorm(jnp.roll(x, 1, axis=0)[None],
                         params["blocks"][0]["ln1_g"], eps=CFG.norm_eps)
        with jax.default_matmul_precision("highest"):
            got = _program_selection(params, x, keys_from=stale)
        assert sum(a != b for a, b in zip(got, want)) > 8
    else:
        short, _, _ = ref.forward(params, jnp.asarray(toks),
                                  dict(HP, window=CFG.window - 1), qb=QB)
        assert float(jnp.abs(short - logits)[CFG.window:].max()) > 1e-3


def test_window_layers_release_their_blocks(served):
    """A long request's window blocks are bounded by the window, not by its
    length; nothing leaks."""
    reqs, _, peak, sched, snap = served
    bs = sched.cache.block_size
    per_req = -(-(CFG.window - 1) // bs) + 2
    chunk = -(-sched.prefill_chunk // bs)
    # three decode rows + the standby, the chunk's own blocks once
    assert 0 < peak <= 4 * per_req + chunk
    assert peak < sum(-(-(n + m) // bs) for n, m in SHAPES)
    assert sched.cache.leaked_blocks() == 0
    assert sched.cache.window_blocks_in_use == 0
    assert sched.cache.blocks_in_use == 0
    assert snap["counters"]["serve.cache.window_blocks_released"] > 0
    assert snap["counters"]["serve.dsa.selected_keys"] > 0
    assert snap["histograms"]["serve.dsa.selected_per_query"]["count"] > 0
    assert snap["histograms"]["moe.pairs_here"]["count"] > 0


def test_window_blocks_go_back_when_their_step_is_read(params, served):
    """With a step's tokens read one step late, the window blocks behind
    it go back one step late too: ``release_behind`` is called at the
    commit, with the fill of the step being committed, while the run's
    ``cache_len`` already counts the step issued after it. Same tokens as
    the fixture's (the reference's), nothing leaked."""
    reqs, want, _, _, _ = served
    sched = Scheduler(params, CFG, max_batch=3, block_size=4, pool_blocks=64,
                      prefill_chunk=8)
    calls = []
    release = sched.cache.release_behind

    def watched(rid, fill):
        calls.append((fill, sched._runs[rid].cache_len,
                      sched._runs[rid].state))
        return release(rid, fill)

    sched.cache.release_behind = watched
    got = sched.serve(_requests())
    for r in reqs:
        np.testing.assert_array_equal(got[r.rid]["emitted"],
                                      want[r.rid]["emitted"])
    decode = [(fill, n) for fill, n, state in calls if state == "decode"]
    # a step read behind the next one: the host's count is one ahead
    assert sum(1 for fill, n in decode if n == fill + 1) > len(decode) // 2
    assert all(n in (fill, fill + 1) for fill, n in decode)
    assert sched.cache.leaked_blocks() == 0
    assert sched.cache.window_blocks_in_use == 0
    snap = get_registry().snapshot()["counters"]
    assert snap["serve.decode_steps_overlapped"] > 0
    assert snap["serve.cache.window_blocks_released"] > 0


def test_preemption_and_resume_give_both_kinds_back(params):
    """Two requests of 12 + 12 tokens in a pool of 9 blocks: both are
    admitted on 4 blocks, both need a fifth at token 17 and one is free, so
    the younger is preempted, gives back its blocks of both kinds and
    resumes by recompute — still the reference's tokens, nothing leaked."""
    rng = np.random.default_rng(11)
    reqs = [Request(rid=i, max_new=12,
                    prompt=rng.integers(0, CFG.vocab_size, 12)
                    .astype(np.int32)) for i in range(2)]
    sched = Scheduler(params, CFG, max_batch=2, block_size=4, pool_blocks=10,
                      prefill_chunk=8)
    out = sched.serve(reqs)
    assert sum(r["preemptions"] for r in out.values()) > 0
    for r in reqs:
        emitted = out[r.rid]["emitted"]
        logits, _, _ = ref.forward(
            params, _padded(np.concatenate([r.prompt, emitted])), HP, qb=QB)
        picks = np.asarray(logits)[11:23].argmax(-1)
        np.testing.assert_array_equal(emitted, picks)
    assert sched.cache.leaked_blocks() == 0
    assert sched.cache.window_blocks_in_use == 0


@pytest.fixture(scope="module")
def taken(params):
    """The benchmark's read-back (``drivers/serve_dots3.py::take_running``):
    SHAPES served until the 40-token request has decoded three tokens, then
    its pages as the programs left them, beside the reference's forward over
    what it was fed. A decode step is unread when the loop stops, as when
    the benchmark's window closes: it has been fed the newest committed
    token, so the pool holds (once the read-back has waited for it) a row
    for every token of prompt + emitted."""
    from benchmark.drivers.serve_dots3 import take_running

    sched = Scheduler(params, CFG, max_batch=3, block_size=4, pool_blocks=64,
                      prefill_chunk=8)
    for r in _requests():
        sched.submit(r)
    while not any(r.state == "decode" and len(r.req.prompt) == 40
                  and len(r.emitted) >= 3 for r in sched._running):
        sched.step()
    got = take_running(sched, CFG, 40, np.random.default_rng(0))
    assert got["rid"] in sched._flight.rows
    assert got["cached"] == 40 + len(got["emitted"])
    # one program's worth of window blocks, whatever the 40 tokens before
    assert got["wkv"].shape[1] == CFG.window - 1 and got["w_lo"] > 32
    return got, np.concatenate([got["prompt"], got["emitted"]])


@pytest.mark.parametrize("over,low,high", [
    (None, 0.0, 1e-5), ({"cache_round": "bfloat16"}, 5e-4, 4e-3),
    ({"cache_round": "int8_rows"}, 2e-3, 1e-2),
    ({"cache_round": "float8_e4m3fn"}, 1e-2, 6e-2)],
    ids=["as_served", "bf16", "int8", "fp8"])
def test_pool_rows_are_the_references_cache(params, taken, over, low, high):
    """What the timed programs leave in the pool is what the reference says
    a cache holds, layer by layer and kind by kind; a reference whose cache
    is a narrower type reads that type's rounding on the first full layer,
    and no less on the deeper ones."""
    from benchmark.drivers.serve_dots3 import pool_errors

    got, full = taken
    _, _, layers = ref.forward(params, _padded(full), dict(HP, **(over or {})),
                               n_tail=S_REF - 39, qb=QB)
    err = pool_errors(CFG, got, layers)
    assert [len(e) for e in err["row_errs_by_layer"]] == [
        2 if k == FULL else 1 for k in CFG.layer_types]
    first = (err["latent_row_err"], err["index_key_err"])
    assert all(low <= v <= high for v in first), err
    # deeper layers see the rounding again through what they are given
    assert low <= err["deep_row_err"] <= max(20 * high, 1e-5), err
    # a cache one position stale is rows of another token altogether
    assert pool_errors(CFG, got, layers, shift=1)["deep_row_err"] > 0.5


def test_shares_sum_to_the_uncut_layer():
    """The parts all 16 / 4 shares give, shared expert counted once, add up
    to the uncut reference layer."""
    cfg = Dots3Config.tiny(n_layers=2)
    p = dots3_init(jax.random.PRNGKey(5), cfg)["blocks"][1]
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 12, cfg.d_model))
    h = _rmsnorm(x, p["ln2_g"], eps=cfg.norm_eps)
    shared = ref._swiglu(h[0], p["shared"])
    total = jnp.zeros_like(x[0])
    for first in range(0, cfg.n_routed_experts, 4):
        share = dict(p, moe=dict(p["moe"], **{
            k: p["moe"][k][first:first + 4] for k in ("w1", "w3", "w2")}))
        with jax.default_matmul_precision("highest"):
            y, _ = dots3.ffn(x, share, dataclasses.replace(
                cfg, experts_held=4, first_expert=first))
        total = total + (y - x)[0] - shared
    want, _ = ref._moe(h[0], p["moe"], cfg.top_k, cfg.routed_scaling, 0)
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=2e-4)


def test_router_picks_equal_the_references_on_its_own_input(params):
    toks = np.random.default_rng(7).integers(0, CFG.vocab_size, S_REF)
    _, _, layers = ref.forward(params, jnp.asarray(toks), HP, qb=QB)
    moe = params["blocks"][1]["moe"]
    idx, _ = sigmoid_topk_route(layers[1]["router_input"], moe["wg"],
                                moe["router_bias"], CFG.top_k,
                                CFG.routed_scaling)
    np.testing.assert_array_equal(
        np.sort(idx, -1), np.sort(layers[1]["router_picks"], -1))


@pytest.mark.parametrize("backend", ["pallas", "jnp"])
@pytest.mark.parametrize("pos0", [0, 5, 40])
def test_flash_with_a_window_against_its_twin(monkeypatch, backend, pos0):
    """256-wide q/k, 128-wide v, the keys of ``window - 1`` positions before
    the queries laid out first (padding where they lie before 0)."""
    monkeypatch.setenv("BYTEPS_KERNEL_BACKEND", backend)
    rng = np.random.default_rng(pos0)
    P, S, H = 16, 64, 2
    q = jnp.asarray(rng.normal(size=(1, S, H, 256)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, S + P, H, 256)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, S + P, H, 128)), jnp.float32)
    got = flash_attention_window(q, k, v, pos0, pos0 - P, P + 1)
    # the definition, by hand
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / 16.0
    rows = pos0 + np.arange(S)[:, None]
    cols = pos0 - P + np.arange(S + P)[None, :]
    ok = (cols <= rows) & (rows - cols <= P) & (cols >= 0)
    want = jnp.einsum("bhqk,bkhd->bqhd",
                      jax.nn.softmax(jnp.where(ok, s, -1e30), -1), v)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def _top_k_mask(sc, K):
    """What ``jax.lax.top_k`` picks among live keys, as a mask."""
    N, L = sc.shape
    top, sel = jax.lax.top_k(jnp.asarray(sc), min(K, L))
    want = np.zeros((N, L), bool)
    for t in range(N):
        want[t, np.asarray(sel[t])[np.asarray(top[t]) > -1e29]] = True
    return want


@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "ties"])
def test_select_mask_is_top_k_without_a_sort(ties):
    """The twin. Few live keys, none past position 150, whole rows of one
    value: the mask holds exactly what ``jax.lax.top_k`` returns, ties by
    position."""
    rng = np.random.default_rng(0)
    N, L, K = 37, 200, 24
    sc = rng.normal(size=(N, L)).astype(np.float32)
    if ties:
        sc = np.round(sc * 2) / 2
    sc[:, 150:] = -1e30
    sc[3, 10:] = -1e30
    sc[5, :100] = 0.0
    got = np.asarray(dsa_index.select_mask_jnp(jnp.asarray(sc), K))
    np.testing.assert_array_equal(got, _top_k_mask(sc, K))


def _kernel_calls(monkeypatch):
    """The shapes ``dsa_select_mask`` is traced for from here on."""
    ran, kernel = [], dsa_index._select
    monkeypatch.setattr(dsa_index, "_select", lambda scores, *a: (
        ran.append(scores.shape), kernel(scores, *a))[1])
    return ran


def _select_case(name):
    """``(scores (N, L) f32, topk)``: whole tiles of the kernel."""
    rng = np.random.default_rng(7)
    N, L, K = 64, 384, 24
    if name == "one_sub_tile":
        L = 128
    elif name == "several_trips":          # two trips of sixteen slices
        N, L, K = 32, 4096, 300
    elif name == "topk_covers_every_key":
        L, K = 128, 128
    sc = rng.normal(size=(N, L)).astype(np.float32)
    if name in ("ties", "several_trips"):
        sc = np.round(sc * 2) / 2
    elif name == "a_row_of_one_value":
        sc[5] = 1.5
        sc[6] = -0.25
    elif name == "few_live_keys":
        sc[3, 10:] = -1e30                 # fewer than topk
        sc[4, K:] = -1e30                  # exactly topk
        sc[:, 300:] = -1e30
    elif name == "a_row_with_none":
        sc[0] = sc[9] = sc[63] = -1e30
    elif name == "causal_tail":            # a chunk at pos0 = 200
        sc[np.arange(L)[None, :] > 200 + np.arange(N)[:, None]] = -1e30
    elif name == "signed_zeros":
        sc = rng.choice(np.array([-1.5, -0.0, 0.0, 0.5, -1e-38, 1e-38],
                                 np.float32), size=(N, L))
        sc[:, 350:] = -1e30
    return sc, K


@pytest.mark.parametrize("case", [
    "distinct", "ties", "a_row_of_one_value", "few_live_keys",
    "a_row_with_none", "causal_tail", "signed_zeros", "one_sub_tile",
    "several_trips", "topk_covers_every_key"])
def test_select_kernel_is_its_twin_and_top_k(monkeypatch, case):
    """``dsa_select_mask`` in interpret mode: the same set as the twin's and
    as ``jax.lax.top_k``'s, key for key."""
    monkeypatch.setenv("BYTEPS_KERNEL_BACKEND", "pallas")
    sc, K = _select_case(case)
    assert dsa_index.select_unsupported_reason(*sc.shape) is None
    ran = _kernel_calls(monkeypatch)
    got, picked, _ = dsa_index.select_mask_counted(jnp.asarray(sc), K)
    assert got.dtype == jnp.int8 and got.shape == sc.shape
    assert bool(ran) == (K < sc.shape[1])      # every key: no kernel
    got = np.asarray(got).astype(bool)
    assert int(picked) == got.sum()
    np.testing.assert_array_equal(
        got, np.asarray(dsa_index.select_mask_jnp(jnp.asarray(sc), K)))
    np.testing.assert_array_equal(got, _top_k_mask(sc, K))


def test_select_kernel_declines_what_it_does_not_tile(monkeypatch):
    """37 x 200 is no whole tile: the dispatcher says so and the twin
    runs."""
    monkeypatch.setenv("BYTEPS_KERNEL_BACKEND", "pallas")
    said = []
    monkeypatch.setattr(dsa_index, "note_fallback",
                        lambda *a: said.append(a))
    monkeypatch.setattr(dsa_index, "_select", None)     # not to be called
    sc = np.random.default_rng(0).normal(size=(37, 200)).astype(np.float32)
    sc[:, 150:] = -1e30
    got, picked, tie_tiles = dsa_index.select_mask_counted(jnp.asarray(sc),
                                                           24)
    assert said and said[0][:2] == ("dsa_select_mask", (37, 200))
    assert int(tie_tiles) == 0 and int(picked) == np.asarray(got).sum()
    np.testing.assert_array_equal(np.asarray(got).astype(bool),
                                  _top_k_mask(sc, 24))


def test_select_kernel_counts_the_tiles_that_placed_ties(monkeypatch):
    """The position passes run in a row tile only where some row has more
    keys at or above its threshold than it takes; the kernel says in how
    many, and the series ``serve.dsa.select_tie_tiles`` carries it."""
    from byteps_tpu.serve.latent_step import LateStats

    monkeypatch.setenv("BYTEPS_KERNEL_BACKEND", "pallas")
    rng = np.random.default_rng(2)
    sc = rng.normal(size=(128, 256)).astype(np.float32)     # two tiles
    sc[3, 10:] = -1e30
    sc[70, :] = -1e30
    _, _, untied = dsa_index.select_mask_counted(jnp.asarray(sc), 24)
    assert int(untied) == 0
    sc[100] = np.round(sc[100] * 2) / 2     # the second tile's alone
    got, picked, tied = dsa_index.select_mask_counted(jnp.asarray(sc), 24)
    assert int(tied) == 1
    assert int(picked) == 126 * 24 + 10     # a row of 10 live keys, one of 0
    np.testing.assert_array_equal(np.asarray(got).astype(bool),
                                  _top_k_mask(sc, 24))
    late = LateStats(dots3.MODEL)
    series = get_registry().counter("serve.dsa.select_tie_tiles")
    before = series.value()
    late.observe(dict(dict.fromkeys(late.names, 0.0),
                      **{"dsa.select_tie_tiles": float(tied)}))
    assert series.value() == before + 1


def test_chunk_program_picks_through_the_kernel(monkeypatch):
    """A 100-token prompt in chunks of 32 against 128 cached keys, shapes
    the selection kernel tiles: the chunk programs call it, count its tie
    tiles into ``pool.stats``, and serve the twin's tokens."""
    from byteps_tpu.serve import latent_step

    cfg = Dots3Config.tiny(experts_held=8, first_expert=4, max_seq=128)
    params = dots3_init(jax.random.PRNGKey(0), cfg)
    prompt = np.random.default_rng(0).integers(
        0, cfg.vocab_size, 100).astype(np.int32)

    def run():
        latent_step.make_latent_prefill_fn.cache_clear()
        sched = Scheduler(params, cfg, max_batch=2, block_size=4,
                          pool_blocks=72, prefill_chunk=32)
        sched.submit(Request(rid=0, max_new=3, prompt=prompt))
        while not sched.finished:
            sched.step()
        sched.flush_stats()
        return sched.results[0]["emitted"]

    want = run()
    monkeypatch.setenv("BYTEPS_KERNEL_BACKEND", "pallas")
    ran = _kernel_calls(monkeypatch)
    got = run()
    latent_step.make_latent_prefill_fn.cache_clear()
    assert ran and set(ran) == {(32, 128)}
    np.testing.assert_array_equal(got, want)
    assert "dsa.select_tie_tiles" in latent_step.stats_names(dots3.MODEL)


@pytest.mark.parametrize("pos0", [0, 64])
def test_masked_flash_against_its_twin(monkeypatch, pos0):
    """``mla_sparse_attn`` in interpret mode: 192-wide q/k, 128-wide v, one
    mask for both heads, tiles after the diagonal skipped."""
    from byteps_tpu.ops.flash_attention import (
        attention_masked_jnp,
        flash_attention_masked,
    )

    monkeypatch.setenv("BYTEPS_KERNEL_BACKEND", "pallas")
    rng = np.random.default_rng(pos0)
    Sq, Sk, H = 64, 128, 2
    q = jnp.asarray(rng.normal(size=(1, Sq, H, 192)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, Sk, H, 192)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, Sk, H, 128)), jnp.float32)
    rows = pos0 + np.arange(Sq)
    mask = (rng.random((Sq, Sk)) < 0.3) & (rows[:, None]
                                           >= np.arange(Sk)[None, :])
    mask[np.arange(Sq), rows] = True             # every query keeps a key
    got = flash_attention_masked(q, k, v, jnp.asarray(mask, jnp.int8), pos0,
                                 0, name="mla_sparse_attn")
    want = attention_masked_jnp(q, k, v, jnp.asarray(mask))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("pos0", [0, 300, 768])
def test_index_score_kernel_against_its_twin(monkeypatch, pos0):
    """``dsa_index_scores`` in interpret mode: whole tiles, tiles on and
    past the diagonal."""
    monkeypatch.setenv("BYTEPS_KERNEL_BACKEND", "pallas")
    rng = np.random.default_rng(0)
    C, L, H, D = 256, 1024, 8, 128
    q = jnp.asarray(rng.normal(size=(C, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(L, D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(C, H)), jnp.float32)
    assert dsa_index.unsupported_reason(C, L, H, D) is None
    got = dsa_index.index_scores(q, k, w, pos0)
    want = dsa_index.index_scores_jnp(q, k, w, pos0)
    np.testing.assert_allclose(got, want, atol=1e-3, rtol=1e-5)
    assert float(got[0, pos0 + 1]) < -1e29
    # shapes it declines take the twin (said once)
    assert dsa_index.unsupported_reason(24, 40, 4, 16) is not None


@pytest.mark.parametrize("feature,kw", [
    ("prefix_cache", dict(prefix_cache=True)),
    ("quant_cache", dict(quant_cache=True)),
    ("role", dict(role="prefill")),
    ("role", dict(role="decode")),
    ("tp_axis", dict(tp_axis="tp")),
    ("adapter_pool", dict(adapter_pool=object())),
], ids=["prefix_cache", "int8_pool", "role_prefill", "role_decode", "tp",
        "lora"])
def test_what_the_latent_layout_does_not_carry_is_refused(params, feature, kw):
    with pytest.raises(NotImplementedError, match=feature):
        Scheduler(params, CFG, max_batch=2, block_size=4, pool_blocks=16,
                  prefill_chunk=8, **kw)


def test_speculation_is_refused_at_submit(params):
    from byteps_tpu.serve import SpecPolicy

    sched = Scheduler(params, CFG, max_batch=2, block_size=4, pool_blocks=16,
                      prefill_chunk=8)
    with pytest.raises(NotImplementedError, match="speculation"):
        sched.submit(Request(rid=0, prompt=np.zeros(4, np.int32), max_new=2,
                             spec=SpecPolicy("lookup", spec_len=2)))
    with pytest.raises(NotImplementedError, match="latent pages"):
        sched.cache.defrag()


def test_switch_routed_layers_are_refused_at_construction():
    from byteps_tpu.models import MoEGPTConfig, moe_gpt_init

    cfg = MoEGPTConfig.tiny() if hasattr(MoEGPTConfig, "tiny") else None
    if cfg is None:
        pytest.skip("no tiny MoE-GPT config")
    params = moe_gpt_init(jax.random.PRNGKey(0), cfg)
    with pytest.raises(NotImplementedError, match="Switch-routed"):
        Scheduler(params, cfg, max_batch=2, block_size=4, pool_blocks=16,
                  prefill_chunk=8)
