"""Qwen3-Next at tiny sizes, every mechanism live: two periods of three
Gated-DeltaNet layers and one gated full-attention layer, a rotation over a
quarter of a 32-wide head, 2 kv heads under 4 query heads, 2 key heads under
4 value heads, 4 convolution taps, sub-chunks of 4 tokens inside prefill
chunks of 8, 8 experts top-3 beside a gated shared expert. The dense model,
the three forms of the gated delta rule, the serve tier's two programs over
k/v pages and a slot pool, and the cache's slot account, each held to the
plain reference (``benchmark/configs/qwen3next_reference.py``) or to its
twin."""

import ast
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.configs import qwen3next_reference as ref
from byteps_tpu.common.metrics import get_registry
from byteps_tpu.models.qwen3_next import (
    FULL,
    LINEAR,
    Qwen3NextConfig,
    expert_ffn,
    param_count,
    qwen3_next_apply,
    qwen3_next_init,
)
from byteps_tpu.ops import gated_delta as gd
from byteps_tpu.parallel.moe import moe_ffn_dropless
from byteps_tpu.serve import Request, Scheduler
from byteps_tpu.serve.families import RecurrentKVFamily, serve_family
from byteps_tpu.serve.paged_cache import PagedKVCache, PoolExhausted

CFG = Qwen3NextConfig.tiny()
BS, CHUNK, QB = 4, 8, 4
S_REF = 48      # every reference forward runs at this length: one compile
#: (prompt length, max_new): three chunks and a tail that is no whole
#: sub-chunk, mid-block ends, one prompt shorter than the convolution
SHAPES = [(37, 6), (22, 9), (2, 12), (19, 7)]


def _hp(cfg=CFG, **over):
    hp = {k: v for k, v in dataclasses.asdict(cfg).items() if k != "dtype"}
    return dict(hp, **over)


@pytest.fixture(scope="module")
def params():
    return qwen3_next_init(jax.random.PRNGKey(0), CFG)


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
    sched = _scheduler(params)
    return sched, sched.serve(_requests())


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


def test_layer_kinds_and_the_cut_configurations_parameter_count():
    assert CFG.layer_types == (LINEAR, LINEAR, LINEAR, FULL) * 2
    assert ref.layer_kinds(_hp()) == list(CFG.layer_types)
    cut = Qwen3NextConfig(max_seq=32768, n_layers=8, experts_held=64,
                          vocab_size=19072)
    assert param_count(cut) == 1979175040
    # a DeltaNet layer's slot: 32 x 128 x 128 f32 and 3 x 8192 bf16
    assert cut.state_bytes() == 2146304


# ---- the rule's three forms ---------------------------------------------------
def _rule_inputs(T, H=3, Dk=8, Dv=128, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    q = unit(jax.random.normal(ks[0], (T, H, Dk))) * Dk ** -0.5
    k = unit(jax.random.normal(ks[1], (T, H, Dk)))
    v = jax.random.normal(ks[2], (T, H, Dv))
    g = -0.5 * jax.nn.softplus(jax.random.normal(ks[3], (T, H)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (T, H)))
    S = 0.1 * jax.random.normal(ks[5], (H, Dk, Dv))
    return q, k, v, g, beta, S


@pytest.mark.parametrize("T,sub,cut", [
    (64, 16, None),        # whole sub-chunks
    (37, 8, None),         # a tail that is padded
    (40, 8, 24),           # two calls: the state carried across a chunk
    (23, 64, 7),           # shorter than one sub-chunk, carried mid-way
    (5, 4, 1),
], ids=["whole", "padded", "carried", "short", "one_then_four"])
def test_chunked_rule_equals_the_recurrent_one(T, sub, cut):
    q, k, v, g, beta, S = _rule_inputs(T)
    o_want, S_want = gd.gdn_recurrent(q, k, v, g, beta, S)
    if cut is None:
        o, S1 = gd.gdn_chunk_fwd(q, k, v, g, beta, S, sub)
    else:
        a, b = ([x[:cut] for x in (q, k, v, g, beta)],
                [x[cut:] for x in (q, k, v, g, beta)])
        o1, S1 = gd.gdn_chunk_fwd(*a, S, sub)
        o2, S1 = gd.gdn_chunk_fwd(*b, S1, sub)
        o = jnp.concatenate([o1, o2])
    np.testing.assert_allclose(o, o_want, atol=2e-5)
    np.testing.assert_allclose(S1, S_want, atol=2e-5)


@pytest.mark.parametrize("H", [3, 32], ids=["h3", "h32_two_blocks"])
def test_decode_kernel_updates_the_slots_in_place_like_its_twin(H):
    """The Pallas kernel (interpreted) against the jnp twin and the
    recurrent rule: rows at scattered slots of layer 1, two of them the
    scratch slot; no other slot and no other layer moves."""
    R, L, N = 5, 2, 7
    q, k, v, g, beta, _ = _rule_inputs(R, H=H)
    pool = jax.random.normal(jax.random.PRNGKey(9), (L, N, H, 8, 128))
    slots = jnp.asarray([3, 1, 0, 6, 0], jnp.int32)
    o_t, p_t = gd.gdn_decode_jnp(q, k, v, g, beta, pool, 1, slots)
    o_k, p_k = gd._decode(q, k, v, jnp.exp(g), beta, pool, 1, slots, True)
    live = np.asarray([0, 1, 3])
    np.testing.assert_allclose(o_k[live], o_t[live], atol=1e-5)
    np.testing.assert_allclose(p_k[:, 1:], p_t[:, 1:], atol=1e-5)
    np.testing.assert_array_equal(p_k[0], pool[0])
    np.testing.assert_array_equal(p_k[1, [2, 4, 5]], pool[1, [2, 4, 5]])
    for r in live:
        o_r, S_r = gd.gdn_recurrent(q[r:r + 1], k[r:r + 1], v[r:r + 1],
                                    g[r:r + 1], beta[r:r + 1],
                                    pool[1, slots[r]])
        np.testing.assert_allclose(o_k[r], o_r[0], atol=1e-5)
        np.testing.assert_allclose(p_k[1, slots[r]], S_r, atol=1e-5)


@pytest.mark.parametrize("backend,counter", [
    ("pallas", "gdn.decode_kernel"), ("jnp", "gdn.decode_twin")])
def test_decode_dispatch_counts_which_form_was_traced(monkeypatch, backend,
                                                      counter):
    monkeypatch.setenv("BYTEPS_KERNEL_BACKEND", backend)
    q, k, v, g, beta, _ = _rule_inputs(2)
    pool = jnp.zeros((1, 3, 3, 8, 128), jnp.float32)
    c = get_registry().counter(counter)
    before = c.value()
    o, pool = gd.gdn_decode(q, k, v, g, beta, pool, 0,
                            jnp.asarray([1, 2], jnp.int32))
    assert c.value() == before + 1
    want, _ = gd.gdn_decode_jnp(q, k, v, g, beta,
                                jnp.zeros_like(pool), 0,
                                jnp.asarray([1, 2], jnp.int32))
    np.testing.assert_allclose(o, want, atol=1e-5)


def test_decode_kernel_refuses_what_it_cannot_tile():
    assert gd.decode_unsupported_reason(32, 128, 128, jnp.float32) is None
    assert "float32" in gd.decode_unsupported_reason(32, 128, 128,
                                                     jnp.bfloat16)
    assert "tiles" in gd.decode_unsupported_reason(4, 16, 16, jnp.float32)


# ---- the model against the reference -------------------------------------------
@pytest.mark.parametrize("recurrent", [True, False],
                         ids=["recurrent", "chunked"])
def test_model_forward_equals_the_reference(params, recurrent):
    toks = np.random.default_rng(3).integers(0, CFG.vocab_size, S_REF)
    got = qwen3_next_apply(params, jnp.asarray(toks)[None], CFG, recurrent)
    want, _ = _ref_forward(params, toks)
    np.testing.assert_allclose(got[0], want, atol=2e-5)


@pytest.mark.parametrize("over,why", [
    (dict(rotary_dim=CFG.head_dim), "every dim of the head rotated"),
    (dict(qk_norm=False), "no q/k norm"),
    (dict(attn_gate=False), "no output gate"),
    (dict(shared_gate=False), "the shared expert without its sigmoid"),
    (dict(q_scale=False), "no 1/sqrt(Dk) on q"),
    (dict(decay_after_update=True), "the decay after the update"),
])
def test_a_reference_off_by_design_is_told_apart(params, over, why):
    toks = np.random.default_rng(3).integers(0, CFG.vocab_size, S_REF)
    got = qwen3_next_apply(params, jnp.asarray(toks)[None], CFG)[0]
    right, _ = _ref_forward(params, toks)
    wrong, _ = _ref_forward(params, toks, **over)
    near = float(jnp.max(jnp.abs(got - right)))
    far = float(jnp.max(jnp.abs(got - wrong)))
    assert far > 50 * max(near, 1e-6), (why, near, far)


def test_the_ep_shares_add_up_to_the_uncut_layer(params):
    """The eight shares, each the routed part of its one held expert of the
    eight plus the shared expert, are what the reference computes for them;
    their routed parts and the shared expert counted ONCE are the layer with
    every expert held."""
    from byteps_tpu.models.gpt import _rmsnorm_zc

    p = params["blocks"][0]
    x = jax.random.normal(jax.random.PRNGKey(4), (11, CFG.d_model))
    h = _rmsnorm_zc(x, p["ln2_g"], None, CFG.norm_eps)
    whole, _ = expert_ffn(CFG, p, h)
    all_held, _, _ = moe_ffn_dropless(h, p["moe"], CFG.top_k, 1.0, 0,
                                      route="softmax")
    shared = whole - all_held
    assert float(jnp.max(jnp.abs(shared))) > 1e-4
    shares = []
    for r in range(CFG.n_experts):
        cfg_r = dataclasses.replace(CFG, experts_held=1, first_expert=r)
        p_r = dict(p, moe=dict(p["moe"], **{
            k: p["moe"][k][r:r + 1] for k in ("w1", "w3", "w2")}))
        share, aux = expert_ffn(cfg_r, p_r, h)
        want, _, _ = ref._moe(x, p_r, _hp(cfg_r))
        np.testing.assert_allclose(share, want - x, atol=2e-6)
        assert aux[1] <= 1                  # experts hit, of the one held
        shares.append(share - shared)
    np.testing.assert_allclose(sum(shares), all_held, atol=2e-6)
    np.testing.assert_allclose(sum(shares) + shared, whole, atol=2e-6)


# ---- served through the scheduler ----------------------------------------------
@pytest.mark.parametrize("i", range(len(SHAPES)),
                         ids=[f"p{n}_n{m}" for n, m in SHAPES])
def test_scheduler_prefill_then_decode_equals_the_reference(params, served,
                                                            i):
    """Chunked prefill (8-token chunks of 4-token sub-chunks) into a slot,
    then packed decode steps whose rows change as requests finish: at every
    generated position the served token is the reference's argmax of one
    full forward over prompt + emitted."""
    _, results = served
    tokens = results[i]["tokens"]
    n, new = SHAPES[i]
    assert len(tokens) == n + new
    logits, _ = _ref_forward(params, tokens)
    rows = np.asarray(logits)[n - 1:n - 1 + new]
    gap = rows.max(-1) - rows[np.arange(new), tokens[n:]]
    assert gap.max() < 1e-4, gap


def test_slots_and_blocks_all_come_back(served):
    sched, _ = served
    cache = sched.cache
    assert cache.state_slots == 1 + 3 + 1          # scratch + admitted at once
    assert cache.slots_in_use == 0 and cache.blocks_in_use == 0
    assert cache.leaked_slots() == 0 and cache.leaked_blocks() == 0
    # the gauges: all given back, and the most held at once was every slot
    assert cache._g_slots.value() == 0 and cache._g_state_bytes.value() == 0
    assert cache._g_slots.max() == 4
    assert cache._g_state_bytes.max() == 4 * 6 * CFG.state_bytes(4)


def _programs(params, max_batch=3):
    family = serve_family(CFG)
    cache = PagedKVCache(
        CFG, block_size=BS, pool_blocks=65, max_batch=max_batch,
        layout=lambda bs, nb: family.layout(
            params, CFG, block_size=bs, pool_blocks=nb, max_batch=max_batch,
            prefill_chunk=CHUNK, quant=False))
    return family, cache


def _prefill(family, cache, params, rid, prompt):
    cache.register(rid)
    cache.ensure(rid, len(prompt) + 8)
    logits = None
    for lo in range(0, len(prompt), CHUNK):
        toks = prompt[lo:lo + CHUNK]
        fn = family.prefill_fn(CFG, BS, len(toks), None, True)
        logits, cache.state = fn(params, cache.state, toks[None],
                                 np.int32(lo), cache.table_row(rid, 16))
    return logits[0, -1]


def test_programs_logits_and_slot_contents_equal_the_reference(params):
    """The two programs called as the scheduler calls them: the last chunk's
    logits, then three packed decode steps in which the two requests CHANGE
    ROWS (and a row holds no request), every logit against the reference;
    then what the slots hold — the state and the convolution's tail of every
    DeltaNet layer — against the reference's after as many positions."""
    family, cache = _programs(params)
    rng = np.random.default_rng(5)
    seqs = {"a": list(rng.integers(0, CFG.vocab_size, 21)),
            "b": list(rng.integers(0, CFG.vocab_size, 10))}
    for rid, seq in seqs.items():
        last = _prefill(family, cache, params, rid, np.asarray(seq, np.int32))
        want, _ = _ref_forward(params, seq)
        np.testing.assert_allclose(last, want[len(seq) - 1], atol=3e-5)
        seq.append(int(jnp.argmax(last)))
    assert cache.slot_of("a") != cache.slot_of("b") and cache.slot_of("a") > 0
    step = family.decode_fn(CFG, BS, None, None)
    for order in (["a", "b", None], [None, "a", "b"], ["b", None, "a"]):
        toks, pos = np.zeros(3, np.int32), np.zeros(3, np.int32)
        tables = np.zeros((3, 17), np.int32)
        for r, rid in enumerate(order):
            if rid is not None:
                toks[r], pos[r] = seqs[rid][-1], len(seqs[rid]) - 1
                tables[r] = cache.table_row(rid, 16)
        logits, cache.state = step(params, cache.state, toks, pos, tables)
        for r, rid in enumerate(order):
            if rid is not None:
                want, _ = _ref_forward(params, seqs[rid])
                np.testing.assert_allclose(
                    logits[r], want[len(seqs[rid]) - 1], atol=3e-5)
                seqs[rid].append(int(jnp.argmax(logits[r])))
    K = CFG.conv_kernel
    for rid, seq in seqs.items():
        n = len(seq) - 1               # the last token was picked, not fed
        _, layers = _ref_forward(params, seq, state_at=n)
        slot = cache.slot_of(rid)
        for i, li in enumerate(CFG.layers_of(LINEAR)):
            np.testing.assert_allclose(cache.state.s[i, slot],
                                       layers[li]["S"], atol=2e-5)
            np.testing.assert_allclose(
                cache.state.conv[i, slot].reshape(K - 1, -1),
                layers[li]["tail"], atol=2e-5)
        table = cache.table_row(rid)[1:]
        for i, li in enumerate(CFG.layers_of(FULL)):
            rows = np.asarray(cache.state.k[i, table[:-(-n // BS)]])
            np.testing.assert_allclose(
                rows.reshape(-1, rows.shape[-1])[:n], layers[li]["k"][:n],
                atol=2e-5)


def test_a_slots_last_owner_is_not_seen(params):
    """Nothing zeroes a slot at release: the next owner's first chunk starts
    from zero whatever it holds (here: NaN)."""
    family, cache = _programs(params)
    cache.state = cache.state._replace(
        s=jnp.full_like(cache.state.s, jnp.nan),
        conv=jnp.full_like(cache.state.conv, jnp.nan))
    prompt = np.random.default_rng(6).integers(0, CFG.vocab_size, 13)
    last = _prefill(family, cache, params, "x", prompt.astype(np.int32))
    want, _ = _ref_forward(params, prompt)
    np.testing.assert_allclose(last, want[12], atol=3e-5)


def test_what_the_programs_count_is_what_the_shapes_say(params):
    from byteps_tpu.serve.paged_cache import STATS_STATE

    family, cache = _programs(params)
    prompt = np.random.default_rng(7).integers(0, CFG.vocab_size, 8)
    _prefill(family, cache, params, "x", prompt.astype(np.int32))
    s = dict(zip(STATS_STATE, np.asarray(cache.state.stats).tolist()))
    assert s["serve.gdn.prefill_tokens"] == 8 * 6
    assert s["serve.attn.prefill_pairs.full"] == 2 * sum(range(1, 9))
    assert s["serve.attn.prefill_pairs.window"] == 0
    assert s["moe.layers"] == 8
    step = family.decode_fn(CFG, BS, None, None)
    tables = np.zeros((3, 17), np.int32)
    tables[1] = cache.table_row("x", 16)
    _, cache.state = step(params, cache.state, np.zeros(3, np.int32),
                          np.asarray([0, 8, 0], np.int32), tables)
    s = dict(zip(STATS_STATE, np.asarray(cache.state.stats).tolist()))
    assert s["serve.gdn.decode_rows"] == 1 * 6
    assert s["serve.kv.decode_keys_read.full"] == 9 * 2
    assert s["serve.kv.decode_keys_read.window"] == 0


def test_preemption_resets_the_slot_and_reproduces_the_tokens(params):
    """Two requests of 12 + 12 tokens in a pool of 9 blocks: the younger is
    preempted mid-decode, its slot goes back, and on resume a slot is granted
    and reset again (counted under its cause); the tokens are those of the
    run with room. Then two more are dropped mid-decode (a replica drained):
    no slot and no block leaks."""
    def two(seed):
        rng = np.random.default_rng(seed)
        return [Request(rid=i, max_new=12, prompt=rng.integers(
            0, CFG.vocab_size, 12).astype(np.int32)) for i in range(2)]

    reg = get_registry()
    admit = reg.counter("serve.state.resets.admit")
    again = reg.counter("serve.state.resets.preempt")
    a0, p0 = admit.value(), again.value()
    free = _scheduler(params, max_batch=2).serve(two(11))
    tight = _scheduler(params, max_batch=2, pool_blocks=10)
    got = tight.serve(two(11))
    preemptions = sum(r["preemptions"] for r in got.values())
    assert preemptions > 0
    for rid in free:
        np.testing.assert_array_equal(got[rid]["tokens"],
                                      free[rid]["tokens"])
    assert admit.value() - a0 == 4
    assert again.value() - p0 == preemptions
    assert tight.cache.slots_in_use == 0 and tight.cache.leaked_blocks() == 0
    sched = _scheduler(params, max_batch=2)
    for r in two(12):
        sched.submit(r)
    while not (len(sched._running) == 2 and all(
            r.state == "decode" and len(r.emitted) > 3
            for r in sched._running)):
        sched.step()
    assert sched.cache.slots_in_use == 2
    assert len(sched.drain_incomplete()) == 2
    assert sched.cache.slots_in_use == 0 and sched.cache.blocks_in_use == 0
    assert sched.cache.leaked_blocks() == 0


def test_the_slot_account_by_hand(params):
    _, cache = _programs(params, max_batch=2)
    assert cache.state_slots == 1 + 3
    for rid in "abc":
        cache.register(rid, resumed=rid == "c")
    assert sorted(cache.slot_of(r) for r in "abc") == [1, 2, 3]
    assert cache.table_row("b", 4)[0] == cache.slot_of("b")
    with pytest.raises(PoolExhausted, match="state slot"):
        cache.register("d")
    assert "d" not in cache._tables
    cache.release("b")
    assert cache.slots_in_use == 2 and cache.leaked_slots() == 0
    cache._slots.pop("a")                      # a slot nobody accounts for
    assert cache.leaked_slots() == 1 and cache.leaked_blocks() == 1


@pytest.mark.parametrize("feature,kw", [
    ("prefix_cache", dict(prefix_cache=True)),
    ("quant_cache", dict(quant_cache=True)),
    ("role", dict(role="prefill")),
    ("tp_axis", dict(tp_axis="tp")),
    ("adapter_pool", dict(adapter_pool=object())),
])
def test_what_a_slot_cannot_do_is_refused_by_name(params, feature, kw):
    with pytest.raises(NotImplementedError) as e:
        _scheduler(params, **kw)
    assert RecurrentKVFamily.REFUSED[feature] in str(e.value)
    assert feature in str(e.value)


def test_speculation_is_refused_at_submit(params):
    from byteps_tpu.serve.scheduler import SpecPolicy

    sched = _scheduler(params)
    with pytest.raises(NotImplementedError, match="rewind a recurrent"):
        sched.submit(Request(rid=0, prompt=np.zeros(4, np.int32), max_new=2,
                             spec=SpecPolicy(kind="lookup")))
