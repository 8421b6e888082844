"""Falcon-H1 at tiny sizes, every mechanism live: two layers that each hold a
Mamba-2 mixer (4 heads of 16 in 2 groups, state 32, 4 convolution taps with
bias, a gated norm grouped by 2) BESIDE grouped-query attention (4 query
heads on 2 k/v heads of 16) on one normed input, a SwiGLU MLP, and every
published multiplier. The dense model, the serve tier's two programs over k/v
pages AND a slot pool in every layer, the one-position readout of a final
chunk and the cache's slot account, each held to the plain reference
(``benchmark/configs/falconh1_reference.py``). Everything is f32: the
tolerances (a few 1e-5 on logits of unit scale) are what the order of f32 sums
moves between the chunked rule and the token-by-token one."""

import ast
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.configs import falconh1_reference as ref
from byteps_tpu.common.metrics import get_registry
from byteps_tpu.models.falcon_h1 import (
    FalconH1Config,
    falcon_h1_apply,
    falcon_h1_init,
    param_count,
)
from byteps_tpu.serve import Request, Scheduler
from byteps_tpu.serve.families import HybridKVFamily, serve_family
from byteps_tpu.serve.paged_cache import STATS_SSD, PagedKVCache

CFG = FalconH1Config.tiny()
BS, CHUNK, QB = 4, 8, 4
S_REF = 48      # every reference forward runs at this length: one compile
#: (prompt length, max_new): three chunks and a tail that is no whole
#: sub-chunk, mid-block ends, one prompt shorter than the convolution
SHAPES = [(37, 6), (22, 9), (2, 12), (19, 7)]
TOL = 5e-5


def _hp(cfg=CFG, **over):
    hp = {k: v for k, v in dataclasses.asdict(cfg).items() if k != "dtype"}
    return dict(hp, **over)


@pytest.fixture(scope="module")
def params():
    return falcon_h1_init(jax.random.PRNGKey(0), CFG)


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


def test_the_published_sizes_and_the_cut_configurations_parameter_count():
    full = FalconH1Config()
    assert full.d_ssm == 4096 and full.conv_channels == 5120
    assert sum(full.in_proj_segments) == 9248
    # a layer's slot: 32 x 256 x 128 f32 and 3 x 5120 bf16
    assert full.state_bytes() == 32 * 256 * 128 * 4 + 3 * 5120 * 2
    cut = dataclasses.replace(full, n_layers=4, max_seq=8192)
    layer = (5120 * (20 + 4 + 4) * 128 + 20 * 128 * 5120      # attention
             + 5120 * 9248 + 4096 * 5120 + 4 * 5120 + 5120    # in, out, conv
             + 3 * 32 + 4096                                  # A, dt, D, norm
             + 3 * 5120 * 21504 + 2 * 5120)                   # MLP, two norms
    assert param_count(cut) == 4 * layer + 2 * 261120 * 5120 + 5120
    assert hash(cut) == hash(dataclasses.replace(
        cut, ssm_multipliers=list(cut.ssm_multipliers)))


# ---- the model against the reference -------------------------------------------
@pytest.mark.parametrize("recurrent", [True, False],
                         ids=["recurrent", "chunked"])
def test_model_forward_equals_the_reference(params, recurrent):
    toks = np.random.default_rng(3).integers(0, CFG.vocab_size, S_REF)
    got = falcon_h1_apply(params, jnp.asarray(toks)[None], CFG, recurrent)
    want, _ = _ref_forward(params, toks)
    np.testing.assert_allclose(got[0], want, atol=TOL)


def test_every_branch_adds_a_comparable_part_and_the_logits_spread(params):
    """What the seeded weights make of the published multipliers: each of
    ssm, attn and mlp adds a tenth or more of the residual it joins (and none
    ten times it), and the logits spread over far more than rounding."""
    toks = np.random.default_rng(3).integers(0, CFG.vocab_size, S_REF)
    logits, shares = falcon_h1_apply(params, jnp.asarray(toks)[None], CFG,
                                     parts=True)
    assert shares.shape == (CFG.n_layers, 3)
    assert float(shares.min()) > 0.1 and float(shares.max()) < 10.0, shares
    assert float(jnp.std(logits)) > 0.3
    _, layers = _ref_forward(params, toks)
    np.testing.assert_allclose(shares, jnp.stack(
        [layer["shares"] for layer in layers]), rtol=1e-3)


@pytest.mark.parametrize("over,why", [
    (dict(decay_after_update=True), "the decay after the update"),
    (dict(dt_bias=False), "dt without dt_bias"),
    (dict(group_shift=1), "B and C of the wrong group"),
    (dict(norm_groups=1), "the gated norm over all of d_ssm"),
    (dict(key_multiplier=1.0), "no key_multiplier"),
    (dict(ssm_out_multiplier=1.0), "no ssm_out_multiplier"),
    (dict(attention_out_multiplier=1.0), "no attention_out_multiplier"),
    (dict(attn_branch=False), "the attention branch dropped"),
    (dict(ssm_branch=False), "the SSM branch dropped"),
    (dict(embedding_multiplier=1.0), "no embedding_multiplier"),
    (dict(mlp_multipliers=(1.0, CFG.mlp_multipliers[1])), "no gate multiplier"),
    (dict(ssm_multipliers=(1.0,) * 5), "no ssm_multipliers"),
])
def test_a_reference_off_by_design_is_told_apart(params, over, why):
    toks = np.random.default_rng(3).integers(0, CFG.vocab_size, S_REF)
    got = falcon_h1_apply(params, jnp.asarray(toks)[None], CFG)[0]
    right, _ = _ref_forward(params, toks)
    wrong, _ = _ref_forward(params, toks, **over)
    near = float(jnp.max(jnp.abs(got - right)))
    far = float(jnp.max(jnp.abs(got - wrong)))
    assert far > 50 * max(near, 1e-6), (why, near, far)


# ---- served through the scheduler ----------------------------------------------
@pytest.mark.parametrize("i", range(len(SHAPES)),
                         ids=[f"p{n}_n{m}" for n, m in SHAPES])
def test_scheduler_prefill_then_decode_equals_the_reference(params, served,
                                                            i):
    """Chunked prefill (8-token chunks of 4-token sub-chunks) into pages and
    a slot, then packed decode steps whose rows change as requests of unequal
    length finish: at every generated position the served token is the
    reference's argmax of one full forward over prompt + emitted."""
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
    assert cache._g_slots.value() == 0 and cache._g_state_bytes.value() == 0
    assert cache._g_slots.max() == 4
    assert cache._g_state_bytes.max() == \
        4 * CFG.n_layers * CFG.state_bytes(4)


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
        # a chunk of this family reads out ONE position: its last
        assert logits.shape == (1, 1, CFG.vocab_size)
    return logits[0, -1]


def test_programs_logits_and_pool_contents_equal_the_reference(params):
    """The two programs called as the scheduler calls them: the last chunk's
    one row of logits, then three packed decode steps in which the two
    requests CHANGE ROWS (and a row holds no request), every logit against
    the reference; then what the pools hold of EVERY layer — the state and
    the convolution's tail in the slot, the k and v rows through the block
    table — against the reference's after as many positions."""
    family, cache = _programs(params)
    assert isinstance(family, HybridKVFamily)
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
                    logits[r], want[len(seqs[rid]) - 1], atol=TOL)
                seqs[rid].append(int(jnp.argmax(logits[r])))
    K = CFG.conv_kernel
    for rid, seq in seqs.items():
        n = len(seq) - 1               # the last token was picked, not fed
        _, layers = _ref_forward(params, seq, state_at=n)
        slot = cache.slot_of(rid)
        table = cache.table_row(rid)[1:]
        for li in range(CFG.n_layers):
            np.testing.assert_allclose(cache.state.s[li, slot],
                                       layers[li]["S"], atol=2e-5)
            np.testing.assert_allclose(
                cache.state.conv[li, slot].reshape(K - 1, -1),
                layers[li]["tail"], atol=2e-5)
            for name, pool_a in (("k", cache.state.k), ("v", cache.state.v)):
                rows = np.asarray(pool_a[li, table[:-(-n // BS)]])
                np.testing.assert_allclose(
                    rows.reshape(-1, rows.shape[-1])[:n],
                    layers[li][name][:n], atol=2e-5)


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
    assert family.late_stats().names == STATS_SSD
    prompt = np.random.default_rng(7).integers(0, CFG.vocab_size, 8)
    _prefill(family, cache, params, "x", prompt.astype(np.int32))
    s = dict(zip(STATS_SSD, np.asarray(cache.state.stats).tolist()))
    assert s["serve.ssd.prefill_tokens"] == 8 * CFG.n_layers
    assert s["serve.attn.prefill_pairs.full"] == \
        CFG.n_layers * sum(range(1, 9))
    assert s["serve.attn.prefill_pairs.window"] == 0
    assert s["moe.layers"] == 0            # a dense MLP: no expert series
    step = family.decode_fn(CFG, BS, None, None)
    tables = np.zeros((3, 17), np.int32)
    tables[1] = cache.table_row("x", 16)
    _, cache.state = step(params, cache.state, np.zeros(3, np.int32),
                          np.asarray([0, 8, 0], np.int32), tables)
    s = dict(zip(STATS_SSD, np.asarray(cache.state.stats).tolist()))
    assert s["serve.ssd.decode_rows"] == 1 * CFG.n_layers
    assert s["serve.kv.decode_keys_read.full"] == 9 * CFG.n_layers


def test_preemption_resets_the_slot_and_reproduces_the_tokens(params):
    """Two requests of 12 + 12 tokens in a pool of 9 blocks: the younger is
    preempted mid-decode, its slot goes back, and on resume a slot is granted
    and its first chunk starts from zero again (counted under its cause); the
    tokens are those of the run with room."""
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


def test_the_decode_step_goes_through_both_kernels(params, monkeypatch):
    """On the Pallas backend (interpreted here) with shapes the kernels take
    — k/v rows of 128, SSM heads of 128 — a packed decode step attends
    through the paged-attention kernel and updates the state through
    ``ssd_decode``, and serves the tokens of the jnp path."""
    cfg = FalconH1Config.tiny(n_kv_heads=2, head_dim=64, n_heads=4,
                              ssm_head_dim=128, ssm_heads=2, ssm_state=8,
                              n_layers=1)
    p = falcon_h1_init(jax.random.PRNGKey(1), cfg)
    reqs = [Request(rid=0, max_new=3, prompt=np.arange(9, dtype=np.int32))]
    kw = dict(max_batch=2, block_size=8, pool_blocks=17, prefill_chunk=8)
    want = Scheduler(p, cfg, **kw).serve(reqs)
    monkeypatch.setenv("BYTEPS_KERNEL_BACKEND", "pallas")
    reg = get_registry()
    before = reg.counter("ssd.decode_kernel").value()
    # (another configuration: the programs' factories are cached by it)
    sched = Scheduler(p, dataclasses.replace(cfg, max_seq=72), **kw)
    got = sched.serve([Request(rid=0, max_new=3,
                               prompt=np.arange(9, dtype=np.int32))])
    assert reg.counter("ssd.decode_kernel").value() > before
    assert sched._decode_paged_attn
    np.testing.assert_array_equal(got[0]["tokens"], want[0]["tokens"])


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
    assert HybridKVFamily.REFUSED[feature] in str(e.value)
    assert feature in str(e.value) and "FalconH1Config" in str(e.value)


def test_speculation_is_refused_at_submit(params):
    from byteps_tpu.serve.scheduler import SpecPolicy

    sched = _scheduler(params)
    with pytest.raises(NotImplementedError, match="rewind a recurrent"):
        sched.submit(Request(rid=0, prompt=np.zeros(4, np.int32), max_new=2,
                             spec=SpecPolicy(kind="lookup")))
