"""byteps_tpu.jax adapter: eager push_pull, broadcast, fused
DistributedOptimizer (SURVEY §7 phase 2 — the minimum end-to-end slice)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import byteps_tpu.jax as bps

N = 8


@pytest.fixture(autouse=True)
def bps_ctx(mesh8):
    bps.init(mesh=mesh8)
    yield
    bps.shutdown()
    # reset module singleton for next test
    import byteps_tpu.jax as bpsmod

    bpsmod._state.__init__()


def test_topology():
    assert bps.size() == N
    assert bps.rank() == 0
    assert bps.local_size() == N


def test_push_pull_average():
    x = jnp.asarray(np.random.RandomState(0).randn(N, 32, 4).astype(np.float32))
    out = bps.push_pull(x, average=True, name="t0")
    assert out.shape == (32, 4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x).mean(0), rtol=1e-5)


def test_push_pull_sum_and_multi_partition(monkeypatch):
    monkeypatch.setenv("BYTEPS_PARTITION_BYTES", "1024")  # force 4 partitions
    from byteps_tpu.common.config import reset_config

    reset_config()
    x = jnp.asarray(np.random.RandomState(1).randn(N, 1000).astype(np.float32))
    out = bps.push_pull(x, average=False, name="t1")
    np.testing.assert_allclose(np.asarray(out), np.asarray(x).sum(0), rtol=1e-4)


def test_push_pull_async_handles_priority():
    xs = [
        jnp.asarray(np.random.RandomState(i).randn(N, 64).astype(np.float32))
        for i in range(4)
    ]
    handles = [bps.push_pull_async(x, name=f"h{i}") for i, x in enumerate(xs)]
    outs = [bps.synchronize(h) for h in handles]
    for x, o in zip(xs, outs):
        np.testing.assert_allclose(np.asarray(o), np.asarray(x).mean(0), rtol=1e-5)


def test_push_pull_compressed_onebit():
    x = jnp.asarray(np.random.RandomState(2).randn(N, 1 << 15).astype(np.float32))
    out = bps.push_pull(
        x, name="c0", compression_params={"compressor": "onebit", "scaling": True}
    )
    # two-way onebit returns sign(majority-vote) * scale per segment: check
    # the sign agreement with the true mean (~0.79 for iid gaussian workers)
    # and that magnitudes are per-segment constants (8 segments -> 8 scales)
    ref = np.asarray(x).mean(0)
    got = np.asarray(out)
    assert (np.sign(ref) == np.sign(got)).mean() > 0.7
    assert len(np.unique(np.abs(got))) == 8


def test_small_tensor_skips_compression():
    """Below BYTEPS_MIN_COMPRESS_BYTES compression is bypassed -> exact."""
    x = jnp.asarray(np.random.RandomState(3).randn(N, 16).astype(np.float32))
    out = bps.push_pull(x, name="small", compression_params={"compressor": "onebit"})
    np.testing.assert_allclose(np.asarray(out), np.asarray(x).mean(0), rtol=1e-5)


def test_push_pull_tree():
    tree = {
        "w": jnp.ones((N, 4, 4)),
        "b": jnp.asarray(np.tile(np.arange(N, dtype=np.float32)[:, None], (1, 3))),
    }
    out = bps.push_pull_tree(tree)
    np.testing.assert_allclose(np.asarray(out["w"]), np.ones((4, 4)))
    np.testing.assert_allclose(np.asarray(out["b"]), np.full(3, 3.5))


def test_broadcast_parameters():
    params = {"w": jnp.asarray(np.random.RandomState(4).randn(N, 5, 5).astype(np.float32))}
    out = bps.broadcast_parameters(params, root_rank=2)
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(params["w"])[2], rtol=1e-6)


def test_declare_tensor_priority_order():
    bps.declare_tensor("a", (10,), np.float32)
    bps.declare_tensor("b", (10,), np.float32)
    reg = bps._state.registry
    assert reg.get("a").priority == 0
    assert reg.get("b").priority == -1


# ---------------- fused DistributedOptimizer e2e ----------------------------
def _make_train_step(mesh, tx, loss_fn):
    sspec = bps.dp_state_specs()

    def per_device_step(params, opt_state, xb, yb):
        grads = jax.grad(loss_fn)(params, xb, yb)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state

    return jax.jit(
        jax.shard_map(
            per_device_step,
            mesh=mesh,
            in_specs=(P(), sspec, P("dp"), P("dp")),
            out_specs=(P(), sspec),
            check_vma=False,
        )
    )


def _linreg_data(n_total=512, d=16, seed=0):
    rng = np.random.RandomState(seed)
    w_true = rng.randn(d, 1).astype(np.float32)
    X = rng.randn(n_total, d).astype(np.float32)
    y = X @ w_true + 0.01 * rng.randn(n_total, 1).astype(np.float32)
    return X, y, w_true


def _loss(params, X, y):
    pred = X @ params["w"] + params["b"]
    return jnp.mean((pred - y) ** 2)


@pytest.mark.parametrize(
    "compression_params",
    [
        None,
        {"compressor": "onebit", "ef": "vanilla", "scaling": True},
        {"compressor": "topk", "k": 0.25, "ef": "vanilla"},
        {"compressor": "randomk", "k": 0.5, "seed": 1},
    ],
    ids=["none", "onebit-ef", "topk-ef", "randomk"],
)
def test_distributed_optimizer_trains(mesh8, compression_params):
    """Data-parallel linear regression on 8 devices must converge — with and
    without compression (EF makes lossy compressors convergence-capable,
    the reference's headline claim)."""
    X, y, w_true = _linreg_data()
    params = {"w": jnp.zeros((16, 1)), "b": jnp.zeros((1,))}
    tx = bps.DistributedOptimizer(
        optax.sgd(0.05),
        compression_params=compression_params,
        num_devices=N,
        partition_bytes=64,  # tiny partitions: exercise chunking
    )
    opt_state = tx.init(params)
    step = _make_train_step(mesh8, tx, _loss)

    Xs = jnp.asarray(X)
    ys = jnp.asarray(y)
    steps = 300 if compression_params else 100
    for i in range(steps):
        params, opt_state = step(params, opt_state, Xs, ys)
    final = float(_loss(params, jnp.asarray(X), jnp.asarray(y)))
    init_loss = float(_loss({"w": jnp.zeros((16, 1)), "b": jnp.zeros((1,))},
                            jnp.asarray(X), jnp.asarray(y)))
    assert final < init_loss * 0.05, (final, init_loss)


def test_reduce_dtype_bf16_changes_wire_numerics(mesh8, monkeypatch):
    """BYTEPS_REDUCE_DTYPE=bfloat16: the fused uncompressed psum runs in
    bf16 (half the ICI bytes) — the aggregated mean shows bf16 rounding
    relative to the fp32 default, and training still converges."""
    monkeypatch.setenv("BYTEPS_REDUCE_DTYPE", "bfloat16")
    from byteps_tpu.common.config import reset_config

    reset_config()

    from byteps_tpu.jax.optimizer import push_pull_inside

    rows = jnp.asarray(
        np.random.RandomState(0).randn(N, 1000).astype(np.float32)
    )
    agg16 = jax.jit(jax.shard_map(
        lambda b: push_pull_inside({"g": b[0]}, axis="dp", n=N)["g"],
        mesh=mesh8, in_specs=P("dp"), out_specs=P(),
    ))(rows)
    want = np.asarray(rows, np.float32).mean(axis=0)
    got = np.asarray(agg16)
    # bf16-rounded, hence close to — but (for a random vector) not exactly
    # equal to — the fp32 mean (atol covers near-zero means whose relative
    # bf16 error is unbounded)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=4e-3)
    assert np.abs(got - want).max() > 0, "bf16 path produced exact fp32"

    monkeypatch.setenv("BYTEPS_REDUCE_DTYPE", "float32")
    reset_config()
    agg32 = jax.jit(jax.shard_map(
        lambda b: push_pull_inside({"g": b[0]}, axis="dp", n=N)["g"],
        mesh=mesh8, in_specs=P("dp"), out_specs=P(),
    ))(rows)
    np.testing.assert_allclose(np.asarray(agg32), want, rtol=1e-6)


def test_batched_chunk_aggregation_matches_sequential(mesh8, monkeypatch):
    """BYTEPS_COMPRESS_BATCH_CHUNKS > 1 (the vmapped-group fast path with
    the EF add hoisted to ONE whole-flat pass) must agree with the
    default sequential per-chunk path — same chunk keys, same selection,
    same residuals (ADVICE r5 #1: the hoist is now real, so pin it)."""
    from byteps_tpu.compression import from_params
    from byteps_tpu.compression.error_feedback import CompressionSpec
    from byteps_tpu.jax.optimizer import push_pull_inside

    spec = from_params({"compressor": "onebit", "ef": "vanilla"})
    L = 4096
    pb = 1024  # 256 f32 elems/chunk -> 16 full chunks
    rows = jnp.asarray(
        np.random.RandomState(7).randn(N, L).astype(np.float32))
    ef0 = jnp.asarray(
        np.random.RandomState(8).randn(N, L).astype(np.float32) * 0.1)
    rng = jax.random.PRNGKey(3)

    def run():
        def body(b, e, r):
            out, new_e = push_pull_inside(
                {"g": b[0]}, axis="dp", n=N, spec=spec, rng=r,
                ef_residual=e[0], partition_bytes=pb)
            return out["g"], new_e[None]

        return jax.jit(jax.shard_map(
            body, mesh=mesh8, in_specs=(P("dp"), P("dp"), P()),
            out_specs=(P(), P("dp")), check_vma=False,
        ))(rows, ef0, rng)

    monkeypatch.setenv("BYTEPS_COMPRESS_BATCH_CHUNKS", "1")
    out_seq, ef_seq = run()
    monkeypatch.setenv("BYTEPS_COMPRESS_BATCH_CHUNKS", "4")
    out_bat, ef_bat = run()
    np.testing.assert_allclose(np.asarray(out_bat), np.asarray(out_seq),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(ef_bat), np.asarray(ef_seq),
                               rtol=1e-6, atol=1e-7)
    # EF actually engaged: residuals are not the zero buffer
    assert float(np.abs(np.asarray(ef_bat)).max()) > 0


def test_distributed_optimizer_matches_single_worker_sgd(mesh8):
    """Uncompressed DP aggregation == training on the pooled batch."""
    X, y, _ = _linreg_data(seed=3)
    params = {"w": jnp.zeros((16, 1)), "b": jnp.zeros((1,))}
    tx = bps.DistributedOptimizer(optax.sgd(0.1), num_devices=N)
    opt_state = tx.init(params)
    step = _make_train_step(mesh8, tx, _loss)

    ref_params = {"w": jnp.zeros((16, 1)), "b": jnp.zeros((1,))}
    ref_tx = optax.sgd(0.1)
    ref_state = ref_tx.init(ref_params)

    @jax.jit
    def ref_step(p, s, X, y):
        g = jax.grad(_loss)(p, X, y)
        u, s = ref_tx.update(g, s, p)
        return optax.apply_updates(p, u), s

    for i in range(10):
        params, opt_state = step(params, opt_state, jnp.asarray(X), jnp.asarray(y))
        ref_params, ref_state = ref_step(ref_params, ref_state, jnp.asarray(X), jnp.asarray(y))
    # mean-of-shard-grads == full-batch grad for MSE with equal shards
    np.testing.assert_allclose(
        np.asarray(params["w"]), np.asarray(ref_params["w"]), rtol=1e-4, atol=1e-6
    )


@pytest.mark.slow
def test_eager_push_pull_applies_error_feedback():
    """Regression: eager path must thread EF residuals (was silently ignored).
    Repeatedly pushing the same grads with onebit+EF, the ACCUMULATED pulled
    sum must track T*mean(grads) (EF compensation), which biased onebit alone
    cannot do."""
    x = jnp.asarray(np.random.RandomState(5).randn(N, 1 << 15).astype(np.float32))
    # two_way=False: EF covers the (one-way) compression fully, so the
    # accumulated pull tracks the true sum; with two_way=True the server-side
    # recompression adds uncompensated error (same as the reference).
    params = {"compressor": "onebit", "ef": "vanilla", "scaling": True,
              "two_way": False}
    T = 60
    acc = np.zeros(1 << 15, np.float32)
    for t in range(T):
        acc += np.asarray(bps.push_pull(x, name="efreg", compression_params=params))
    ref = np.asarray(x).mean(0) * T
    rel = np.linalg.norm(acc - ref) / np.linalg.norm(ref)
    assert rel < 0.2, rel
    # EF state exists per partition
    assert any(k[0] == "efreg" for k in bps._state.ef_state)


def test_eager_rng_differs_per_partition_and_version(monkeypatch):
    """Regression: partitions/steps must not reuse identical randomk indices.

    (Tensor must exceed BYTEPS_MIN_COMPRESS_BYTES=65536, read from the config
    cached at init(); partition bytes are read lazily so the monkeypatch
    applies to partitioning.)"""
    monkeypatch.setenv("BYTEPS_PARTITION_BYTES", "65536")  # 2 partitions
    from byteps_tpu.common.config import reset_config

    reset_config()
    L = 1 << 15
    x = jnp.asarray(np.random.RandomState(6).randn(N, L).astype(np.float32))
    params = {"compressor": "randomk", "k": 0.05}
    o1 = np.asarray(bps.push_pull(x, name="rk", compression_params=params))
    o2 = np.asarray(bps.push_pull(x, name="rk", compression_params=params))
    s1, s2 = set(np.nonzero(o1)[0]), set(np.nonzero(o2)[0])
    assert 0 < len(s1) < L  # compression actually ran
    # different step (version) -> different sampled support
    assert len(s1 & s2) < 0.5 * len(s1)
    # two partitions within one push: supports not identical modulo chunk size
    half = L // 2
    p1 = {i for i in s1 if i < half}
    p2 = {i - half for i in s1 if i >= half}
    assert p1 != p2


def test_broadcast_preserves_int_dtypes():
    big = 1 << 25  # would corrupt through float32
    params = {"step": jnp.full((N, 1), big + 3, jnp.int32)}
    out = bps.broadcast_parameters(params, root_rank=1)
    assert out["step"].dtype == jnp.int32
    assert int(out["step"][0]) == big + 3


# --- the raw path: buckets of whole leaves, chained (PR 49) ---------------

_BUCKET_PB = 4096     # one leaf larger, several smaller, a scalar


def _mixed_tree(seed=11):
    """Per-device gradients, leading axis N: mixed shapes, dtypes, sizes."""
    r = np.random.RandomState(seed)

    def f32(*shape):
        return jnp.asarray(r.randn(N, *shape).astype(np.float32))

    return {
        "a": f32(300),                       # 1,200 B
        "big": f32(64, 40),                  # 10,240 B > _BUCKET_PB
        "blocks": [{"w": f32(20, 30), "b": f32(30)},
                   {"w": f32(20, 30), "b": f32(30)}],
        "half": f32(7, 9).astype(jnp.bfloat16),
        "scalar": f32(),
        "tail": f32(50),
    }


def _run_inside(mesh, fn, tree, check_vma=True):
    """``fn`` on each device's slice of ``tree`` under shard_map."""
    return jax.shard_map(
        lambda t: fn(jax.tree.map(lambda x: x[0], t)),
        mesh=mesh, in_specs=P("dp"), out_specs=P(), check_vma=check_vma)


def _flat_push_pull(tree, average, acc_dtype, chunk_elems):
    """The parent's raw path (PR 48 and before): every leaf raveled into
    one flat vector, cut into partitions with a psum each, cut back."""
    leaves, treedef = jax.tree.flatten(tree)
    flat = jnp.concatenate([jnp.ravel(l).astype(acc_dtype) for l in leaves])
    outs = []
    for off in range(0, flat.shape[0], chunk_elems):
        s = jax.lax.psum(flat[off:off + chunk_elems], "dp")
        outs.append(s / N if average else s)
    agg = jnp.concatenate(outs)
    res, off = [], 0
    for l in leaves:
        res.append(agg[off:off + l.size].reshape(l.shape).astype(l.dtype))
        off += l.size
    return jax.tree.unflatten(treedef, res)


def _count_primitives(jaxpr, counts=None):
    counts = {} if counts is None else counts
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        counts[name] = counts.get(name, 0) + 1
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _count_primitives(inner, counts)
    return counts


@pytest.mark.parametrize("reduce_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("average", [True, False], ids=["mean", "sum"])
def test_bucket_path_equals_pmean_and_the_flat_path(
        mesh8, monkeypatch, average, reduce_dtype):
    """Whole-leaf buckets give what a per-leaf pmean gives and, bit for
    bit, what the parent's flat vector gave; the traced program holds the
    leaves' psums (jax 0.9 binds one a leaf of a list; XLA's combiner
    makes a bucket's one all-reduce), one barrier between neighbouring
    buckets, no concatenate."""
    from byteps_tpu.common.config import reset_config
    from byteps_tpu.jax.optimizer import plan_buckets, push_pull_inside

    monkeypatch.setenv("BYTEPS_REDUCE_DTYPE", reduce_dtype)
    reset_config()
    acc = jnp.dtype(reduce_dtype)
    tree = _mixed_tree()

    def bucketed(t):
        return push_pull_inside(t, axis="dp", n=N, average=average,
                                partition_bytes=_BUCKET_PB)

    def flat(t):
        return _flat_push_pull(t, average, acc, _BUCKET_PB // acc.itemsize)

    def per_leaf(t):
        red = jax.lax.pmean if average else jax.lax.psum
        return jax.tree.map(lambda x: red(x, "dp"), t)

    got = jax.jit(_run_inside(mesh8, bucketed, tree))(tree)
    old = jax.jit(_run_inside(mesh8, flat, tree))(tree)
    want = jax.jit(_run_inside(mesh8, per_leaf, tree))(tree)
    for g, o, w, src in zip(*map(jax.tree.leaves, (got, old, want, tree))):
        assert g.dtype == src.dtype and g.shape == src.shape[1:]
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(o, np.float32))
        tol = 1e-6 if acc == jnp.float32 and g.dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            rtol=tol, atol=tol * 4)

    one = jax.tree.map(lambda x: x[0], tree)
    buckets = plan_buckets(jax.tree.leaves(one), _BUCKET_PB, acc)
    assert len(buckets) >= 2
    assert sorted(i for b in buckets for i in b) == list(
        range(len(jax.tree.leaves(one))))        # every leaf, whole, once
    counts = _count_primitives(
        jax.make_jaxpr(_run_inside(mesh8, bucketed, tree))(tree).jaxpr)
    n_psum = sum(v for k, v in counts.items() if k.startswith("psum"))
    assert n_psum == len(jax.tree.leaves(one))
    assert counts.get("optimization_barrier", 0) == len(buckets) - 1
    assert "concatenate" not in counts
    reset_config()


def test_bucket_plan_closes_at_partition_bytes_and_follows_the_order():
    """A bucket closes once it holds partition_bytes, a leaf is never
    split, and the buckets go in the order their last leaf is produced;
    with no backward in sight that is the reversed tree order."""
    from byteps_tpu.jax.optimizer import plan_buckets

    f32 = jnp.float32
    leaves = [jax.ShapeDtypeStruct(s, f32) for s in
              [(100,), (100,), (2000,), (10,), (), (1200,), (5,)]]
    assert plan_buckets(leaves, 4096, f32) == [[6], [3, 4, 5], [0, 1, 2]]
    # leaf 1 is produced last (a tied embedding): its bucket goes last
    order = [6, 9, 5, 4, 3, 2, 1]
    assert plan_buckets(leaves, 4096, f32, order) == [[6], [3, 4, 5],
                                                      [0, 1, 2]]
    order = [1, 2, 3, 4, 5, 6, 0]
    assert plan_buckets(leaves, 4096, f32, order) == [[6], [0, 1, 2],
                                                      [3, 4, 5]]
    # in bf16 a bucket holds twice the elements
    assert plan_buckets(leaves, 4096, jnp.bfloat16) == [[3, 4, 5, 6],
                                                        [0, 1, 2]]


# sha1 of str(make_jaxpr(...)) at the parent commit (PR 48, 4f3f7b4), jax
# 0.9.0: the compressed branches keep the flat vector and their chunks
_COMPRESSED_JAXPR_SHA1 = {
    "onebit-ef": "d024cbebfe24077ae5b8e905ffd93d5027a5ae23",
    "topk": "bd04051d4d5a3c90ceeff389106eff291df87c10",
}


@pytest.mark.parametrize("case", sorted(_COMPRESSED_JAXPR_SHA1))
def test_compressed_branches_trace_as_the_parent(mesh8, case):
    import hashlib

    from byteps_tpu.compression import from_params
    from byteps_tpu.jax.optimizer import push_pull_inside

    tree = _mixed_tree()
    total = sum(l[0].size for l in jax.tree.leaves(tree))
    if case == "onebit-ef":
        spec = from_params({"compressor": "onebit", "ef": "vanilla"})
        ef = jnp.zeros((N, total), jnp.float32)

        def body(t, e):
            out, new_e = push_pull_inside(
                jax.tree.map(lambda x: x[0], t), axis="dp", n=N, spec=spec,
                rng=jax.random.PRNGKey(0), ef_residual=e[0],
                partition_bytes=_BUCKET_PB)
            return out, new_e[None]

        fn = jax.shard_map(body, mesh=mesh8, in_specs=(P("dp"), P("dp")),
                           out_specs=(P(), P("dp")), check_vma=False)
        text = str(jax.make_jaxpr(fn)(tree, ef))
    else:
        spec = from_params({"compressor": "topk", "k": 0.25})
        fn = _run_inside(
            mesh8, lambda t: push_pull_inside(
                t, axis="dp", n=N, spec=spec, rng=jax.random.PRNGKey(0),
                partition_bytes=_BUCKET_PB), tree, check_vma=False)
        text = str(jax.make_jaxpr(fn)(tree))
    assert "concatenate" in text and "optimization_barrier" not in text
    got = hashlib.sha1(text.encode()).hexdigest()
    assert got == _COMPRESSED_JAXPR_SHA1[case], got


def _tiny_gpt_backward():
    from byteps_tpu.models import GPTConfig, gpt_init
    from byteps_tpu.models.gpt import gpt_loss

    cfg = GPTConfig.tiny()           # two layers, tied readout, learned wpe
    params = gpt_init(jax.random.PRNGKey(0), cfg)
    tok = jnp.zeros((2, 16), jnp.int32)
    vag = jax.value_and_grad(lambda p, a, b: gpt_loss(p, a, b, cfg))
    return cfg, params, tok, vag


class _Chains:
    """The chains the raw path logged while the block ran, one a trace
    (the package's loggers do not propagate to pytest's)."""

    def __enter__(self):
        import logging

        self.lines = []
        outer = self

        class Keep(logging.Handler):
            def emit(self, record):
                msg = record.getMessage()
                if "chained: " in msg:
                    outer.lines.append(msg.split("chained: ")[1])

        self._handler = Keep(level=logging.INFO)
        self._logger = logging.getLogger("byteps_tpu.jax.optimizer")
        self._logger.addHandler(self._handler)
        return self.lines

    def __exit__(self, *exc):
        self._logger.removeHandler(self._handler)


def _bucket_keys(tree, buckets):
    from byteps_tpu.jax.optimizer import _top_key

    paths = [p for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]
    return [sorted({_top_key(paths[i]) for i in b}) for b in buckets]


def test_backward_order_puts_the_tied_embedding_last():
    """The order is read from the traced backward, not from a key's name:
    GPT's tied wte (and wpe) is complete only after the embedding's
    backward, block 1's leaves before block 0's; the gradients are those
    of a plain call, bit for bit."""
    from byteps_tpu.jax.optimizer import plan_buckets, value_and_grad_in_order

    _, params, tok, vag = _tiny_gpt_backward()
    loss, grads, order = value_and_grad_in_order(vag, params, tok, tok)
    want_loss, want = vag(params, tok, tok)
    assert float(loss) == float(want_loss)
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    leaves = jax.tree.leaves(grads)
    keys = _bucket_keys(grads, plan_buckets(leaves, 4096, jnp.float32, order))
    assert keys[-1] == ["wte"], keys
    assert "wpe" in keys[-2], keys
    first0 = min(i for i, k in enumerate(keys) if k == ["blocks[0]"])
    last1 = max(i for i, k in enumerate(keys) if k == ["blocks[1]"])
    assert last1 < first0 < len(keys) - 2, keys
    # with no backward in sight: reversed tree order, wte FIRST — which
    # is why the factories read the order
    keys = _bucket_keys(grads, plan_buckets(leaves, 4096, jnp.float32))
    assert keys[0] == ["wte"] and keys[-1] == ["blocks[0]"], keys


def test_direct_push_pull_inside_chains_in_reversed_leaf_order(mesh8):
    """A direct call has no backward to read: reversed leaf order; under
    backward_order the same call follows the order it is given, and an
    order of another tree is refused."""
    from byteps_tpu.jax.optimizer import backward_order, push_pull_inside

    tree = {"a": jnp.ones((N, 1500)), "b": jnp.ones((N, 1500)),
            "c": jnp.ones((N, 1500))}

    def agg(t):
        return push_pull_inside(t, axis="dp", n=N, partition_bytes=4096)

    with _Chains() as chains:
        jax.make_jaxpr(_run_inside(mesh8, agg, tree))(tree)
        with backward_order([0, 2, 1]):
            jax.make_jaxpr(_run_inside(mesh8, agg, tree))(tree)
    assert chains == ["c > b > a", "a > c > b"], chains
    with backward_order([0, 1]), pytest.raises(ValueError, match="another"):
        jax.make_jaxpr(_run_inside(mesh8, agg, tree))(tree)


def test_one_chip_step_lowers_to_the_parent_text():
    """n == 1 aggregates nothing: the step calls value_and_grad as ever and
    its program is the parent's. sha1 of the lowered text of
    make_gpt_train_step(GPTConfig.tiny(), one device, adamw(1e-3)) at the
    parent commit (PR 48, 4f3f7b4; jax 0.9.0, CPU):
    c46b677d58b0fdeb7e1cf20e230ca7b43a32664d — a later change to the model
    or the step moves it; recompute it at that change's parent."""
    import hashlib

    from byteps_tpu.models import GPTConfig
    from byteps_tpu.models.train import make_gpt_train_step
    from byteps_tpu.parallel import MeshAxes, make_mesh

    mesh = make_mesh(MeshAxes(), devices=jax.devices()[:1])
    step, params, opt_state, bsh = make_gpt_train_step(
        GPTConfig.tiny(), mesh, optax.adamw(1e-3))
    tok = jax.device_put(jnp.zeros((4, 16), jnp.int32), bsh)
    text = step.lower(params, opt_state, tok, tok).as_text()
    assert "optimization_barrier" not in text
    assert hashlib.sha1(text.encode()).hexdigest() == \
        "c46b677d58b0fdeb7e1cf20e230ca7b43a32664d"


def test_dp_step_chains_its_buckets_in_the_backward_order(mesh8, monkeypatch):
    """The factory reads the order where the reduced axis is larger than
    one: the dp=8 step logs its chain at a trace, block 1 before block 0, wte
    last, and its program holds a barrier between neighbouring buckets
    (its losses against the flat path's golden: tests/test_multislice.py)."""
    from byteps_tpu.models import GPTConfig
    from byteps_tpu.models.train import make_gpt_train_step
    from byteps_tpu.parallel import MeshAxes, make_mesh

    mesh = make_mesh(MeshAxes(dp=8), devices=jax.devices()[:8])
    step, params, opt_state, bsh = make_gpt_train_step(
        GPTConfig.tiny(), mesh, optax.adamw(1e-3), partition_bytes=8192)
    tok = jax.device_put(jnp.zeros((8, 16), jnp.int32), bsh)
    import byteps_tpu.models.train as train_mod

    reads = []
    real = train_mod.value_and_grad_in_order
    monkeypatch.setattr(
        train_mod, "value_and_grad_in_order",
        lambda *a: reads.append(1) or real(*a))
    with _Chains() as chains:
        text = step.lower(params, opt_state, tok, tok).as_text()
        # a second trace of the same shapes (the step's second call, a
        # tuner's move) reads the order from the factory's memo
        jax.clear_caches()
        again = step.lower(params, opt_state, tok, tok).as_text()
    assert len(chains) == 2 and chains[0] == chains[1], chains
    assert len(reads) == 1
    assert again.count("optimization_barrier") == 13
    order = chains[0].split(" > ")
    assert order[-1] == "wte" and "wpe" in order[-2], order
    assert order[0].startswith("blocks[1]"), order
    assert order.index("blocks[0] x6") > order.index("blocks[1] x6")
    assert text.count("optimization_barrier") == 13      # 14 buckets


def test_fused_step_event_says_what_the_raw_path_aggregated(
        mesh8, monkeypatch, tmp_path):
    """BYTEPS_TRACE_ON: the per-step marker carries the bucket plan beside
    the partition count."""
    from byteps_tpu.common.config import reset_config
    from byteps_tpu.common.tracing import get_tracer, reset_tracer

    monkeypatch.setenv("BYTEPS_TRACE_ON", "1")
    monkeypatch.setenv("BYTEPS_TRACE_DIR", str(tmp_path))
    reset_config()
    reset_tracer()
    X, y, _ = _linreg_data(seed=5)
    params = {"w": jnp.zeros((16, 1)), "b": jnp.zeros((1,))}
    tx = bps.DistributedOptimizer(optax.sgd(0.1), num_devices=N,
                                  partition_bytes=32)
    opt_state = tx.init(params)
    step = _make_train_step(mesh8, tx, _loss)
    for _ in range(2):
        params, opt_state = step(params, opt_state, X, y)
    jax.block_until_ready(params)
    jax.effects_barrier()
    fused = [e for e in get_tracer()._events
             if e["tid"] == "FUSED_PUSHPULL"]
    assert fused, get_tracer()._events[:3]
    # 4 B of b close no bucket, 64 B of w do: one bucket of both leaves
    assert fused[0]["args"] == {
        "total_elems": 17, "chunks": 3, "buckets": 1,
        "bucket_bytes_max": 68, "chained": 0}
    monkeypatch.delenv("BYTEPS_TRACE_ON")
    reset_config()
    reset_tracer()
