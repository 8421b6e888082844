"""Fused in-jit gradient aggregation + ``DistributedOptimizer``.

Reference analog: ``byteps/torch/__init__.py`` ``DistributedOptimizer``
(wraps the user's optimizer, intercepts gradients, push_pulls them, then
steps). The TPU-idiomatic form is an ``optax.GradientTransformation``
wrapper whose ``update`` runs **inside the user's shard_map/pmap'd train
step**. Raw gradients are aggregated in BUCKETS of whole leaves of
``BYTEPS_PARTITION_BYTES`` each, one all-reduce a bucket, chained in the
order the backward yields them (:func:`_aggregate_buckets`); compressed
gradients are flattened, concatenated, partitioned into
``BYTEPS_PARTITION_BYTES`` chunks (declaration = pytree order, so chunk
issue order preserves the reference's priority semantics), and each chunk is
aggregated with the compressed collective. Error-feedback and
Nesterov-momentum state live in the optimizer state pytree (per-device,
sharded over dp — each device is a "worker" with its own residual), which is
the pure-functional replacement for the reference's C++ side buffers.
"""

from __future__ import annotations

import contextlib
import os
import threading
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.extend.core import Var

from byteps_tpu.common.config import get_config
from byteps_tpu.common.logging import get_logger
from byteps_tpu.comm.ici import (
    compressed_allreduce_local,
    compressed_reduce_scatter_local,
)
from byteps_tpu.compression import from_params
from byteps_tpu.compression.error_feedback import CompressionSpec, momentum_step

log = get_logger("jax.optimizer")


def _flatten_concat(tree):
    leaves = jax.tree.leaves(tree)
    flats = [jnp.ravel(l).astype(jnp.float32) for l in leaves]
    sizes = [f.shape[0] for f in flats]
    return jnp.concatenate(flats) if len(flats) > 1 else flats[0], sizes


def _unconcat_unflatten(flat, tree, sizes):
    leaves, treedef = jax.tree.flatten(tree)
    outs = []
    off = 0
    for leaf, s in zip(leaves, sizes):
        outs.append(flat[off:off + s].reshape(leaf.shape).astype(leaf.dtype))
        off += s
    return jax.tree.unflatten(treedef, outs)


def _chunk_bounds(total: int, chunk_elems: int):
    bounds = []
    off = 0
    while off < total:
        ln = min(chunk_elems, total - off)
        bounds.append((off, ln))
        off += ln
    return bounds or [(0, total)]


def _aggregate_flat(
    flat: jnp.ndarray,
    axis: str,
    n: int,
    average: bool,
    spec: CompressionSpec,
    rng: Optional[jnp.ndarray],
    ef_flat: Optional[jnp.ndarray],
    chunk_elems: int,
    two_way: bool,
    chunk_id_offset: int = 0,
):
    """Chunk a flat fp32 grad vector and aggregate each chunk over ``axis``
    with the compressed collective (``spec.enabled``; raw gradients never
    take a flat form, see :func:`_aggregate_buckets`).

    Returns ``(agg_flat, new_ef_flat_or_None, num_chunks)``. The chunking is
    the reference's tensor partitioning (BYTEPS_PARTITION_BYTES,
    operations.cc): a chunk is the codec's wire contract. The chunks all
    depend on the whole flat vector, so none of them leaves before the
    backward has ended.
    """
    total = flat.shape[0]
    bounds = _chunk_bounds(total, chunk_elems)
    if rng is None:
        if spec.compressor.stochastic:
            raise ValueError(
                f"{spec.compressor.name} requires an rng that advances "
                "every step; pass rng= (DistributedOptimizer does this "
                "automatically from its step count)"
            )
        rng = jax.random.PRNGKey(0)

    out_chunks = []
    new_e_chunks = [] if ef_flat is not None else None

    def one_chunk(g, crng, e):
        """Per-chunk body, shared by the batched (vmapped) full chunks
        and the ragged tail — one definition so their semantics cannot
        diverge."""
        res = compressed_allreduce_local(
            g, crng, spec.compressor, axis, n,
            average=average, two_way=two_way, ef_residual=e,
        )
        return res if e is not None else (res, None)

    # BYTEPS_COMPRESS_BATCH_CHUNKS > 1 runs full chunks in vmapped
    # groups of that size (an UNROLLED loop of vmap calls — see the
    # scan note below): per-chunk semantics are unchanged (same fold_in
    # key per chunk id, selection/EF still per chunk_elems partition —
    # the wire contract), but each group's codec runs as
    # (group, chunk_elems) array ops instead of per-chunk sequential
    # op-chains, and the group size bounds the live f32 intermediates
    # (an all-chunks vmap OOMs a v5e next to the model+opt state).
    # Remainder full chunks take one smaller vmap; the ragged tail
    # keeps the scalar path (its k resolves against the true tail
    # length, exactly as before). Default 1 = OFF, and deliberately so:
    # with the fused n==1 roundtrip and the Pallas codec kernels each
    # per-chunk body is already a few big ops, and vmap batching only
    # adds slicing/stacking glue — measured on v5e, gpt2m+topk-block
    # 80.4 ms (off) vs 92.2 ms (groups of 16) and bert+onebit 43.3 vs
    # 68.4. Raise it only for codecs that still emit many small XLA ops
    # per chunk, and re-measure (docs/env.md).
    group = int(os.environ.get("BYTEPS_COMPRESS_BATCH_CHUNKS", "1"))
    nfull = total // chunk_elems
    pre_added = False
    if nfull > 1 and group > 1:
        # The EF add IS hoisted to ONE whole-flat pass here (the tail
        # chunks below then slice the pre-added flat and ask only for
        # the residual back — compressed_allreduce_local's documented
        # return_residual contract), and the chunk views are chosen so
        # every reshape is a layout no-op: a 1-D f32 array tiles as
        # 1024 consecutive elements, and any (..., m, 128) view with
        # m % 8 == 0 preserves that physical order — whereas the naive
        # (nchunks, chunk_elems) 2-D stacking interleaves 8 CHUNKS per
        # tile and forced a full relayout of the gradient in each
        # direction (round-5 xprof: ~22 ms/step of 'data formatting' on
        # GPT-2-medium, on top of per-chunk small-op overhead the
        # batching already removes).
        lanes = 128 if chunk_elems % 128 == 0 else 1
        m = chunk_elems // lanes
        want_res = ef_flat is not None
        if want_res:
            flat = flat + ef_flat          # the single whole-flat EF add
            pre_added = True

        def body(g, k):
            r = compressed_allreduce_local(
                g.reshape(-1), k, spec.compressor, axis, n,
                average=average, two_way=two_way,
                return_residual=want_res,
            )
            return r if want_res else (r, jnp.zeros((), jnp.float32))

        def vchunk(gs, ids):
            keys = jax.vmap(
                lambda i: jax.random.fold_in(rng, chunk_id_offset + i)
            )(ids)
            return jax.vmap(body)(gs, keys)

        # unrolled loop of vmapped groups — NOT a lax.scan: scan stacks
        # its per-iteration outputs with full-array dynamic-update-slice
        # copies every step (measured 2.5× WORSE than the sequential
        # per-chunk form), while the unrolled concatenate lets XLA
        # write each group's output once. The (·, m, lanes) group view
        # keeps the minor dims layout-compatible with the flat source.
        for g0 in range(0, nfull, group):
            g1 = min(nfull, g0 + group)
            gs = jax.lax.slice_in_dim(
                flat, g0 * chunk_elems,
                g1 * chunk_elems).reshape(g1 - g0, m, lanes)
            out_g, ne_g = vchunk(gs, jnp.arange(g0, g1))
            out_chunks.append(out_g.reshape(-1))
            if ef_flat is not None:
                new_e_chunks.append(ne_g.reshape(-1))
        bounds = bounds[nfull:]
        ci0 = nfull
    else:
        ci0 = 0

    for ci, (off, ln) in enumerate(bounds, start=ci0):
        g = jax.lax.slice_in_dim(flat, off, off + ln)
        crng = jax.random.fold_in(rng, chunk_id_offset + ci)
        if pre_added:
            # flat already carries the residual (hoisted add above)
            out, ne = compressed_allreduce_local(
                g, crng, spec.compressor, axis, n,
                average=average, two_way=two_way,
                return_residual=True,
            )
            new_e_chunks.append(ne)
        else:
            e = (
                jax.lax.slice_in_dim(ef_flat, off, off + ln)
                if ef_flat is not None
                else None
            )
            out, ne = one_chunk(g, crng, e)
            if e is not None:
                new_e_chunks.append(ne)
        out_chunks.append(out)
    agg = out_chunks[0] if len(out_chunks) == 1 else jnp.concatenate(out_chunks)
    new_e = None
    if new_e_chunks is not None:
        new_e = (
            new_e_chunks[0] if len(new_e_chunks) == 1
            else jnp.concatenate(new_e_chunks)
        )
    return agg, new_e, len(bounds) + ci0


def _vma(x) -> frozenset:
    return frozenset(getattr(jax.typeof(x), "vma", ()) or ())


def _vma_groups(leaves):
    """Group leaf indices by their VMA (varying-mesh-axes) type.

    Concatenating a tp-sharded leaf with a replicated one would widen the
    replicated leaf's inferred variance to the union and break shard_map's
    out_specs check (and hide real type information). Grouping keeps each
    concat type-pure; without VMA tracking every leaf lands in one group,
    which is exactly the old behavior.
    """
    groups: Dict[frozenset, list] = {}
    for i, l in enumerate(leaves):
        groups.setdefault(_vma(l), []).append(i)
    return list(groups.values())


def _leaf_bytes(leaf, dtype) -> int:
    return int(np.prod(leaf.shape, dtype=np.int64)) * jnp.dtype(dtype).itemsize


def plan_buckets(leaves, partition_bytes: int, acc_dtype, order=None):
    """The raw path's plan: lists of leaf indices, one list a bucket, in
    the order their all-reduces are chained.

    A bucket is whole leaves of one VMA group, walked in tree order; it
    closes once it holds ``partition_bytes`` (counted in the reduce dtype).
    A leaf is never split: inside one XLA program nothing pre-empts a
    transfer for a partition to protect, and the all-reduce pipelines its
    own pieces. ``order[i]`` is the place of leaf ``i`` in the backward
    (:func:`value_and_grad_in_order`); a bucket is ready when its
    last-produced leaf is, and the buckets go in that order. With no
    backward in sight the order is the reversed tree order, which is the
    backward's for a tree laid out as the forward reads it.
    """
    if order is None:
        order = range(len(leaves) - 1, -1, -1)
    buckets = []
    for idxs in _vma_groups(leaves):
        cur, held = [], 0
        for i in idxs:
            cur.append(i)
            held += _leaf_bytes(leaves[i], acc_dtype)
            if held >= partition_bytes:
                buckets.append(cur)
                cur, held = [], 0
        if cur:
            buckets.append(cur)
    # stable: buckets ready at the same place keep their tree order
    buckets.sort(key=lambda b: max(order[i] for i in b))
    return buckets


def _aggregate_buckets(leaves, buckets, axis, n: int, average: bool,
                       acc_dtype):
    """One all-reduce a bucket, each chained to the one before.

    Independent all-reduces are merged by XLA's combiner into a few tuple
    all-reduces that wait for the whole backward, every gradient live until
    then. The chain is a data dependency the combiner respects: bucket
    ``k + 1``'s gradients pass an ``optimization_barrier`` together with
    bucket ``k``'s sums, so all-reduce ``k + 1`` follows all-reduce ``k``,
    a bucket's leaves (jax binds one ``psum`` a leaf) are the only ones it
    merges, and each all-reduce sits between the backward kernels that
    yield its leaves. They stay SYNCHRONOUS: the wire is not hidden, but a
    gradient is summed, and its buffer free for the update, soon after it
    is made (v5e, PR 49: 2 ms of a 217-ms GPT-2-medium step against the
    same buckets unchained; the TPU compiler's async all-reduce options hid
    4 ms of wire for 5 ms of slower products and 1.4 GiB: not passed). The
    ``/ n`` and the cast back are outside the chain, where they fuse into
    what reads them.
    """
    sums = list(leaves)
    after = {}      # VMA type -> the sums of the last bucket of that type
    for b in buckets:
        gs = [leaves[i] for i in b]
        # a barrier gives every operand the union of their types, so the
        # chain runs inside a VMA group (one group on a dp-only mesh and
        # wherever check_vma is off), and only the gradients' side of it
        # is read: the sums keep theirs, replicated over the axis
        vma = _vma(gs[0])
        if vma in after:
            _, gs = jax.lax.optimization_barrier((after[vma], gs))
        after[vma] = jax.lax.psum([g.astype(acc_dtype) for g in gs], axis)
        for i, x in zip(b, after[vma]):
            sums[i] = x
    return [
        (x / n if average else x).astype(leaf.dtype)
        for x, leaf in zip(sums, leaves)
    ]


def value_and_grad_in_order(vag, *args):
    """``vag(*args)`` traced once, and where the backward yields each leaf.

    ``vag`` is a ``jax.value_and_grad`` (or anything returning ``(out,
    grads)``). Returns ``(out, grads, order)``: ``order[i]`` is the index,
    in the jaxpr of ``vag``, of the equation whose result is gradient leaf
    ``i`` (``-1`` for a leaf that is an input or a constant). The jaxpr
    that was inspected is the one evaluated, so the backward is traced
    once. The order is the program's own: a tied embedding's gradient is
    complete only after the embedding's backward, whatever its key says.
    """
    closed, shape = jax.make_jaxpr(vag, return_shape=True)(*args)
    n_grads = len(jax.tree.leaves(shape[1]))
    made_at = {}
    for at, eqn in enumerate(closed.jaxpr.eqns):
        for v in eqn.outvars:
            made_at[v] = at
    order = [
        made_at.get(v, -1) if isinstance(v, Var) else -1
        for v in closed.jaxpr.outvars[-n_grads:]
    ] if n_grads else []
    flat = jax.core.eval_jaxpr(
        closed.jaxpr, closed.consts, *jax.tree.leaves(args))
    out, grads = jax.tree.unflatten(jax.tree.structure(shape), flat)
    return out, grads, order


_TRACING = threading.local()


@contextlib.contextmanager
def backward_order(order):
    """While the block is traced, the raw aggregation chains its buckets in
    ``order`` (of :func:`value_and_grad_in_order`; ``None`` changes
    nothing). Trace-time state of the calling thread, like
    ``jax.named_scope``: the order belongs to the gradients of ONE traced
    backward and reaches ``tx.update`` without a place in optax's
    signature."""
    was = getattr(_TRACING, "order", None)
    _TRACING.order = order
    try:
        yield
    finally:
        _TRACING.order = was


def _current_order(n_leaves: int):
    order = getattr(_TRACING, "order", None)
    if order is not None and len(order) != n_leaves:
        raise ValueError(
            f"backward_order holds {len(order)} places, the gradients "
            f"being aggregated have {n_leaves} leaves: the order is of "
            "another tree")
    return order


def _top_key(path) -> str:
    """A leaf's top-level key, with the index below it where that is a
    list's (``blocks[3]``)."""
    k = path[0]
    top = str(getattr(k, "key", getattr(k, "idx", getattr(k, "name", k))))
    if len(path) > 1 and hasattr(path[1], "idx"):
        top += f"[{path[1].idx}]"
    return top


def _push_pull_raw(grads, axis, n: int, average: bool, partition_bytes: int):
    """The raw path of :func:`push_pull_inside` for ``n > 1``: returns the
    aggregated tree and what the ``fused_step`` event says of the plan."""
    # BYTEPS_REDUCE_DTYPE: the aggregation dtype of the raw psums —
    # bfloat16 halves the ICI bytes (a bucket still closes at
    # partition_bytes, so half as many buckets) at reduced summation
    # precision (the reference PS always sums fp32; a TPU-only lever)
    acc_dtype = jnp.dtype(get_config().reduce_dtype)
    paths, treedef = jax.tree_util.tree_flatten_with_path(grads)
    leaves = [leaf for _, leaf in paths]
    buckets = plan_buckets(leaves, partition_bytes, acc_dtype,
                           _current_order(len(leaves)))
    plan = dict(
        buckets=len(buckets),
        bucket_bytes_max=max(
            sum(_leaf_bytes(leaves[i], acc_dtype) for i in b)
            for b in buckets),
        chained=len(buckets) > 1,
    )
    # once a trace: the chain by top-level key, so that a reader sees what
    # leaves last (GPT-2's tied wte); equal neighbours are counted
    runs = []
    for b in buckets:
        name = "+".join(dict.fromkeys(_top_key(paths[i][0]) for i in b))
        if runs and runs[-1][0] == name:
            runs[-1][1] += 1
        else:
            runs.append([name, 1])
    log.info(
        "raw aggregation over %s: %d buckets of whole leaves (largest %d "
        "bytes in %s), chained: %s", axis, plan["buckets"],
        plan["bucket_bytes_max"], acc_dtype.name,
        " > ".join(nm if k == 1 else f"{nm} x{k}" for nm, k in runs))
    agg = _aggregate_buckets(leaves, buckets, axis, n, average, acc_dtype)
    return jax.tree.unflatten(treedef, agg), plan


def push_pull_inside(
    grads,
    axis: Optional[str] = None,
    n: Optional[int] = None,
    average: bool = True,
    spec: Optional[CompressionSpec] = None,
    rng: Optional[jnp.ndarray] = None,
    ef_residual: Optional[jnp.ndarray] = None,
    partition_bytes: Optional[int] = None,
    two_way: bool = True,
):
    """Aggregate a gradient pytree across the dp axis, **inside** shard_map.

    Returns ``agg_grads`` (same structure as ``grads``), or
    ``(agg_grads, new_ef_residual)`` when ``ef_residual`` is given (a flat
    fp32 vector of the total parameter count, laid out in VMA-group order —
    treat it as opaque state).

    This is the fused analog of per-tensor ``push_pull`` calls, in one
    trace. Raw gradients go in buckets of whole leaves, one all-reduce a
    bucket, chained in the backward's order (what
    :func:`value_and_grad_in_order` read and :func:`backward_order` holds
    while the step is traced; reversed tree order without it), so a
    bucket is summed among the backward kernels that follow it, not after
    the last of them; no flat vector is built. Compressed gradients are
    raveled into one flat vector and chunked: every chunk waits for the
    whole backward.
    """
    cfg = get_config()
    axis = axis or cfg.dp_axis
    if n is None:
        n = jax.lax.axis_size(axis)
    if spec is None:
        spec = from_params(None)
    if n == 1 and not spec.enabled:
        # single-worker fast path: aggregation is the identity — skip the
        # flatten/chunk machinery entirely (reference: single-machine mode
        # short-circuits the PS pipeline, operations.cc queue-list build).
        # Residual is zeroed exactly like the chunked uncompressed path: no
        # compression happened, so no error may be carried forward.
        if ef_residual is not None:
            return grads, jnp.zeros_like(ef_residual)
        return grads
    partition_bytes = partition_bytes or cfg.partition_bytes
    if not spec.enabled:
        agg_tree, _ = _push_pull_raw(grads, axis, n, average,
                                     partition_bytes)
        if ef_residual is not None:
            # nothing was compressed, so no error is carried forward
            return agg_tree, jnp.zeros_like(ef_residual)
        return agg_tree

    leaves, treedef = jax.tree.flatten(grads)
    # compression requires fp32 (kernel contract), and so is the residual
    acc_dtype = jnp.dtype("float32")
    chunk_elems = max(1, partition_bytes // acc_dtype.itemsize)
    out_leaves = [None] * len(leaves)
    groups = _vma_groups(leaves)
    ef_off = 0
    chunk_id = 0
    new_e_parts = [] if ef_residual is not None else None
    for idxs in groups:
        flats = [jnp.ravel(leaves[i]).astype(acc_dtype) for i in idxs]
        sizes = [f.shape[0] for f in flats]
        flat = flats[0] if len(flats) == 1 else jnp.concatenate(flats)
        gtotal = flat.shape[0]
        e = (
            jax.lax.slice_in_dim(ef_residual, ef_off, ef_off + gtotal)
            if ef_residual is not None else None
        )
        agg, new_e, nchunks = _aggregate_flat(
            flat, axis, n, average, spec, rng, e, chunk_elems, two_way,
            chunk_id_offset=chunk_id,
        )
        chunk_id += nchunks
        if new_e_parts is not None:
            new_e_parts.append(new_e)  # always set when ef_residual given
        off = 0
        for i, s in zip(idxs, sizes):
            leaf = leaves[i]
            out_leaves[i] = (
                agg[off:off + s].reshape(leaf.shape).astype(leaf.dtype)
            )
            off += s
        ef_off += gtotal
    agg_tree = jax.tree.unflatten(treedef, out_leaves)
    if ef_residual is not None:
        new_e_flat = (
            new_e_parts[0] if len(new_e_parts) == 1
            else jnp.concatenate(new_e_parts)
        )
        return agg_tree, new_e_flat
    return agg_tree


class DistributedOptState(NamedTuple):
    inner: Any
    count: jnp.ndarray                      # step counter (rng derivation)
    ef: Optional[jnp.ndarray]               # flat EF residual or None
    momentum: Optional[jnp.ndarray]         # flat momentum buffer or None


def DistributedOptimizer(
    tx: optax.GradientTransformation,
    compression_params: Optional[Dict[str, Any]] = None,
    axis: Optional[str] = None,
    num_devices: Optional[int] = None,
    average: bool = True,
    partition_bytes: Optional[int] = None,
    seed: int = 0,
    per_device_numel: Optional[int] = None,
    state_leading: tuple = (),
    zero: bool = False,
    dcn_axis: Optional[str] = None,
    num_dcn: Optional[int] = None,
) -> optax.GradientTransformation:
    """Wrap an optax transformation with BytePS gradient aggregation.

    ``update`` MUST be called inside a shard_map/pmap context that defines
    the dp ``axis``. Gradients entering ``update`` are per-device; the
    wrapper aggregates them (compressed if configured), updates EF/momentum
    state, then applies the inner transformation to the aggregated grads.

    ``zero=True`` is ZeRO-1 (no reference analog — the reference keeps
    full optimizer replicas per GPU worker): the inner state lives on one
    flat fp32 vector sharded over dp, gradients arrive at each worker as
    its owned segment (``psum_scatter``, or the compressed collective's
    owner-sum half), the inner ``tx`` steps only that segment, and the
    resulting *updates* segment is all_gathered — optimizer-state HBM
    drops to 1/n_dp, and the second wire direction carries update bytes
    instead of gradient bytes. Requires the check_vma=False step mode
    (the all_gathered updates are replicated but typed dp-varying). The
    flat gradient aggregates as ONE scatter — ``partition_bytes``
    chunking does not apply (chunk boundaries would bake into the inner
    state layout, breaking the tuner's retrace-without-reinit contract).

    ZeRO restriction: the inner ``tx`` must be ELEMENTWISE in the
    gradient (sgd / momentum / adam / adamw / scale chains) — it sees
    only this worker's 1/n segment, so cross-element transforms compute
    from partial data (clip_by_global_norm would clip by the segment
    norm; adafactor's factoring collapses on the flat 1-D layout). Use
    ``zero=False`` for those.

    When the step composes other model-parallel axes (pp stages, ep expert
    groups) each device's gradient pytree is a *shard* of the params:
    pass ``per_device_numel`` (that shard's element count) and
    ``state_leading`` (the sizes of those axes, e.g. ``(n_pp,)``) so the
    EF/momentum worker buffers come out shaped
    ``state_leading + (n_dp * per_device_numel,)`` — shard them
    ``P(pp_axis, ..., dp_axis)`` and every device sees exactly its own
    flat residual (``update`` ravels whatever block arrives).

    ``dcn_axis`` turns on the HIERARCHICAL multi-slice path (the BytePS
    thesis applied to an ICI×DCN topology): each slice reduce-scatters
    its gradients RAW over the fast intra-slice ``axis`` (every dp rank
    owns one flat segment), the owned segment is exchanged across slices
    over ``dcn_axis`` — compressed with EF when ``compression_params``
    is set, so the codec pays down only the slow inter-slice wire — and
    the result all_gathers back over ``axis``. EF/momentum residuals are
    per-(slice, dp-rank) SEGMENT state: buffers come out sized
    ``ceil(total/n_dp)`` per device, sharded ``P(..., (dcn_axis, axis))``
    via ``dp_state_specs(dcn_axis=)``. Incompatible with ``zero`` (the
    ZeRO-1 segment flow owns the scatter already). On a slice-only mesh
    pass the DCN axis as ``axis`` instead — the legacy single-axis path
    then compresses straight over DCN.

    Reference: ``DistributedOptimizer(optimizer, named_parameters,
    compression, ...)`` in byteps/torch — same contract, functional form.
    """
    cfg = get_config()
    axis_name = axis or cfg.dp_axis
    spec = from_params(compression_params)
    if zero and dcn_axis is not None:
        raise ValueError(
            "zero=True and dcn_axis are mutually exclusive — ZeRO-1's "
            "segment flow already owns the reduce-scatter; shard over "
            "one axis or use the ZeRO-3 factory for multi-slice FSDP")
    n_dcn = (num_dcn if num_dcn is not None else 1) if dcn_axis else 1

    def _seg_of(total: int, n: int) -> int:
        return -(-total // n)

    def init_fn(params):
        # count elements from shapes — params may be tp-sharded global
        # arrays here (no ravel/concat, which would force a resharding)
        total = per_device_numel if per_device_numel is not None else sum(
            int(np.prod(l.shape)) if l.ndim else 1
            for l in jax.tree.leaves(params)
        )
        if zero:
            n = num_devices if num_devices is not None else len(jax.devices())
            seg = -(-total // n)
            proto = jnp.zeros(tuple(state_leading) + (n * seg,), jnp.float32)
            inner = tx.init(proto)
        else:
            inner = tx.init(params)
        # EF / momentum are PER-DEVICE worker state (each device is one
        # reference worker): globally state_leading + (n * total,), sharded
        # over (those axes..., dp) so each device's shard_map block is its
        # own (total,) buffer. Shard with `dp_state_specs()`; see that
        # helper's docstring. Under dcn_axis each worker's residual covers
        # only its OWNED dp segment (the only data it compresses), so the
        # global buffer is (n_dcn * n_dp * seg,) over (dcn, dp).
        n = num_devices if num_devices is not None else len(jax.devices())
        if dcn_axis is not None:
            shape = tuple(state_leading) + (n_dcn * n * _seg_of(total, n),)
        else:
            shape = tuple(state_leading) + (n * total,)
        ef = (
            jnp.zeros(shape, jnp.float32)
            if (spec.enabled and spec.ef)
            else None
        )
        mom = (
            jnp.zeros(shape, jnp.float32)
            if (spec.enabled and spec.momentum)
            else None
        )
        return DistributedOptState(
            inner=inner, count=jnp.zeros((), jnp.int32), ef=ef, momentum=mom
        )

    def _zero_update(grads, state, params, n, rng, ef_shape, mom_shape):
        """ZeRO-1 step: segment-owner aggregation → inner tx on the owned
        segment → all_gather of the updates segment."""
        if params is None:
            raise ValueError(
                "ZeRO mode requires params= in update (the inner transform "
                "steps a params segment)")
        flat, sizes = _flatten_concat(grads)
        total = flat.shape[0]
        seg = -(-total // n)
        mom = state.momentum
        if spec.enabled and mom is not None:
            flat, mom = momentum_step(flat, mom, spec.mu)
        if spec.enabled:
            if state.ef is not None:
                my_seg, new_ef = compressed_reduce_scatter_local(
                    flat, rng, spec.compressor, axis_name, n,
                    average=average, ef_residual=state.ef)
            else:
                my_seg = compressed_reduce_scatter_local(
                    flat, rng, spec.compressor, axis_name, n,
                    average=average)
                new_ef = None
        else:
            # BYTEPS_REDUCE_DTYPE applies here as on the chunked path:
            # bf16 halves the scatter's wire bytes, sum accuracy reduced
            padded = jnp.pad(flat, (0, n * seg - total)).astype(
                jnp.dtype(cfg.reduce_dtype))
            s = jax.lax.psum_scatter(
                padded, axis_name, scatter_dimension=0,
                tiled=True).astype(jnp.float32)
            my_seg = s / n if average else s
            new_ef = state.ef
        if cfg.trace_on:
            jax.debug.callback(
                _fused_trace_callback, state.count,
                total_elems=total, chunks=1,
            )
        # the inner state block arrives (1, ..., 1, seg) under its
        # (pp/ep..., dp) sharding — flatten for the segment step, restore
        # the block shape on the way out
        lead = len(state_leading)

        def to_seg(l):
            if (hasattr(l, "ndim") and l.ndim == lead + 1
                    and l.shape[-1] == seg):
                return l.reshape(seg)
            return l

        inner_seg = jax.tree.map(to_seg, state.inner)
        p_flat, _ = _flatten_concat(params)
        p_pad = jnp.pad(p_flat, (0, n * seg - total))
        my_id = jax.lax.axis_index(axis_name)
        p_seg = jax.lax.dynamic_slice_in_dim(p_pad, my_id * seg, seg)
        upd_seg, new_inner_seg = tx.update(my_seg, inner_seg, p_seg)
        upd_full = jax.lax.all_gather(upd_seg, axis_name, axis=0, tiled=True)
        updates = _unconcat_unflatten(upd_full[:total], grads, sizes)
        new_inner = jax.tree.map(
            lambda nl, ol: nl.reshape(ol.shape)
            if (hasattr(ol, "shape") and hasattr(nl, "shape")
                and nl.shape != ol.shape) else nl,
            new_inner_seg, state.inner)
        if new_ef is not None:
            new_ef = new_ef.reshape(ef_shape)
        if mom is not None:
            mom = mom.reshape(mom_shape)
        return updates, DistributedOptState(
            inner=new_inner, count=state.count + 1, ef=new_ef, momentum=mom
        )

    def _hier_update(grads, state, params, n, rng, ef_shape, mom_shape,
                     chunk_elems):
        """Multi-slice step: raw ICI reduce-scatter over dp → compressed
        (EF'd, chunked) exchange of the owned segment across dcn_axis →
        raw ICI all_gather — only segment-sized compressed payloads ever
        cross the DCN wire, and each does so exactly once."""
        flat, sizes = _flatten_concat(grads)
        total = flat.shape[0]
        seg = _seg_of(total, n)
        if n > 1:
            padded = jnp.pad(flat, (0, n * seg - total))
            my_seg = jax.lax.psum_scatter(
                padded, axis_name, scatter_dimension=0, tiled=True)
        else:
            my_seg = flat
        mom = state.momentum
        if mom is not None:
            my_seg, mom = momentum_step(my_seg, mom, spec.mu)
        agg_seg, new_ef, nchunks = _aggregate_flat(
            my_seg, dcn_axis, n_dcn, False, spec, rng, state.ef,
            chunk_elems, spec.two_way,
        )
        if n > 1:
            full = jax.lax.all_gather(
                agg_seg, axis_name, axis=0, tiled=True)[:total]
        else:
            full = agg_seg[:total]
        if average:
            full = full / (n * n_dcn)
        updates_grads = _unconcat_unflatten(full, grads, sizes)
        if cfg.trace_on:
            jax.debug.callback(
                _fused_trace_callback, state.count,
                total_elems=total, chunks=nchunks,
            )
        updates, new_inner = tx.update(updates_grads, state.inner, params)
        if new_ef is not None:
            new_ef = new_ef.reshape(ef_shape)
        if mom is not None:
            mom = mom.reshape(mom_shape)
        return updates, DistributedOptState(
            inner=new_inner, count=state.count + 1, ef=new_ef, momentum=mom
        )

    def update_fn(grads, state: DistributedOptState, params=None):
        n = num_devices if num_devices is not None else jax.lax.axis_size(axis_name)
        # spec.seed (reference compression_params 'seed') co-determines the
        # stream so configs differing in seed actually differ
        rng = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(seed), spec.seed), state.count
        )

        total = sum(
            int(np.prod(l.shape)) if l.ndim else 1
            for l in jax.tree.leaves(grads)
        )
        # inside shard_map the state block may carry collapsed leading axes
        # ((1, ..., total) under a (pp, ..., dp) sharding) — work on the
        # flat view and restore the block shape on return
        ef_shape = state.ef.shape if state.ef is not None else None
        mom_shape = state.momentum.shape if state.momentum is not None else None
        state = state._replace(
            ef=state.ef.ravel() if state.ef is not None else None,
            momentum=(state.momentum.ravel()
                      if state.momentum is not None else None),
        )
        expected = _seg_of(total, n) if dcn_axis is not None else total
        for buf, kind in ((state.ef, "EF"), (state.momentum, "momentum")):
            if buf is not None and buf.shape[0] != expected:
                raise ValueError(
                    f"{kind} state has {buf.shape[0]} elements per device but "
                    f"this device expects {expected}. Most likely "
                    "DistributedOptimizer was built without num_devices= on a "
                    "mesh whose dp axis does not span all jax.devices() — "
                    "pass num_devices=mesh.shape['dp'] (and per_device_numel= "
                    "on pp/ep meshes where each device grads a param shard)."
                )

        if zero:
            return _zero_update(grads, state, params, n, rng,
                                ef_shape, mom_shape)

        if dcn_axis is not None and spec.enabled:
            pb = partition_bytes or cfg.partition_bytes
            return _hier_update(grads, state, params, n, rng,
                                ef_shape, mom_shape, max(1, pb // 4))

        # raw multi-slice: one psum over the combined (dcn, dp) tuple axis
        # — VMA-compatible, XLA lowers it hierarchically on hybrid meshes
        agg_axis = (dcn_axis, axis_name) if dcn_axis is not None else axis_name
        agg_n = n * n_dcn

        mom = state.momentum
        with jax.named_scope("grad_aggregate"):
            if spec.enabled and mom is not None:
                # Nesterov momentum before compression (reference:
                # nesterov_momentum.cc decorator)
                flat, sizes = _flatten_concat(grads)
                flat, mom = momentum_step(flat, mom, spec.mu)
                grads_in = _unconcat_unflatten(flat, grads, sizes)
            else:
                grads_in = grads

            plan = {}
            if not spec.enabled and agg_n > 1:
                agg, plan = _push_pull_raw(
                    grads_in, agg_axis, agg_n, average,
                    partition_bytes or cfg.partition_bytes)
                new_ef = state.ef
            elif spec.enabled and state.ef is not None:
                agg, new_ef = push_pull_inside(
                    grads_in, agg_axis, agg_n, average, spec, rng,
                    ef_residual=state.ef, partition_bytes=partition_bytes,
                    two_way=spec.two_way,
                )
            else:
                agg = push_pull_inside(
                    grads_in, agg_axis, agg_n, average, spec, rng,
                    partition_bytes=partition_bytes, two_way=spec.two_way,
                )
                new_ef = state.ef

        if cfg.trace_on:
            # Per-execution dispatch-site marker (SURVEY §5.1): the fused
            # path lives inside XLA where the host tracer cannot see, so a
            # debug callback surfaces one event per executed step and
            # advances the trace step window. count makes it idempotent
            # across shard_map's per-shard duplicates; zero overhead when
            # BYTEPS_TRACE_ON is off (branch is trace-time static).
            pb = partition_bytes or cfg.partition_bytes
            itemsize = (
                4 if spec.enabled else jnp.dtype(cfg.reduce_dtype).itemsize
            )
            nchunks = -(-total * itemsize // pb)
            jax.debug.callback(
                _fused_trace_callback, state.count,
                total_elems=total, chunks=nchunks, **plan,
            )

        with jax.named_scope("optimizer_update"):
            updates, new_inner = tx.update(agg, state.inner, params)
        if new_ef is not None:
            new_ef = new_ef.reshape(ef_shape)
        if mom is not None:
            mom = mom.reshape(mom_shape)
        return updates, DistributedOptState(
            inner=new_inner, count=state.count + 1, ef=new_ef, momentum=mom
        )

    return optax.GradientTransformation(init_fn, update_fn)


def _fused_trace_callback(count, **plan) -> None:
    """``total_elems`` and ``chunks``; on the raw path also ``buckets``,
    ``bucket_bytes_max`` and ``chained`` (docs/timeline.md)."""
    from byteps_tpu.common.tracing import get_tracer

    get_tracer().fused_step(int(count), {k: int(v) for k, v in plan.items()})


def dp_state_specs(axis: Optional[str] = None,
                   leading_axes: tuple = (),
                   dcn_axis: Optional[str] = None) -> DistributedOptState:
    """PartitionSpec prefix-tree for a ``DistributedOptState``.

    Use as the shard_map in/out spec for the optimizer state: the inner
    optax state and step count are replicated (every device applies the same
    aggregated update), while the EF/momentum buffers are sharded over the
    dp axis (per-device worker state)::

        spec = bps.dp_state_specs()
        step = jax.shard_map(per_device_step, mesh=mesh,
                             in_specs=(P(), spec, P("dp"), P("dp")),
                             out_specs=(P(), spec), check_vma=False)

    ``leading_axes`` names the extra state axes of a pp/ep-composed
    optimizer built with ``state_leading`` (buffer spec becomes
    ``P(*leading_axes, dp)``). ``dcn_axis`` names the slice axis of a
    hierarchical (``DistributedOptimizer(dcn_axis=...)``) optimizer —
    the segment buffers then shard over the combined ``(dcn, dp)`` axes.
    """
    from jax.sharding import PartitionSpec as P

    axis = axis or get_config().dp_axis
    if dcn_axis is not None:
        buf = P(*leading_axes, (dcn_axis, axis))
    else:
        buf = P(*leading_axes, axis)
    return DistributedOptState(inner=P(), count=P(), ef=buf, momentum=buf)
