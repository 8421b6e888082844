"""Sharded training-step factories for the model zoo.

Builds full jitted train steps over a (dp, tp, sp) mesh: per-device
loss+grad via ``shard_map`` (ring attention over sp, Megatron collectives
over tp inside the models), and BytePS aggregation over dp through
``DistributedOptimizer`` (reference hot path, SURVEY §3.2 — here fused into
one XLA program: raw gradients are all-reduced in buckets chained in the
order the backward yields them, ``_vag_in_order`` / ``_update`` below).

VMA notes (apply to every factory): per-device AD is exact under
``check_vma=True`` — replicated params' cotangents get their sp/tp psums
auto-inserted, and marking params dp-varying (``pcast``) keeps grads
per-replica LOCAL so dp aggregation stays in DistributedOptimizer. The
compressed collective (comm/ici.py) and the ZeRO-1 all_gather defeat the
VMA replication analysis, so those modes run ``check_vma=False`` with the
VMA-equivalent gradient assembly done explicitly: pp/ep stage-partial
grads psum over the axes their specs don't shard (``_manual_axis_sums``),
and tp/sp — whose in-forward collectives leave no-VMA AD computing
``d(sum over replicated loss copies)/dw`` via psum self-transpose — get
the same psums plus a uniform division by the tp*sp axis product
(``_novma_collective_fix``; pinned against the VMA path in
tests/test_compressed_parallel.py). Every parallel composition therefore
works compressed: dp x {tp, sp, pp, ep} and their products.
"""

from __future__ import annotations

import collections
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from byteps_tpu.common.flight_recorder import get_flight_recorder
from byteps_tpu.common.tracing import get_tracer, traced_program
from byteps_tpu.jax.optimizer import (
    DistributedOptimizer,
    backward_order,
    dp_state_specs,
    value_and_grad_in_order,
)
from byteps_tpu.models.bert import BertConfig, bert_init, bert_mlm_loss
from byteps_tpu.models.gpt import (
    GPTConfig,
    gpt_init,
    gpt_loss,
    gpt_pp_loss,
)
from byteps_tpu.models.resnet import ResNetConfig, resnet_init, resnet_loss
from byteps_tpu.models.t5 import T5Config, t5_init, t5_loss
from byteps_tpu.models.vit import ViTConfig, vit_init, vit_loss
from byteps_tpu.parallel.partitioner import Partitioner, stacked_logical_specs
from byteps_tpu.parallel.sharding import opt_state_specs


def _axis(mesh: Mesh, name: str) -> Optional[str]:
    return name if name in mesh.axis_names else None


def _check_seq_layout(seq_layout, sp=None):
    if seq_layout not in ("contiguous", "zigzag"):
        raise ValueError(f"unknown seq_layout {seq_layout!r} — expected "
                         "'contiguous' or 'zigzag'")
    if seq_layout == "zigzag" and sp is None:
        # the zigzag contract is "feed zigzag_permutation-permuted tokens";
        # without an sp axis the ring degenerates to contiguous attention
        # and that pre-permuted input would train on silently scrambled data
        raise ValueError(
            "seq_layout='zigzag' requires a mesh with an sp axis — the "
            "layout only exists to balance the causal ring over sp; on "
            "this mesh the permuted inputs would just be scrambled tokens")


def _resolve_init_params(init_params, cfg, pspecs, init_fn=gpt_init):
    """Fresh ``init_fn`` (:func:`gpt_init`) weights, or the caller's
    ``init_params`` (e.g. imported via ``models.import_hf``, or made on
    the device from a seed) validated — tree structure AND leaf shapes —
    against what the config would initialize, so a config/weights
    mismatch fails here instead of as a shape error deep inside the
    jitted step."""
    if init_params is None:
        return init_fn(jax.random.PRNGKey(0), cfg)
    want = jax.tree_util.tree_structure(pspecs)
    got = jax.tree_util.tree_structure(init_params)
    if want != got:
        raise ValueError(
            "init_params tree structure does not match the config's "
            f"parameter tree:\n  config expects {want}\n  got {got}")
    expect = jax.eval_shape(
        lambda: init_fn(jax.random.PRNGKey(0), cfg))
    bad = []

    def _cmp(path, e, g):
        if tuple(e.shape) != tuple(jnp.shape(g)):
            bad.append(f"  {jax.tree_util.keystr(path)}: config expects "
                       f"{tuple(e.shape)}, got {tuple(jnp.shape(g))}")

    jax.tree_util.tree_map_with_path(_cmp, expect, init_params)
    if bad:
        raise ValueError(
            "init_params leaf shapes do not match the config:\n"
            + "\n".join(bad))
    return init_params


def _novma_collective_fix(grads, pspecs, mesh, rep_axes, extra_sum_axes=()):
    """Correct check_vma=False gradients for in-forward collective axes.

    In no-VMA mode ``jax.lax.psum`` is its own transpose, so the adjoint
    computes ``d(sum over all replicated loss copies)/dw`` — every
    device's raw grad carries the cotangents of EVERY replica's loss copy
    (verified: after the per-leaf psums, every leaf is exactly
    ``prod(rep_axes sizes)`` times the VMA path's gradient, uniformly).
    The fix: psum each leaf over the axes its spec doesn't shard (what
    VMA would auto-insert; ``extra_sum_axes`` adds pp/ep whose
    stage-partial sums are needed too), then divide ALL leaves by the
    ``rep_axes`` product. ``rep_axes`` must be exactly the axes the loss
    is REPLICATED over before grad (tp/sp here — pp's loss is
    stage-masked and ep's is a per-device local mean, so they get sums
    but no division)."""
    rep_axes = tuple(a for a in rep_axes if a is not None)
    sum_axes = rep_axes + tuple(a for a in extra_sum_axes if a is not None)
    if not sum_axes:
        return grads
    grads = _manual_axis_sums(grads, pspecs, sum_axes)
    denom = 1
    for a in rep_axes:
        denom *= mesh.shape[a]
    if denom > 1:
        grads = jax.tree.map(lambda g: g / denom, grads)
    return grads


def _dist_state_setup(mesh, params, pspecs, dp, zero_1, slc=None):
    """The per-factory distributed-state bookkeeping: which mesh axes give
    each device its own worker state, the per-device grads numel, and the
    kwargs both _make_tx and _shard_params_state need."""
    if zero_1 and dp is None:
        raise ValueError(
            "zero_1=True requires a dp mesh axis — ZeRO-1 shards the "
            "optimizer state over dp and there is nothing to shard over "
            "on this mesh")
    if zero_1 and slc is not None:
        raise ValueError(
            "zero_1=True does not compose with a slice_ mesh axis — the "
            "ZeRO-1 segment flow owns the dp reduce-scatter; use "
            "zero_3=True for multi-slice FSDP instead")
    state_axes = _state_axes(mesh, pspecs, dp)
    pd_numel = _per_device_numel(params, pspecs, mesh)
    tx_kw = dict(
        per_device_numel=pd_numel,
        state_leading=tuple(mesh.shape[a] for a in state_axes),
        zero=zero_1,
    )
    return state_axes, tx_kw, (pd_numel if zero_1 else None)


def _state_axes(mesh, pspecs, dp) -> tuple:
    """Mesh axes (besides dp) that shard the params — each combination of
    their indices is a distinct "worker" whose EF/momentum residual must be
    its own buffer (pp stages grad different layer slabs, ep groups
    different expert slabs). Ordered by mesh axis order."""
    used = set()
    for spec in jax.tree.leaves(pspecs, is_leaf=lambda x: isinstance(x, P)):
        used |= _spec_axes(spec)
    return tuple(a for a in mesh.axis_names if a in used and a != dp)


def _per_device_numel(params, pspecs, mesh) -> int:
    """Element count of one device's gradient pytree: each leaf's numel
    divided by the sizes of the mesh axes its spec shards it over."""

    def leaf_numel(leaf, spec):
        n = int(np.prod(leaf.shape)) if leaf.ndim else 1
        for a in _spec_axes(spec):
            n //= mesh.shape[a]
        return n

    counts = jax.tree.map(leaf_numel, params, pspecs,
                          is_leaf=lambda x: x is None)
    return sum(jax.tree.leaves(counts))


def _accumulating_value_and_grad(loss_fn, accum_steps, weight_fn=None):
    """Gradient accumulation: ``accum_steps`` sequential microbatches per
    step, activations for only one microbatch live at a time (lax.scan).

    Reference analog: ``backward_passes_per_step`` in the torch adapter
    (byteps/torch DistributedOptimizer) — there, N backward passes skip
    the push_pull on all but the Nth; here the N grad computations fuse
    into one jitted scan and the aggregation sees their weighted mean.

    ``weight_fn(*microbatch) -> scalar`` gives each microbatch's weight in
    that mean. Losses that normalize per-call by a data-dependent count
    (BERT's masked mean) need it: mean-of-means mis-weights microbatches
    with unequal counts, while count-weighted averaging reproduces the
    full-batch mean exactly. Default (None) = equal weights, exact for
    fixed-size means (GPT's every-token loss).
    """
    vag = jax.value_and_grad(loss_fn)
    if accum_steps <= 1:
        return vag

    def accum(params, *batch):
        B = batch[0].shape[0]
        if B % accum_steps != 0:
            raise ValueError(
                f"per-device batch {B} not divisible by "
                f"accum_steps={accum_steps}")
        mbs = tuple(
            b.reshape((accum_steps, B // accum_steps) + b.shape[1:])
            for b in batch
        )

        # the scan carry must be a type fixed point under check_vma=True,
        # but per-leaf grad vma can differ from the params' (auto-psums
        # narrow replicated leaves, conservative inference widens others)
        # and differ per microbatch path — widen everything to the union
        # of the params' varying axes (semantically free; resym collapses
        # the excess after the scan)
        def vma_of(tree):
            out = set()
            for leaf in jax.tree.leaves(tree):
                out |= set(getattr(jax.typeof(leaf), "vma", ()) or ())
            return out

        pvma = vma_of(params)
        # the loss also varies over the BATCH's axes, which the params
        # need not share: on the one-device mesh every axis exists at
        # size 1, the batch spec still names (slice_, dp), and _pcast_dp
        # leaves the params alone there — so the loss carry widens to
        # the union, while grads (and their weight) stay on the params'
        svma = pvma | vma_of(batch)

        def widen(x, to=pvma):
            need = tuple(sorted(
                to - set(getattr(jax.typeof(x), "vma", ()) or ())))
            return jax.lax.pcast(x, need, to="varying") if need else x

        def on_params_axes(w):
            # a data-dependent weight is typed like the batch; where
            # that exceeds the params' axes those axes have size 1 (see
            # above), so the pmean is the identity with a narrower type
            excess = tuple(sorted(vma_of(w) - pvma))
            return jax.lax.pmean(w, excess) if excess else w

        def body(carry, mb):
            loss_sum, grad_sum, w_sum = carry
            loss, grads = vag(params, *mb)
            w = (weight_fn(*mb).astype(jnp.float32) if weight_fn is not None
                 else jnp.float32(1.0))
            wp = on_params_axes(w)
            return (loss_sum + widen(loss * w, svma),
                    jax.tree.map(lambda a, g: a + widen(g * wp),
                                 grad_sum, grads),
                    w_sum + widen(wp)), None

        zeros = jax.tree.map(lambda l: widen(jnp.zeros_like(l)), params)
        zf = widen(jnp.zeros((), jnp.float32))
        (loss_sum, grad_sum, w_sum), _ = jax.lax.scan(
            body, (widen(zf, svma), zeros, zf), mbs
        )
        w_safe = jnp.where(w_sum > 0.0, w_sum, 1.0)
        return (loss_sum / w_safe,
                jax.tree.map(lambda g: g / w_safe, grad_sum))

    return accum


def _manual_axis_sums(grads, pspecs, axes):
    """No-vma grad assembly: psum each leaf over the listed mesh axes it is
    NOT sharded on (its stage-partial contributions), leaving sharded
    leaves (whose spec names the axis) stage-local. Under check_vma=True
    these psums are what VMA auto-inserts; the compressed paths run
    check_vma=False and do them explicitly."""

    def fix(g, spec):
        need = tuple(a for a in axes if a not in _spec_axes(spec))
        return jax.lax.psum(g, need) if need else g

    return jax.tree.map(fix, grads, pspecs, is_leaf=lambda x: x is None)


def _make_tx(mesh, base_tx, compression_params, partition_bytes, dp,
             per_device_numel=None, state_leading=(), zero=False,
             dcn=None):
    """Wrap base_tx with data-parallel aggregation (or pass through on a
    mesh with no data axes).

    ``dcn`` names the slice_ axis of a hybrid ICI×DCN mesh: aggregation
    then runs hierarchically (raw intra-slice reduce-scatter over ``dp``,
    compressed inter-slice exchange over ``dcn``, intra-slice all_gather
    — DistributedOptimizer's ``dcn_axis`` path). On a slice-only mesh
    (no dp axis) the DCN axis becomes THE worker axis and the legacy
    single-axis path compresses straight over the inter-slice wire.

    Separated from the params/state sharding so the auto-tuner can rebuild
    the transformation at a new partition size without re-initializing
    optimizer state (partition size affects chunking only, never state
    shapes)."""
    if dp is None and dcn is None:
        return base_tx
    if dp is None:
        dp, dcn = dcn, None
    kw = {}
    if dcn is not None:
        kw = dict(dcn_axis=dcn, num_dcn=mesh.shape[dcn])
    return DistributedOptimizer(
        base_tx, compression_params=compression_params, axis=dp,
        num_devices=mesh.shape[dp], partition_bytes=partition_bytes,
        per_device_numel=per_device_numel, state_leading=state_leading,
        zero=zero, **kw,
    )


def _shard_params_state(mesh, tx, params, pspecs, dp, state_axes=(),
                        zero_numel=None, slc=None):
    """device_put params, init + shard the optimizer state.

    ``zero_numel`` (ZeRO-1 mode, = per-device grads numel) switches the
    inner-state sharding rule: the inner transform's state lives on flat
    vectors shaped ``state_leading + (n_dp * ceil(numel/n_dp),)``, sharded
    ``P(*state_axes, dp)`` so each worker holds only its segment's
    moments. ``slc`` (hybrid mesh) shards the hierarchical optimizer's
    segment buffers over the combined ``(slice_, dp)`` axes."""
    params = jax.device_put(
        params, jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
    )
    opt_state = tx.init(params)
    ospecs = opt_state_specs(opt_state, params, pspecs)
    agg_dp, agg_dcn = (dp, slc) if dp is not None else (slc, None)
    if agg_dp is not None:
        # EF / momentum flats are per-worker state: one buffer per (pp/ep
        # stage combination, dp worker)
        buf_specs = dp_state_specs(axis=agg_dp, leading_axes=state_axes,
                                   dcn_axis=agg_dcn)
        buf = buf_specs.ef
        ospecs = ospecs._replace(
            ef=buf if opt_state.ef is not None else None,
            momentum=buf if opt_state.momentum is not None else None,
        )
        if zero_numel is not None:
            n = mesh.shape[agg_dp]
            proto_shape = tuple(mesh.shape[a] for a in state_axes) + (
                n * (-(-zero_numel // n)),
            )
            ospecs = ospecs._replace(inner=jax.tree.map(
                lambda l: buf if getattr(l, "shape", None) == proto_shape
                else P(),
                opt_state.inner,
            ))
    opt_state = jax.device_put(
        opt_state, jax.tree.map(lambda s: NamedSharding(mesh, s), ospecs)
    )
    return params, opt_state, ospecs


def _finalize_step(build_jit, partition_bytes, dp, tunable=True,
                   stats_names=()):
    """Return the jitted step, auto-tuned when BYTEPS_AUTO_TUNE=1.

    The tuned wrapper re-invokes ``build_jit`` with new partition sizes as
    the search moves (ByteScheduler's online partition tuning, SURVEY §2.6,
    transposed to the fused path where a move costs one cached retrace).
    ``tunable=False`` (ZeRO-1 mode) skips the tuner: the zero path
    aggregates the whole flat gradient in one scatter, so partition size
    changes nothing and every 'move' would retrace an identical program.
    ``stats_names`` (a model whose step returns a stats vector, see
    :class:`_TickingStep`) cannot be tuned — the tuner hands the jitted
    function's outputs straight to the caller — and says so rather than
    leave BYTEPS_AUTO_TUNE=1 without effect in silence."""
    from byteps_tpu.common.config import get_config

    cfg = get_config()
    if cfg.auto_tune and dp is not None and tunable and stats_names:
        raise ValueError(
            "BYTEPS_AUTO_TUNE=1 cannot tune a step that returns a stats "
            f"vector ({', '.join(stats_names)}): AutoTunedStep passes the "
            "jitted outputs through; unset it for this model")
    if cfg.auto_tune and dp is not None and tunable:
        from byteps_tpu.jax.tuned_step import AutoTunedStep

        # ticks the flight recorder inside its own __call__, and tests
        # rely on the factory returning the instance: not wrapped
        return AutoTunedStep(build_jit,
                             partition_bytes or cfg.partition_bytes)
    return _TickingStep(build_jit(partition_bytes), stats_names)


class _TickingStep:
    """The jitted step plus the always-on train-step telemetry
    (docs/observability.md): one flight-recorder tick per DISPATCHED
    step — a host-side function call, unlike the optimizer's in-program
    debug-callback marker, which costs a host sync and stays gated on
    BYTEPS_TRACE_ON. Everything else is the jitted function's own:
    ``step.lower(...).compile()`` gives the program's text and memory
    analysis ahead of time.

    With ``stats_names`` the jitted step returns one more output, a small
    f32 vector with one value per name (what the model counted inside the
    step: routed pairs, the loss's terms). The caller still gets ``(loss,
    params, opt_state)``; the vector is kept and observed into the
    registry histograms of those names on a LATER call, once it is ready
    — a step the caller waited for is — so reading it never waits on the
    device. :meth:`flush_stats` observes what is still held."""

    _HELD_MAX = 8       # steps of run-ahead before a read may wait

    def __init__(self, jitted, stats_names=()):
        self._jitted = traced_program("train.step", jitted)
        self._stats_names = tuple(stats_names)
        self._held = collections.deque()

    def __call__(self, *args, **kwargs):
        # the host's share of a step: what the caller's step time holds
        # beyond this span is its wait in block_until_ready
        with get_tracer().span("train.dispatch", "TRAIN"):
            out = self._jitted(*args, **kwargs)
        # relative tick: the recorder may already be ahead (eager
        # rounds, a previous model) — a private 1-based counter
        # would be dropped there (FlightRecorder.tick docstring)
        get_flight_recorder().tick()
        if self._stats_names:
            self.flush_stats(ready_only=True)
            self._held.append(out[-1])
            out = out[:-1]
        return out

    def flush_stats(self, ready_only: bool = False) -> None:
        """Observe the held stats vectors, oldest first; with
        ``ready_only`` stop at the first the device has not finished
        (unless more than ``_HELD_MAX`` are held)."""
        from byteps_tpu.common.metrics import get_registry

        reg = get_registry()
        while self._held and (not ready_only or self._held[0].is_ready()
                              or len(self._held) > self._HELD_MAX):
            for name, v in zip(self._stats_names,
                               np.asarray(self._held.popleft())):
                reg.histogram(name).observe(float(v))

    def __getattr__(self, name):
        return getattr(self._jitted, name)


def _collapse_vma(x):
    """pmean away conservative VMA widening on a replicated value — a
    numerical identity (the values already agree across the collapsed
    axes); returns x untouched when it carries no varying axes."""
    vma = tuple(sorted(getattr(jax.typeof(x), "vma", ()) or ()))
    return jax.lax.pmean(x, vma) if vma else x


def _spec_axes(spec) -> set:
    """Flatten a PartitionSpec's entries to the set of mesh axis names."""
    axes = set()
    for part in spec:
        if part is None:
            continue
        axes.update((part,) if isinstance(part, str) else part)
    return axes


def _make_resymmetrize(pspecs, dp, slc=None):
    """Collapse conservative VMA variance on grad leaves (numerical identity
    — AD's auto-psums already made replicated grads bit-identical across
    sp/tp; only the inferred *type* is too wide on some paths)."""
    keep = {a for a in (dp, slc) if a is not None}

    def resym(g, spec):
        allowed = _spec_axes(spec)
        vma = set(getattr(jax.typeof(g), "vma", ()) or ())
        excess = tuple(sorted(a for a in vma
                              if a not in allowed and a not in keep))
        return jax.lax.pmean(g, excess) if excess else g

    def apply(grads):
        return jax.tree.map(resym, grads, pspecs,
                            is_leaf=lambda x: x is None)

    return apply


def _build_pp_jit(mesh, pspecs, ospecs, batch_spec, loss_fn, tx, dp, pp,
                  ep=None, ep_size=1, mean_axes=(), use_vma=True,
                  rep_axes=(), slc=None):
    """The grad-assembly skeleton both pipeline factories share: per-device
    masked loss -> psum of each leaf's stage-partial grads over the axes it
    is NOT sharded on (pp always; ep and tp/sp too under check_vma=False,
    where no VMA auto-psum exists), optional uniform /ep, the
    ``rep_axes`` (tp/sp) replicated-loss division (see
    ``_novma_collective_fix``), resym, dp aggregation via ``tx``, and
    VMA-collapsed loss reporting. ``use_vma=False`` is the compressed /
    ZeRO mode (their collectives defeat VMA's replication analysis)."""
    resym = _make_resymmetrize(pspecs, dp, slc)
    chains = _chains(mesh, use_vma, slc, dp)

    def per_device_step(params, opt_state, tokens, targets):
        grad_params = _pcast_dp(params, dp, mesh, use_vma, slc)
        # loss_fn returns the last-stage-masked loss: grading through an
        # already-replicated psum double-counts (psum transpose)
        loss, grads, order = _vag_in_order(
            jax.value_and_grad(loss_fn), chains, grad_params, tokens, targets
        )
        loss = jax.lax.psum(loss, pp)  # replicate for reporting
        if use_vma:
            # VMA auto-inserts the ep/tp/sp psums for invariant leaves;
            # manual-summing them too would double-count
            grads = _manual_axis_sums(grads, pspecs, (pp,))
        else:
            grads = _novma_collective_fix(
                grads, pspecs, mesh, rep_axes, extra_sum_axes=(pp, ep))
        if ep_size > 1:
            grads = jax.tree.map(lambda g: g / ep_size, grads)
        grads = resym(grads)  # collapse conservative VMA widening (no-op
        # without VMA types, as is _collapse_vma below)
        updates, opt_state = _update(tx, order, grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        if mean_axes:
            loss = jax.lax.pmean(loss, mean_axes)
        loss = _collapse_vma(loss)
        return loss, params, opt_state

    sharded = jax.shard_map(
        per_device_step,
        mesh=mesh,
        in_specs=(pspecs, ospecs, batch_spec, batch_spec),
        out_specs=(P(), pspecs, ospecs),
        check_vma=use_vma,
    )
    return jax.jit(sharded, donate_argnums=(0, 1))


def _pcast_dp(params, dp, mesh, use_vma, slc=None):
    """Mark params varying over the data axes (dp and, on hybrid meshes,
    slice_) so AD yields per-replica LOCAL grads (aggregation must stay
    in DistributedOptimizer, the framework's hot path)."""
    axes = tuple(a for a in (slc, dp)
                 if a is not None and mesh.shape[a] > 1)
    if axes and use_vma:
        return jax.tree.map(lambda x: jax.lax.pcast(x, axes, to="varying"),
                            params)
    return params


def _chains(mesh, raw: bool, *axes) -> Optional[dict]:
    """Where this step all-reduces RAW gradients over a data axis larger
    than one the optimizer chains its buckets, and in what order is worth
    reading: an empty memo for :func:`_vag_in_order` to keep the order in.
    Elsewhere None (compressed and ZeRO-1 gradients travel as one flat
    vector; a single worker aggregates nothing)."""
    if raw and any(a is not None and mesh.shape[a] > 1 for a in axes):
        return {}
    return None


def _vag_in_order(vag, chains: Optional[dict], *args):
    """``(out, grads, order)`` of ``vag(*args)``: where the step chains its
    buckets (``chains`` is its memo), ``order`` is the place the traced
    backward yields each leaf at (:func:`value_and_grad_in_order`, which
    traces the backward once and evaluates what it traced). A step is
    traced again for its second call and for every size the tuner visits:
    the same shapes give the same order, read once. Elsewhere ``vag`` is
    called as ever, so a one-chip step's program text stays what it was,
    and ``order`` is None."""
    if chains is None:
        return (*vag(*args), None)
    key = tuple((l.shape, str(l.dtype)) for l in jax.tree.leaves(args))
    if key in chains:
        return (*vag(*args), chains[key])
    out, grads, chains[key] = value_and_grad_in_order(vag, *args)
    return out, grads, chains[key]


def _update(tx, order, grads, opt_state, params):
    """``tx.update`` traced under the backward's order."""
    with backward_order(order):
        return tx.update(grads, opt_state, params)


def make_gpt_train_step(
    cfg: GPTConfig,
    mesh: Mesh,
    base_tx: optax.GradientTransformation,
    compression_params: Optional[Dict[str, Any]] = None,
    partition_bytes: Optional[int] = None,
    remat: bool = False,
    zero_1: bool = False,
    zero_3: bool = False,
    accum_steps: int = 1,
    seq_layout: str = "contiguous",
    init_params: Optional[Dict[str, Any]] = None,
    chunked_ce=True,
):
    """Returns ``(step, params, opt_state, batch_sharding)``.

    ``init_params`` (structure of :func:`gpt_init`) starts training from
    existing weights — e.g. a checkpoint imported with
    ``models.import_hf`` — instead of a fresh initialization.
    ``step(params, opt_state, tokens, targets) -> (loss, params, opt_state)``
    is jitted over ``mesh``; tokens/targets are global (B, S) arrays
    sharded (dp, sp) by ``batch_sharding``. ``remat=True`` rematerializes
    each transformer block in the backward pass (HBM for FLOPs — the
    long-context lever; numerics unchanged). ``zero_1=True`` shards the
    inner optimizer state over dp (ZeRO-1: psum_scatter'd grads, segment
    update, all_gathered updates — 1/n_dp the optimizer HBM; composes
    with compression_params, whose EF residuals stay per-worker;
    requires an ELEMENTWISE base_tx — see DistributedOptimizer's
    ZeRO note).
    ``accum_steps>1`` accumulates gradients over that many sequential
    microbatches before the (single) aggregation+update — the torch
    adapter's ``backward_passes_per_step``, fused into the jitted step.
    ``seq_layout="zigzag"`` runs the load-balanced causal ring over sp
    (feed tokens/targets pre-permuted with ``zigzag_permutation``;
    positions and attention follow the layout — projected ~2x sp
    utilization for causal attention at scale, from the load-balance
    arithmetic; unmeasured, needs real multi-chip sp hardware).
    ``chunked_ce=True`` (default) fuses readout+CE so the f32 (B, S, V)
    logits never materialize (``ops/chunked_ce.py``; the flagship MFU
    lever — docs/performance.md §attribution); ``"vocab_parallel"``
    additionally splits the readout's vocab over tp (ntp× less readout
    GEMM/live logits, at f32-roundoff drift from the dp-only trajectory
    — see gpt_loss); ``False`` is the dense escape hatch the fused path
    is pinned against. All three accepted by every logits-bearing
    factory in this module.

    ``zero_3=True`` delegates to the ZeRO-3 FSDP factory
    (:func:`byteps_tpu.parallel.zero3.make_gpt_zero3_train_step`): params
    live as flat segments sharded over the slice_/dp axis, all-gathered
    just-in-time per layer inside a remat'd block — per-chip param AND
    optimizer memory drop ~n_shard×. Its returned ``params`` is the
    segment dict, not the gpt tree (gather with ``zero3_gather_params``).
    """
    if zero_3:
        if zero_1:
            raise ValueError("zero_1 and zero_3 are mutually exclusive")
        from byteps_tpu.parallel.zero3 import make_gpt_zero3_train_step
        return make_gpt_zero3_train_step(
            cfg, mesh, base_tx,
            compression_params=compression_params,
            partition_bytes=partition_bytes, remat=remat,
            seq_layout=seq_layout, init_params=init_params,
            chunked_ce=chunked_ce)
    part = Partitioner.for_config(cfg, mesh)
    dp, tp, sp, slc = part.dp, part.tp, part.sp, part.slice_
    _check_seq_layout(seq_layout, sp)
    use_vma = compression_params is None and not zero_1
    pspecs = part.param_specs(cfg)
    params = _resolve_init_params(init_params, cfg, pspecs)
    state_axes, tx_kw, zero_numel = _dist_state_setup(
        mesh, params, pspecs, dp, zero_1, slc=slc)
    params, opt_state, ospecs = _shard_params_state(
        mesh,
        _make_tx(mesh, base_tx, compression_params, partition_bytes, dp,
                 dcn=slc, **tx_kw),
        params, pspecs, dp, state_axes=state_axes, zero_numel=zero_numel,
        slc=slc,
    )
    batch_spec = part.batch_spec()
    mean_axes = tuple(a for a in (slc, dp) if a is not None)
    resym = _make_resymmetrize(pspecs, dp, slc)
    chains = _chains(mesh, use_vma, slc, dp)

    # Grad loss is dp-LOCAL (dp_axis=None): each dp replica is one reference
    # worker computing the grad of its own local mean loss; averaging across
    # workers is DistributedOptimizer's job (push_pull average=True). A dp
    # pmean inside the loss would double-apply the 1/n_dp.
    loss_fn = functools.partial(
        gpt_loss, cfg=cfg, dp_axis=None, tp_axis=tp, sp_axis=sp,
        remat=remat, seq_layout=seq_layout, chunked_ce=chunked_ce,
    )

    def build_jit(pb):
        tx = _make_tx(mesh, base_tx, compression_params, pb, dp, dcn=slc,
                      **tx_kw)

        vag = _accumulating_value_and_grad(loss_fn, accum_steps)

        def per_device_step(params, opt_state, tokens, targets):
            grad_params = _pcast_dp(params, dp, mesh, use_vma, slc)
            loss, grads, order = _vag_in_order(
                vag, chains, grad_params, tokens, targets)
            if use_vma:
                grads = resym(grads)
            else:
                grads = _novma_collective_fix(grads, pspecs, mesh, (tp, sp))
            updates, opt_state = _update(tx, order, grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            if mean_axes:
                loss = jax.lax.pmean(loss, mean_axes)  # global mean loss
            return _collapse_vma(loss), params, opt_state

        sharded = jax.shard_map(
            per_device_step,
            mesh=mesh,
            in_specs=(pspecs, ospecs, batch_spec, batch_spec),
            out_specs=(P(), pspecs, ospecs),
            check_vma=use_vma,
        )
        # donate params/opt_state: the step is an in-place update at the XLA
        # level (halves HBM traffic for the weight/optimizer buffers)
        return jax.jit(sharded, donate_argnums=(0, 1))

    return (
        _finalize_step(build_jit, partition_bytes, dp or slc,
                       tunable=not zero_1),
        params, opt_state, NamedSharding(mesh, batch_spec),
    )


def make_gpt_lora_train_step(
    cfg: GPTConfig,
    mesh: Mesh,
    base_tx: optax.GradientTransformation,
    rank: int = 8,
    alpha: float = 16.0,
    targets: Tuple[str, ...] = ("wq", "wv"),
    base_params: Optional[Dict[str, Any]] = None,
    init_adapters: Optional[Dict[str, Any]] = None,
    rng: Optional[jax.Array] = None,
    compression_params: Optional[Dict[str, Any]] = None,
    partition_bytes: Optional[int] = None,
    remat: bool = False,
    accum_steps: int = 1,
    seq_layout: str = "contiguous",
    chunked_ce=True,
):
    """LoRA fine-tuning step over a (dp[, tp][, sp]) mesh: the frozen
    base never moves and ONLY the adapter gradients ride the dp
    aggregation tier (compressed or not) — rank/d_model the gradient
    traffic of full fine-tuning per targeted projection.

    ``base_params`` (default: fresh init) is typically an imported
    checkpoint (``models.import_hf``); ``init_adapters`` resumes from
    saved adapters and ``rng`` seeds a fresh adapter init (multi-seed
    sweeps). Returns ``(step, adapters,
    opt_state, base, batch_sharding)`` with
    ``step(adapters, opt_state, base, tokens, targets) ->
    (loss, adapters, opt_state)`` — the base is an explicit input
    (replicated over dp/sp, tp-sharded like the dense factory), never
    donated, never updated. ``b`` adapters start at zero, so step 0
    computes exactly the frozen model's loss. Merge for inference or
    export with :func:`byteps_tpu.models.lora.merge_lora`
    (``scale = alpha / rank``).

    Under tp, column-parallel targets add NO collective (``a``
    replicated, ``b`` column-sharded); row-parallel targets psum a thin
    ``(B, S, rank)`` intermediate. ``compression_params`` composes the
    same way as the dense factory (no-VMA explicit psums over tp/sp on
    the adapter grads).
    """
    from byteps_tpu.models.lora import (
        graft_lora, lora_init, lora_param_specs)

    part = Partitioner.for_config(cfg, mesh)
    dp, tp, sp, slc = part.dp, part.tp, part.sp, part.slice_
    _check_seq_layout(seq_layout, sp)
    use_vma = compression_params is None
    scale = alpha / rank

    base_specs = part.param_specs(cfg)
    base = _resolve_init_params(base_params, cfg, base_specs)
    base = jax.device_put(
        base, jax.tree.map(lambda s: NamedSharding(mesh, s), base_specs,
                           is_leaf=lambda x: isinstance(x, P)))

    aspecs = lora_param_specs(cfg, tp, rank, targets)
    if init_adapters is not None:
        adapters = init_adapters
        want = jax.tree_util.tree_structure(aspecs)
        got = jax.tree_util.tree_structure(adapters)
        if want != got:
            raise ValueError(
                "init_adapters tree structure does not match "
                f"(rank/targets/n_layers?):\n  expects {want}\n  got {got}")
    else:
        adapters = lora_init(rng if rng is not None
                             else jax.random.PRNGKey(1), cfg, rank, targets)
    # EF/momentum compressor state must be sized/sharded for THIS mesh
    # (per-device grads are tp-local shards) — same bookkeeping as the
    # dense factory
    state_axes, tx_kw, _ = _dist_state_setup(mesh, adapters, aspecs, dp,
                                             False, slc=slc)
    adapters, opt_state, ospecs = _shard_params_state(
        mesh,
        _make_tx(mesh, base_tx, compression_params, partition_bytes, dp,
                 dcn=slc, **tx_kw),
        adapters, aspecs, dp, state_axes=state_axes, slc=slc,
    )
    batch_spec = part.batch_spec()
    mean_axes = tuple(a for a in (slc, dp) if a is not None)
    resym = _make_resymmetrize(aspecs, dp, slc)
    chains = _chains(mesh, use_vma, slc, dp)

    def loss_fn(adapters, base, tokens, targets_):
        grafted = graft_lora(base, adapters, scale)
        return gpt_loss(grafted, tokens, targets_, cfg, dp_axis=None,
                        tp_axis=tp, sp_axis=sp, remat=remat,
                        seq_layout=seq_layout, chunked_ce=chunked_ce)

    def build_jit(pb):
        tx = _make_tx(mesh, base_tx, compression_params, pb, dp, dcn=slc,
                      **tx_kw)

        def per_device_step(adapters, opt_state, base, tokens, targets_):
            # base rides the closure: the accumulator microbatches every
            # positional batch arg, and the frozen base is not a batch
            vag = _accumulating_value_and_grad(
                lambda a, tok, tgt: loss_fn(a, base, tok, tgt),
                accum_steps)
            grad_adapters = _pcast_dp(adapters, dp, mesh, use_vma, slc)
            loss, grads, order = _vag_in_order(
                vag, chains, grad_adapters, tokens, targets_)
            if use_vma:
                grads = resym(grads)
            else:
                grads = _novma_collective_fix(grads, aspecs, mesh, (tp, sp))
            updates, opt_state = _update(tx, order, grads, opt_state, adapters)
            adapters = optax.apply_updates(adapters, updates)
            if mean_axes:
                loss = jax.lax.pmean(loss, mean_axes)
            return _collapse_vma(loss), adapters, opt_state

        sharded = jax.shard_map(
            per_device_step,
            mesh=mesh,
            in_specs=(aspecs, ospecs, base_specs, batch_spec, batch_spec),
            out_specs=(P(), aspecs, ospecs),
            check_vma=use_vma,
        )
        return jax.jit(sharded, donate_argnums=(0, 1))

    return (
        _finalize_step(build_jit, partition_bytes, dp or slc),
        adapters, opt_state, base, NamedSharding(mesh, batch_spec),
    )


def make_gpt_pp_train_step(
    cfg: GPTConfig,
    mesh: Mesh,
    base_tx: optax.GradientTransformation,
    n_micro: int = 4,
    compression_params: Optional[Dict[str, Any]] = None,
    partition_bytes: Optional[int] = None,
    remat: bool = False,
    zero_1: bool = False,
    seq_layout: str = "contiguous",
    init_params: Optional[Dict[str, Any]] = None,
    chunked_ce=True,
):
    """Pipeline-parallel GPT train step over a (pp, dp[, tp][, sp]) mesh.

    ``init_params`` takes UNSTACKED weights (the :func:`gpt_init` /
    ``import_hf`` structure) and stacks them into the pipeline slab here.

    Transformer blocks are stacked on a leading layer axis and sharded
    ``P('pp')`` — each stage owns n_layers/pp contiguous layers and its
    optimizer moments for them; microbatches flow stage-to-stage via
    ppermute (GPipe schedule, backward derived by AD). tp and sp axes
    compose inside the stages (Megatron col/row-parallel matmuls and ring
    attention per layer, their collectives typed by VMA — the step runs
    check_vma=True, so replicated params' cotangents get their psums
    auto-inserted exactly as in the dense factory). dp aggregation is
    DistributedOptimizer as everywhere else; grads of pp-replicated
    leaves (embeddings, final LN) are psum'd over pp first.

    ``compression_params`` enables compressed dp aggregation
    (check_vma=False mode, like the dense factory's): each stage
    compresses its own slab + replicated-leaf grads over dp, with
    per-(stage, worker) EF/momentum state; tp/sp compose via the
    explicit no-VMA gradient assembly (``_novma_collective_fix``).

    ``seq_layout="zigzag"`` runs the load-balanced causal ring over sp
    inside the stages — feed tokens/targets pre-permuted with
    ``zigzag_permutation`` exactly as for the dense factory.

    Returns ``(step, params, opt_state, batch_sharding)`` like
    :func:`make_gpt_train_step`; ``params["blocks"]`` is the stacked slab.
    """
    from byteps_tpu.models.gpt import block_logical_specs
    from byteps_tpu.parallel.pipeline import stack_blocks

    part = Partitioner.for_config(cfg, mesh)
    dp, pp = part.dp, part.pp
    tp, sp, slc = part.tp, part.sp, part.slice_
    if pp is None:
        raise ValueError("mesh has no pp axis — use make_gpt_train_step")
    _check_seq_layout(seq_layout, sp)
    use_vma = compression_params is None and not zero_1
    nstages = mesh.shape[pp]
    if cfg.n_layers % nstages != 0:
        raise ValueError(
            f"n_layers={cfg.n_layers} not divisible by pp={nstages}"
        )
    raw = _resolve_init_params(init_params, cfg, part.param_specs(cfg))
    # pp-replicated leaves follow the config's tree (wpe only under
    # learned positions, lnf_b only under layernorm, lm_head only
    # untied); the blocks become the stacked stage slab
    params = {k: v for k, v in raw.items() if k != "blocks"}
    params["blocks"] = stack_blocks(raw["blocks"])
    pspecs = {k: P() for k in params if k != "blocks"}
    pspecs["blocks"] = part.resolve(stacked_logical_specs(
        block_logical_specs(cfg.mlp, use_bias=cfg.use_bias, norm=cfg.norm)))
    state_axes, tx_kw, zero_numel = _dist_state_setup(
        mesh, params, pspecs, dp, zero_1, slc=slc)
    params, opt_state, ospecs = _shard_params_state(
        mesh,
        _make_tx(mesh, base_tx, compression_params, partition_bytes, dp,
                 dcn=slc, **tx_kw),
        params, pspecs, dp, state_axes=state_axes, zero_numel=zero_numel,
        slc=slc,
    )
    batch_spec = part.batch_spec()
    loss_fn = functools.partial(
        gpt_pp_loss, cfg=cfg, pp_axis=pp, n_micro=n_micro, tp_axis=tp,
        sp_axis=sp, remat=remat,
        vma_axes=tuple(mesh.axis_names) if use_vma else (),
        seq_layout=seq_layout, chunked_ce=chunked_ce,
    )

    def build_jit(pb):
        tx = _make_tx(mesh, base_tx, compression_params, pb, dp, dcn=slc,
                      **tx_kw)
        return _build_pp_jit(
            mesh, pspecs, ospecs, batch_spec, loss_fn, tx, dp, pp,
            mean_axes=tuple(a for a in (slc, dp) if a is not None),
            use_vma=use_vma, rep_axes=(tp, sp), slc=slc,
        )

    return (
        _finalize_step(build_jit, partition_bytes, dp or slc,
                       tunable=not zero_1),
        params, opt_state, NamedSharding(mesh, batch_spec),
    )


def make_gpt_moe_train_step(
    cfg,
    mesh: Mesh,
    base_tx: optax.GradientTransformation,
    compression_params: Optional[Dict[str, Any]] = None,
    partition_bytes: Optional[int] = None,
    remat: bool = False,
    zero_1: bool = False,
    seq_layout: str = "contiguous",
    chunked_ce=True,
    init_params: Optional[Dict[str, Any]] = None,
):
    """Expert-parallel MoE GPT train step over a (dp, ep[, tp][, sp]) mesh.

    The batch shards over dp AND ep (every device routes its own tokens to
    all experts via all_to_all); expert-stacked FFN weights shard P('ep')
    and, with a tp axis, Megatron col/row shard their ff dim (attention
    runs tp-parallel too). The step runs check_vma=True: VMA auto-inserts
    the collectives for replicated-param cotangents over ep/tp, and one
    uniform /ep turns the summed per-device grads into the mean the
    mean-of-local-means loss needs; dp averaging stays in
    DistributedOptimizer as everywhere else.

    ``compression_params`` enables compressed dp aggregation
    (check_vma=False mode): the ep psums of ep-invariant leaves run
    explicitly (tp/sp via ``_novma_collective_fix``), then each
    (ep group, dp worker) compresses its grads over dp with its own
    EF/momentum state.

    ``seq_layout="zigzag"`` runs the load-balanced causal ring over sp —
    feed tokens/targets pre-permuted with ``zigzag_permutation``, as for
    the dense factory. (The load-balancing aux term is a function of
    per-device router statistics, so its VALUE legitimately depends on
    how tokens shard; the nll is exact across layouts.)

    ``cfg`` picks the model (:func:`_moe_family`): a ``MoEGPTConfig``
    (Switch/GShard capacity routing, expert-parallel over ep) or a
    ``JoyAIConfig`` (``models/joyai.py``: latent attention, dropless
    sigmoid top-k routing over the experts held here, a shared expert,
    the MTP loss). A model may name *buffers* among its leaves (the
    routing correction bias): they ride in ``params`` — no gradient is
    taken, the optimizer holds no state for them and decays nothing of
    them; the model's own ``step_buffers`` rule moves them after the
    step, or they come back unchanged — and a stats vector its loss
    returns (``_TickingStep``). ``init_params`` starts from the caller's
    weights (the model's init tree, e.g. made on the device from a seed)
    instead of ``PRNGKey(0)`` ones built on the host.

    Returns ``(step, params, opt_state, batch_sharding)``.
    """
    (model_init, model_loss, buffer_keys, stats_names,
     step_buffers) = _moe_family(cfg)

    part = Partitioner.for_config(cfg, mesh)
    dp, ep = part.dp, part.ep
    tp, sp, slc = part.tp, part.sp, part.slice_
    if part.pp is not None:
        raise ValueError(
            "mesh has a pp axis — use make_gpt_moe_pp_train_step for "
            "pipelined MoE"
        )
    _check_seq_layout(seq_layout, sp)
    use_vma = compression_params is None and not zero_1
    ep_size = mesh.shape[ep] if ep is not None else 1
    if ep is not None and getattr(cfg, "n_experts", ep_size) % ep_size != 0:
        raise ValueError(
            f"n_experts={cfg.n_experts} not divisible by ep={ep_size}"
        )
    all_specs = part.param_specs(cfg)
    params = _resolve_init_params(init_params, cfg, all_specs,
                                  init_fn=model_init)
    # the optimizer's view: the tree less its buffers (all of it where a
    # model has none; ``pspecs`` is that view's specs from here on)
    pspecs = _drop_buffers(all_specs, buffer_keys)
    trainable = _drop_buffers(params, buffer_keys)
    state_axes, tx_kw, zero_numel = _dist_state_setup(
        mesh, trainable, pspecs, dp, zero_1, slc=slc)
    trainable, opt_state, ospecs = _shard_params_state(
        mesh,
        _make_tx(mesh, base_tx, compression_params, partition_bytes, dp,
                 dcn=slc, **tx_kw),
        trainable, pspecs, dp, state_axes=state_axes, zero_numel=zero_numel,
        slc=slc,
    )
    if buffer_keys:
        params = _with_buffers(jax.device_put(params, jax.tree.map(
            lambda s: NamedSharding(mesh, s), all_specs)), trainable)
    else:
        params = trainable
    batch_spec = part.batch_spec()
    resym = _make_resymmetrize(pspecs, dp, slc)
    chains = _chains(mesh, use_vma, slc, dp)
    loss_fn = functools.partial(model_loss, cfg=cfg, ep_axis=ep,
                                tp_axis=tp, sp_axis=sp, remat=remat,
                                seq_layout=seq_layout,
                                chunked_ce=chunked_ce)

    def build_jit(pb):
        tx = _make_tx(mesh, base_tx, compression_params, pb, dp, dcn=slc,
                      **tx_kw)

        def per_device_step(all_params, opt_state, tokens, targets):
            params = _drop_buffers(all_params, buffer_keys)
            grad_params = _pcast_dp(params, dp, mesh, use_vma, slc)
            out, grads, order = _vag_in_order(jax.value_and_grad(
                lambda p: loss_fn(_with_buffers(all_params, p), tokens,
                                  targets), has_aux=bool(stats_names)
            ), chains, grad_params)
            loss, (stats, buffer_aux) = (out if stats_names
                                         else (out, (None, None)))
            if not use_vma:
                grads = _novma_collective_fix(
                    grads, pspecs, mesh, (tp, sp), extra_sum_axes=(ep,))
            if ep is not None:
                # the global loss is the MEAN of per-device local means;
                # the ep-invariant leaves' grads must arrive SUMMED over
                # ep (VMA auto-psum under check_vma=True, explicit psums
                # in compressed mode via _novma_collective_fix) and the
                # expert slabs already summed their peers' contributions
                # through the all_to_all transpose — one uniform /ep
                # gives means
                grads = jax.tree.map(lambda g: g / ep_size, grads)
            grads = resym(grads)  # collapse conservative VMA widening
            updates, opt_state = _update(tx, order, grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            axes = tuple(a for a in (slc, dp, ep) if a is not None)
            if axes:
                loss = jax.lax.pmean(loss, axes)
            loss = _collapse_vma(loss)
            params = _with_buffers(all_params, params)
            if step_buffers is not None:
                # the model's own rule for its buffers, from what the loss
                # counted over every rank's tokens
                if axes:
                    buffer_aux = jax.lax.psum(buffer_aux, axes)
                params = step_buffers(params, _collapse_vma(buffer_aux), cfg)
            if stats_names:
                if axes:    # one device's counts and loss terms, averaged
                    stats = jax.lax.pmean(stats, axes)
                return loss, params, opt_state, _collapse_vma(stats)
            return loss, params, opt_state

        sharded = jax.shard_map(
            per_device_step,
            mesh=mesh,
            in_specs=(all_specs, ospecs, batch_spec, batch_spec),
            out_specs=(P(), all_specs, ospecs)
            + ((P(),) if stats_names else ()),
            check_vma=use_vma,
        )
        return jax.jit(sharded, donate_argnums=(0, 1))

    return (
        _finalize_step(build_jit, partition_bytes, dp or slc,
                       tunable=not zero_1, stats_names=stats_names),
        params, opt_state, NamedSharding(mesh, batch_spec),
    )


def _moe_family(cfg):
    """``(init, loss, buffer_keys, stats_names, step_buffers)`` of the
    model a config of the ``moe_gpt`` family names
    (``parallel/partitioner.py``'s table). A model with ``stats_names``
    returns ``(loss, (stats, buffer_aux))``; ``step_buffers(params,
    buffer_aux, cfg)`` is its rule for its buffers after a step."""
    if type(cfg).__name__ == "JoyAIConfig":
        from byteps_tpu.models import joyai
        return (joyai.joyai_init, joyai.joyai_loss, joyai.BUFFER_KEYS,
                joyai.STEP_STATS, joyai.joyai_step_buffers)
    from byteps_tpu.models.moe_gpt import moe_gpt_init, moe_gpt_loss
    return moe_gpt_init, moe_gpt_loss, (), (), None


def _drop_buffers(tree, buffer_keys):
    """``tree`` (dicts and lists of leaves) without the dict entries whose
    key is in ``buffer_keys``: the optimizer's view, which tree maps,
    optimizer state and gradient assembly see. The tree itself where
    there are no such keys."""
    if not buffer_keys:
        return tree
    if isinstance(tree, dict):
        return {k: _drop_buffers(v, buffer_keys) for k, v in tree.items()
                if k not in buffer_keys}
    if isinstance(tree, (list, tuple)):
        return type(tree)(_drop_buffers(v, buffer_keys) for v in tree)
    return tree


def _with_buffers(full, trainable):
    """``trainable`` (a :func:`_drop_buffers` view of ``full``, or
    something of its structure) with the entries it lacks taken from
    ``full``."""
    if isinstance(full, dict):
        return {k: _with_buffers(v, trainable[k]) if k in trainable else v
                for k, v in full.items()}
    if isinstance(full, (list, tuple)):
        return type(full)(_with_buffers(f, t)
                          for f, t in zip(full, trainable))
    return trainable


def make_gpt_moe_pp_train_step(
    cfg,
    mesh: Mesh,
    base_tx: optax.GradientTransformation,
    n_micro: int = 4,
    compression_params: Optional[Dict[str, Any]] = None,
    partition_bytes: Optional[int] = None,
    remat: bool = False,
    zero_1: bool = False,
    seq_layout: str = "contiguous",
    chunked_ce=True,
):
    """Pipelined MoE GPT over a (pp, dp[, ep][, tp][, sp]) mesh — the full
    composition: GPipe microbatch pipelining whose stages hold MoE blocks
    with all_to_all expert dispatch (ep), Megatron-sharded experts and
    attention (tp), and ring attention (sp), all typed by VMA in one
    jitted program. Routing happens per microbatch (capacity from the
    microbatch token count). Grad assembly combines the pp and ep rules:
    pp-replicated leaves psum over pp, then everything divides by ep
    (mean of per-device local means); dp aggregation stays in
    DistributedOptimizer.

    ``seq_layout="zigzag"`` follows the same pre-permuted-input contract
    as every other factory (see :func:`make_gpt_moe_train_step`'s note on
    the aux term).

    Returns ``(step, params, opt_state, batch_sharding)``;
    ``params["blocks"]`` is the stacked MoE-block slab.
    """
    from byteps_tpu.models.moe_gpt import (
        moe_block_logical_specs,
        moe_gpt_init,
        moe_gpt_pp_loss,
    )
    from byteps_tpu.parallel.pipeline import stack_blocks

    part = Partitioner.for_config(cfg, mesh)
    dp, pp = part.dp, part.pp
    ep, tp, sp, slc = part.ep, part.tp, part.sp, part.slice_
    if pp is None:
        raise ValueError("mesh has no pp axis — use make_gpt_moe_train_step")
    _check_seq_layout(seq_layout, sp)
    use_vma = compression_params is None and not zero_1
    nstages = mesh.shape[pp]
    ep_size = mesh.shape[ep] if ep is not None else 1
    if cfg.n_layers % nstages != 0:
        raise ValueError(
            f"n_layers={cfg.n_layers} not divisible by pp={nstages}"
        )
    if ep is not None and cfg.n_experts % ep_size != 0:
        raise ValueError(
            f"n_experts={cfg.n_experts} not divisible by ep={ep_size}"
        )
    raw = moe_gpt_init(jax.random.PRNGKey(0), cfg)
    params = {k: v for k, v in raw.items() if k != "blocks"}
    params["blocks"] = stack_blocks(raw["blocks"])
    pspecs = {k: P() for k in params if k != "blocks"}
    pspecs["blocks"] = part.resolve(stacked_logical_specs(
        moe_block_logical_specs(use_bias=cfg.use_bias, norm=cfg.norm,
                                mlp=cfg.mlp)))
    state_axes, tx_kw, zero_numel = _dist_state_setup(
        mesh, params, pspecs, dp, zero_1, slc=slc)
    params, opt_state, ospecs = _shard_params_state(
        mesh,
        _make_tx(mesh, base_tx, compression_params, partition_bytes, dp,
                 dcn=slc, **tx_kw),
        params, pspecs, dp, state_axes=state_axes, zero_numel=zero_numel,
        slc=slc,
    )
    batch_spec = part.batch_spec()
    loss_fn = functools.partial(
        moe_gpt_pp_loss, cfg=cfg, pp_axis=pp, n_micro=n_micro,
        ep_axis=ep, tp_axis=tp, sp_axis=sp, remat=remat,
        vma_axes=tuple(mesh.axis_names) if use_vma else (),
        seq_layout=seq_layout, chunked_ce=chunked_ce,
    )

    def build_jit(pb):
        tx = _make_tx(mesh, base_tx, compression_params, pb, dp, dcn=slc,
                      **tx_kw)
        return _build_pp_jit(
            mesh, pspecs, ospecs, batch_spec, loss_fn, tx, dp, pp,
            ep=ep, ep_size=ep_size if ep is not None else 1,
            mean_axes=tuple(a for a in (slc, dp, ep) if a is not None),
            use_vma=use_vma, rep_axes=(tp, sp), slc=slc,
        )

    return (
        _finalize_step(build_jit, partition_bytes, dp or slc,
                       tunable=not zero_1),
        params, opt_state, NamedSharding(mesh, batch_spec),
    )


def make_bert_train_step(
    cfg: BertConfig,
    mesh: Mesh,
    base_tx: optax.GradientTransformation,
    compression_params: Optional[Dict[str, Any]] = None,
    partition_bytes: Optional[int] = None,
    remat: bool = False,
    zero_1: bool = False,
    accum_steps: int = 1,
    chunked_ce=True,
):
    """``step(params, opt_state, tokens, targets, mask)`` — MLM pretraining
    step (BASELINE config 3 shape), same sharding story as GPT (zero_1 /
    accum_steps / chunked_ce semantics included)."""
    part = Partitioner.for_config(cfg, mesh)
    dp, tp, sp, slc = part.dp, part.tp, part.sp, part.slice_
    use_vma = compression_params is None and not zero_1
    pspecs = part.param_specs(cfg)
    params = bert_init(jax.random.PRNGKey(0), cfg)
    state_axes, tx_kw, zero_numel = _dist_state_setup(
        mesh, params, pspecs, dp, zero_1, slc=slc)
    params, opt_state, ospecs = _shard_params_state(
        mesh,
        _make_tx(mesh, base_tx, compression_params, partition_bytes, dp,
                 dcn=slc, **tx_kw),
        params, pspecs, dp, state_axes=state_axes, zero_numel=zero_numel,
        slc=slc,
    )
    batch_spec = part.batch_spec()
    mean_axes = tuple(a for a in (slc, dp) if a is not None)
    resym = _make_resymmetrize(pspecs, dp, slc)
    chains = _chains(mesh, use_vma, slc, dp)
    loss_fn = functools.partial(
        bert_mlm_loss, cfg=cfg, dp_axis=None, tp_axis=tp, sp_axis=sp,
        remat=remat, chunked_ce=chunked_ce,
    )

    def build_jit(pb):
        tx = _make_tx(mesh, base_tx, compression_params, pb, dp, dcn=slc,
                      **tx_kw)
        # masked-mean loss: weight each microbatch by its mask count so
        # the accumulated gradient equals the full-batch masked mean; the
        # count must be the sp-GLOBAL one (the loss normalizes by it after
        # its sp psum) or the weights would be sp-varying while the grads
        # are sp-replicated
        def _mask_count(tokens, targets, mask):
            w = mask.sum()
            return jax.lax.psum(w, sp) if sp is not None else w

        vag = _accumulating_value_and_grad(loss_fn, accum_steps,
                                           weight_fn=_mask_count)

        def per_device_step(params, opt_state, tokens, targets, mask):
            grad_params = _pcast_dp(params, dp, mesh, use_vma, slc)
            loss, grads, order = _vag_in_order(
                vag, chains, grad_params, tokens, targets, mask)
            if use_vma:
                grads = resym(grads)
            else:
                grads = _novma_collective_fix(grads, pspecs, mesh, (tp, sp))
            updates, opt_state = _update(tx, order, grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            if mean_axes:
                loss = jax.lax.pmean(loss, mean_axes)
            return _collapse_vma(loss), params, opt_state

        sharded = jax.shard_map(
            per_device_step,
            mesh=mesh,
            in_specs=(pspecs, ospecs, batch_spec, batch_spec, batch_spec),
            out_specs=(P(), pspecs, ospecs),
            check_vma=use_vma,
        )
        return jax.jit(sharded, donate_argnums=(0, 1))

    return (
        _finalize_step(build_jit, partition_bytes, dp or slc,
                       tunable=not zero_1),
        params, opt_state, NamedSharding(mesh, batch_spec),
    )


def make_t5_train_step(
    cfg: T5Config,
    mesh: Mesh,
    base_tx: optax.GradientTransformation,
    compression_params: Optional[Dict[str, Any]] = None,
    partition_bytes: Optional[int] = None,
    remat: bool = False,
    zero_1: bool = False,
    accum_steps: int = 1,
    chunked_ce=True,
):
    """``step(params, opt_state, src, tgt_in, tgt_out) -> (loss, params,
    opt_state)`` — encoder-decoder seq2seq over a (dp, tp, sp) mesh;
    blocks and tp sharding shared with GPT/BERT, cross-attention added by
    the decoder blocks (models/t5.py). With an sp axis BOTH sides
    sequence-shard: non-causal encoder ring, causal decoder ring, and a
    rectangular cross-attention ring over the sp-sharded encoder memory
    (src and tgt lengths must each divide by the sp size)."""
    part = Partitioner.for_config(cfg, mesh)
    dp, tp, sp, slc = part.dp, part.tp, part.sp, part.slice_
    use_vma = compression_params is None and not zero_1
    pspecs = part.param_specs(cfg)
    params = t5_init(jax.random.PRNGKey(0), cfg)
    state_axes, tx_kw, zero_numel = _dist_state_setup(
        mesh, params, pspecs, dp, zero_1, slc=slc)
    params, opt_state, ospecs = _shard_params_state(
        mesh,
        _make_tx(mesh, base_tx, compression_params, partition_bytes, dp,
                 dcn=slc, **tx_kw),
        params, pspecs, dp, state_axes=state_axes, zero_numel=zero_numel,
        slc=slc,
    )
    batch_spec = part.batch_spec()
    mean_axes = tuple(a for a in (slc, dp) if a is not None)
    resym = _make_resymmetrize(pspecs, dp, slc)
    chains = _chains(mesh, use_vma, slc, dp)
    loss_fn = functools.partial(
        t5_loss, cfg=cfg, dp_axis=None, tp_axis=tp, sp_axis=sp, remat=remat,
        chunked_ce=chunked_ce,
    )

    def build_jit(pb):
        tx = _make_tx(mesh, base_tx, compression_params, pb, dp, dcn=slc,
                      **tx_kw)
        vag = _accumulating_value_and_grad(loss_fn, accum_steps)

        def per_device_step(params, opt_state, src, tgt_in, tgt_out):
            grad_params = _pcast_dp(params, dp, mesh, use_vma, slc)
            loss, grads, order = _vag_in_order(
                vag, chains, grad_params, src, tgt_in, tgt_out)
            if use_vma:
                grads = resym(grads)
            else:
                grads = _novma_collective_fix(grads, pspecs, mesh, (tp, sp))
            updates, opt_state = _update(tx, order, grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            if mean_axes:
                loss = jax.lax.pmean(loss, mean_axes)
            return _collapse_vma(loss), params, opt_state

        sharded = jax.shard_map(
            per_device_step,
            mesh=mesh,
            in_specs=(pspecs, ospecs, batch_spec, batch_spec, batch_spec),
            out_specs=(P(), pspecs, ospecs),
            check_vma=use_vma,
        )
        return jax.jit(sharded, donate_argnums=(0, 1))

    return (
        _finalize_step(build_jit, partition_bytes, dp or slc,
                       tunable=not zero_1),
        params, opt_state, NamedSharding(mesh, batch_spec),
    )


def make_vit_train_step(
    cfg: ViTConfig,
    mesh: Mesh,
    base_tx: optax.GradientTransformation,
    compression_params: Optional[Dict[str, Any]] = None,
    partition_bytes: Optional[int] = None,
    remat: bool = False,
    zero_1: bool = False,
    accum_steps: int = 1,
):
    """``step(params, opt_state, images, labels) -> (loss, params,
    opt_state)`` — ViT classification over a (dp, tp) mesh; blocks and
    their tp sharding are shared with GPT/BERT, the batch axis with
    ResNet (sp intentionally unsupported — models/vit.py rationale)."""
    part = Partitioner.for_config(cfg, mesh)
    dp, tp, slc = part.dp, part.tp, part.slice_
    use_vma = compression_params is None and not zero_1
    pspecs = part.param_specs(cfg)
    params = vit_init(jax.random.PRNGKey(0), cfg)
    state_axes, tx_kw, zero_numel = _dist_state_setup(
        mesh, params, pspecs, dp, zero_1, slc=slc)
    params, opt_state, ospecs = _shard_params_state(
        mesh,
        _make_tx(mesh, base_tx, compression_params, partition_bytes, dp,
                 dcn=slc, **tx_kw),
        params, pspecs, dp, state_axes=state_axes, zero_numel=zero_numel,
        slc=slc,
    )
    batch_spec = part.batch_spec()
    mean_axes = tuple(a for a in (slc, dp) if a is not None)
    resym = _make_resymmetrize(pspecs, dp, slc)
    chains = _chains(mesh, use_vma, slc, dp)
    loss_fn = functools.partial(
        vit_loss, cfg=cfg, dp_axis=None, tp_axis=tp, remat=remat,
    )

    def build_jit(pb):
        tx = _make_tx(mesh, base_tx, compression_params, pb, dp, dcn=slc,
                      **tx_kw)
        vag = _accumulating_value_and_grad(loss_fn, accum_steps)

        def per_device_step(params, opt_state, images, labels):
            grad_params = _pcast_dp(params, dp, mesh, use_vma, slc)
            loss, grads, order = _vag_in_order(
                vag, chains, grad_params, images, labels)
            if use_vma:
                grads = resym(grads)
            else:
                grads = _novma_collective_fix(grads, pspecs, mesh, (tp,))
            updates, opt_state = _update(tx, order, grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            if mean_axes:
                loss = jax.lax.pmean(loss, mean_axes)
            return _collapse_vma(loss), params, opt_state

        sharded = jax.shard_map(
            per_device_step,
            mesh=mesh,
            in_specs=(pspecs, ospecs, batch_spec, batch_spec),
            out_specs=(P(), pspecs, ospecs),
            check_vma=use_vma,
        )
        return jax.jit(sharded, donate_argnums=(0, 1))

    return (
        _finalize_step(build_jit, partition_bytes, dp or slc,
                       tunable=not zero_1),
        params, opt_state, NamedSharding(mesh, batch_spec),
    )


def make_resnet_train_step(
    cfg: ResNetConfig,
    mesh: Mesh,
    base_tx: optax.GradientTransformation,
    compression_params: Optional[Dict[str, Any]] = None,
    partition_bytes: Optional[int] = None,
    zero_1: bool = False,
):
    """``step(params, opt_state, bn_state, images, labels) ->
    (loss, params, opt_state, bn_state)`` — dp-only conv family
    (BASELINE config 2 shape); BN stats are dp-synced (SyncBN) so the
    replicated bn_state stays identical everywhere.
    """
    part = Partitioner.for_config(cfg, mesh)
    dp, slc = part.dp, part.slice_
    use_vma = compression_params is None and not zero_1
    params, bn_state = resnet_init(jax.random.PRNGKey(0), cfg)
    pspecs = part.param_specs(cfg, params)
    state_axes, tx_kw, zero_numel = _dist_state_setup(
        mesh, params, pspecs, dp, zero_1, slc=slc)
    params, opt_state, ospecs = _shard_params_state(
        mesh,
        _make_tx(mesh, base_tx, compression_params, partition_bytes, dp,
                 dcn=slc, **tx_kw),
        params, pspecs, dp, state_axes=state_axes, zero_numel=zero_numel,
        slc=slc,
    )
    sspecs = jax.tree.map(lambda _: P(), bn_state)
    bn_state = jax.device_put(
        bn_state, jax.tree.map(lambda s: NamedSharding(mesh, s), sspecs)
    )
    batch_spec = part.batch_spec()
    mean_axes = tuple(a for a in (slc, dp) if a is not None)
    # SyncBN statistics sync over every data axis (slice_ and dp)
    bn_axes = mean_axes if mean_axes else None
    resym = _make_resymmetrize(pspecs, dp, slc)
    chains = _chains(mesh, use_vma, slc, dp)

    def loss_fn(params, bn_state, images, labels):
        return resnet_loss(params, bn_state, images, labels, cfg,
                           dp_axis=bn_axes, train=True)

    def build_jit(pb):
        tx = _make_tx(mesh, base_tx, compression_params, pb, dp, dcn=slc,
                      **tx_kw)

        def per_device_step(params, opt_state, bn_state, images, labels):
            grad_params = _pcast_dp(params, dp, mesh, use_vma, slc)
            (loss, new_bn), grads, order = _vag_in_order(
                jax.value_and_grad(loss_fn, has_aux=True), chains,
                grad_params, bn_state, images, labels
            )
            if use_vma:
                grads = resym(grads)
                # SyncBN pmean makes stats unvarying, but conservative VMA
                # can widen the state type the same way it widens grads
                new_bn = jax.tree.map(_collapse_vma, new_bn)
            updates, opt_state = _update(tx, order, grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            if mean_axes:
                loss = jax.lax.pmean(loss, mean_axes)
            return loss, params, opt_state, new_bn

        sharded = jax.shard_map(
            per_device_step,
            mesh=mesh,
            in_specs=(pspecs, ospecs, sspecs, batch_spec, batch_spec),
            out_specs=(P(), pspecs, ospecs, sspecs),
            check_vma=use_vma,
        )
        return jax.jit(sharded, donate_argnums=(0, 1, 2))

    return (
        _finalize_step(build_jit, partition_bytes, dp or slc,
                       tunable=not zero_1),
        params, opt_state, bn_state, NamedSharding(mesh, batch_spec),
    )


def synthetic_batch(
    rng: jnp.ndarray, cfg: GPTConfig, batch: int, seq: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Random next-token LM batch (the reference benchmarks train on
    synthetic data too — example/pytorch/benchmark_byteps.py)."""
    toks = jax.random.randint(rng, (batch, seq + 1), 0, cfg.vocab_size)
    return toks[:, :-1], toks[:, 1:]


def synthetic_mlm_batch(rng: jnp.ndarray, cfg: BertConfig, batch: int,
                        seq: int, mask_rate: float = 0.15):
    """(corrupted tokens, targets, mask) for MLM pretraining."""
    k1, k2 = jax.random.split(rng)
    targets = jax.random.randint(k1, (batch, seq), 0, cfg.vocab_size)
    mask = jax.random.bernoulli(k2, mask_rate, (batch, seq))
    mask_id = cfg.vocab_size - 1  # last id doubles as [MASK] in synthetic data
    tokens = jnp.where(mask, mask_id, targets)
    return tokens, targets, mask.astype(jnp.int32)

def make_eval_step(cfg: GPTConfig, mesh: Mesh, seq_layout: str = "contiguous",
                   chunked_ce=True):
    """Jitted eval step: ``eval_step(params, tokens, targets) -> mean nll``
    over the (dp, sp)-sharded batch — exp() of the running mean is the
    perplexity. Shares gpt_loss (and therefore every config option:
    rope/GQA/SwiGLU, zigzag layout, chunked readout+CE) with the train
    factories; no optimizer, no grads, safe to call on training params at
    any step.
    """
    part = Partitioner.for_config(cfg, mesh)
    dp, tp, sp, slc = part.dp, part.tp, part.sp, part.slice_
    _check_seq_layout(seq_layout, sp)
    batch_spec = part.batch_spec()
    pspecs = part.param_specs(cfg)

    def per_device(params, tokens, targets):
        loss = gpt_loss(params, tokens, targets, cfg, dp_axis=dp,
                        tp_axis=tp, sp_axis=sp, seq_layout=seq_layout,
                        chunked_ce=chunked_ce)
        if slc is not None:
            loss = jax.lax.pmean(loss, slc)
        return _collapse_vma(loss)

    sharded = jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(pspecs, batch_spec, batch_spec),
        out_specs=P(),
        check_vma=True,
    )
    return jax.jit(sharded), NamedSharding(mesh, batch_spec)


def evaluate_perplexity(eval_step, params, batches, batch_sharding) -> float:
    """Mean perplexity over an iterable of (tokens, targets) host batches."""
    total, n = 0.0, 0
    for tokens, targets in batches:
        tok = jax.device_put(tokens, batch_sharding)
        tgt = jax.device_put(targets, batch_sharding)
        total += float(eval_step(params, tok, tgt))
        n += 1
    if n == 0:
        raise ValueError("evaluate_perplexity: no batches")
    return float(np.exp(total / n))
