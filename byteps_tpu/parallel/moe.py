"""Expert parallelism (ep axis): Mixture-of-Experts FFN with all_to_all
dispatch.

The reference is data-parallel only; ep is the last of the "beyond
reference" mesh axes (pp/tp/sp being the others). TPU-first design
(Switch/GShard style): top-1 or top-2 gating with a static per-expert
capacity (XLA needs static shapes — tokens beyond capacity are dropped,
their residual path passes through untouched), dispatch/combine as
einsums against a one-hot (token, expert, slot) tensor so the MXU does
the routing, and expert placement over the ``ep`` mesh axis with a pair
of ``lax.all_to_all`` collectives shipping token slots to their expert's
owner and back over ICI.

Inside ``shard_map`` each device owns ``E / ep_size`` experts
(expert-stacked weights sharded ``P('ep')`` on their leading axis) and
every device routes its OWN tokens to all E experts — dp and ep compose:
dp replicas each contribute their local batch's slots.

A second path, :func:`moe_ffn_dropless` (end of the file), routes as
DeepSeek-V3-family models do — sigmoid scores, top-k on score + a
correction bias, hundreds of experts, NO dropped token — by grouping the
(token, expert) pairs by expert and running grouped matrix products
(``ops/grouped_matmul.py``) over the experts this device holds. It is told
which experts those are and runs no exchange; it also counts the picks
of every routed expert, from which :func:`noaux_bias_step` moves the
correction bias between steps. The capacity path above is unchanged and
stays ``MoEGPTConfig``'s.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from byteps_tpu.common.metrics import get_registry


def topk_dispatch(gate_logits: jnp.ndarray, capacity: int, k: int = 1):
    """Top-k routing tensors from ``(T, E)`` gate logits (k=1: Switch;
    k=2: GShard-style, second choices take slots after first choices and
    the two gates renormalize to sum 1 per token).

    Returns ``(dispatch, combine, aux_loss)``: ``dispatch`` is a one-hot
    ``(T, E, C)`` float tensor mapping each kept (token, choice) to its
    (expert, slot); ``combine`` is ``dispatch`` scaled by the choice's
    gate weight; ``aux_loss`` is the Switch load-balancing loss on the
    FIRST choice (mean_e frac_tokens_e · mean_prob_e · E).
    """
    T, E = gate_logits.shape
    if not 1 <= k <= E:
        raise ValueError(f"router top-k must satisfy 1 <= k <= n_experts "
                         f"({E}); got k={k}")
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    remaining = probs
    onehots, gates = [], []
    for _ in range(k):
        expert = jnp.argmax(remaining, axis=-1)            # (T,)
        oh = jax.nn.one_hot(expert, E, dtype=jnp.float32)  # (T, E)
        gates.append(jnp.sum(probs * oh, axis=-1))
        onehots.append(oh)
        remaining = remaining * (1.0 - oh)
    if k > 1:
        # renormalize so each token's kept choices sum to 1 (GShard).
        # NEVER for k=1: that would collapse every weight to exactly 1.0,
        # silencing the router's gradient through the task loss — Switch
        # keeps the raw softmax prob as the combine weight
        gate_sum = sum(gates)
        gates = [g / jnp.maximum(gate_sum, 1e-9) for g in gates]

    dispatch = jnp.zeros((T, E, capacity), jnp.float32)
    combine = jnp.zeros((T, E, capacity), jnp.float32)
    used = jnp.zeros((E,), jnp.float32)  # slots consumed by earlier ranks
    for oh, gate in zip(onehots, gates):
        # slot = rank among earlier tokens of this expert AT THIS CHOICE
        # rank, offset by slots used by earlier choice ranks
        slot = (jnp.cumsum(oh, axis=0) - 1.0) * oh + used[None, :] * oh
        kept = (slot < capacity) & (oh > 0)
        slot_oh = jax.nn.one_hot(
            jnp.sum(jnp.clip(slot, 0, capacity - 1),
                    axis=-1).astype(jnp.int32),
            capacity, dtype=jnp.float32,
        )
        d = kept.astype(jnp.float32)[:, :, None] * slot_oh[:, None, :]
        dispatch = dispatch + d
        combine = combine + d * gate[:, None, None]
        used = used + jnp.sum(oh, axis=0)
    frac = onehots[0].mean(axis=0)
    aux = E * jnp.sum(frac * probs.mean(axis=0))
    return dispatch, combine, aux


def top1_dispatch(gate_logits: jnp.ndarray, capacity: int):
    """Switch-style top-1 routing (see :func:`topk_dispatch`)."""
    return topk_dispatch(gate_logits, capacity, k=1)


def moe_ffn(
    x: jnp.ndarray,
    params,
    capacity_factor: float = 1.25,
    ep_axis: Optional[str] = None,
    activation=jax.nn.gelu,
    router_topk: int = 1,
    tp_axis: Optional[str] = None,
    no_drop: bool = False,
):
    """MoE feed-forward over the trailing feature dim of ``x (..., d)``.

    ``params``: ``wg (d, E)`` gate; expert-stacked ``w1 (E_loc, d, ff)``,
    ``b1 (E_loc, ff)``, ``w2 (E_loc, ff, d)``, ``b2 (E_loc, d)`` — with
    ``ep_axis`` set these are THIS device's expert slab (global tensors
    sharded ``P('ep')``); without it they hold all experts. With
    ``tp_axis`` the experts are additionally Megatron-sharded: w1/b1
    column-parallel over the ff dim, w2 row-parallel with a psum over tp
    restoring the full output (`moe_specs(ep, tp)` gives the layout).

    Returns ``(y, aux_loss)`` with ``y`` shaped like ``x``. Dropped
    (over-capacity) tokens produce zero — add the residual outside, as the
    transformer block does. ``no_drop=True`` sets capacity so NO token can
    be dropped (``T`` slots per expert — the worst-case load, since a
    token's k choices are distinct experts) — decode-time routing, where
    a drop silently corrupts the sample. Memory note: that worst case
    allocates ``E × T × d`` dispatch slots per layer, so no-drop prefill
    of a long prompt spikes HBM roughly ``E×`` the dense activation;
    chunk long prefills (gpt_apply_cached accepts any T) if that
    pressure shows up in profiles.
    """
    ep = jax.lax.axis_size(ep_axis) if ep_axis is not None else 1
    e_loc = params["w1"].shape[0]
    E = e_loc * ep
    lead = x.shape[:-1]
    d = x.shape[-1]
    T = 1
    for s in lead:
        T *= s
    xt = x.reshape(T, d)
    # gating/dispatch in f32 (standard Switch practice); the expert
    # matmuls and the all_to_all payload run in x.dtype like the dense
    # family's _mlp — bf16 configs keep full MXU rate and half ICI bytes
    gate_logits = xt.astype(jnp.float32) @ params["wg"].astype(jnp.float32)
    # no-drop worst case is T (a token's k choices are DISTINCT experts,
    # so any one expert receives at most T assignments)
    cap = (T if no_drop
           else max(1, int(capacity_factor * router_topk * T / E)))
    dispatch, combine, aux = topk_dispatch(gate_logits, cap, k=router_topk)
    slots = jnp.einsum(
        "tec,td->ecd", dispatch.astype(x.dtype), xt
    )                                                      # (E, cap, d)
    if ep_axis is not None:
        # ship each expert's slots to its owner: (E, cap, d) →
        # (ep, E_loc, cap, d) → all_to_all → every device holds, for its
        # OWN experts, the slots from every peer: (ep, E_loc, cap, d)
        slots = slots.reshape(ep, e_loc, cap, d)
        slots = jax.lax.all_to_all(
            slots, ep_axis, split_axis=0, concat_axis=0, tiled=False
        )
        # (ep, E_loc, cap, d): axis 0 now indexes the SOURCE device; bring
        # the local-expert axis out front for the expert matmuls
        slots = slots.transpose(1, 0, 2, 3).reshape(e_loc, ep * cap, d)
    h = jnp.einsum("ecd,edf->ecf", slots, params["w1"].astype(x.dtype))
    h = h + params["b1"][:, None, :].astype(x.dtype)
    if "w3" in params:
        # gated experts (structural dispatch, like the dense _mlp):
        # silu(slots·w1) ∘ (slots·w3), per expert
        g = jnp.einsum("ecd,edf->ecf", slots, params["w3"].astype(x.dtype))
        g = g + params["b3"][:, None, :].astype(x.dtype)
        h = jax.nn.silu(h) * g
    else:
        h = activation(h)
    y = jnp.einsum("ecf,efd->ecd", h, params["w2"].astype(x.dtype))
    if tp_axis is not None:
        # row-parallel: each tp shard computed a partial over its ff slice
        y = jax.lax.psum(y, tp_axis)
    y = y + params["b2"][:, None, :].astype(x.dtype)
    if ep_axis is not None:
        y = y.reshape(e_loc, ep, cap, d).transpose(1, 0, 2, 3)
        y = jax.lax.all_to_all(
            y, ep_axis, split_axis=0, concat_axis=0, tiled=False
        )
        # axis 0 = expert-group owner: global expert e = owner*E_loc + local
        y = y.reshape(E, cap, d)
    out = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), y)
    return out.reshape(*lead, d).astype(x.dtype), aux.astype(jnp.float32)


def moe_init(rng, d: int, ff: int, n_experts: int, std: float = 0.02,
             mlp: str = "gelu"):
    """Expert-stacked MoE FFN params (shard w1/b1/w2/b2 ``P('ep')``).
    ``mlp="swiglu"`` adds the per-expert gate stack ``w3/b3`` (llama-
    style gated experts — the FFN mirrors the dense family's
    ``_mlp`` structural dispatch)."""
    if mlp not in ("gelu", "swiglu"):
        raise ValueError(f"unknown mlp {mlp!r} — expected 'gelu' or "
                         "'swiglu'")
    k = jax.random.split(rng, 4)
    p = {
        "wg": jax.random.normal(k[0], (d, n_experts), jnp.float32) * std,
        "w1": jax.random.normal(k[1], (n_experts, d, ff), jnp.float32) * std,
        "b1": jnp.zeros((n_experts, ff), jnp.float32),
        "w2": jax.random.normal(k[2], (n_experts, ff, d), jnp.float32) * std,
        "b2": jnp.zeros((n_experts, d), jnp.float32),
    }
    if mlp == "swiglu":
        p["w3"] = jax.random.normal(k[3], (n_experts, d, ff),
                                    jnp.float32) * std
        p["b3"] = jnp.zeros((n_experts, ff), jnp.float32)
    return p


def moe_logical_specs(mlp: str = "gelu"):
    """Logical-axis dict for :func:`moe_init` output: experts over the
    expert axis, each expert's ff dim Megatron col/row over mlp."""
    return {
        "wg": (None, None),
        "w1": ("expert", "embed", "mlp"), "b1": ("expert", "mlp"),
        "w2": ("expert", "mlp", "embed"), "b2": ("expert",),
        **({"w3": ("expert", "embed", "mlp"), "b3": ("expert", "mlp")}
           if mlp == "swiglu" else {}),
    }


def moe_specs(ep_axis: Optional[str], tp_axis: Optional[str] = None,
              mlp: str = "gelu"):
    """PartitionSpec dict for :func:`moe_init` output: experts over ep,
    and (optionally) Megatron col/row sharding of each expert's ff dim
    over tp."""
    from byteps_tpu.parallel.partitioner import resolve_specs, rules_from_axes
    return resolve_specs(moe_logical_specs(mlp),
                         rules_from_axes(tp_axis=tp_axis, ep_axis=ep_axis))


# --------------------------------------------------------------------------
# Dropless many-expert routing (DeepSeek-V3 style): sigmoid scores, top-k on
# score + correction bias, pairs grouped by expert, grouped matrix products
# over the experts held here. The Switch path above is unchanged.
# --------------------------------------------------------------------------
def _router_logits(xt: jnp.ndarray, wg: jnp.ndarray):
    """``x·wg`` in f32 at full precision: a pick must not turn on bf16
    rounding."""
    return jax.lax.dot_general(
        xt.astype(jnp.float32), wg.astype(jnp.float32),
        (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _picked_weights(score: jnp.ndarray, idx: jnp.ndarray, scale: float):
    """``(idx int32, scale · score_i / Σ_picked score)`` of the picks."""
    picked = jnp.take_along_axis(score, idx, axis=-1)
    return (idx.astype(jnp.int32),
            scale * picked / jnp.sum(picked, axis=-1, keepdims=True))


def sigmoid_topk_route(xt: jnp.ndarray, wg: jnp.ndarray, bias: jnp.ndarray,
                       k: int, scale: float):
    """``(idx (T, k) int32, weight (T, k) f32)`` of ``noaux_tc`` routing
    without a group limit: scores ``s = sigmoid(x·wg)`` in f32 (the
    product at full precision: a pick must not turn on bf16 rounding),
    the ``k`` experts with the largest ``s + bias``, and weights taken
    from ``s`` alone — ``scale · s_i / Σ_picked s``. ``bias`` is a buffer:
    it steers the picks and takes no gradient."""
    s = jax.nn.sigmoid(_router_logits(xt, wg))
    _, idx = jax.lax.top_k(s + jax.lax.stop_gradient(bias)[None, :], k)
    return _picked_weights(s, idx, scale)


def sigmoid_group_topk_route(xt: jnp.ndarray, wg: jnp.ndarray,
                             bias: jnp.ndarray, k: int, scale: float,
                             n_group: int, topk_group: int):
    """``(idx (T, k) int32, weight (T, k) f32)`` of ``noaux_tc`` routing WITH
    its group limit (DeepSeek-V3's ``n_group`` / ``topk_group``): the ``E``
    experts lie in ``n_group`` groups of ``E / n_group`` neighbours; a
    group's score is the sum of its two largest choice scores ``s + bias``;
    the ``topk_group`` groups of largest score stay and every other group's
    experts are out (``-inf``); the ``k`` largest choice scores among those
    left are the picks, and the weights come from ``s`` alone, as
    :func:`sigmoid_topk_route`'s. A token's picks so lie in at most
    ``topk_group`` groups: under expert parallelism it reaches that many
    nodes at most. Of equal scores the lower index wins, groups and experts
    alike (``jax.lax.top_k``). With one group the rule is
    :func:`sigmoid_topk_route`. The routing product is f32 at full
    precision, for that function's reason."""
    s = jax.nn.sigmoid(_router_logits(xt, wg))
    choice = s + jax.lax.stop_gradient(bias)[None, :]
    T, E = choice.shape
    per = E // n_group
    if per * n_group != E or not 1 <= topk_group <= n_group:
        raise ValueError(
            f"{E} experts do not lie in {n_group} equal groups of which "
            f"{topk_group} stay")
    if k > topk_group * per:
        raise ValueError(
            f"top_k {k} > the {topk_group * per} experts of {topk_group} "
            "groups")
    group_score = jnp.sum(jax.lax.top_k(
        choice.reshape(T, n_group, per), min(2, per))[0], axis=-1)
    _, kept = jax.lax.top_k(group_score, topk_group)          # (T, kept)
    stays = jnp.any(kept[:, :, None] == jnp.arange(
        n_group, dtype=kept.dtype)[None, None, :], axis=1)    # (T, n_group)
    _, idx = jax.lax.top_k(jnp.where(
        jnp.repeat(stays, per, axis=1), choice, -jnp.inf), k)
    return _picked_weights(s, idx, scale)


def softmax_topk_route(xt: jnp.ndarray, wg: jnp.ndarray, k: int,
                       scale: float = 1.0):
    """``(idx (T, k) int32, weight (T, k) f32)`` of softmax routing with
    renormalised top-k weights (``norm_topk_prob``): ``p = softmax(x·wg)``
    over all experts in f32 (the product at full precision, as
    :func:`sigmoid_topk_route` and for its reason), the ``k`` largest, and
    ``scale · p_i / Σ_picked p``. No bias steers the picks."""
    p = jax.nn.softmax(_router_logits(xt, wg), axis=-1)
    picked, idx = jax.lax.top_k(p, k)
    weight = scale * picked / jnp.sum(picked, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), weight


#: the routing rules of :func:`moe_ffn_dropless`: ``name -> (xt, params, k,
#: scale) -> (idx, weight)``; ``sigmoid_bias_groups`` takes its ``n_group``
#: and ``topk_group`` besides (the layer's ``group_limit``)
ROUTES = {
    "sigmoid_bias": lambda xt, p, k, scale: sigmoid_topk_route(
        xt, p["wg"], p["router_bias"], k, scale),
    "sigmoid_bias_groups": lambda xt, p, k, scale, n_group, topk_group:
        sigmoid_group_topk_route(xt, p["wg"], p["router_bias"], k, scale,
                                 n_group, topk_group),
    "softmax": lambda xt, p, k, scale: softmax_topk_route(
        xt, p["wg"], k, scale),
}


def _rows_of(x, index):
    """``x[index]`` with zero rows where ``index`` is out of range."""
    return jnp.take(x, index, axis=0, mode="fill", fill_value=0)


@jax.custom_vjp
def _dispatch(x, row_token, pair_row):
    """``xs[r] = x[row_token[r]]`` (zero where the row holds no pair).
    Its transpose is a gather too: token ``t``'s pairs sit in the rows
    ``pair_row[t, :]``, so no scatter runs in either direction."""
    del pair_row
    return _rows_of(x, row_token)


def _dispatch_fwd(x, row_token, pair_row):
    return _rows_of(x, row_token), pair_row


def _dispatch_bwd(pair_row, dxs):
    dx = sum(_rows_of(dxs, pair_row[:, j]).astype(jnp.float32)
             for j in range(pair_row.shape[1]))
    return dx.astype(dxs.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(ys, weight, row_pair, pair_row):
    """``out[t] = Σ_j weight[t, j] · ys[pair_row[t, j]]`` (a pair whose
    expert is not held has no row and adds nothing), f32 accumulation."""
    del row_pair
    return sum(weight[:, j, None] * _rows_of(ys, pair_row[:, j])
               .astype(jnp.float32)
               for j in range(pair_row.shape[1])).astype(ys.dtype)


def _combine_fwd(ys, weight, row_pair, pair_row):
    return (_combine(ys, weight, row_pair, pair_row),
            (ys, weight, row_pair, pair_row))


def _combine_bwd(res, dout):
    ys, weight, row_pair, pair_row = res
    k = pair_row.shape[1]
    row_w = _rows_of(weight.reshape(-1), row_pair)
    dys = (row_w[:, None] * _rows_of(dout, row_pair // k)
           .astype(jnp.float32)).astype(ys.dtype)
    dof = dout.astype(jnp.float32)
    dweight = jnp.stack(
        [jnp.sum(_rows_of(ys, pair_row[:, j]).astype(jnp.float32) * dof,
                 axis=-1) for j in range(k)], axis=-1)
    return dys, dweight.astype(weight.dtype), None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


# The same four moves as Pallas kernels over the live prefix of the buffer
# (``ops/moe_rows.py``), for a buffer that is mostly empty. ``slots`` is the
# row plan's third map (:func:`_slots`), ``n_live (1,)`` the tiles that hold
# a pair. A tile past them is NOT written by ``_dispatch_live`` nor by
# ``_combine_live``'s ``dys``; :func:`moe_ffn_dropless` says why none is read.
@jax.custom_vjp
def _dispatch_live(x, row_pair, slots, n_live):
    """:func:`_dispatch` on the live tiles."""
    from byteps_tpu.ops.moe_rows import moe_rows_to_buffer

    return moe_rows_to_buffer(x, row_pair, slots[1].shape[1], None, n_live)


def _dispatch_live_fwd(x, row_pair, slots, n_live):
    return _dispatch_live(x, row_pair, slots, n_live), (slots, n_live)


def _dispatch_live_bwd(res, dxs):
    from byteps_tpu.ops.moe_rows import moe_rows_to_tokens

    (count, rows, _), n_live = res
    ones = (jnp.arange(rows.shape[1])[None, :] < count[:, None])
    return (moe_rows_to_tokens(dxs, count, rows, ones.astype(jnp.float32),
                               n_live), None, None, None)


_dispatch_live.defvjp(_dispatch_live_fwd, _dispatch_live_bwd)


@jax.custom_vjp
def _combine_live(ys, weight, row_pair, slots, n_live):
    """:func:`_combine` over the pairs that have a row: the same f32 sums
    in the same order, so the same bits."""
    from byteps_tpu.ops.moe_rows import moe_rows_to_tokens

    del row_pair
    count, rows, slot_of = slots
    return moe_rows_to_tokens(ys, count, rows, _by_slot(weight, slot_of),
                              n_live)


def _combine_live_fwd(ys, weight, row_pair, slots, n_live):
    return (_combine_live(ys, weight, row_pair, slots, n_live),
            (ys, weight, row_pair, slots, n_live))


def _combine_live_bwd(res, dout):
    from byteps_tpu.ops.moe_rows import moe_rows_dweight, moe_rows_to_buffer

    ys, weight, row_pair, (count, rows, slot_of), n_live = res
    k = weight.shape[1]
    dys = moe_rows_to_buffer(dout, row_pair, k, weight, n_live)
    # back from slots to pairs as it went (:func:`_by_slot`): a compare
    # and a sum, where a gather would look 131,072 scalars up one by one
    dweight = jnp.sum(jnp.where(
        _lands(slot_of),
        moe_rows_dweight(ys, count, rows, dout, n_live)[:, None, :], 0.0),
        axis=2)
    return dys, dweight.astype(weight.dtype), None, None, None


_combine_live.defvjp(_combine_live_fwd, _combine_live_bwd)


def _slots(pair_row: jnp.ndarray, n_rows: int):
    """The row plan's third map, for the kernels: a token's pairs that have
    a row, pushed to the front in the order they came. ``pair_row (T, k)``
    → ``count (T,)`` such pairs, ``rows (T, k)`` their rows by slot (the
    buffer's length in an empty slot) and ``slot_of (T, k)`` each pair's
    slot (``k``: it has no row). Compare-and-count, as :func:`_row_plan`."""
    k = pair_row.shape[1]
    has = pair_row < n_rows
    before = jnp.cumsum(has.astype(jnp.int32), axis=1)
    slot_of = jnp.where(has, before - 1, k)
    rows = jnp.sum(jnp.where(_lands(slot_of), pair_row[:, :, None], 0),
                   axis=1)
    empty = jnp.arange(k, dtype=jnp.int32)[None, :] >= before[:, -1:]
    return before[:, -1], jnp.where(empty, n_rows, rows), slot_of


def _lands(slot_of: jnp.ndarray):
    """``(T, k pairs, k slots)``: pair ``j`` of token ``t`` lands in slot
    ``r`` (a pair with no row in none)."""
    return slot_of[:, :, None] == jnp.arange(slot_of.shape[1],
                                             dtype=jnp.int32)


def _by_slot(weight: jnp.ndarray, slot_of: jnp.ndarray):
    """``weight (T, k)`` by slot: zero in an empty slot (one value and
    zeros summed: exact)."""
    return jnp.sum(jnp.where(_lands(slot_of), weight[:, :, None], 0.0),
                   axis=1)


#: Tiles of the worst-case row buffer from which the row kernels run (THE
#: RULE of :func:`moe_ffn_dropless`): a training step's buffer (JoyAI's: 528
#: tiles, +17.6% tokens/s, set-up unmoved) and not a serve chunk's (72–144
#: tiles). There the kernels paid too (dots3 +8.1%, DeepSeek-V3.2 +1.7%
#: tokens/s) but a serve cell lowers ~40 chunk programs before it serves,
#: each with the kernels of its own, and warm ``setup_s`` rose 5% and 9.6%
#: against a bound of 10% (PERF.md §6, PR 62; ROADMAP A4b has what lifts it).
ROW_KERNEL_TILES = 256


def dropless_row_tile(pairs: int, held: int, itemsize: int) -> int:
    """The row tile :func:`moe_ffn_dropless` lays its groups out by, from
    the program's static shapes alone: the rows a held expert would get if
    all ``pairs`` landed here evenly, ``ceil(pairs / held)``, rounded up to
    a power of two, no smaller than the least row block Mosaic tiles for
    operands of ``itemsize`` bytes and no larger than ``ROW_TILE``. The
    buffer pads every group to a tile, so a decode step's few pairs an
    expert ride in 16-row tiles (Mellum2: 192 pairs over 64 experts in
    1,216 rows, where 256-row tiles took 16,640), and a chunk or a training
    step, 256 rows an expert or more, keeps ``ROW_TILE`` and the program it
    had."""
    from byteps_tpu.ops.grouped_matmul import ROW_TILE, min_row_tile

    even = -(-pairs // held)
    return min(max(1 << (even - 1).bit_length(), min_row_tile(itemsize)),
               ROW_TILE)


def _row_plan(local: jnp.ndarray, held: int, tm: int):
    """The dropless layer's two integer maps, from each pair's expert
    ``local (P,)`` (``0 .. held - 1``; ``held`` = not held here). The groups
    lie one after another in a buffer of ``(ceil(P / tm) + held) · tm`` rows
    (the static worst case), each padded to the tile ``tm``, a group's
    pairs in the order they came (the stable order: it fixes the order of
    ``_combine``'s f32 sums). Returns ``pair_row (P,)`` (pair → row; the
    buffer's length where the pair is not held), ``row_pair (rows,)`` (row →
    pair; ``P`` where the row holds none), ``counts`` and ``padded``
    ``(held,)``.

    All of it is compare-and-count: a pair's row is its group's first row
    plus the pairs of its expert before it, both picked out of the ``(P,
    held)`` compare by a one-hot sum, and ``row_pair`` is that map's
    inverse — ONE scatter of the pairs' numbers. No sort, no search and no
    ``held``-sized table looked up by a gather: a TPU gathers scalars one
    by one, and a ``searchsorted`` is a loop of such gathers, each waiting
    for the one before. Which group a row lies in is only ever asked a TILE
    at a time, by the kernels (``grouped_matmul._tile_groups``)."""
    P_ = local.shape[0]
    n_rows = (-(-P_ // tm) + held) * tm
    onehot = local[:, None] == jnp.arange(held, dtype=jnp.int32)[None, :]
    before = jnp.cumsum(onehot.astype(jnp.int32), axis=0)
    counts = before[-1]                                        # (held,)
    padded = -(-counts // tm) * tm
    starts = jnp.cumsum(padded) - padded
    row = jnp.sum(jnp.where(onehot, starts[None, :] + before - 1, 0), axis=1)
    # a pair not held goes past the buffer, each to a place of its own so
    # that the indices are unique as promised, and is dropped
    pairs = jnp.arange(P_, dtype=jnp.int32)
    target = jnp.where(local < held, row, n_rows + pairs)
    row_pair = jnp.full((n_rows,), P_, jnp.int32).at[target].set(
        pairs, mode="drop", unique_indices=True)
    return jnp.minimum(target, n_rows), row_pair, counts, padded


def moe_ffn_dropless(x: jnp.ndarray, params, top_k: int, scale: float,
                     first_expert: int = 0, row_tile: Optional[int] = None,
                     route: str = "sigmoid_bias",
                     group_limit: Optional[Tuple[int, int]] = None):
    """Top-k MoE feed-forward in which no token is ever dropped, over the
    experts THIS device holds; ``route`` names the rule that picks and
    weighs (:data:`ROUTES`: ``sigmoid_bias``, DeepSeek-V3's ``noaux_tc``,
    ``sigmoid_bias_groups``, the same under its group limit ``group_limit
    = (n_group, topk_group)``, or ``softmax`` with renormalised top-k
    weights), and everything after the picks is one path.

    ``params``: ``wg (d, E)`` the router over all ``E`` routed experts,
    ``router_bias (E,)`` the correction bias (a buffer; ``sigmoid_bias``
    alone reads it, a ``softmax`` tree has no such leaf), and the gated
    expert stacks ``w1``/``w3 (held, d, ff)``, ``w2 (held, ff, d)`` of the
    ``held`` experts ``first_expert .. first_expert + held - 1``. Every
    token is routed over all ``E``; the ``T·k`` (token, expert) pairs are
    grouped by expert in the order they came (:func:`_row_plan`), the pairs
    of held experts laid out group after group — each group padded with
    zero rows to the row tile of ``ops/grouped_matmul.py`` — and gate/up,
    SwiGLU and down run as three
    grouped products; the rows are then gathered back and added with
    their weights. The row buffer is sized for the worst case, all ``T·k``
    pairs held here, so its shape is static and nothing can overflow; the
    kernels visit only the tiles that hold pairs. The tile follows from
    the shapes (:func:`dropless_row_tile`; ``row_tile`` is the tests'
    override), and the trace counts it: ``moe.row_tile.<tm>`` once a trace
    of the layer, the gauges ``moe.row_buffer_rows`` the buffer's rows and
    ``moe.row_plan_tiles`` its tiles.
    What the experts held elsewhere would add is left out (under expert
    parallelism their owners compute it; this layer runs no exchange).

    **How the rows move — THE RULE.** Rows go to the buffer and back, forward
    and in the backward, either as XLA gathers over the whole buffer
    (:func:`_dispatch` / :func:`_combine`) or as the Pallas kernels of
    ``ops/moe_rows.py`` (:func:`_dispatch_live` / :func:`_combine_live`),
    which visit only the tiles that hold a pair. The kernels run where the
    buffer is sparse by construction and a tile is worth a kernel's grid
    step: ``held < n_routed`` (``w1``'s experts against ``wg``'s: an
    expert-parallel share, JoyAI's step 6% full), the 256-row tile and a
    buffer of :data:`ROW_KERNEL_TILES` tiles or more (a training step's: a
    decode step's 640–3,328 rows in 16- or 32-row tiles are not worth five
    kernel launches, and a serve chunk's 72–144 tiles gained tokens/s but
    cost their cell's set-up 5–10%, every chunk program lowering kernels of
    its own), the Pallas backend (``ops/backend.py::use_pallas``) and rows
    the kernels can pack (``moe_rows.rows_supported``). Everywhere else —
    every expert held (SDAR, Mellum2), a serve program, the jnp twins — the
    gathers run, untouched; they remain only until the serve chunks and the
    dense-buffer cells move to the kernels too (ROADMAP A4b). Both paths give the same bits: the same
    pairs in the same rows, the same f32 sums in the same order. Counted
    once a trace: ``moe.row_move.kernel`` or ``moe.row_move.gather``.

    **What a dead tile holds** (a tile past the last group's; the live
    ones are a prefix). Under the gathers ``xs`` is zero there. Under the
    kernels ``xs`` and, in the backward, ``dys`` are NOT WRITTEN there: they
    hold what the allocation held, NaN for all anyone knows. Nothing reads
    them: ``grouped_matmul`` and its backward visit live tiles only and
    zero the dead rows of their own results (``gate``, ``up``, ``ys``, the
    products' ``dlhs``: zero on both paths; the SwiGLU between them is row
    by row, zero where both are), ``_combine_live`` and ``_dispatch_live``'s
    backward fetch only rows that hold a pair, and ``stats`` counts from the
    integer maps (``tests/test_joyai.py::
    test_nothing_reads_a_dead_tile_of_the_row_buffer`` poisons every one).

    Returns ``(y, stats, load)``: ``y`` shaped like ``x``, ``stats`` f32
    ``(3,)`` = pairs computed here (the rows of the buffer that hold a
    pair), pairs in all (those plus the pairs routed elsewhere: ``T·k``
    unless a held pair lost its row), heaviest held expert over the mean
    held expert; ``load`` f32 ``(E,)`` the tokens each of ALL routed
    experts was picked by (what :func:`noaux_bias_step` balances). Under a
    ``group_limit`` ``stats`` has a fourth value: the groups that hold at
    least one of a token's picks, the mean over the tokens (at most
    ``topk_group``: the nodes a token reaches)."""
    from byteps_tpu.ops.backend import use_pallas
    from byteps_tpu.ops.grouped_matmul import grouped_matmul
    from byteps_tpu.ops.moe_rows import ROWS, rows_supported

    held = params["w1"].shape[0]
    lead, d = x.shape[:-1], x.shape[-1]
    xt = x.reshape(-1, d)
    T = xt.shape[0]
    # sub-regions of the caller's block/moe, for a device trace's scope
    # table (docs/observability.md §Regions on the device): metadata only
    with jax.named_scope("moe/route"):
        idx, weight = ROUTES[route](xt, params, top_k, scale,
                                    *(group_limit or ()))
    P_ = T * top_k
    tm = (dropless_row_tile(P_, held, x.dtype.itemsize)
          if row_tile is None else row_tile)
    with jax.named_scope("moe/plan"):
        local = idx.reshape(P_) - first_expert
        is_held = (local >= 0) & (local < held)
        pair_row, row_pair, counts, padded = _row_plan(
            jnp.where(is_held, local, held), held, tm)
    n_rows = row_pair.shape[0]
    n_routed = params["wg"].shape[1]
    # THE RULE (docstring): the tile and the path are chosen while tracing
    # from static shapes, so they are counted there
    live = (held < n_routed and tm == ROWS
            and n_rows // tm >= ROW_KERNEL_TILES and use_pallas()
            and rows_supported(T, d, x.dtype))
    reg = get_registry()
    reg.counter(f"moe.row_tile.{tm}").inc()
    reg.counter(f"moe.row_move.{'kernel' if live else 'gather'}").inc()
    reg.gauge("moe.row_buffer_rows").set(n_rows)
    reg.gauge("moe.row_plan_tiles").set(n_rows // tm)
    pair_row = pair_row.reshape(T, top_k)

    with jax.named_scope("moe/plan"):
        if live:
            slots = _slots(pair_row, n_rows)
            n_live = (jnp.sum(padded) // tm).reshape(1)
            xs = _dispatch_live(xt, row_pair, slots, n_live)
        else:
            xs = _dispatch(xt, row_pair // top_k, pair_row)
    with jax.named_scope("moe/experts"):
        gate = grouped_matmul(xs, params["w1"], padded, tm)
        up = grouped_matmul(xs, params["w3"], padded, tm)
        ys = grouped_matmul(jax.nn.silu(gate) * up, params["w2"], padded,
                            tm)
    with jax.named_scope("moe/combine"):
        y = (_combine_live(ys, weight, row_pair, slots, n_live) if live
             else _combine(ys, weight, row_pair, pair_row))

    # counted from the row buffer the kernels ran over, not from the
    # router's ids: a held pair that got no row is missing from both
    here = jnp.sum(row_pair < P_).astype(jnp.float32)
    stats = jnp.stack([
        here, here + jnp.sum(~is_held).astype(jnp.float32),
        jnp.max(counts).astype(jnp.float32) * held / jnp.maximum(here, 1.0)])
    if group_limit is not None:
        of_group = idx // (n_routed // group_limit[0])         # (T, k)
        hit = jnp.any(of_group[:, :, None] == jnp.arange(
            group_limit[0], dtype=jnp.int32)[None, None, :], axis=1)
        stats = jnp.concatenate([stats, jnp.mean(
            jnp.sum(hit, axis=-1).astype(jnp.float32))[None]])
    load = jnp.sum(idx.reshape(P_, 1) == jnp.arange(
        n_routed, dtype=jnp.int32)[None, :], axis=0)
    return y.reshape(*lead, d).astype(x.dtype), stats, load.astype(jnp.float32)


def noaux_bias_step(bias: jnp.ndarray, load: jnp.ndarray, rate: float):
    """The ``noaux_tc`` balancing rule (auxiliary-loss-free balancing,
    DeepSeek-V3 section 2.1.2), applied between steps: an expert picked by
    more tokens than the mean expert has its correction bias lowered by
    ``rate``, one picked by fewer raised by ``rate`` — ``bias + rate ·
    sign(mean(load) - load)``. ``load (E,)`` is the step's count of picks
    per routed expert (summed over the data-parallel ranks by the
    caller)."""
    return bias + rate * jnp.sign(jnp.mean(load) - load).astype(bias.dtype)


def moe_dropless_init(rng, d: int, ff: int, n_routed: int, held: int,
                      std: float = 0.02, bias_std: float = 0.0):
    """Params of :func:`moe_ffn_dropless`: a router over ``n_routed``
    experts, its correction bias (drawn once at ``bias_std`` and never
    trained: a buffer) and the gated stacks of the ``held`` experts."""
    k = jax.random.split(rng, 5)
    return {
        "wg": jax.random.normal(k[0], (d, n_routed), jnp.float32) * std,
        "router_bias": jax.random.normal(k[4], (n_routed,),
                                         jnp.float32) * bias_std,
        "w1": jax.random.normal(k[1], (held, d, ff), jnp.float32) * std,
        "w3": jax.random.normal(k[3], (held, d, ff), jnp.float32) * std,
        "w2": jax.random.normal(k[2], (held, ff, d), jnp.float32) * std,
    }


def moe_dropless_logical_specs():
    """Logical axes of :func:`moe_dropless_init`'s leaves. The stacks hold
    this device's experts already, so nothing shards over ``expert``."""
    return {"wg": (None, None), "router_bias": (None,),
            "w1": (None, "embed", "mlp"), "w3": (None, "embed", "mlp"),
            "w2": (None, "mlp", "embed")}
