"""The plain reference of DeepSeek-V3.2-Exp (``deepseek_v32``), cut to the
share of the model one chip of an ep=32 deployment holds.

Straight ``jax.numpy`` in float32 with ``precision=highest`` on every
product: no kernel, no cache, no batching, nothing imported from the
program. It reads the parameter tree ``models/deepseek_v32.py::dsv32_init``
makes (the one thing it shares with the system under test; every matrix is
upcast where it is used, one at a time) and takes every size from ``hp``,
the configuration file's ``gpt_config``.

The published architecture (``config.json`` of the source, read as
``benchmark/configs/deepseek-v3.2-exp-ep32-l5.json`` lists under
``assumed``): pre-norm blocks, RMSNorm eps 1e-6; latent attention (MLA),
``c_q = rms(h W_qa)``, ``q = c_q W_qb``, ``[c_kv | k_rope] = h W_kva``, ``c_kv
<- rms(c_kv)``, rotary on ``q_rope`` and on the one ``k_rope`` all heads
share, at YaRN's frequencies (:func:`rope_inv_freq`: ``factor`` 40 over an
original context of 4,096, ``beta_fast`` 32, ``beta_slow`` 1; cos and sin
times ``mscale / mscale_all_dim`` = 1), the softmax scale ``(nope +
rope)^-1/2 · m²`` with ``m = 0.1 · mscale_all_dim · ln(factor) + 1``
(DeepSeek-V3's inference code); EVERY layer attends over the ``index_topk``
keys of largest indexer score ``I[t, s] = sum_j w_j relu(qI_j[t] . kI[s])``;
heads side by side through ``W_o``, no gate; dense SwiGLU in the leading
layers, then sigmoid routing with a correction bias under the group limit
(:func:`route`: the experts in ``n_group`` groups, a group's score the sum of
its two largest choice scores, ``topk_group`` groups stay, the ``top_k``
largest choice scores among them are the picks, weights ``routed_scaling ·
sigma_e / sum_picked sigma``) plus a shared expert; final RMSNorm and an
untied head.

How it is computed, none of which changes a number:

* Queries go in blocks of ``qb`` (``jax.lax.map``), so that 29k positions fit
  beside the resident weights: a block's dense indexer scores ``(Hi, qb, S)``
  are the largest intermediate.
* The softmax runs over the rows ``jax.lax.top_k`` picked, in the absorbed
  form (``q_nope`` through the key part of ``W_kvb``, the mix of latents
  through its value part): materialised keys of 2,048 picks a query would be
  86 GFLOP a query.
* Only the last layer is cut to the asked-for tail of positions: every
  layer is a selecting layer and needs every position below it.
* What follows attention (output projection, feed-forward) runs inside the
  same loop over blocks of queries: a dense layer's ``(S, 18432)``
  intermediates at 29k positions would not fit beside the weights.
* ``experts_held`` / ``first_expert``: every token is routed over ALL
  experts; only the held experts' terms are added (a ``scan`` over them).
* The group limit is taken by SORTING (a stable descending ``argsort`` of the
  group scores, then of the masked choice scores: of equal scores the lower
  index first), the selected keys by ``jax.lax.top_k``.

Departures from the published description, each deliberate: rotary pairs
are adjacent dims ``(2i, 2i+1)`` in attention (the family's
``rope_interleave``) and half-split in the indexer, which rotates the FIRST
``rope`` dims of its heads at the same YaRN frequencies; the indexer has no
Hadamard rotation (a rotation of both sides leaves the products as they are)
and its keys are not fp8; the indexer's LayerNorm has the model's eps; the
MTP module (``num_nextn_predict_layers`` 1) is left out, as Hugging Face's
modeling drops it at load; a masked group's experts read ``-inf`` (the
inference code), not 0.

``hp`` keys that are never set in a run that decides ``correct``, for the
limits' second readings (``benchmark/controls/dsv32_limits.py``):
``cache_round`` (what a narrower cache would hand back), ``softmax_mscale:
false`` (no ``m²``), ``group_limit: false`` (top-k over all experts).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
_mm = functools.partial(jnp.matmul, precision=_HI)
_es = functools.partial(jnp.einsum, precision=_HI)
_NEG = -1e30


def _f(w):
    return w.astype(jnp.float32)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f(g)


def _ln(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * _f(g) + _f(b)


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def rope_inv_freq(hp):
    """``(inv_freq (rope / 2,), factor on cos and sin)``: YaRN. ``f_i =
    theta^(-2i/D)``; ``d(r) = D ln(original / (2 pi r)) / (2 ln theta)``;
    ``lo = floor(d(beta_fast))``, ``hi = ceil(d(beta_slow))`` clipped to ``0
    .. D - 1``; ``ramp_i = clip((i - lo) / (hi - lo), 0, 1)``; ``f_i <- f_i /
    factor · ramp_i + f_i (1 - ramp_i)``. Float64, no sequence length in
    it."""
    D, theta = hp["qk_rope_dim"], hp["rope_base"]
    orig, factor = hp["yarn_original_max_seq"], hp["yarn_factor"]

    def d(r):
        return D * math.log(orig / (2 * math.pi * r)) / (2 * math.log(theta))

    lo = max(math.floor(d(hp["yarn_beta_fast"])), 0)
    hi = min(math.ceil(d(hp["yarn_beta_slow"])), D - 1)
    if hi == lo:
        hi += 0.001
    inv = []
    for i in range(D // 2):
        f = theta ** (-2.0 * i / D)
        ramp = min(max((i - lo) / (hi - lo), 0.0), 1.0)
        inv.append(f / factor * ramp + f * (1.0 - ramp))
    return inv, (yarn_mscale(factor, hp["yarn_mscale"])
                 / yarn_mscale(factor, hp["yarn_mscale_all_dim"]))


def softmax_scale(hp):
    """``(nope + rope)^-1/2 · m²``."""
    m = yarn_mscale(hp["yarn_factor"], hp["yarn_mscale_all_dim"]) \
        if hp.get("softmax_mscale", True) else 1.0
    return (hp["qk_nope_dim"] + hp["qk_rope_dim"]) ** -0.5 * m * m


def _rope(x, pos, hp, interleaved):
    """``x (S, H, D)`` rotated at ``pos (S,)``."""
    inv, factor = rope_inv_freq(hp)
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None, None] \
        * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    if interleaved:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                         axis=-1).reshape(x.shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _swiglu(x, p):
    return _mm(jax.nn.silu(_mm(x, _f(p["w1"]))) * _mm(x, _f(p["w3"])),
               _f(p["w2"]))


def route(h, moe, hp):
    """``(idx (T, k), weight (T, k))``: sigmoid scores; choice scores =
    score + bias; the group limit by sorting; weights from the scores alone,
    normalised, times ``routed_scaling``."""
    k = hp["top_k"]
    s = jax.nn.sigmoid(_mm(h, _f(moe["wg"])))
    choice = s + _f(moe["router_bias"])[None, :]
    if hp.get("group_limit", True):
        T, E = choice.shape
        G = hp["n_group"]
        by_group = jnp.sort(choice.reshape(T, G, E // G), axis=-1)
        score = by_group[..., -2:].sum(-1) if E // G > 1 \
            else by_group[..., -1]
        kept = jnp.argsort(-score, axis=-1, stable=True)[:, :hp["topk_group"]]
        stays = (kept[:, :, None] == jnp.arange(G)[None, None, :]).any(1)
        choice = jnp.where(jnp.repeat(stays, E // G, axis=1), choice,
                           -jnp.inf)
    idx = jnp.argsort(-choice, axis=-1, stable=True)[:, :k]
    picked = jnp.take_along_axis(s, idx, axis=-1)
    return idx, hp["routed_scaling"] * picked / jnp.sum(
        picked, axis=-1, keepdims=True)


def _moe(h, moe, hp):
    idx, weight = route(h, moe, hp)

    def add(y, held):
        e, w = held
        we = jnp.sum(jnp.where(idx == hp["first_expert"] + e, weight, 0.0),
                     -1)
        return y + we[:, None] * _swiglu(h, w), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(h), (
        jnp.arange(moe["w1"].shape[0]),
        {k: moe[k] for k in ("w1", "w3", "w2")}))
    return y, idx


def _index_rot(x, pos, hp):
    r = hp["qk_rope_dim"]
    return jnp.concatenate([_rope(x[..., :r], pos, hp, False), x[..., r:]],
                           axis=-1)


def index_keys(h, idx, pos, hp):
    """``kI (S, Di)``: one LayerNormed, rotated key a position."""
    k = _ln(_mm(h, _f(idx["wk"])), idx["k_norm_g"], idx["k_norm_b"],
            hp["norm_eps"])
    return _index_rot(k[:, None, :], pos, hp)[:, 0]


def index_queries(h, c_q, idx, pos, hp):
    """``(qI (S, Hi, Di), w (S, Hi))`` for the queries at ``pos``."""
    Hi, Di = hp["index_n_heads"], hp["index_head_dim"]
    qi = _index_rot(_mm(c_q, _f(idx["wq"])).reshape(-1, Hi, Di), pos, hp)
    return qi, _mm(h, _f(idx["ww"])) * (Hi ** -0.5 * Di ** -0.5)


def index_scores(qi, ki, w):
    """Dense ``I (T, S)``: ``sum_j w_j relu(qI_j . kI)``."""
    return _es("th,hts->ts", w, jax.nn.relu(_es("thd,sd->hts", qi, ki)))


def _cached(x, hp):
    """What a cache would hand back of ``x``: ``x`` itself, or — with
    ``hp["cache_round"]`` naming a narrower type — ``x`` rounded to it. For
    the readings that set the limits of ``correct``; never set in a run that
    decides it."""
    to = hp.get("cache_round")
    if to is None:
        return x
    if to == "int8_rows":       # one scale a row, as this repo's int8 pool
        scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
        return jnp.round(x / scale) * scale
    # not a pair of casts: the compiler may drop one as excess precision
    kind = jnp.finfo(jnp.dtype(to))
    return jax.lax.reduce_precision(x, exponent_bits=kind.nexp,
                                    mantissa_bits=kind.nmant)


def _layer(p, x, out_lo, hp, dense, qb):
    """One block. ``x (S, d)`` holds positions ``0 ..``; returns the block's
    output for positions ``out_lo ..`` and what the checks read: ``cache``
    is what a cache would hold of this layer (the latent rows ``c_kv``,
    ``k_rope`` and the indexer keys ``ki``, every position)."""
    eps = hp["norm_eps"]
    H, nope, rope, vd = (hp["n_heads"], hp["qk_nope_dim"], hp["qk_rope_dim"],
                         hp["v_head_dim"])
    r = hp["kv_lora_rank"]
    S = x.shape[0]
    pos = jnp.arange(S)
    inter = hp.get("rope_interleave", True)
    h = _rms(x, p["ln1_g"], eps)
    kv_a = _mm(h, _f(p["wkv_a"]))
    c_kv = _cached(_rms(kv_a[:, :r], p["kv_norm_g"], eps), hp)
    k_rope = _cached(_rope(kv_a[:, None, r:], pos, hp, inter)[:, 0], hp)
    ki = _cached(index_keys(h, p["idx"], pos, hp), hp)
    extra = {"cache": {"c_kv": c_kv, "k_rope": k_rope, "ki": ki}}
    xq, hq, posq = x[out_lo:], h[out_lo:], pos[out_lo:]
    c_q = _rms(_mm(hq, _f(p["wq_a"])), p["q_norm_g"], eps)
    wkv = _f(p["wkv_b"]).reshape(r, H, nope + vd)
    scale = softmax_scale(hp)
    nq = S - out_lo
    K = min(hp["index_topk"], S)

    def block(i):
        sl = functools.partial(jax.lax.dynamic_slice_in_dim,
                               start_index=i * qb, slice_size=qb)
        t = sl(posq)
        q = _mm(sl(c_q), _f(p["wq_b"])).reshape(-1, H, nope + rope)
        q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], t, hp, inter)
        qi, w = index_queries(sl(hq), sl(c_q), p["idx"], t, hp)
        sc = index_scores(qi, ki, w)
        sc = jnp.where(jnp.arange(S)[None, :] <= t[:, None], sc, _NEG)
        top, sel = jax.lax.top_k(sc, K)
        valid = top > _NEG / 2
        q_abs = _es("thn,rhn->thr", q_nope, wkv[..., :nope])
        s = (_es("thr,tkr->thk", q_abs, c_kv[sel])
             + _es("thd,tkd->thk", q_rope, k_rope[sel])) * scale
        pr = jax.nn.softmax(jnp.where(valid[:, None, :], s, _NEG), -1)
        o = _es("thr,rhv->thv", _es("thk,tkr->thr", pr, c_kv[sel]),
                wkv[..., nope:])
        x1 = sl(xq) + _mm(o.reshape(-1, H * vd), _f(p["wo"]))
        h2 = _rms(x1, p["ln2_g"], eps)
        picked = jnp.where(valid, sel, -1)
        if dense:
            return x1 + _swiglu(h2, p["mlp"]), (), (), picked
        ym, idx = _moe(h2, p["moe"], hp)
        return x1 + ym + _swiglu(h2, p["shared"]), h2, idx, picked

    x, h2, idx, sel = jax.lax.map(block, jnp.arange(nq // qb))
    extra["selected"] = sel.reshape(nq, K)
    if not dense:
        extra["router_input"] = h2.reshape(nq, -1)
        extra["router_picks"] = idx.reshape(nq, -1)
    return x.reshape(nq, -1), extra


@functools.lru_cache(maxsize=64)
def _layer_program(out_lo, hp_items, dense, qb):
    """One jitted layer: a second forward of the same length finds it
    compiled."""
    return jax.jit(functools.partial(_layer, out_lo=out_lo,
                                     hp=dict(hp_items), dense=dense, qb=qb))


def forward(params, tokens, hp, n_tail=None, qb=128, keep=None):
    """Logits ``(n, V)`` f32 of the last ``n >= n_tail`` positions of
    ``tokens (S,)`` (``S`` a multiple of ``qb``; all of them with ``n_tail``
    None), the position of the first of them, and per layer what the checks
    read: ``cache`` (what a cache would hold of the layer), ``selected``
    (the picked positions per query of ``out_lo ..``, -1 where fewer exist),
    ``router_input`` / ``router_picks`` (expert layers), ``input`` (the
    block's); always ``out_lo`` and ``in_lo`` (0). ``keep``: ``name ->
    layers`` (None: every layer) of what is kept — a layer's ``input`` or
    ``router_input`` at 29k positions is 0.8 GB; None keeps it all."""
    S = int(tokens.shape[0])
    if S % qb:
        raise ValueError(f"{S} positions are not whole blocks of {qb}")
    exact_lo = 0 if n_tail is None else (S - n_tail) // qb * qb
    x = _f(params["wte"])[tokens]
    hp_items = tuple(sorted(
        (k, v) for k, v in hp.items()
        if isinstance(v, (int, float, str, bool)) or v is None))
    layers, last = [], len(params["blocks"]) - 1
    for li, p in enumerate(params["blocks"]):
        out_lo = exact_lo if li == last else 0
        x_in = x
        x, extra = _layer_program(out_lo, hp_items, "mlp" in p, qb)(p, x)
        extra["input"] = x_in
        layers.append(dict(
            {k: v for k, v in extra.items() if keep is None or (
                k in keep and (keep[k] is None or li in keep[k]))},
            out_lo=out_lo, in_lo=0))
        del extra
    logits = _mm(_rms(x, params["lnf_g"], hp["norm_eps"]),
                 _f(params["lm_head"]))
    return logits, exact_lo, layers
