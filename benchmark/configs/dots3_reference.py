"""The plain reference of dots3-note-prev (``dots3_note``), text decoder,
cut to the share of the model one chip of an ep=8 deployment holds.

Straight ``jax.numpy`` in float32 with ``precision=highest`` on every
product: no kernel, no cache, no batching, nothing imported from the
program. It reads the parameter tree ``models/dots3.py::dots3_init`` makes
(the one thing it shares with the system under test; every matrix is upcast
where it is used, one at a time) and takes every size from ``hp``, the
configuration file's ``gpt_config``.

The published architecture (``config.json`` of the source, read as
``benchmark/configs/dots3-note-ep8.json`` lists under ``assumed``): pre-norm
blocks, RMSNorm; latent attention (MLA) with the variance alignment ``a =
sqrt(hidden / rank)`` on both normed latents; *full* layers attend over the
``index_topk`` keys of largest indexer score ``I[t, s] = sum_j w_j relu(qI_j[t]
. kI[s])`` (DeepSeek-V3.2's indexer, less its Hadamard rotation and fp8
cache), *sliding* layers over the last ``window`` keys (the query's own
included) with their own ranks and head count; a headwise sigmoid gate on the
attention output; a dense SwiGLU in layer 0, then sigmoid top-k routing with
a correction bias and no group limit over all routed experts plus a shared
expert; final RMSNorm and an untied head.

How it is computed, none of which changes a number:

* Queries go in blocks of ``qb`` (``jax.lax.map``), so that 30k positions fit
  beside the resident weights: a block's dense indexer scores ``(Hi, qb, S)``
  are the largest intermediate.
* A full layer's softmax runs over the rows ``jax.lax.top_k`` picked, in
  the absorbed form (``q_nope`` through the key part of ``wkv_b``, the mix of
  latents through its value part): materialised keys of 2,048 picks a query
  would be 86 GFLOP a query. A sliding layer materialises k and v of its
  window.
* Only what the asked-for tail of positions needs is computed: a sliding
  layer needs its input ``window - 1`` positions further back than its
  output, a full layer needs all of it. Layer 0 is full, so it always runs
  over every position. A run of sliding layers works on one span of
  positions (the first ``window - 1`` of each layer's output there are short
  of keys and are never read), so the run is one compiled program.
* What follows attention (gate, output projection, feed-forward) runs inside
  the same loop over blocks of queries: a dense layer's ``(S, 13824)``
  intermediates at 31k positions would not fit beside the weights.
* ``experts_held`` / ``first_expert``: every token is routed over ALL
  experts; only the held experts' terms are added (a ``scan`` over them: 32
  unrolled experts took 90 s a layer to compile).

Departures from the source, each deliberate: rotary pairs are adjacent dims
``(2i, 2i+1)`` in attention and half-split in the indexer, which rotates the
FIRST ``rope`` dims of its heads; the indexer's LayerNorm has the model's
eps; vision and audio towers and the MTP module are not in the row's config
and are left out.
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


def _rope(x, pos, theta, interleaved):
    """``x (S, H, D)`` rotated at ``pos (S,)``."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if interleaved:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                         axis=-1).reshape(x.shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _swiglu(x, p):
    return _mm(jax.nn.silu(_mm(x, _f(p["w1"]))) * _mm(x, _f(p["w3"])),
               _f(p["w2"]))


def route(h, moe, top_k, scale):
    """``(idx (T, k), weight (T, k))``: sigmoid scores, the ``k`` largest of
    score + bias, weights from the scores alone, normalised."""
    s = jax.nn.sigmoid(_mm(h, _f(moe["wg"])))
    _, idx = jax.lax.top_k(s + _f(moe["router_bias"])[None, :], top_k)
    picked = jnp.take_along_axis(s, idx, axis=-1)
    return idx, scale * picked / jnp.sum(picked, axis=-1, keepdims=True)


def _moe(h, moe, top_k, scale, first_expert):
    idx, weight = route(h, moe, top_k, scale)

    def add(y, held):
        e, w = held
        we = jnp.sum(jnp.where(idx == first_expert + e, weight, 0.0), -1)
        return y + we[:, None] * _swiglu(h, w), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(h), (
        jnp.arange(moe["w1"].shape[0]),
        {k: moe[k] for k in ("w1", "w3", "w2")}))
    return y, idx


def _dims(hp, kind):
    pre = "" if kind == "full" else "swa_"
    d = {k: hp[pre + k] for k in ("q_lora_rank", "kv_lora_rank",
                                  "qk_nope_dim", "qk_rope_dim", "v_head_dim")}
    d["heads"] = hp[pre + "n_heads"]
    d["theta"] = hp["rope_base" if kind == "full" else "swa_rope_base"]
    rescale = hp.get("lora_rescale", True)
    d["a_q"] = math.sqrt(hp["d_model"] / d["q_lora_rank"]) if rescale else 1.0
    d["a_kv"] = math.sqrt(hp["d_model"] / d["kv_lora_rank"]) if rescale \
        else 1.0
    return d


def _index_rot(x, pos, hp):
    r = hp["qk_rope_dim"]
    return jnp.concatenate(
        [_rope(x[..., :r], pos, hp["rope_base"], False), x[..., r:]], axis=-1)


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
    the reading that sets the limits of ``correct`` (PERF.md section 2: what
    a cache in the nearest precision below bf16 does to each compared
    number); never set in a run that decides ``correct``."""
    to = hp.get("cache_round")
    if to is None:
        return x
    if to == "int8_rows":       # one scale a row, as this repo's int8 pool
        scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
        return jnp.round(x / scale) * scale
    # not a pair of casts: the compiler may drop one as excess precision
    # (on the chip it kept c_kv whole and rounded k_rope beside it)
    kind = jnp.finfo(jnp.dtype(to))
    return jax.lax.reduce_precision(x, exponent_bits=kind.nexp,
                                    mantissa_bits=kind.nmant)


def _layer(p, x, in_lo, out_lo, hp, kind, dense, qb):
    """One block. ``x (n_in, d)`` holds positions ``in_lo ..``; returns the
    block's output for positions ``out_lo ..`` and what the checks read:
    ``cache`` is what a cache would hold of this layer (the latent rows
    ``c_kv``, ``k_rope`` and on a full layer the indexer keys ``ki``, for
    positions ``in_lo ..``)."""
    eps = hp["norm_eps"]
    a = _dims(hp, kind)
    H, nope, rope, vd = (a["heads"], a["qk_nope_dim"], a["qk_rope_dim"],
                         a["v_head_dim"])
    r = a["kv_lora_rank"]
    n_in = x.shape[0]
    skip = out_lo - in_lo
    pos = in_lo + jnp.arange(n_in)
    h = _rms(x, p["ln1_g"], eps)
    kv_a = _mm(h, _f(p["wkv_a"]))
    c_kv = a["a_kv"] * _rms(kv_a[:, :r], p["kv_norm_g"], eps)
    k_rope = _rope(kv_a[:, None, r:], pos, a["theta"],
                   hp.get("rope_interleave", True))[:, 0]
    c_kv, k_rope = _cached(c_kv, hp), _cached(k_rope, hp)
    extra = {"cache": {"c_kv": c_kv, "k_rope": k_rope}}
    xq, hq, posq = x[skip:], h[skip:], pos[skip:]
    c_q = a["a_q"] * _rms(_mm(hq, _f(p["wq_a"])), p["q_norm_g"], eps)
    wkv = _f(p["wkv_b"]).reshape(r, H, nope + vd)
    scale = (nope + rope) ** -0.5
    nq = n_in - skip

    def queries(sl):
        """``(q_nope, q_rope, positions)`` of one block of queries: per-head
        queries exist a block at a time (all of them would be gigabytes)."""
        t = sl(posq)
        q = _mm(sl(c_q), _f(p["wq_b"])).reshape(-1, H, nope + rope)
        return q[..., :nope], _rope(q[..., nope:], t, a["theta"],
                                    hp.get("rope_interleave", True)), t

    def finish(sl, o):
        """The rest of the block for one block of queries, attention output
        ``o (qb, H, v)`` in: ``(x out, router input, router picks)``."""
        gate = jax.nn.sigmoid(_mm(sl(hq), _f(p["w_gate"])))
        x1 = sl(xq) + _mm((o * gate[..., None]).reshape(-1, H * vd),
                          _f(p["wo"]))
        h2 = _rms(x1, p["ln2_g"], eps)
        if dense:
            return x1 + _swiglu(h2, p["mlp"]), (), ()
        ym, idx = _moe(h2, p["moe"], hp["top_k"], hp["routed_scaling"],
                       hp["first_expert"])
        return x1 + ym + _swiglu(h2, p["shared"]), h2, idx

    if kind == "full":
        assert in_lo == 0, "a full layer attends over every position"
        ki = _cached(index_keys(h, p["idx"], pos, hp), hp)
        extra["cache"]["ki"] = ki
        K = min(hp["index_topk"], n_in)

        def block(i):
            sl = functools.partial(jax.lax.dynamic_slice_in_dim,
                                   start_index=i * qb, slice_size=qb)
            q_nope, q_rope, t = queries(sl)
            qi, w = index_queries(sl(hq), sl(c_q), p["idx"], t, hp)
            sc = index_scores(qi, ki, w)
            sc = jnp.where(jnp.arange(n_in)[None, :] <= t[:, None], sc, _NEG)
            top, sel = jax.lax.top_k(sc, K)
            valid = top > _NEG / 2
            q_abs = _es("thn,rhn->thr", q_nope, wkv[..., :nope])
            s = (_es("thr,tkr->thk", q_abs, c_kv[sel])
                 + _es("thd,tkd->thk", q_rope, k_rope[sel])) * scale
            pr = jax.nn.softmax(jnp.where(valid[:, None, :], s, _NEG), -1)
            o = _es("thr,rhv->thv", _es("thk,tkr->thr", pr, c_kv[sel]),
                    wkv[..., nope:])
            return finish(sl, o) + (jnp.where(valid, sel, -1),)

        x, h2, idx, sel = jax.lax.map(block, jnp.arange(nq // qb))
        extra["selected"] = sel.reshape(nq, K)
    else:
        P = hp["window"] - 1
        # the window's keys of a block of queries: the P positions before
        # it and its own, k and v materialised
        ck = jnp.concatenate([jnp.zeros((P, r)), c_kv])
        kr = jnp.concatenate([jnp.zeros((P, rope)), k_rope])

        def block(i):
            sl = functools.partial(jax.lax.dynamic_slice_in_dim,
                                   start_index=i * qb, slice_size=qb)
            q_nope, q_rope, t = queries(sl)
            # keys at positions t[0] - P .. t[0] + qb - 1
            c = jax.lax.dynamic_slice_in_dim(ck, skip + i * qb, P + qb)
            kpos = t[0] - P + jnp.arange(P + qb)
            kk = jnp.concatenate(
                [_es("sr,rhn->shn", c, wkv[..., :nope]),
                 jnp.broadcast_to(jax.lax.dynamic_slice_in_dim(
                     kr, skip + i * qb, P + qb)[:, None, :],
                     (P + qb, H, rope))], axis=-1)
            vv = _es("sr,rhv->shv", c, wkv[..., nope:])
            qq = jnp.concatenate([q_nope, q_rope], axis=-1)
            s = _es("thd,shd->hts", qq, kk) * scale
            gap = t[:, None] - kpos[None, :]
            ok = (gap >= 0) & (gap <= P) & (kpos[None, :] >= in_lo)
            pr = jax.nn.softmax(jnp.where(ok[None], s, _NEG), -1)
            return finish(sl, _es("hts,shv->thv", pr, vv))

        x, h2, idx = jax.lax.map(block, jnp.arange(nq // qb))

    if not dense:
        extra["router_input"] = h2.reshape(nq, -1)
        extra["router_picks"] = idx.reshape(nq, -1)
    return x.reshape(nq, -1), extra


def plan(kinds, S, n_tail, window, qb):
    """``(out_lo per layer, exact_lo)``: the first position whose output
    each layer produces so that the last layer's output is exact from
    ``exact_lo`` (a multiple of ``qb`` no later than ``S - n_tail``) on. A
    full layer needs every position below it; a run of sliding layers shares
    one first position, ``window - 1`` rounded up to ``qb`` before
    ``exact_lo`` for each layer of the run, and each of its layers is exact
    that much later than the one before."""
    need = exact_lo = (S - n_tail) // qb * qb
    back = -(-(window - 1) // qb) * qb
    out = [0] * len(kinds)
    for li in reversed(range(len(kinds))):
        if kinds[li] == "full":
            out[li], need = need, 0
        elif li + 1 < len(kinds) and kinds[li + 1] != "full":
            out[li] = out[li + 1]               # further into the run
        else:
            run = 1
            while li - run >= 0 and kinds[li - run] != "full":
                run += 1
            out[li] = need = max(0, need - back * run)
    return out, exact_lo


@functools.lru_cache(maxsize=64)
def _layer_program(in_lo, out_lo, hp_items, kind, dense, qb):
    """One jitted layer for one plan entry: a second forward of the same
    length finds it compiled."""
    hp = {k: (list(v) if isinstance(v, tuple) else v) for k, v in hp_items}
    return jax.jit(functools.partial(_layer, in_lo=in_lo, out_lo=out_lo,
                                     hp=hp, kind=kind, dense=dense, qb=qb))


def forward(params, tokens, hp, n_tail=None, qb=128):
    """Logits ``(n, V)`` f32 of the last ``n >= n_tail`` positions of
    ``tokens (S,)`` (``S`` a multiple of ``qb``; all of them with ``n_tail``
    None), the position of the first of them, and per layer what the checks
    read: ``selected`` (full layers: the picked positions per query, -1
    where fewer exist), ``router_input`` / ``router_picks`` (expert layers),
    ``cache`` (what a cache would hold of the layer, from ``in_lo``),
    ``out_lo``, ``in_lo`` and the block's ``input``."""
    S = int(tokens.shape[0])
    if S % qb:
        raise ValueError(f"{S} positions are not whole blocks of {qb}")
    kinds = list(hp["layer_types"])
    out_lo, exact_lo = plan(kinds, S, S if n_tail is None else n_tail,
                            hp["window"], qb)
    x = _f(params["wte"])[tokens]
    hp_items = tuple(sorted(
        (k, tuple(v) if isinstance(v, (list, tuple)) else v)
        for k, v in hp.items() if isinstance(v, (int, float, str, bool,
                                                 list, tuple))))
    lo, layers = 0, []
    for li, p in enumerate(params["blocks"]):
        x_in = x
        x, extra = _layer_program(lo, out_lo[li], hp_items, kinds[li],
                                  "mlp" in p, qb)(p, x)
        layers.append(dict(extra, out_lo=out_lo[li], input=x_in, in_lo=lo))
        lo = out_lo[li]
    logits = _mm(_rms(x[exact_lo - lo:], params["lnf_g"], hp["norm_eps"]),
                 _f(params["lm_head"]))
    return logits, exact_lo, layers
