"""The plain reference of Mellum2-12B-A2.5B-Instruct (``model_type``
``mellum``), cut in depth alone.

Straight ``jax.numpy`` in float32 with ``precision=highest`` on every
product: no kernel, no cache, no batching, nothing imported from the
program. It reads the parameter tree ``models/mellum2.py::mellum2_init``
makes (the one thing it shares with the system under test; every matrix is
upcast where it is used, one at a time) and takes every size from ``hp``, the
configuration file's ``gpt_config``.

The published architecture (``config.json`` of the source, read as
``benchmark/configs/mellum2-12b-a2.5b-l8.json`` lists under ``assumed``), for
layer ``l`` of kind ``layer_types[l]``, input ``x (T, d)``, positions ``pos``:

* ``h = rmsnorm(x, g1)``; ``q = h Wq -> (T, H, D)``, ``k = h Wk``, ``v = h
  Wv -> (T, Hkv, D)``; no bias, no q/k norm.
* RoPE on q and k, half-split pairs over all ``D`` dims, ``cos, sin = f ·
  cos/sin(pos · inv_freq)``. Sliding: ``inv_freq[i] = theta^(-2i/D)``, ``f =
  1``. Full (YaRN): ``d(r) = D ln(original / (2 pi r)) / (2 ln theta)``, ``lo =
  max(floor(d(beta_fast)), 0)``, ``hi = min(ceil(d(beta_slow)), D - 1)``,
  ``ramp[i] = clip((i - lo) / (hi - lo), 0, 1)``, ``inv_freq[i] = base_i /
  factor · ramp[i] + base_i · (1 - ramp[i])``, ``f = attention_factor``.
* scores ``q · k / sqrt(D)``; query head ``j`` reads kv head ``j // (H /
  Hkv)``; key ``s`` visible to query ``t`` iff ``s <= t`` and, on a sliding
  layer, ``t - s < window``; softmax; ``x = x + concat(o) Wo``.
* ``h = rmsnorm(x, g2)``; ``p = softmax(h Wr)`` over all experts; ``(w, e) =
  top_k(p)``; ``w = w / sum(w)``; ``x = x + sum_j w_j · (silu(h Wg[e_j]) * (h
  Wu[e_j])) Wd[e_j]``.
* ``embed -> layers -> rmsnorm -> lm_head`` (untied).

How it is computed, none of which changes a number:

* Attention goes in blocks of ``qb`` queries (``jax.lax.map``): a full
  layer's block scores every key, ``(H, qb, S)``; a sliding layer's block
  scores the ``window - 1`` keys before it and its own.
* The experts run as a ``scan`` over all of them, each over every position
  with the weight its picks gave it (0 where it was not picked): the sum over
  a token's picked experts, in expert order.
* Only what the asked-for tail of positions needs is computed
  (:func:`plan`): a full layer needs every position of its input, a sliding
  layer its input ``window - 1`` further back than its output.

``hp`` keys a limits' second reading lays over the configuration's (never set
in a run that decides ``correct``): ``cache_round`` (k and v as a narrower
cache would hand them back), ``yarn`` False (full layers rotate as sliding
ones do), ``attention_factor`` (another factor on cos/sin), ``renormalize``
False (top-k weights as the softmax gave them), ``router_dtype`` (the router's
product on operands rounded to it), ``window``.
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


def inv_freq_and_factor(hp, kind):
    """``(inv_freq (D/2,) python floats, f)`` of a layer kind."""
    D, theta = hp["head_dim"], hp["rope_base"]
    base = [theta ** (-2.0 * i / D) for i in range(D // 2)]
    if kind != "full" or not hp.get("yarn", True):
        return base, 1.0

    def d(r):
        return D * math.log(hp["yarn_original_max_seq"] / (2 * math.pi * r)) \
            / (2 * math.log(theta))

    lo = max(math.floor(d(hp["yarn_beta_fast"])), 0)
    hi = min(math.ceil(d(hp["yarn_beta_slow"])), D - 1)
    if hi == lo:
        hi += 0.001
    inv = []
    for i, b in enumerate(base):
        ramp = min(max((i - lo) / (hi - lo), 0.0), 1.0)
        inv.append(b / hp["yarn_factor"] * ramp + b * (1.0 - ramp))
    return inv, hp.get("attention_factor", hp["yarn_attention_factor"])


def _rope(x, pos, inv_freq, f):
    """``x (S, H, D)`` rotated at ``pos (S,)``, half-split pairs."""
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None, None] \
        * jnp.asarray(inv_freq, jnp.float32)
    cos, sin = f * jnp.cos(ang), f * jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def route(h, wg, hp):
    """``(idx (T, k), weight (T, k))``: softmax over all experts, the ``k``
    largest, renormalised to sum 1."""
    if hp.get("router_dtype"):
        to = jnp.dtype(hp["router_dtype"])
        logits = jnp.matmul(h.astype(to), wg.astype(to),
                            preferred_element_type=jnp.float32)
    else:
        logits = _mm(h, _f(wg))
    p = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(p, hp["top_k"])
    if hp.get("renormalize", True):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx, w


def _moe(h, moe, hp):
    idx, weight = route(h, moe["wg"], hp)

    def add(y, held):
        e, w = held
        we = jnp.sum(jnp.where(idx == e, weight, 0.0), -1)
        out = _mm(jax.nn.silu(_mm(h, _f(w["w1"]))) * _mm(h, _f(w["w3"])),
                  _f(w["w2"]))
        return y + we[:, None] * out, None

    y, _ = jax.lax.scan(add, jnp.zeros_like(h), (
        jnp.arange(moe["w1"].shape[0]),
        {k: moe[k] for k in ("w1", "w3", "w2")}))
    return y, idx


def _cached(x, hp):
    """What a cache would hand back of ``x (S, Hkv, D)``: ``x`` itself, or —
    with ``hp["cache_round"]`` — ``x`` narrowed: ``int8_rows`` as this repo's
    int8 pool does it (one absmax scale a position and head), else rounded
    to the named float type."""
    to = hp.get("cache_round")
    if to is None:
        return x
    if to == "int8_rows":
        scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
        return jnp.round(x / scale) * scale
    kind = jnp.finfo(jnp.dtype(to))
    return jax.lax.reduce_precision(x, exponent_bits=kind.nexp,
                                    mantissa_bits=kind.nmant)


def _layer(p, x, in_lo, out_lo, hp, kind, qb):
    """One block. ``x (n_in, d)`` holds positions ``in_lo ..``; returns the
    block's output for positions ``out_lo ..`` and what the checks read:
    ``cache`` (k after RoPE and v, ``(n_in, Hkv · D)``, positions ``in_lo
    ..``), ``router_input`` and ``router_picks`` (positions ``out_lo ..``)."""
    eps = hp["norm_eps"]
    H, Hkv, D = hp["n_heads"], hp["n_kv_heads"], hp["head_dim"]
    G = H // Hkv
    n_in = x.shape[0]
    skip = out_lo - in_lo
    nq = n_in - skip
    pos = in_lo + jnp.arange(n_in)
    inv_freq, f = inv_freq_and_factor(hp, kind)
    h = _rms(x, p["ln1_g"], eps)
    k = _cached(_rope(_mm(h, _f(p["wk"])).reshape(n_in, Hkv, D), pos,
                      inv_freq, f), hp)
    v = _cached(_mm(h, _f(p["wv"])).reshape(n_in, Hkv, D), hp)
    q = _rope(_mm(h[skip:], _f(p["wq"])).reshape(nq, H, D), pos[skip:],
              inv_freq, f).reshape(nq, Hkv, G, D)
    scale = D ** -0.5

    if kind == "full":
        assert in_lo == 0, "a full layer attends over every position"

        def block(i):
            qq = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)
            t = skip + i * qb + jnp.arange(qb)
            s = _es("thgd,shd->hgts", qq, k) * scale
            ok = jnp.arange(n_in)[None, :] <= t[:, None]
            pr = jax.nn.softmax(jnp.where(ok, s, _NEG), -1)
            return _es("hgts,shd->thgd", pr, v)
    else:
        P = hp["window"] - 1
        kp = jnp.concatenate([jnp.zeros((P, Hkv, D)), k])
        vp = jnp.concatenate([jnp.zeros((P, Hkv, D)), v])

        def block(i):
            qq = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)
            t = in_lo + skip + i * qb + jnp.arange(qb)
            # keys at positions t[0] - P .. t[0] + qb - 1
            kk = jax.lax.dynamic_slice_in_dim(kp, skip + i * qb, P + qb)
            vv = jax.lax.dynamic_slice_in_dim(vp, skip + i * qb, P + qb)
            kpos = t[0] - P + jnp.arange(P + qb)
            s = _es("thgd,shd->hgts", qq, kk) * scale
            gap = t[:, None] - kpos[None, :]
            ok = (gap >= 0) & (gap <= P) & (kpos[None, :] >= in_lo)
            pr = jax.nn.softmax(jnp.where(ok, s, _NEG), -1)
            return _es("hgts,shd->thgd", pr, vv)

    o = jax.lax.map(block, jnp.arange(nq // qb)).reshape(nq, H * D)
    x1 = x[skip:] + _mm(o, _f(p["wo"]))
    h2 = _rms(x1, p["ln2_g"], eps)
    y, idx = _moe(h2, p["moe"], hp)
    return x1 + y, {"cache": {"k": k.reshape(n_in, -1),
                              "v": v.reshape(n_in, -1)},
                    "router_input": h2, "router_picks": idx}


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
def _layer_program(in_lo, out_lo, hp_items, kind, qb):
    """One jitted layer for one plan entry: a second forward of the same
    length finds it compiled."""
    hp = {k: (list(v) if isinstance(v, tuple) else v) for k, v in hp_items}
    return jax.jit(functools.partial(_layer, in_lo=in_lo, out_lo=out_lo,
                                     hp=hp, kind=kind, qb=qb))


def forward(params, tokens, hp, n_tail=None, qb=128, router_layers=None):
    """Logits ``(n, V)`` f32 of the last ``n >= n_tail`` positions of
    ``tokens (S,)`` (``S`` a multiple of ``qb``; all of them with ``n_tail``
    None), the position of the first of them, and per layer what the checks
    read: ``cache`` (what a cache would hold of the layer, from ``in_lo``),
    ``router_input`` / ``router_picks`` (from ``out_lo``; of the layers in
    ``router_layers`` alone where that is given: 30k positions of one
    layer's router input are 0.28 GB), ``out_lo`` and ``in_lo``."""
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
        x, extra = _layer_program(lo, out_lo[li], hp_items, kinds[li], qb)(
            p, x)
        if router_layers is not None and li not in router_layers:
            extra = {"cache": extra["cache"]}
        layers.append(dict(extra, out_lo=out_lo[li], in_lo=lo))
        lo = out_lo[li]
    logits = _mm(_rms(x[exact_lo - lo:], params["lnf_g"], hp["norm_eps"]),
                 _f(params["lm_head"]))
    return logits, exact_lo, layers
