"""The plain reference of SDAR-30B-A3B-Chat (``model_type`` ``sdar_moe``), cut
in depth alone.

Straight ``jax.numpy`` in float32 with ``precision=highest`` on every
product: no kernel, no cache, no batching, nothing imported from the
program. It reads the parameter tree ``models/sdar.py::sdar_init`` makes (the
one thing it shares with the system under test; every matrix is upcast where
it is used, one at a time) and takes every size from ``hp``, the
configuration file's ``gpt_config``.

The published architecture (``config.json`` of the source, read as
``benchmark/configs/sdar-30b-a3b-chat-l6.json`` lists under ``assumed``), for
a layer with input ``x (T, d)`` at positions ``pos``, ``B = block_length``:

* ``h = rmsnorm(x, g1)``; ``q = h Wq -> (T, H, D)``, ``k = h Wk``, ``v = h
  Wv -> (T, Hkv, D)``; no bias; ``q = rmsnorm(q, gq)``, ``k = rmsnorm(k,
  gk)`` over each head.
* RoPE on q and k, half-split pairs over all ``D`` dims, ``inv_freq[i] =
  theta^(-2i/D)``.
* scores ``q · k / sqrt(D)``; query head ``j`` reads kv head ``j // (H /
  Hkv)``; key ``s`` visible to query ``t`` iff ``s // B <= t // B`` (with
  ``hp["causal"]``, a second reading's: iff ``s <= t``); softmax; ``x = x +
  concat(o) Wo``.
* ``h = rmsnorm(x, g2)``; ``p = softmax(h Wr)`` over all experts; ``(w, e) =
  top_k(p)``; ``w = w / sum(w)``; ``x = x + sum_j w_j · (silu(h Wg[e_j]) * (h
  Wu[e_j])) Wd[e_j]``.
* ``embed -> layers -> rmsnorm -> lm_head`` (untied); position ``i``'s
  logits are about position ``i``'s token.

The sampler (:func:`generate`): the next ``B`` positions hold ``mask_id``
(the first block keeps the prompt's last ``len % B`` tokens); a pass is a
forward over prefix + block; of the positions still masked the ``B / steps``
whose greedy token (the mask token left out) has the largest softmax
probability take it, ties to the earlier position; when none is masked the
block is final. No cache: every pass is a whole forward.

How it is computed, none of which changes a number:

* Attention goes in blocks of ``qb`` queries (``jax.lax.map``).
* The experts run as a ``scan`` over all of them, each over every position
  with the weight its picks gave it (0 where it was not picked).
* ``prefix``: under the block-causal mask no earlier block sees a later one,
  so the k and v rows of whole earlier blocks are the same whatever follows;
  a forward may be given them (an earlier forward's own rows) and compute the
  later positions alone.

``hp`` keys a limits' second reading lays over the configuration's (never set
in a run that decides ``correct``): ``causal`` True, ``cache_round`` (k and v
as a narrower cache would hand them back), ``router_dtype`` (the router's
product on operands rounded to it), ``compute_dtype`` (every product on
operands rounded to it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
_NEG = -1e30


def _f(w):
    return w.astype(jnp.float32)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f(g)


def _ops(hp):
    """``(matmul, einsum)``: f32 at the highest precision, or — a second
    reading's ``compute_dtype`` — on operands rounded to that type."""
    to = hp.get("compute_dtype")
    if not to:
        return (functools.partial(jnp.matmul, precision=_HI),
                functools.partial(jnp.einsum, precision=_HI))
    to = jnp.dtype(to)

    def mm(a, b):
        return jnp.matmul(a.astype(to), b.astype(to),
                          preferred_element_type=jnp.float32)

    def es(spec, a, b):
        return jnp.einsum(spec, a.astype(to), b.astype(to),
                          preferred_element_type=jnp.float32)
    return mm, es


def _rope(x, pos, theta):
    """``x (S, H, D)`` rotated at ``pos (S,)``, half-split pairs."""
    D = x.shape[-1]
    half = D // 2
    inv = jnp.asarray([theta ** (-2.0 * i / D) for i in range(half)],
                      jnp.float32)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def route(h, wg, hp):
    """``(idx (T, k), weight (T, k))``: softmax over all experts, the ``k``
    largest, renormalised to sum 1."""
    to = hp.get("router_dtype") or hp.get("compute_dtype")
    if to:
        to = jnp.dtype(to)
        logits = jnp.matmul(h.astype(to), wg.astype(to),
                            preferred_element_type=jnp.float32)
    else:
        logits = jnp.matmul(h, _f(wg), precision=_HI)
    p = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(p, hp["top_k"])
    return idx, w / jnp.sum(w, axis=-1, keepdims=True)


def _moe(h, moe, hp):
    mm, _ = _ops(hp)
    idx, weight = route(h, moe["wg"], hp)

    def add(y, held):
        e, w = held
        we = jnp.sum(jnp.where(idx == e, weight, 0.0), -1)
        out = mm(jax.nn.silu(mm(h, _f(w["w1"]))) * mm(h, _f(w["w3"])),
                 _f(w["w2"]))
        return y + we[:, None] * out, None

    y, _ = jax.lax.scan(add, jnp.zeros_like(h), (
        jnp.arange(moe["w1"].shape[0]),
        {k: moe[k] for k in ("w1", "w3", "w2")}))
    return y, idx


def _cached(x, hp):
    """What a cache would hand back of ``x``: ``x`` itself, or — with
    ``hp["cache_round"]`` — ``x`` rounded to the named float type."""
    to = hp.get("cache_round")
    if to is None:
        return x
    kind = jnp.finfo(jnp.dtype(to))
    return jax.lax.reduce_precision(x, exponent_bits=kind.nexp,
                                    mantissa_bits=kind.nmant)


def _layer(p, x, pk, pv, start, hp, qb):
    """One block over ``x (n, d)`` at positions ``start ..`` with the rows of
    positions ``[0, start)`` given (``pk``, ``pv (start, Hkv · D)``).
    Returns the block's output and what the checks read: ``k`` (after the
    norm and RoPE) and ``v`` ``(n, Hkv · D)``, ``router_input`` and
    ``router_picks``."""
    mm, es = _ops(hp)
    eps, blk = hp["norm_eps"], hp["block_length"]
    H, Hkv, D = hp["n_heads"], hp["n_kv_heads"], hp["head_dim"]
    G, n = H // Hkv, x.shape[0]
    pos = start + jnp.arange(n)
    h = _rms(x, p["ln1_g"], eps)
    q = _rms(mm(h, _f(p["wq"])).reshape(n, H, D), p["q_norm"], eps)
    k = _rms(mm(h, _f(p["wk"])).reshape(n, Hkv, D), p["k_norm"], eps)
    q = _rope(q, pos, hp["rope_base"]).reshape(n, Hkv, G, D)
    k = _cached(_rope(k, pos, hp["rope_base"]), hp)
    v = _cached(mm(h, _f(p["wv"])).reshape(n, Hkv, D), hp)
    kk = jnp.concatenate([pk.reshape(-1, Hkv, D), k])
    vv = jnp.concatenate([pv.reshape(-1, Hkv, D), v])
    kpos = jnp.arange(kk.shape[0])
    scale = D ** -0.5

    def block(i):
        qq = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)
        t = start + i * qb + jnp.arange(qb)
        s = es("thgd,shd->hgts", qq, kk) * scale
        ok = kpos[None, :] <= t[:, None] if hp.get("causal") else \
            kpos[None, :] // blk <= t[:, None] // blk
        pr = jax.nn.softmax(jnp.where(ok, s, _NEG), -1)
        return es("hgts,shd->thgd", pr, vv)

    o = jax.lax.map(block, jnp.arange(n // qb)).reshape(n, H * D)
    x1 = x + mm(o, _f(p["wo"]))
    h2 = _rms(x1, p["ln2_g"], eps)
    y, idx = _moe(h2, p["moe"], hp)
    return x1 + y, {"k": k.reshape(n, -1), "v": v.reshape(n, -1),
                    "router_input": h2, "router_picks": idx}


@functools.lru_cache(maxsize=64)
def _layer_program(hp_items, qb):
    hp = dict(hp_items)
    return jax.jit(functools.partial(_layer, hp=hp, qb=qb))


@functools.lru_cache(maxsize=8)
def _head_program(hp_items):
    hp = dict(hp_items)
    mm, _ = _ops(hp)
    return jax.jit(lambda x, g, head: mm(_rms(x, g, hp["norm_eps"]),
                                         _f(head)))


def forward(params, tokens, hp, start=0, prefix=None, logits_from=0, qb=None,
            router_layers=None):
    """Logits ``(n - logits_from, V)`` f32 of ``tokens (n,)`` at positions
    ``start ..`` (all of them with ``logits_from`` 0; None: no logits), and
    per layer what the checks read: ``k``, ``v`` ``(n, Hkv · D)`` (what a
    cache would hold of the layer), ``router_input`` / ``router_picks`` (of
    the layers in ``router_layers`` alone where that is given). ``prefix``:
    per layer the ``(k, v)`` rows of positions ``[0, start)`` (``start`` a
    multiple of ``block_length``; None with ``start`` 0). ``n`` is a multiple
    of ``qb`` (default: ``n`` itself)."""
    n = int(tokens.shape[0])
    qb = n if qb is None else qb
    if n % qb or start % hp["block_length"]:
        raise ValueError(f"{n} positions from {start}: not whole blocks of "
                         f"{qb} queries from a block boundary")
    hp_items = tuple(sorted((k, v) for k, v in hp.items()
                            if isinstance(v, (int, float, str, bool))))
    kv = hp["n_kv_heads"] * hp["head_dim"]
    x = _f(params["wte"][tokens])
    layers = []
    for li, p in enumerate(params["blocks"]):
        pk, pv = (jnp.zeros((0, kv)),) * 2 if prefix is None else prefix[li]
        x, extra = _layer_program(hp_items, qb)(p, x, pk, pv, start)
        if router_layers is not None and li not in router_layers:
            extra = {"k": extra["k"], "v": extra["v"]}
        layers.append(extra)
    if logits_from is None:
        return None, layers
    return _head_program(hp_items)(x[logits_from:], params["lnf_g"],
                                   params["lm_head"]), layers


def fix(logits, blk, n_fix, mask_id):
    """The sampler's rule on one block, numpy: of the positions of ``blk``
    that hold ``mask_id``, the ``n_fix`` whose greedy token (the mask token
    left out) is most probable take it, ties to the earlier position.
    Returns ``(blk, the positions fixed, confidence of every position)``."""
    lg = np.array(logits, np.float64)
    lg[:, mask_id] = -np.inf
    best = lg.argmax(-1)
    m = lg.max(-1)
    conf = 1.0 / np.exp(lg - m[:, None]).sum(-1)
    masked = np.flatnonzero(np.asarray(blk) == mask_id)
    order = masked[np.argsort(-conf[masked], kind="stable")][:n_fix]
    out = np.array(blk)
    out[order] = best[order]
    return out, sorted(int(i) for i in order), conf


def generate(params, prompt, gen_len, hp, steps, eos_id=None, pad_to=None):
    """``gen_len`` tokens after ``prompt`` by diffusion over blocks of
    ``hp["block_length"]``, ``steps`` denoising passes a block (stopping at
    ``eos_id``). Returns ``(tokens, fixed_at, passes)``: the generated
    tokens, the pass (1 ..) each was fixed at, and for every pass ``(block
    start, the block before the pass, its logits (B, V), the block after
    it)``. ``pad_to``: every forward runs over that many positions (zeros
    after the block, which no earlier position sees), one program."""
    B, mask = hp["block_length"], hp["mask_id"]
    ctx = [int(t) for t in prompt]
    start = len(ctx) // B * B
    committed, given = ctx[:start], ctx[start:]
    out, fixed_at, passes = [], [], []
    while True:
        blk = np.asarray(given + [mask] * (B - len(given)), np.int32)
        at = [0] * B
        pass_no = 0
        while (blk == mask).any():
            pass_no += 1
            toks = np.zeros(pad_to or start + B, np.int32)
            toks[:start] = committed
            toks[start:start + B] = blk
            logits, _ = forward(params, jnp.asarray(toks), hp,
                                logits_from=0)
            logits = np.asarray(logits[start:start + B], np.float32)
            new, fixed, _ = fix(logits, blk, B // steps, mask)
            for i in fixed:
                at[i] = pass_no
            passes.append((start, blk, logits, new))
            blk = new
        for t, a in zip(blk[len(given):], at[len(given):]):
            out.append(int(t))
            fixed_at.append(a)
            if len(out) == gen_len or (eos_id is not None and t == eos_id):
                return (np.asarray(out, np.int32),
                        np.asarray(fixed_at, np.int32), passes)
        committed = committed + [int(t) for t in blk]
        start, given = start + B, []
