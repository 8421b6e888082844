"""The plain reference of Qwen3-Next-80B-A3B-Instruct (``model_type``
``qwen3_next``), on the share of it one chip of ep=8 holds.

Straight ``jax.numpy`` in float32 with ``precision=highest`` on every
product: no kernel, no cache, no chunked rule, no batching, nothing imported
from the program. It reads the parameter tree
``models/qwen3_next.py::qwen3_next_init`` makes (the one thing it shares with
the system under test; every matrix is upcast where it is used) and takes
every size from ``hp``, the configuration file's ``gpt_config``.

The published architecture (``config.json`` of the source, read as
``benchmark/configs/qwen3-next-80b-a3b-ep8-l8.json`` lists under
``assumed``). Layer ``i`` is full attention iff ``(i + 1) %
full_attention_interval == 0``, else Gated DeltaNet; ``zc(x, w) = x /
sqrt(mean(x^2) + eps) · (1 + w)``; input ``x (S, d)``, positions ``0 .. S``:

* **Full**: ``h = zc(x, g1)``; ``h Wq -> (S, H, 2D)`` split on the last axis
  into ``q`` and ``gate``; ``k, v = h Wk, h Wv -> (S, Hkv, D)``; ``q = zc(q,
  qn)``, ``k = zc(k, kn)`` over ``D``; RoPE on the first ``rotary_dim = D ·
  partial_rotary_factor`` dims of q and k, half-split pairs (``i`` with ``i +
  rotary_dim / 2``), ``inv_freq[i] = theta^(-2i / rotary_dim)``; scores ``q ·
  k / sqrt(D)``, query head ``j`` reads kv head ``j // (H / Hkv)``, causal
  softmax; ``x + (o * sigmoid(gate)) Wo``.
* **Gated DeltaNet**: ``h = zc(x, g1)``; ``h W_qkvz -> q | k | v | z``
  (``Hk Dk | Hk Dk | Hv Dv | Hv Dv``), ``h W_ba -> b | a``; ``c_t = silu(sum_j
  w_j · m_{t-K+1+j})`` over ``m = concat(q, k, v)``, zeros before position 0;
  ``beta = sigmoid(b)``, ``g = -exp(A_log) · softplus(a + dt_bias)``; q, k
  L2-normalised (``x / sqrt(sum x^2 + 1e-6)``), each key head repeated for
  its ``Hv / Hk`` value heads, ``q / sqrt(Dk)``. Per value head, from ``S =
  0``, TOKEN BY TOKEN: ``S = exp(g_t) S``; ``u = beta_t (v_t - S^T k_t)``;
  ``S = S + k_t u^T``; ``o_t = S^T q_t``. ``y = w · o / sqrt(mean(o^2) + eps)
  · silu(z)`` per head; ``x + y W_out``.
* **MoE**: ``h = zc(x, g2)``; ``p = softmax(h Wr)`` over all ``n_experts``;
  top ``top_k``, renormalised; the held experts ``first_expert ..
  first_expert + experts_held - 1`` computed (a ``scan`` over them, each over
  every position with the weight its picks gave it), what the others would
  add left out, as the program leaves it; plus ``sigmoid(h w_sg) ·
  SwiGLU_shared(h)``.
* ``embed -> layers -> zc -> lm_head`` (untied), over the vocabulary rows held.

Attention goes in blocks of ``qb`` queries against every key. ``forward``
also hands back what a serving cache would hold after ``state_at`` positions:
each DeltaNet layer's ``S`` and the convolution's last ``K - 1`` inputs, each
full layer's k (rotated) and v rows.

``hp`` keys a limits' second reading lays over the configuration's (never set
in a run that decides ``correct``): ``state_round`` (the state rounded to that
type after every token), ``decay_after_update`` (``S = exp(g)(S + k u^T)``),
``q_scale`` False (no ``1 / sqrt(Dk)``), ``rotary_dim`` (another share of the
head rotated), ``shared_gate`` False (the shared expert ungated),
``router_dtype`` (the router's product on operands rounded to it),
``qk_norm`` False (q and k as projected), ``attn_gate`` False (the attention
output ungated).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
_mm = functools.partial(jnp.matmul, precision=_HI)
_es = functools.partial(jnp.einsum, precision=_HI)
_NEG = -1e30


def _f(w):
    return w.astype(jnp.float32)


def _zc(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + _f(g))


def layer_kinds(hp):
    return ["full" if (i + 1) % hp["full_attention_interval"] == 0
            else "linear" for i in range(hp["n_layers"])]


def _rope(x, pos, rd, theta):
    """``x (S, H, D)``: the first ``rd`` dims rotated at ``pos``, half-split
    pairs; the rest as they are."""
    half = rd // 2
    inv = jnp.asarray([theta ** (-2.0 * i / rd) for i in range(half)],
                      jnp.float32)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:rd]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                            x[..., rd:]], -1)


def route(h, wg, hp):
    """``(idx (T, k), weight (T, k))``: softmax over all experts, the ``k``
    largest, renormalised to sum 1."""
    if hp.get("router_dtype"):
        to = jnp.dtype(hp["router_dtype"])
        logits = jnp.matmul(h.astype(to), wg.astype(to),
                            preferred_element_type=jnp.float32)
    else:
        logits = _mm(h, _f(wg))
    w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), hp["top_k"])
    return idx, w / jnp.sum(w, axis=-1, keepdims=True)


def _swiglu(h, w):
    return _mm(jax.nn.silu(_mm(h, _f(w["w1"]))) * _mm(h, _f(w["w3"])),
               _f(w["w2"]))


def _moe(x, p, hp):
    """``x + moe(zc(x))``, the router's input and its picks."""
    h = _zc(x, p["ln2_g"], hp["norm_eps"])
    idx, weight = route(h, p["moe"]["wg"], hp)

    def add(y, held):
        e, w = held
        we = jnp.sum(jnp.where(idx == e, weight, 0.0), -1)
        return y + we[:, None] * _swiglu(h, w), None

    held = p["moe"]["w1"].shape[0]
    y, _ = jax.lax.scan(add, jnp.zeros_like(h), (
        hp["first_expert"] + jnp.arange(held),
        {k: p["moe"][k] for k in ("w1", "w3", "w2")}))
    shared = _swiglu(h, p["shared"])
    if hp.get("shared_gate", True):
        shared = shared * jax.nn.sigmoid(_mm(h, _f(p["shared_gate"])))
    return x + y + shared, h, idx


def _full_layer(p, x, state_at, hp, qb):
    del state_at                      # a cache holds every row: the caller's
    S = x.shape[0]
    H, Hkv, D = hp["n_heads"], hp["n_kv_heads"], hp["head_dim"]
    rd = hp.get("rotary_dim", int(D * hp["partial_rotary_factor"]))
    eps, pos = hp["norm_eps"], jnp.arange(S)
    h = _zc(x, p["ln1_g"], eps)
    qg = _mm(h, _f(p["wq"])).reshape(S, H, 2 * D)
    q, gate = qg[..., :D], qg[..., D:]
    k = _mm(h, _f(p["wk"])).reshape(S, Hkv, D)
    v = _mm(h, _f(p["wv"])).reshape(S, Hkv, D)
    if hp.get("qk_norm", True):
        q, k = _zc(q, p["q_norm"], eps), _zc(k, p["k_norm"], eps)
    q = _rope(q, pos, rd, hp["rope_base"])
    k = _rope(k, pos, rd, hp["rope_base"])
    q = q.reshape(S, Hkv, H // Hkv, D)

    def block(i):
        qq = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)
        t = i * qb + jnp.arange(qb)
        s = _es("thgd,shd->hgts", qq, k) * D ** -0.5
        pr = jax.nn.softmax(
            jnp.where(pos[None, :] <= t[:, None], s, _NEG), -1)
        return _es("hgts,shd->thgd", pr, v)

    o = jax.lax.map(block, jnp.arange(S // qb)).reshape(S, H, D)
    if hp.get("attn_gate", True):
        o = o * jax.nn.sigmoid(gate)
    o = o.reshape(S, H * D)
    return x + _mm(o, _f(p["wo"])), {"k": k.reshape(S, -1),
                                     "v": v.reshape(S, -1)}


def _linear_layer(p, x, state_at, hp, qb):
    del qb
    S = x.shape[0]
    Hk, Hv = hp["linear_key_heads"], hp["linear_value_heads"]
    Dk, Dv, K = hp["linear_key_dim"], hp["linear_value_dim"], hp["conv_kernel"]
    kw, eps = Hk * Dk, hp["norm_eps"]
    h = _zc(x, p["ln1_g"], eps)
    qkvz = _mm(h, _f(p["in_qkvz"]))
    ba = _mm(h, _f(p["in_ba"]))
    mixed, z = qkvz[:, :2 * kw + Hv * Dv], qkvz[:, 2 * kw + Hv * Dv:]
    win = jnp.concatenate([jnp.zeros((K - 1, mixed.shape[1])), mixed])
    w = _f(p["conv_w"])
    c = jax.nn.silu(sum(win[j:j + S] * w[j] for j in range(K)))

    def unit(a):
        return a / jnp.sqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    q = unit(c[:, :kw].reshape(S, Hk, Dk))
    if hp.get("q_scale", True):
        q = q * Dk ** -0.5
    k = unit(c[:, kw:2 * kw].reshape(S, Hk, Dk))
    q, k = (jnp.repeat(a, Hv // Hk, axis=1) for a in (q, k))
    v = c[:, 2 * kw:].reshape(S, Hv, Dv)
    beta = jax.nn.sigmoid(ba[:, :Hv])
    g = -jnp.exp(_f(p["A_log"])) * jax.nn.softplus(
        ba[:, Hv:] + _f(p["dt_bias"]))
    to = hp.get("state_round")
    after = hp.get("decay_after_update", False)

    def token(carry, inp):
        St, kept = carry
        t, q_t, k_t, v_t, g_t, b_t = inp
        decay = jnp.exp(g_t)[:, None, None]
        if not after:
            St = St * decay
        u = b_t[:, None] * (v_t - _es("hkv,hk->hv", St, k_t))
        St = St + k_t[:, :, None] * u[:, None, :]
        if after:
            St = St * decay
        if to:
            # (reduce_precision: a convert there and back is XLA's to drop)
            kind = jnp.finfo(jnp.dtype(to))
            St = jax.lax.reduce_precision(St, exponent_bits=kind.nexp,
                                          mantissa_bits=kind.nmant)
        # what a slot holds once ``state_at`` positions are in
        kept = jnp.where(t == state_at - 1, St, kept)
        return (St, kept), _es("hkv,hk->hv", St, q_t)

    zero = jnp.zeros((Hv, Dk, Dv), jnp.float32)
    (_, kept), o = jax.lax.scan(token, (zero, zero),
                                (jnp.arange(S), q, k, v, g, beta))
    y = _f(p["gdn_norm"]) * o / jnp.sqrt(
        jnp.mean(o * o, -1, keepdims=True) + eps) \
        * jax.nn.silu(z.reshape(S, Hv, Dv))
    # positions state_at - (K - 1) .. state_at - 1 of the convolution's input
    tail = jax.lax.dynamic_slice_in_dim(win, state_at, K - 1)
    return x + _mm(y.reshape(S, -1), _f(p["out_proj"])), {"S": kept,
                                                         "tail": tail}


def _layer(p, x, state_at, hp, kind, qb):
    mix = _full_layer if kind == "full" else _linear_layer
    x, held = mix(p, x, state_at, hp, qb)
    x, router_input, picks = _moe(x, p, hp)
    return x, dict(held, router_input=router_input, router_picks=picks)


@functools.lru_cache(maxsize=64)
def _layer_program(hp_items, kind, qb):
    """One jitted layer a kind: a second forward of the same length finds it
    compiled (``state_at`` is data)."""
    return jax.jit(functools.partial(_layer, hp=dict(hp_items), kind=kind,
                                     qb=qb))


def forward(params, tokens, hp, state_at, lo=0, qb=128, router_layers=None):
    """Logits ``(S - lo, V)`` f32 of positions ``lo ..`` of ``tokens (S,)``
    (``S`` a multiple of ``qb``), and per layer what the checks read: a
    DeltaNet layer's ``S (Hv, Dk, Dv)`` and ``tail (K - 1, channels)`` after
    ``state_at`` positions, a full layer's ``k`` / ``v (S, Hkv · D)``;
    ``router_input`` / ``router_picks`` of the layers in ``router_layers``
    (all with None)."""
    S = int(tokens.shape[0])
    if S % qb:
        raise ValueError(f"{S} positions are not whole blocks of {qb}")
    hp_items = tuple(sorted(
        (k, v) for k, v in hp.items()
        if isinstance(v, (int, float, str, bool))))
    x = _f(params["wte"])[tokens]
    layers = []
    for li, (p, kind) in enumerate(zip(params["blocks"], layer_kinds(hp))):
        x, held = _layer_program(hp_items, kind, qb)(
            p, x, jnp.int32(state_at))
        if router_layers is not None and li not in router_layers:
            held = {k: v for k, v in held.items()
                    if not k.startswith("router_")}
        layers.append(dict(held, kind=kind))
    logits = _mm(_zc(x[lo:], params["lnf_g"], hp["norm_eps"]),
                 _f(params["lm_head"]))
    return logits, layers
