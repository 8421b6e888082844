"""The plain reference of Phi-4-mini-flash-reasoning (``model_type``
``phi4flash``; the SambaY decoder-hybrid-decoder of arXiv:2507.06607 with
differential attention).

Straight ``jax.numpy`` in float32 with ``precision=highest`` on every product:
no kernel, no cache, no chunked rule, no batching, no skipped layer, nothing
imported from the program. It reads the parameter tree
``models/phi4_flash.py::phi4_flash_init`` makes (the one thing it shares with
the system under test; every matrix is upcast where it is used; the tree's
``lambda_init`` constants are NOT read: ``lam0`` is computed from the layer's
index here) and takes every size from ``hp``, the configuration file's
``gpt_config``.

The architecture, as ``benchmark/configs/phi-4-mini-flash-reasoning.json``
lists under ``assumed``. ``LN(v; g, b)`` a LayerNorm with bias, eps 1e-5;
input ``x (S, d)``, positions ``0 .. S``; EVERY layer on EVERY position:

* ``x0 = wte[tokens]``; layer ``i``: ``x <- x + mix_i(LN(x; ln1))``, ``x <- x
  + ((silu(gate) * up) w_down)``, ``[gate | up] = LN(x; ln2) w_gu``; ``logits
  = LN(x; lnf) wte^T``. No positional encoding.
* ``mix_i``, ``half = L / 2``: ``i`` even ``<= half`` **Mamba**; ``i`` odd ``<
  half`` **window** attention; ``i = half + 1`` **full** attention; above it
  ``i`` even a **GMU**, ``i`` odd **cross**-attention over layer ``half +
  1``'s k and v.
* **Mamba**: ``[u | z] = h W_in``; ``u_t <- silu(b + sum_j w_j u_{t-K+1+j})``,
  zeros before position 0; ``[d' | B | C] = u W_x``; ``delta = softplus(d'
  W_dt + b_dt)``; ``A = -exp(A_log)`` ``(d_inner, N)``; from ``S = 0``, TOKEN
  BY TOKEN: ``S = exp(delta_t (x) A) * S + (delta_t * u_t) (x) B_t``; ``y_t =
  S C_t + D * u_t``; ``out = (y * silu(z)) W_out``. Layer ``half`` hands ``m =
  y`` (before the gate) to the GMUs.
* **GMU**: ``out = (silu(h W_1) * m) W_2``.
* **Attention** (differential): ``q = h W_q + b_q`` (H heads of D); own k/v
  layers ``k, v = h W_k + b_k, h W_v + b_v`` (Hkv heads). Query pair ``j`` is
  heads ``(2j, 2j+1)``, kv pair ``g`` is ``(k_2g, k_2g+1)`` with ``V_g =
  [v_2g | v_2g+1]``; pair ``j`` uses kv pair ``j // 2``. ``A1 = softmax(q_2j
  K1^T / sqrt(D) + mask) V_g``, ``A2`` likewise of ``q_2j+1`` and ``K2``;
  ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0(i)``, ``lam0(i) = 0.8 - 0.6
  exp(-0.3 i)``; ``o_j = rms(A1 - lam A2; g_sub, eps) (1 - lam0(i))``; ``out =
  concat_j(o_j) W_o + b_o``. Causal; a window layer's query sees its own key
  and the ``window - 1`` before it.

Departures from the published description: none in the arithmetic. Attention
goes in blocks of ``qb`` queries against every key, the MLP in blocks of its
width and the head in blocks of the vocabulary, over the rows asked for alone
(``rows``), so that the published widths fit one chip's memory in f32.
``forward`` also hands back what a serving cache would hold after ``state_at``
positions: a Mamba layer's ``S (d_inner, N)`` and the convolution's last ``K -
1`` inputs, a k/v-owning layer's k and v rows.

``hp`` keys a limits' second reading lays over the configuration's (never set
in a run that decides ``correct``): ``state_round`` (the state rounded to that
type after every token), ``m_after_gate`` (the GMUs read ``y * silu(z)``),
``lambda_depth_shift`` (``lam0`` of layer ``i + shift``), ``sub_norm`` False
(the sub-norm left out), ``window_keys`` (another count of keys), ``cross_own_kv``
(the cross layers attend over zeroed k and v of their own), ``stale_full_kv``
= P (the full layer's k and v rows of positions below P made from the
residual as it ENTERED the layer before it), ``rope_base`` (a half-split
rotary on q and k of every attention layer).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
_mm = functools.partial(jnp.matmul, precision=_HI)
_es = functools.partial(jnp.einsum, precision=_HI)
_NEG = -1e30
MLP_BLOCKS = 4            # the MLP's width in this many column blocks
VOCAB_BLOCK = 12504       # vocabulary rows a block of the head (16 blocks)


def _f(w):
    return w.astype(jnp.float32)


def _ln(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * _f(g) + _f(b)


def kind_of(i: int, L: int) -> str:
    half = L // 2
    if i <= half + 1:
        return "mamba" if i % 2 == 0 else ("full" if i == half + 1
                                           else "window")
    return "gmu" if i % 2 == 0 else "cross"


def _rope(x, pos, theta):
    """``x (S, H, D)`` rotated at ``pos``, half-split pairs (a control)."""
    half = x.shape[-1] // 2
    inv = jnp.asarray([theta ** (-i / half) for i in range(half)],
                      jnp.float32)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def kv_rows(p, h, hp):
    """The k and v rows ``(S, Hkv, D)`` a layer that owns them makes."""
    S = h.shape[0]
    Hkv, D = hp["n_kv_heads"], hp["d_head"]
    k = (_mm(h, _f(p["wk"])) + _f(p["bk"])).reshape(S, Hkv, D)
    v = (_mm(h, _f(p["wv"])) + _f(p["bv"])).reshape(S, Hkv, D)
    if hp.get("rope_base"):
        k = _rope(k, jnp.arange(S), hp["rope_base"])
    return k, v


def diff_attention(p, h, k, v, depth, window, hp, qb):
    """``mix_i(h)`` of an attention layer over keys ``k`` and values ``v (S,
    Hkv, D)``: its own, or in a cross layer the full layer's."""
    S = h.shape[0]
    H, Hkv, D = hp["n_heads"], hp["n_kv_heads"], hp["d_head"]
    G, J = Hkv // 2, H // 2               # kv pairs, query pairs
    pos = jnp.arange(S)
    q = (_mm(h, _f(p["wq"])) + _f(p["bq"])).reshape(S, H, D)
    if hp.get("rope_base"):
        q = _rope(q, pos, hp["rope_base"])
    q = q.reshape(S, G, J // G, 2, D)     # pair j = g * (J / G) + r
    k = k.reshape(S, G, 2, D)
    vg = v.reshape(S, G, 2 * D)
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * (depth + hp.get(
        "lambda_depth_shift", 0)))
    lam = jnp.exp(jnp.sum(_f(p["lambda_q1"]) * _f(p["lambda_k1"]))) \
        - jnp.exp(jnp.sum(_f(p["lambda_q2"]) * _f(p["lambda_k2"]))) + lam0

    def block(i):
        qq = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)
        t = (i * qb + jnp.arange(qb))[:, None]
        ok = pos[None, :] <= t
        if window is not None:
            ok &= pos[None, :] > t - window
        # s[g, r, c, t, s]: component c of pair (g, r) on key component c
        s = _es("tgrcd,sgcd->grcts", qq, k) * D ** -0.5
        a = _es("grcts,sge->tgrce",
                jax.nn.softmax(jnp.where(ok, s, _NEG), -1), vg)
        a = a[..., 0, :] - lam * a[..., 1, :]             # (qb, G, J/G, 2D)
        if hp.get("sub_norm", True):
            a = a / jnp.sqrt(jnp.mean(a * a, -1, keepdims=True)
                             + hp["norm_eps"]) * _f(p["subln_g"])
        return a * (1.0 - lam0)

    o = jax.lax.map(block, jnp.arange(S // qb)).reshape(S, H * D)
    return _mm(o, _f(p["wo"])) + _f(p["bo"])


def mamba(p, h, state_at, hp):
    """``(out, m, held)``: the mixer's output, the scan's output before the
    gate, and what a slot holds after ``state_at`` positions."""
    S = h.shape[0]
    Dn, N, K, R = hp["d_inner"], hp["ssm_state"], hp["conv_kernel"], \
        hp["dt_rank"]
    uz = _mm(h, _f(p["in_proj"]))
    u, z = uz[:, :Dn], uz[:, Dn:]
    win = jnp.concatenate([jnp.zeros((K - 1, Dn)), u])
    w = _f(p["conv_w"])
    u = jax.nn.silu(sum(win[j:j + S] * w[j] for j in range(K))
                    + _f(p["conv_b"]))
    dbc = _mm(u, _f(p["x_proj"]))
    delta = jax.nn.softplus(_mm(dbc[:, :R], _f(p["dt_proj"]))
                            + _f(p["dt_bias"]))
    Bm, Cm = dbc[:, R:R + N], dbc[:, R + N:]
    # the tree keeps A_log in the slot's layout (N, d_inner)
    A, D = -jnp.exp(_f(p["A_log"])).T, _f(p["D"])            # (Dn, N)
    to = hp.get("state_round")

    def token(carry, inp):
        St, kept = carry
        t, u_t, d_t, B_t, C_t = inp
        St = jnp.exp(d_t[:, None] * A) * St \
            + (d_t * u_t)[:, None] * B_t[None, :]
        if to:
            # (reduce_precision: a convert there and back is XLA's to drop)
            kind = jnp.finfo(jnp.dtype(to))
            St = jax.lax.reduce_precision(St, exponent_bits=kind.nexp,
                                          mantissa_bits=kind.nmant)
        kept = jnp.where(t == state_at - 1, St, kept)
        return (St, kept), _mm(St, C_t) + D * u_t

    zero = jnp.zeros((Dn, N), jnp.float32)
    (_, kept), y = jax.lax.scan(token, (zero, zero),
                                (jnp.arange(S), u, delta, Bm, Cm))
    gated = y * jax.nn.silu(z)
    # positions state_at - (K - 1) .. state_at - 1 of the convolution's input
    tail = jax.lax.dynamic_slice_in_dim(win, state_at, K - 1)
    m = gated if hp.get("m_after_gate", False) else y
    return _mm(gated, _f(p["out_proj"])), m, {"S": kept, "tail": tail}


def _mlp(p, h):
    ff = p["w_down"].shape[0]
    nb = MLP_BLOCKS if ff % MLP_BLOCKS == 0 else 1
    w = ff // nb

    def add(y, i):
        gate, up = (jax.lax.dynamic_slice_in_dim(p["w_gu"], o + i * w, w,
                                                 axis=1) for o in (0, ff))
        down = jax.lax.dynamic_slice_in_dim(p["w_down"], i * w, w, axis=0)
        return y + _mm(jax.nn.silu(_mm(h, _f(gate))) * _mm(h, _f(up)),
                       _f(down)), None

    return jax.lax.scan(add, jnp.zeros_like(h), jnp.arange(nb))[0]


def _layer(p, x, carried, state_at, depth, *, kind, hp, qb):
    """One layer of ``kind`` at ``depth`` (data: layers of a kind share a
    program): ``carried = (m, k, v, x_before)`` — the last Mamba layer's scan
    output, the full layer's keys and values, and the residual as it entered
    the layer before this one. Returns ``(x, carried, held)``."""
    eps = hp["norm_eps"]
    m, ks, vs, x_before = carried
    h = _ln(x, p["ln1_g"], p["ln1_b"], eps)
    held = {}
    if kind == "mamba":
        out, m, held = mamba(p, h, state_at, hp)
    elif kind == "gmu":
        out = _mm(jax.nn.silu(_mm(h, _f(p["w1"]))) * m, _f(p["w2"]))
    elif kind == "cross":
        if hp.get("cross_own_kv", False):
            ks, vs = jnp.zeros_like(ks), jnp.zeros_like(vs)
        out = diff_attention(p, h, ks, vs, depth, None, hp, qb)
    else:
        k, v = kv_rows(p, h, hp)
        if kind == "full":
            stale = hp.get("stale_full_kv", 0)
            if stale:
                k0, v0 = kv_rows(p, _ln(x_before, p["ln1_g"], p["ln1_b"],
                                        eps), hp)
                old = (jnp.arange(x.shape[0]) < stale)[:, None, None]
                k, v = jnp.where(old, k0, k), jnp.where(old, v0, v)
            ks, vs = k, v
        out = diff_attention(
            p, h, k, v, depth,
            hp.get("window_keys", hp["window"]) if kind == "window" else None,
            hp, qb)
        held = {"k": k.reshape(x.shape[0], -1),
                "v": v.reshape(x.shape[0], -1)}
    mid = x + out
    y = mid + _mlp(p, _ln(mid, p["ln2_g"], p["ln2_b"], eps))
    return y, (m, ks, vs, x), held


@functools.lru_cache(maxsize=64)
def _layer_program(hp_items, qb, kind):
    """The jitted layer of one kind: a second forward of the same length
    finds it compiled (``state_at`` and the depth are data)."""
    return jax.jit(functools.partial(_layer, kind=kind, hp=dict(hp_items),
                                     qb=qb))


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, g, b, wte, eps):
    """``LN(x; g, b) wte^T`` in blocks of the vocabulary."""
    h = _ln(x, g, b, eps)
    V = wte.shape[0]
    vb = VOCAB_BLOCK if V % VOCAB_BLOCK == 0 else V
    cols = jax.lax.map(
        lambda i: _mm(h, _f(jax.lax.dynamic_slice_in_dim(
            wte, i * vb, vb, axis=0)).T), jnp.arange(V // vb))
    return jnp.moveaxis(cols, 0, 1).reshape(x.shape[0], V)


def forward(params, tokens, hp, state_at=0, rows=None, qb=128):
    """Logits ``(len(rows), V)`` f32 of positions ``rows`` (None: every
    position) of ``tokens (S,)`` (``S`` a multiple of ``qb``), and per layer
    what the checks read: a Mamba layer's ``S (d_inner, N)`` and ``tail (K -
    1, d_inner)`` after ``state_at`` positions, a k/v-owning layer's ``k`` /
    ``v (S, Hkv · D)``; ``{}`` for a layer that keeps nothing."""
    S = int(tokens.shape[0])
    if S % qb:
        raise ValueError(f"{S} positions are not whole blocks of {qb}")
    hp_items = tuple(sorted(
        (k, v) for k, v in hp.items()
        if isinstance(v, (int, float, str, bool))))
    x = _f(params["wte"][tokens])
    Hkv, D = hp["n_kv_heads"], hp["d_head"]
    carried = (jnp.zeros((S, hp["d_inner"])), jnp.zeros((S, Hkv, D)),
               jnp.zeros((S, Hkv, D)), x)
    layers = []
    with jax.default_matmul_precision("highest"):
        for depth, p in enumerate(params["blocks"]):
            kind = kind_of(depth, hp["n_layers"])
            x, carried, held = _layer_program(hp_items, qb, kind)(
                p, x, carried, jnp.int32(state_at), jnp.float32(depth))
            layers.append(held)
        if rows is not None:
            x = x[jnp.asarray(rows)]
        logits = _head(x, params["lnf_g"], params["lnf_b"], params["wte"],
                       eps=hp["norm_eps"])
    return logits, layers
