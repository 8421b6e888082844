"""The plain reference of Falcon-H1-34B-Instruct (``model_type``
``falcon_h1``).

Straight ``jax.numpy`` in float32 with ``precision=highest`` on every
product: no kernel, no cache, no chunked rule, no batching, nothing imported
from the program. It reads the parameter tree
``models/falcon_h1.py::falcon_h1_init`` makes (the one thing it shares with
the system under test; every matrix is upcast where it is used) and takes
every size and multiplier from ``hp``, the configuration file's
``gpt_config``.

The published architecture (``config.json`` of the source and the modeling
code of ``transformers``, ``models/falcon_h1``, read as
``benchmark/configs/falcon-h1-34b-instruct-l4.json`` lists under
``assumed``). Every layer is the same; ``rms(v, w) = v / sqrt(mean(v^2) +
eps) · w``; input ``x (S, d)``, positions ``0 .. S``:

* ``x0 = wte[tokens] · embedding_multiplier``.
* ``h = rms(x, ln1_g)``; ``x <- x + (ssm(h) + attn(h))``.
* **attn**: ``a = h · attention_in_multiplier``; ``q = a Wq -> (S, H, D)``,
  ``k = (a Wk) · key_multiplier``, ``v = a Wv -> (S, Hkv, D)``; RoPE on all
  ``D`` dims of q and k, half-split pairs (``i`` with ``i + D / 2``),
  ``inv_freq[i] = theta^(-2i / D)``; scores ``q · k / sqrt(D)``, query head
  ``j`` reads kv head ``j // (H / Hkv)``, causal softmax; ``(o Wo) ·
  attention_out_multiplier``.
* **ssm** (Mamba-2): ``u = (h · ssm_in_multiplier) W_in``, columns ``z | x |
  B | C | dt`` (``d_ssm | d_ssm | G N | G N | Hs``), segment ``i`` times
  ``ssm_multipliers[i]``; ``c_t = silu(b + sum_j w_j · m_{t-K+1+j})`` over ``m
  = concat(x, B, C)``, zeros before position 0; ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)``. Per head ``i`` of group ``g = i // (Hs /
  G)``, from ``S = 0``, TOKEN BY TOKEN: ``S = exp(dt_t A) S + B_t (dt_t
  x_t)^T``; ``y_t = S^T C_t + D x_t``. ``y <- rms_grouped(y · silu(z),
  ssm_norm)``, the mean inside each of the ``G`` groups of ``d_ssm / G``
  channels; ``(y W_out) · ssm_out_multiplier``.
* ``h2 = rms(x, ln2_g)``; ``x <- x + ((silu((h2 W1) · mlp_multipliers[0]) ·
  (h2 W3)) W2) · mlp_multipliers[1]``.
* ``logits = (rms(x, lnf_g) lm_head) · lm_head_multiplier`` (untied).

Departures from the published description: none in the arithmetic. Attention
goes in blocks of ``qb`` queries against every key, the MLP in blocks of its
width and the head in blocks of the vocabulary, over the rows asked for alone
(``rows``), so that the published widths fit one chip's memory in f32;
``dt`` is not clamped (the published ``time_step_limit`` is (0, inf)).
``forward`` also hands back what a serving cache would hold after
``state_at`` positions: each layer's ``S`` and the convolution's last ``K -
1`` inputs, and its k (rotated) and v rows.

``hp`` keys a limits' second reading lays over the configuration's (never set
in a run that decides ``correct``): ``state_round`` (the state rounded to that
type after every token), ``decay_after_update`` (``S = exp(dt A)(S + B (dt
x)^T)``), ``dt_bias`` False (``dt = softplus(dt)``), ``group_shift`` (head
``i`` reads the B and C of group ``(g + shift) % G``), ``norm_groups`` (the
gated norm's mean over that many groups), ``attn_branch`` / ``ssm_branch``
False (the branch left out), and any multiplier by its own name.
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
VOCAB_BLOCK = 16320       # vocabulary columns a block of the head


def _f(w):
    return w.astype(jnp.float32)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f(g)


def _size(a):
    return jnp.sqrt(jnp.mean(a * a))


def _rope(x, pos, theta):
    """``x (S, H, D)`` rotated at ``pos``, half-split pairs."""
    half = x.shape[-1] // 2
    inv = jnp.asarray([theta ** (-i / half) for i in range(half)],
                      jnp.float32)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attn(p, h, hp, qb):
    """``attn(h)`` and the k and v rows a cache holds."""
    S = h.shape[0]
    H, Hkv, D = hp["n_heads"], hp["n_kv_heads"], hp["head_dim"]
    pos = jnp.arange(S)
    a = h * hp["attention_in_multiplier"]
    q = _mm(a, _f(p["wq"])).reshape(S, H, D)
    k = (_mm(a, _f(p["wk"])) * hp["key_multiplier"]).reshape(S, Hkv, D)
    v = _mm(a, _f(p["wv"])).reshape(S, Hkv, D)
    q = _rope(q, pos, hp["rope_base"]).reshape(S, Hkv, H // Hkv, D)
    k = _rope(k, pos, hp["rope_base"])

    def block(i):
        qq = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)
        t = i * qb + jnp.arange(qb)
        s = _es("thgd,shd->hgts", qq, k) * D ** -0.5
        pr = jax.nn.softmax(
            jnp.where(pos[None, :] <= t[:, None], s, _NEG), -1)
        return _es("hgts,shd->thgd", pr, v)

    o = jax.lax.map(block, jnp.arange(S // qb)).reshape(S, H * D)
    out = _mm(o, _f(p["wo"])) * hp["attention_out_multiplier"]
    return out, {"k": k.reshape(S, -1), "v": v.reshape(S, -1)}


def _ssm(p, h, state_at, hp):
    """``ssm(h)`` and what a slot holds after ``state_at`` positions."""
    S = h.shape[0]
    Hs, P = hp["ssm_heads"], hp["ssm_head_dim"]
    G, N, K = hp["ssm_groups"], hp["ssm_state"], hp["conv_kernel"]
    ds, eps = Hs * P, hp["norm_eps"]
    u = _mm(h * hp["ssm_in_multiplier"], _f(p["in_proj"]))
    widths = (ds, ds, G * N, G * N, Hs)
    z, x, Bm, Cm, dt = (a * m for a, m in zip(
        jnp.split(u, [sum(widths[:i]) for i in range(1, 5)], axis=1),
        hp["ssm_multipliers"]))
    mixed = jnp.concatenate([x, Bm, Cm], axis=1)
    win = jnp.concatenate([jnp.zeros((K - 1, mixed.shape[1])), mixed])
    w = _f(p["conv_w"])
    c = jax.nn.silu(sum(win[j:j + S] * w[j] for j in range(K))
                    + _f(p["conv_b"]))
    x = c[:, :ds].reshape(S, Hs, P)
    shift = hp.get("group_shift", 0)
    Bm, Cm = (jnp.roll(jnp.repeat(a.reshape(S, G, N), Hs // G, axis=1),
                       shift * (Hs // G), axis=1)
              for a in (c[:, ds:ds + G * N], c[:, ds + G * N:]))
    if hp.get("dt_bias", True):
        dt = dt + _f(p["dt_bias"])
    dt = jax.nn.softplus(dt)
    A, D = -jnp.exp(_f(p["A_log"])), _f(p["D"])
    to = hp.get("state_round")
    after = hp.get("decay_after_update", False)

    def token(carry, inp):
        St, kept = carry
        t, x_t, dt_t, B_t, C_t = inp
        decay = jnp.exp(dt_t * A)[:, None, None]
        if not after:
            St = St * decay
        St = St + B_t[:, :, None] * (dt_t[:, None] * x_t)[:, None, :]
        if after:
            St = St * decay
        if to:
            # (reduce_precision: a convert there and back is XLA's to drop)
            kind = jnp.finfo(jnp.dtype(to))
            St = jax.lax.reduce_precision(St, exponent_bits=kind.nexp,
                                          mantissa_bits=kind.nmant)
        # what a slot holds once ``state_at`` positions are in
        kept = jnp.where(t == state_at - 1, St, kept)
        return (St, kept), _es("hnp,hn->hp", St, C_t) + D[:, None] * x_t

    zero = jnp.zeros((Hs, N, P), jnp.float32)
    (_, kept), y = jax.lax.scan(token, (zero, zero),
                                (jnp.arange(S), x, dt, Bm, Cm))
    y = y.reshape(S, ds) * jax.nn.silu(z)
    g = y.reshape(S, hp.get("norm_groups", G), -1)
    g = g / jnp.sqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
    y = g.reshape(S, ds) * _f(p["ssm_norm"])
    # positions state_at - (K - 1) .. state_at - 1 of the convolution's input
    tail = jax.lax.dynamic_slice_in_dim(win, state_at, K - 1)
    return _mm(y, _f(p["out_proj"])) * hp["ssm_out_multiplier"], \
        {"S": kept, "tail": tail}


def _mlp(p, h, hp):
    ff = p["w1"].shape[1]
    nb = MLP_BLOCKS if ff % MLP_BLOCKS == 0 else 1
    w = ff // nb

    def add(y, i):
        w1, w3 = (jax.lax.dynamic_slice_in_dim(p[n], i * w, w, axis=1)
                  for n in ("w1", "w3"))
        w2 = jax.lax.dynamic_slice_in_dim(p["w2"], i * w, w, axis=0)
        gate = jax.nn.silu(_mm(h, _f(w1)) * hp["mlp_multipliers"][0])
        return y + _mm(gate * _mm(h, _f(w3)), _f(w2)), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(h), jnp.arange(nb))
    return y * hp["mlp_multipliers"][1]


def _layer(p, x, state_at, hp, qb):
    h = _rms(x, p["ln1_g"], hp["norm_eps"])
    y_ssm, held = _ssm(p, h, state_at, hp)
    y_attn, rows = _attn(p, h, hp, qb)
    if not hp.get("ssm_branch", True):
        y_ssm = jnp.zeros_like(y_ssm)
    if not hp.get("attn_branch", True):
        y_attn = jnp.zeros_like(y_attn)
    mid = x + (y_ssm + y_attn)
    y_mlp = _mlp(p, _rms(mid, p["ln2_g"], hp["norm_eps"]), hp)
    # each branch's share of the residual it is added to
    shares = jnp.stack([_size(y_ssm), _size(y_attn), _size(y_mlp)]) / _size(x)
    return mid + y_mlp, dict(held, **rows, shares=shares)


@functools.lru_cache(maxsize=64)
def _layer_program(hp_items, qb):
    """The one jitted layer: a second forward of the same length finds it
    compiled (``state_at`` is data)."""
    return jax.jit(functools.partial(_layer, hp=dict(hp_items), qb=qb))


@functools.partial(jax.jit, static_argnames=("eps", "scale"))
def _head(x, g, lm_head, eps, scale):
    """``(rms(x, g) lm_head) · scale`` in blocks of the vocabulary."""
    h = _rms(x, g, eps)
    V = lm_head.shape[1]
    vb = VOCAB_BLOCK if V % VOCAB_BLOCK == 0 else V
    cols = jax.lax.map(
        lambda i: _mm(h, _f(jax.lax.dynamic_slice_in_dim(
            lm_head, i * vb, vb, axis=1))), jnp.arange(V // vb))
    return jnp.moveaxis(cols, 0, 1).reshape(x.shape[0], V) * scale


def forward(params, tokens, hp, state_at, rows=None, qb=128):
    """Logits ``(len(rows), V)`` f32 of positions ``rows`` (None: every
    position) of ``tokens (S,)`` (``S`` a multiple of ``qb``), and per layer
    what the checks read: ``S (Hs, N, P)`` and ``tail (K - 1, channels)``
    after ``state_at`` positions, ``k`` / ``v (S, Hkv · D)``, and
    ``shares (3,)``: the size of the ssm, attn and mlp branches over the
    size of the residual they join."""
    S = int(tokens.shape[0])
    if S % qb:
        raise ValueError(f"{S} positions are not whole blocks of {qb}")
    hp_items = tuple(sorted(
        (k, tuple(v) if isinstance(v, (list, tuple)) else v)
        for k, v in hp.items()
        if isinstance(v, (int, float, str, bool, list, tuple))))
    x = _f(params["wte"][tokens]) * hp["embedding_multiplier"]
    layers = []
    for p in params["blocks"]:
        x, held = _layer_program(hp_items, qb)(p, x, jnp.int32(state_at))
        layers.append(held)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    logits = _head(x, params["lnf_g"], params["lm_head"],
                   eps=hp["norm_eps"], scale=hp["lm_head_multiplier"])
    return logits, layers
