"""Phi-4-mini-flash-reasoning (``model_type`` ``phi4flash``): the SambaY
decoder-hybrid-decoder (arXiv:2507.06607) with differential attention — a
*self-decoder* of Mamba-1 and window-attention layers that ends in ONE
full-attention layer, and a *cross-decoder* whose layers keep no cache of their
own: Gated Memory Units gate the last Mamba layer's scan output, and
cross-attention layers project a query only and read the full-attention layer's
keys and values.

Every layer ``i`` (0-based), with ``LN`` a LayerNorm with bias::

    x <- x + mix_i(LN(x; ln1));   x <- x + mlp(LN(x; ln2))
    logits = LN(x; lnf) wte^T     (tied; no positional encoding of any kind)

``mix_i`` by index (:func:`layer_kinds`; ``half = n_layers / 2``): ``i`` even
and ``<= half`` a **Mamba** layer, ``i`` odd and ``< half`` **window**
attention, ``i = half + 1`` **full** attention, above it ``i`` even a **GMU**
and ``i`` odd **cross**-attention over layer ``half + 1``'s k and v.

* **Mamba** (:func:`mamba_inputs` → a form of the rule in
  ``ops/selective_scan.py`` → the gate): ``[u | z] = h in_proj``; ``u <-
  silu(conv(u))``, a causal depthwise convolution of ``conv_kernel`` taps with
  bias; ``[d' | B | C] = u x_proj`` (``dt_rank | N | N``); ``delta =
  softplus(d' dt_proj + dt_bias)``; ``A = -exp(A_log)``; the rule gives ``y``;
  ``out = (y * silu(z)) out_proj``. The LAST Mamba layer also hands ``m = y``
  (before the gate) to every GMU above it. Between tokens a sequence carries
  ``S (N, d_inner)`` f32 and the convolution's last ``conv_kernel - 1`` inputs.
* **GMU** (:func:`gmu_half`): ``out = (silu(h w1) * m) w2``, ``m`` of the SAME
  position.
* **Differential attention** (:func:`diff_attn_half`, all attention layers):
  ``q = h wq + bq`` (``H`` heads of ``D``), and in a layer that owns k/v ``k,
  v = h wk + bk, h wv + bv`` (``Hkv`` heads). Adjacent heads pair: query pair
  ``j`` is heads ``(2j, 2j + 1)``, kv pair ``g`` is ``(k_2g, k_2g+1)`` with
  ``V_g = [v_2g | v_2g+1]``; pair ``j`` uses kv pair ``j // 2``. ``A1 =
  softmax(q_2j K1^T / sqrt(D)) V_g``, ``A2 = softmax(q_2j+1 K2^T / sqrt(D))
  V_g``, ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0(i)``, ``lam0(i) = 0.8 -
  0.6 exp(-0.3 i)``; ``o_j = rms(A1 - lam A2; subln_g) (1 - lam0(i))``; ``out =
  concat_j(o_j) wo + bo``. It rides the kernels the other families use: a
  page's minor axis ``Hkv x D`` IS ``Hkv / 2`` heads of ``2D`` — kv pair
  ``g``'s keys side by side, and its values — so the queries padded to ``2D``
  (``[q_2j | 0]``, ``[0 | q_2j+1]``, times ``sqrt 2`` for the kernels' ``1 /
  sqrt(2D)``) against ``Hkv / 2`` heads give ``A1`` and ``A2``, each ``2D``
  wide, from ``attend`` as it is. So ``cfg.head_dim`` (what the pool and the
  programs read) is ``2 D`` and ``cfg.kv_heads`` is ``Hkv / 2``.
* **MLP** (:func:`mlp`): ``(silu(gate) * up) w_down``, ``[gate | up] = h
  w_gu``.

The first halves are given to the serve tier's two programs by
``serve/families.py::SharedKVFamily``; :func:`phi4_flash_apply` is the dense
forward over whole sequences (tests).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from byteps_tpu.models.gpt import _layernorm, _readout, ffn_half
from byteps_tpu.models.mellum2 import dense_attend
from byteps_tpu.ops.selective_scan import (
    sscan_chunk_fwd,
    sscan_decode,
    sscan_recurrent,
)

MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window", "full", "gmu", "cross"
#: the leaves of a block that are constants of its depth, not parameters
CONSTANTS = ("lambda_init",)


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064
    max_seq: int = 262144
    d_model: int = 2560
    n_layers: int = 32
    # attention: the published heads; ``head_dim`` below is a PAIR's
    n_heads: int = 40
    n_kv_heads: int = 20
    d_head: int = 64
    window: int = 512                  # keys a query sees, its own included
    # the Mamba-1 mixer (the family's defaults: the config gives none)
    d_inner: int = 5120
    ssm_state: int = 16
    conv_kernel: int = 4
    dt_rank: int = 160
    sscan_sub_chunk: int = 64          # tokens a step of the chunked rule
    d_ff: int = 10240
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    # what the shared block and the paged programs read of a configuration
    # (``GPTConfig``'s names); this model has one answer to each. No position
    # is encoded: "rope" keeps the embedding from adding a table, and every
    # layer's ``LayerKind.rope`` is 0 (``rope_base`` is what the scheme's
    # validator asks for; nothing rotates by it)
    pos_embedding = "rope"
    rope_base = 1.0
    norm = "layernorm"
    use_bias = True
    tied_readout = True

    def __post_init__(self):
        if self.n_layers % 4 or self.n_layers < 8:
            raise ValueError("n_layers must be a multiple of 4, at least 8 "
                             "(a self-decoder that ends on a full layer and "
                             "a cross-decoder of GMU / cross pairs)")
        if self.n_heads % self.n_kv_heads or self.n_kv_heads % 2 \
                or (self.n_heads // self.n_kv_heads) % 2:
            raise ValueError("heads pair: n_kv_heads even, an even number of "
                             "query heads a k/v head")

    @property
    def head_dim(self) -> int:
        """A k/v PAIR's width: what a head is to the pool and the kernels."""
        return 2 * self.d_head

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads // 2

    def state_bytes(self, itemsize: int = 2) -> int:
        """What one request holds of one Mamba layer: the f32 state and the
        convolution's tail in ``dtype`` (``itemsize`` bytes)."""
        return (self.ssm_state * self.d_inner * 4
                + (self.conv_kernel - 1) * self.d_inner * itemsize)

    @classmethod
    def tiny(cls, **kw) -> "Phi4FlashConfig":
        """Unit-test size: eight layers (every kind, two of the window
        kind), four query heads on two k/v heads a pair, a window and
        sub-chunks shorter than a prefill chunk."""
        base = dict(vocab_size=256, max_seq=64, d_model=32, n_layers=8,
                    n_heads=8, n_kv_heads=4, d_head=8, window=6, d_inner=64,
                    ssm_state=8, dt_rank=4, sscan_sub_chunk=4, d_ff=64,
                    dtype=jnp.float32)
        base.update(kw)
        return cls(**base)


def layer_kinds(cfg: Phi4FlashConfig) -> Tuple[str, ...]:
    """Each layer's mixer (module docstring)."""
    half = cfg.n_layers // 2

    def kind(i):
        if i <= half + 1:
            return MAMBA if i % 2 == 0 else (FULL if i == half + 1
                                             else WINDOW)
        return GMU if i % 2 == 0 else CROSS

    return tuple(kind(i) for i in range(cfg.n_layers))


def lambda_init(depth) -> float:
    """``lam0(i)`` of layer ``i`` (0-based)."""
    return 0.8 - 0.6 * math.exp(-0.3 * depth)


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------
def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def at_depth(p: Dict[str, Any], depth: int) -> Dict[str, Any]:
    """An attention layer's leaves with ``lambda_init``, its ``lam0(depth)``:
    a constant, not a parameter (:data:`CONSTANTS`), carried as a leaf because
    the chunk program runs layers of one shape under one trace. Any other
    layer as it is."""
    if "wq" not in p:
        return p
    return dict(p, lambda_init=jnp.float32(lambda_init(depth)))


def phi4_flash_block_init(rng, cfg: Phi4FlashConfig, kind: str
                          ) -> Dict[str, Any]:
    """One layer's parameters in ``cfg.dtype`` (``A_log``, ``dt_bias``, ``D``
    and the four lambda vectors f32). Matrices at ``fan_in^-0.5``, so that
    every mixer adds a part of unit scale to the residual; norm weights around
    1, biases N(0, 0.1); ``A_log = log(1..N)`` along the state axis,
    ``dt_bias`` the inverse softplus of a step log-uniform in [1e-3, 1e-1] and
    ``D = 1``: Mamba's own draw; the lambda vectors N(0, 0.1) as published.
    (:func:`at_depth` adds an attention layer's constant.)"""
    d, dt, ff, Dn = cfg.d_model, cfg.dtype, cfg.d_ff, cfg.d_inner
    H, Hkv, D, N = cfg.n_heads, cfg.n_kv_heads, cfg.d_head, cfg.ssm_state
    k = jax.random.split(rng, 20)

    def around_one(key, n):
        return (1.0 + jax.random.normal(key, (n,)) * 0.1).astype(dt)

    p = {"ln1_g": around_one(k[0], d), "ln1_b": _normal(k[1], (d,), 0.1, dt),
         "ln2_g": around_one(k[2], d), "ln2_b": _normal(k[3], (d,), 0.1, dt),
         "w_gu": _normal(k[4], (d, 2 * ff), d ** -0.5, dt),
         "w_down": _normal(k[5], (ff, d), ff ** -0.5, dt)}
    if kind == MAMBA:
        step = jnp.exp(jax.random.uniform(k[6], (Dn,), jnp.float32,
                                          math.log(1e-3), math.log(1e-1)))
        p.update({
            "in_proj": _normal(k[7], (d, 2 * Dn), d ** -0.5, dt),
            # tap j multiplies the input conv_kernel - 1 - j tokens back
            "conv_w": _normal(k[8], (cfg.conv_kernel, Dn),
                              cfg.conv_kernel ** -0.5, dt),
            "conv_b": _normal(k[9], (Dn,), 0.1, dt),
            "x_proj": _normal(k[10], (Dn, cfg.dt_rank + 2 * N),
                              Dn ** -0.5, dt),
            "dt_proj": _normal(k[11], (cfg.dt_rank, Dn),
                               cfg.dt_rank ** -0.5, dt),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            # (N, d_inner): the state's own layout (ops/selective_scan.py)
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[:, None],
                (N, Dn)),
            "D": jnp.ones((Dn,), jnp.float32),
            "out_proj": _normal(k[12], (Dn, d), Dn ** -0.5, dt)})
    elif kind == GMU:
        p.update({"w1": _normal(k[7], (d, Dn), d ** -0.5, dt),
                  "w2": _normal(k[8], (Dn, d), Dn ** -0.5, dt)})
    else:
        p.update({"wq": _normal(k[7], (d, H * D), d ** -0.5, dt),
                  "bq": _normal(k[8], (H * D,), 0.1, dt),
                  "wo": _normal(k[9], (H * D, d), (H * D) ** -0.5, dt),
                  "bo": _normal(k[10], (d,), 0.1, dt),
                  "subln_g": around_one(k[11], 2 * D)})
        for i, name in enumerate(("lambda_q1", "lambda_k1", "lambda_q2",
                                  "lambda_k2")):
            p[name] = _normal(k[12 + i], (D,), 0.1, jnp.float32)
        if kind != CROSS:
            p.update({"wk": _normal(k[16], (d, Hkv * D), d ** -0.5, dt),
                      "bk": _normal(k[17], (Hkv * D,), 0.1, dt),
                      "wv": _normal(k[18], (d, Hkv * D), d ** -0.5, dt),
                      "bv": _normal(k[19], (Hkv * D,), 0.1, dt)})
    return p


def phi4_flash_head_init(rng, cfg: Phi4FlashConfig) -> Dict[str, Any]:
    """The tied embedding (logits of about unit spread) and the final norm."""
    k = jax.random.split(rng, 3)
    d, dt = cfg.d_model, cfg.dtype
    return {"wte": _normal(k[0], (cfg.vocab_size, d), d ** -0.5, dt),
            "lnf_g": (1.0 + jax.random.normal(k[1], (d,)) * 0.1).astype(dt),
            "lnf_b": _normal(k[2], (d,), 0.1, dt)}


def phi4_flash_init(rng, cfg: Phi4FlashConfig) -> Dict[str, Any]:
    keys = jax.random.split(rng, 1 + cfg.n_layers)
    return {**phi4_flash_head_init(keys[0], cfg),
            "blocks": [at_depth(phi4_flash_block_init(keys[1 + li], cfg,
                                                      kind), li)
                       for li, kind in enumerate(layer_kinds(cfg))]}


def param_count(cfg: Phi4FlashConfig) -> int:
    """Parameters of the tree (its :data:`CONSTANTS` are not)."""
    shapes = jax.eval_shape(lambda: phi4_flash_init(jax.random.PRNGKey(0),
                                                    cfg))
    blocks = [{k: v for k, v in p.items() if k not in CONSTANTS}
              for p in shapes.pop("blocks")]
    return sum(math.prod(a.shape)
               for a in jax.tree_util.tree_leaves((shapes, blocks)))


# --------------------------------------------------------------------------
# the five first halves, and the MLP
# --------------------------------------------------------------------------
def _matmul(x, w, out=None):
    return jnp.einsum("...d,df->...f", x, w.astype(x.dtype),
                      preferred_element_type=out)


def diff_attn_half(cfg: Phi4FlashConfig, x, p, head_dim, positions, attend,
                   tp_axis=None, rope=0.0, norm_fn=_layernorm, norm_eps=1e-5,
                   use_bias=True, delta=None, kind=None):
    """The first half of an attention layer, ``models/gpt.py::attn_half``'s
    signature and ``attend`` contract (``positions``, ``tp_axis``, ``rope``,
    ``use_bias`` and ``delta`` are the signature's: one answer here).
    ``attend`` is given ``H`` padded query heads of ``head_dim = 2D`` and, in a
    layer that owns k/v, ``Hkv / 2`` heads of k and of v (None, None in a
    cross layer, whose ``attend`` reads another layer's). ``kind``: the
    layer's ``LayerKind``, for the region's name. Returns ``(x, carry)``."""
    del positions, tp_axis, rope, use_bias, delta
    B, T = x.shape[:2]
    H, D = cfg.n_heads, cfg.d_head
    cross = "wk" not in p
    name = "cross" if cross else (
        "self" if kind is None or kind.window is None else "window")
    with jax.named_scope(f"block/attn/{name}"):
        h = norm_fn(x, p["ln1_g"], p["ln1_b"], norm_eps)
        # sqrt 2: the kernels divide by sqrt(head_dim) = sqrt(2 D)
        q = ((_matmul(h, p["wq"], jnp.float32) + p["bq"].astype(jnp.float32))
             * math.sqrt(2.0)).astype(h.dtype).reshape(B, T, H // 2, 2, D)
        zero = jnp.zeros((B, T, H // 2, D), h.dtype)
        q = jnp.stack([jnp.concatenate([q[..., 0, :], zero], -1),
                       jnp.concatenate([zero, q[..., 1, :]], -1)], axis=3)
        k = v = None
        if not cross:
            k = (_matmul(h, p["wk"]) + p["bk"].astype(h.dtype)).reshape(
                B, T, cfg.kv_heads, head_dim)
            v = (_matmul(h, p["wv"]) + p["bv"].astype(h.dtype)).reshape(
                B, T, cfg.kv_heads, head_dim)
        o, carry = attend(q.reshape(B, T, H, head_dim), k, v)
        # (the paged kernel returns a decode step's rows without the T axis)
        o = o.reshape(B, T, H // 2, 2, head_dim).astype(jnp.float32)
        lam0 = p["lambda_init"]
        lam = jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"])) \
            - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + lam0
        a = o[..., 0, :] - lam * o[..., 1, :]
        a = a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True) + norm_eps) \
            * p["subln_g"].astype(jnp.float32) * (1.0 - lam0)
        out = _matmul(a.astype(h.dtype).reshape(B, T, H * D), p["wo"]) \
            + p["bo"].astype(h.dtype)
        return x + out, carry


def mamba_inputs(cfg: Phi4FlashConfig, p, h, tail):
    """Everything the rule takes, from the normed input ``h (B, T, d)`` and
    the convolution's tail ``(B, conv_kernel - 1, d_inner)`` (the inputs
    before this call's first token; zeros at a sequence's start). Returns
    ``(u, delta (B, T, d_inner), B, C (B, T, N))`` in f32, ``z (B, T,
    d_inner)`` and the tail after the last token."""
    T, K, N, R = h.shape[1], cfg.conv_kernel, cfg.ssm_state, cfg.dt_rank
    uz = _matmul(h, p["in_proj"])
    u, z = uz[..., :cfg.d_inner], uz[..., cfg.d_inner:]
    win = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
    w = p["conv_w"].astype(jnp.float32)
    u = sum(win[:, j:j + T].astype(jnp.float32) * w[j] for j in range(K))
    u = jax.nn.silu(u + p["conv_b"].astype(jnp.float32))
    dbc = _matmul(u.astype(h.dtype), p["x_proj"], jnp.float32)
    delta = jax.nn.softplus(
        _matmul(dbc[..., :R].astype(h.dtype), p["dt_proj"], jnp.float32)
        + p["dt_bias"].astype(jnp.float32))
    return (u, delta, dbc[..., R:R + N], dbc[..., R + N:], z,
            win[:, T:].astype(tail.dtype))


def _rule_constants(p):
    return (-jnp.exp(p["A_log"].astype(jnp.float32)),
            p["D"].astype(jnp.float32))


def mamba_half(cfg: Phi4FlashConfig, x, p, s_pool, c_pool, layer, slots,
               fresh=None, norm_fn=_layernorm, norm_eps=1e-5):
    """The first half of a Mamba layer over the slot pools ``s_pool (L,
    slots, N, d_inner)`` f32 and ``c_pool (L, slots, (conv_kernel - 1) ·
    d_inner)`` (a slot's tail flat on the minor axis), layer ``layer`` of
    them. ``slots (R,)``: a packed decode step, ``x (R, 1, d)``, row ``r`` at
    slot ``slots[r]``, the state updated in place by ``sscan_decode``. ``slots
    ()``: a prefill chunk of one request, ``x (1, C, d)``, by the chunked
    rule; ``fresh`` (a traced bool) starts it from a zero state and a zero
    tail whatever the slot holds. Returns ``(x, s_pool, c_pool, m)``: ``m (B,
    T, d_inner)`` f32 the scan's output before the gate, what the GMUs above
    the LAST such layer read."""
    with jax.named_scope("block/ssm"):
        h = norm_fn(x, p["ln1_g"], p["ln1_b"], norm_eps)
        chunk = slots.ndim == 0
        tail = c_pool[layer, slots[None] if chunk else slots].reshape(
            -1, cfg.conv_kernel - 1, cfg.d_inner)
        if chunk:
            tail = jnp.where(fresh, jnp.zeros((), tail.dtype), tail)
        with jax.named_scope("ssm/in"):
            u, delta, Bm, Cm, z, tail = mamba_inputs(cfg, p, h, tail)
        tail = tail.reshape(tail.shape[0], -1)
        A, D = _rule_constants(p)
        if chunk:
            S = jnp.where(fresh, 0.0, s_pool[layer, slots])
            with jax.named_scope("sscan/chunk"):
                y, S = sscan_chunk_fwd(u[0], delta[0], A, Bm[0], Cm[0], D, S,
                                       cfg.sscan_sub_chunk)
            s_pool = s_pool.at[layer, slots].set(S)
            c_pool = c_pool.at[layer, slots].set(tail[0])
            y = y[None]
        else:
            with jax.named_scope("sscan/decode"):
                y, s_pool = sscan_decode(u[:, 0], delta[:, 0], A, Bm[:, 0],
                                         Cm[:, 0], D, s_pool, layer, slots)
            c_pool = c_pool.at[layer, slots].set(tail)
            y = y[:, None]
        with jax.named_scope("ssm/out"):
            out = _matmul((y * jax.nn.silu(z.astype(jnp.float32)))
                          .astype(h.dtype), p["out_proj"])
        return x + out, s_pool, c_pool, y


def gmu_half(cfg: Phi4FlashConfig, x, p, m, norm_fn=_layernorm,
             norm_eps=1e-5):
    """The first half of a Gated Memory Unit: ``x + (silu(h w1) * m) w2``,
    ``m (B, T, d_inner)`` f32 the scan output of the same positions."""
    with jax.named_scope("block/ssm/gmu"):
        h = norm_fn(x, p["ln1_g"], p["ln1_b"], norm_eps)
        gate = jax.nn.silu(_matmul(h, p["w1"], jnp.float32))
        return x + _matmul((gate * m).astype(h.dtype), p["w2"])


def mlp(cfg: Phi4FlashConfig, p, h):
    """The block's FFN, ``ffn_half``'s ``ffn(h) -> (out, aux)`` once bound to
    a config and a layer (no expert: ``aux`` None)."""
    gu = _matmul(h, p["w_gu"])
    return _matmul(jax.nn.silu(gu[..., :cfg.d_ff]) * gu[..., cfg.d_ff:],
                   p["w_down"]), None


# --------------------------------------------------------------------------
# the dense forward
# --------------------------------------------------------------------------
def phi4_flash_apply(params, tokens, cfg: Phi4FlashConfig,
                     recurrent: bool = True):
    """Logits ``(B, S, V)`` f32 of whole sequences from position 0, every
    layer on every position, every Mamba layer from a zero state: token by
    token (``recurrent``) or by the chunked rule."""
    Bn, S = tokens.shape
    kw = dict(norm_fn=_layernorm, norm_eps=cfg.norm_eps)
    rule = sscan_recurrent if recurrent else \
        (lambda *a: sscan_chunk_fwd(*a, cfg.sscan_sub_chunk))
    x = params["wte"][tokens].astype(cfg.dtype)
    m = shared = None
    for p, kind in zip(params["blocks"], layer_kinds(cfg)):
        if kind == MAMBA:
            h = _layernorm(x, p["ln1_g"], p["ln1_b"], cfg.norm_eps)
            tail = jnp.zeros((Bn, cfg.conv_kernel - 1, cfg.d_inner),
                             cfg.dtype)
            u, delta, Bm, Cm, z, _ = mamba_inputs(cfg, p, h, tail)
            A, D = _rule_constants(p)
            S0 = jnp.zeros((cfg.ssm_state, cfg.d_inner), jnp.float32)
            m = jax.vmap(lambda a, b, c, d: rule(a, b, A, c, d, D, S0)[0])(
                u, delta, Bm, Cm)
            x = x + _matmul((m * jax.nn.silu(z.astype(jnp.float32)))
                            .astype(x.dtype), p["out_proj"])
        elif kind == GMU:
            x = gmu_half(cfg, x, p, m, **kw)
        else:
            attend = dense_attend(cfg.window if kind == WINDOW else None)
            if kind == FULL:
                def attend(q, k, v, _a=attend):
                    return _a(q, k, v)[0], (k, v)
            elif kind == CROSS:
                def attend(q, k, v, _a=attend):
                    return _a(q, *shared)
            x, carry = diff_attn_half(cfg, x, p, cfg.head_dim, None, attend,
                                      **kw)
            if kind == FULL:
                shared = carry
        x, _ = ffn_half(x, p, None, lambda h2, p=p: mlp(cfg, p, h2), **kw)
    return _readout(params, x, _layernorm, cfg.norm_eps)
