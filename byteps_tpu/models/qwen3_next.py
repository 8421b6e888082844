"""Qwen3-Next-80B-A3B (``model_type`` ``qwen3_next``): three Gated-DeltaNet
layers to every gated full-attention layer, and in every layer a
softmax-routed expert feed-forward (top-k of all the experts, weights
renormalised) beside one shared expert behind a sigmoid gate — on the share
of the model one chip of an expert-parallel deployment holds (``experts_held``
of ``n_experts``, ``vocab_size`` rows of the vocabulary).

Pre-norm residual, ``h = x + mixer(norm(x))``, ``out = h + moe(norm(h))``;
every ``norm`` but one is the ZERO-CENTRED RMSNorm ``x̂ · (1 + w)`` in f32
(``models/gpt.py::_rmsnorm_zc``); no biases. Layer ``i`` is full attention
iff ``(i + 1) % full_attention_interval == 0``.

* **Full attention** (:func:`full_attn_half`): ``wq: d → H · 2 · D`` viewed
  ``(T, H, 2D)`` and split into ``q`` and ``gate``; ``wk``, ``wv: d → Hkv ·
  D``; ``q``, ``k`` normed over the head (``q_norm``, ``k_norm``); half-split
  rotary pairs over the FIRST ``rotary_dim`` of a head alone; causal softmax
  attention at ``D^-0.5``, ``H / Hkv`` query heads a k/v head;
  ``x + wo(attn ⊙ sigmoid(gate))``. ``attend(q, k, v) -> (o, carry)`` is
  the caller's, as ``models/gpt.py::attn_half`` has it: the dense forward
  here, the k/v pool's in the serve tier.
* **Gated DeltaNet** (:func:`gdn_inputs` → a form of the rule in
  ``ops/gated_delta.py`` → :func:`gdn_output`): ``in_qkvz: d → q | k | v |
  z`` (``Hk·Dk | Hk·Dk | Hv·Dv | Hv·Dv`` columns, flat; the published layout
  interleaves them by key head, a permutation of columns), ``in_ba: d → b |
  a``. ``concat(q, k, v)`` goes through a causal depthwise convolution of
  ``conv_kernel`` taps, then SiLU; ``beta = sigmoid(b)``, ``g = −exp(A_log) ·
  softplus(a + dt_bias)``; ``q``, ``k`` L2-normalised over the head, each key
  head repeated for its ``Hv / Hk`` value heads, ``q`` scaled by ``Dk^-0.5``;
  the rule; then per head ``w · ô · silu(z)`` (``ô`` the RMS-normalised
  output, ``w`` NOT zero-centred) and ``out_proj``. Between tokens a
  sequence carries ``S (Hv, Dk, Dv)`` f32 and the convolution's last
  ``conv_kernel − 1`` inputs. :func:`gdn_half` is the layer over a slot pool
  (``serve/paged_cache.py``): the packed decode step's rows through
  ``gdn_decode`` in place, a prefill chunk through ``gdn_chunk_fwd``.
* **MoE** (:func:`expert_ffn`): ``parallel/moe.py::moe_ffn_dropless`` with
  ``route="softmax"`` over the experts held here, plus ``sigmoid(x · w_sg) ·
  SwiGLU_shared(x)``.

:func:`qwen3_next_apply` is the dense forward over whole sequences (tests);
the MTP module of the published checkpoint is not modelled.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from byteps_tpu.models.gpt import (
    _mlp,
    _readout,
    _rmsnorm_zc,
    ffn_half,
    rope_rotate,
)
from byteps_tpu.models.mellum2 import dense_attend
from byteps_tpu.ops.gated_delta import (
    gdn_chunk_fwd,
    gdn_decode,
    gdn_recurrent,
)
from byteps_tpu.parallel.moe import moe_dropless_init, moe_ffn_dropless

FULL, LINEAR = "full", "linear"


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936          # rows of the vocabulary held here
    max_seq: int = 262144
    d_model: int = 2048
    n_layers: int = 48
    full_attention_interval: int = 4
    # full attention
    n_heads: int = 16
    n_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_base: float = 1e7
    # Gated DeltaNet
    linear_key_heads: int = 16
    linear_value_heads: int = 32
    linear_key_dim: int = 128
    linear_value_dim: int = 128
    conv_kernel: int = 4
    gdn_sub_chunk: int = 64           # tokens a step of the chunked rule
    # experts
    n_experts: int = 512
    experts_held: int = 512
    first_expert: int = 0
    top_k: int = 10
    d_ff_expert: int = 512
    d_ff_shared: int = 512
    norm_eps: float = 1e-6
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16

    # what the shared block and the paged programs read of a configuration
    # (``GPTConfig``'s names); this model has one answer to each
    pos_embedding = "rope"
    norm = "rmsnorm_zero_centred"
    use_bias = False
    tied_readout = False

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads or \
                self.linear_value_heads % self.linear_key_heads:
            raise ValueError(
                "n_heads must be a multiple of n_kv_heads and "
                "linear_value_heads of linear_key_heads")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(f"rotary_dim {self.rotary_dim} must be even "
                             f"and within head_dim {self.head_dim}")
        if not (0 <= self.first_expert and self.first_expert
                + self.experts_held <= self.n_experts):
            raise ValueError(
                f"experts {self.first_expert}..{self.first_expert} + "
                f"{self.experts_held} are not among the {self.n_experts}")
        if not 1 <= self.top_k <= self.n_experts or self.conv_kernel < 2:
            raise ValueError("top_k must be in 1..n_experts and "
                             "conv_kernel >= 2")

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return tuple(FULL if (i + 1) % self.full_attention_interval == 0
                     else LINEAR for i in range(self.n_layers))

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.layer_types) if k == kind)

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def key_width(self) -> int:
        return self.linear_key_heads * self.linear_key_dim

    @property
    def value_width(self) -> int:
        return self.linear_value_heads * self.linear_value_dim

    @property
    def conv_channels(self) -> int:
        return 2 * self.key_width + self.value_width

    def state_bytes(self, itemsize: int = 2) -> int:
        """What one request holds of one DeltaNet layer: the f32 state and
        the convolution's tail in ``dtype`` (``itemsize`` bytes)."""
        return (self.linear_value_heads * self.linear_key_dim
                * self.linear_value_dim * 4
                + (self.conv_kernel - 1) * self.conv_channels * itemsize)

    @classmethod
    def tiny(cls, **kw) -> "Qwen3NextConfig":
        """Unit-test size: two periods of both layer kinds, a rotation over
        a quarter of the head, two value heads a key head, a share of the
        experts held, sub-chunks shorter than a prefill chunk."""
        base = dict(vocab_size=128, max_seq=64, d_model=64, n_layers=8,
                    n_heads=4, n_kv_heads=2, head_dim=32, rope_base=10000.0,
                    linear_key_heads=2, linear_value_heads=4,
                    linear_key_dim=16, linear_value_dim=16, gdn_sub_chunk=4,
                    n_experts=8, experts_held=8, top_k=3, d_ff_expert=32,
                    d_ff_shared=32, dtype=jnp.float32)
        base.update(kw)
        return cls(**base)


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------
def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def qwen3_next_block_init(rng, cfg: Qwen3NextConfig, kind: str
                          ) -> Dict[str, Any]:
    """One layer's leaves in ``cfg.dtype`` (``A_log`` and ``dt_bias`` f32).
    Zero-centred norm weights are drawn around 0, the DeltaNet output
    norm's around 1; ``A_log = log U(0, 16)`` as published; ``dt_bias`` the
    inverse softplus of a step log-uniform in [1e-3, 1e-1] (Mamba-2's
    draw: the published ones(·) under random projections forgets the state
    within a token, which would leave nothing of it to compare)."""
    d, dt, std = cfg.d_model, cfg.dtype, cfg.init_std
    k = jax.random.split(rng, 12)
    moe = moe_dropless_init(k[0], d, cfg.d_ff_expert, cfg.n_experts,
                            cfg.experts_held, std=std)
    p = {
        "ln1_g": _normal(k[1], (d,), std, dt),
        "ln2_g": _normal(k[2], (d,), std, dt),
        "moe": {n: v.astype(dt) for n, v in moe.items()
                if n != "router_bias"},
        "shared": {"w1": _normal(k[3], (d, cfg.d_ff_shared), std, dt),
                   "w3": _normal(k[4], (d, cfg.d_ff_shared), std, dt),
                   "w2": _normal(k[5], (cfg.d_ff_shared, d), std, dt)},
        "shared_gate": _normal(k[6], (d, 1), std, dt),
    }
    if kind == FULL:
        H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        kk = jax.random.split(k[7], 6)
        p.update(
            wq=_normal(kk[0], (d, H * 2 * D), std, dt),
            wk=_normal(kk[1], (d, Hkv * D), std, dt),
            wv=_normal(kk[2], (d, Hkv * D), std, dt),
            wo=_normal(kk[3], (H * D, d), std, dt),
            q_norm=_normal(kk[4], (D,), std, dt),
            k_norm=_normal(kk[5], (D,), std, dt))
        return p
    Hv = cfg.linear_value_heads
    kk = jax.random.split(k[8], 7)
    step = jnp.exp(jax.random.uniform(kk[5], (Hv,), jnp.float32,
                                      math.log(1e-3), math.log(1e-1)))
    p.update(
        in_qkvz=_normal(kk[0], (d, cfg.conv_channels + cfg.value_width),
                        std, dt),
        in_ba=_normal(kk[1], (d, 2 * Hv), std, dt),
        # tap j multiplies the input conv_kernel - 1 - j tokens back
        conv_w=_normal(kk[2], (cfg.conv_kernel, cfg.conv_channels),
                       cfg.conv_kernel ** -0.5, dt),
        A_log=jnp.log(jax.random.uniform(kk[3], (Hv,), jnp.float32,
                                         1e-3, 16.0)),
        dt_bias=step + jnp.log(-jnp.expm1(-step)),
        gdn_norm=(1.0 + jax.random.normal(kk[4], (cfg.linear_value_dim,),
                                          jnp.float32) * std).astype(dt),
        out_proj=_normal(kk[6], (cfg.value_width, d), std, dt))
    return p


def qwen3_next_head_init(rng, cfg: Qwen3NextConfig) -> Dict[str, Any]:
    """Embedding, final norm (zero-centred) and the untied head."""
    k = jax.random.split(rng, 3)
    d, dt, std = cfg.d_model, cfg.dtype, cfg.init_std
    return {"wte": _normal(k[0], (cfg.vocab_size, d), std, dt),
            "lm_head": _normal(k[1], (d, cfg.vocab_size), std, dt),
            "lnf_g": _normal(k[2], (d,), std, dt)}


def qwen3_next_init(rng, cfg: Qwen3NextConfig) -> Dict[str, Any]:
    keys = jax.random.split(rng, 1 + cfg.n_layers)
    return {**qwen3_next_head_init(keys[0], cfg),
            "blocks": [qwen3_next_block_init(keys[1 + li], cfg, kind)
                       for li, kind in enumerate(cfg.layer_types)]}


def param_count(cfg: Qwen3NextConfig) -> int:
    shapes = jax.eval_shape(lambda: qwen3_next_init(jax.random.PRNGKey(0),
                                                    cfg))
    return sum(math.prod(a.shape) for a in jax.tree_util.tree_leaves(shapes))


# --------------------------------------------------------------------------
# full attention
# --------------------------------------------------------------------------
def _matmul(x, w):
    return jnp.einsum("...d,df->...f", x, w.astype(x.dtype))


def rotate_partial(x, pos, cfg: Qwen3NextConfig):
    """Half-split rotation of the first ``rotary_dim`` of each head; the
    rest passes through."""
    r = cfg.rotary_dim
    if r == x.shape[-1]:
        return rope_rotate(x, pos, cfg.rope_base)
    return jnp.concatenate(
        [rope_rotate(x[..., :r], pos, cfg.rope_base), x[..., r:]], axis=-1)


def full_attn_half(cfg: Qwen3NextConfig, x, p, head_dim, positions, attend,
                   tp_axis=None, rope_base=0.0, norm_fn=_rmsnorm_zc,
                   norm_eps=1e-6, use_bias=False, delta=None):
    """The first half of a full-attention block, ``x + wo(attend(q, k, v) ⊙
    sigmoid(gate))``: ``models/gpt.py::attn_half``'s signature and
    ``attend`` contract, with this model's q/k norms, partial rotation and
    output gate (``tp_axis``, ``rope_base``, ``use_bias`` and ``delta`` are
    the signature's: one answer here). Returns ``(x, carry)``."""
    del tp_axis, rope_base, use_bias, delta
    B, T = x.shape[:2]
    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, head_dim
    with jax.named_scope("block/attn"):
        h = norm_fn(x, p["ln1_g"], None, norm_eps)
        qg = _matmul(h, p["wq"]).reshape(B, T, H, 2 * D)
        q, gate = qg[..., :D], qg[..., D:]
        k = _matmul(h, p["wk"]).reshape(B, T, Hkv, D)
        v = _matmul(h, p["wv"]).reshape(B, T, Hkv, D)
        q = norm_fn(q, p["q_norm"], None, norm_eps)
        k = norm_fn(k, p["k_norm"], None, norm_eps)
        pos = positions()
        q, k = rotate_partial(q, pos, cfg), rotate_partial(k, pos, cfg)
        o, carry = attend(q, k, v)
        # (the paged kernel returns a decode step's rows without the T axis)
        o = o.reshape(q.shape)
        o = o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(o.dtype)
        return x + _matmul(o.reshape(B, T, H * D), p["wo"]), carry


# --------------------------------------------------------------------------
# Gated DeltaNet
# --------------------------------------------------------------------------
def gdn_inputs(cfg: Qwen3NextConfig, p, h, tail):
    """Everything the rule takes, from the normed input ``h (B, T, d)`` and
    the convolution's tail ``(B, conv_kernel - 1, channels)`` (the inputs
    before this call's first token; zeros at a sequence's start). Returns
    ``(q, k (B, T, Hv, Dk), v (B, T, Hv, Dv), g, beta (B, T, Hv))`` in f32,
    ``z (B, T, Hv, Dv)`` and the tail after the last token."""
    B, T = h.shape[:2]
    Hk, Hv = cfg.linear_key_heads, cfg.linear_value_heads
    Dk, Dv, K = cfg.linear_key_dim, cfg.linear_value_dim, cfg.conv_kernel
    qkvz = _matmul(h, p["in_qkvz"])
    ba = _matmul(h, p["in_ba"]).astype(jnp.float32)
    mixed, z = qkvz[..., :cfg.conv_channels], qkvz[..., cfg.conv_channels:]
    win = jnp.concatenate([tail.astype(mixed.dtype), mixed], axis=1)
    w = p["conv_w"].astype(jnp.float32)
    conv = sum(win[:, j:j + T].astype(jnp.float32) * w[j] for j in range(K))
    conv = jax.nn.silu(conv)
    q = conv[..., :cfg.key_width].reshape(B, T, Hk, Dk)
    k = conv[..., cfg.key_width:2 * cfg.key_width].reshape(B, T, Hk, Dk)
    v = conv[..., 2 * cfg.key_width:].reshape(B, T, Hv, Dv)

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    rep = Hv // Hk
    q = jnp.repeat(unit(q) * Dk ** -0.5, rep, axis=2)
    k = jnp.repeat(unit(k), rep, axis=2)
    beta = jax.nn.sigmoid(ba[..., :Hv])
    g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        ba[..., Hv:] + p["dt_bias"].astype(jnp.float32))
    return (q, k, v, g, beta, z.reshape(B, T, Hv, Dv),
            win[:, T:].astype(tail.dtype))


def gdn_output(cfg: Qwen3NextConfig, p, o, z, dtype):
    """``out_proj(w · ô · silu(z))``: ``o (B, T, Hv, Dv)`` f32, RMS-normed
    per head, gated by ``z``."""
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.norm_eps)
    y = p["gdn_norm"].astype(jnp.float32) * o \
        * jax.nn.silu(z.astype(jnp.float32))
    B, T = y.shape[:2]
    return _matmul(y.reshape(B, T, -1).astype(dtype), p["out_proj"])


def gdn_half(cfg: Qwen3NextConfig, x, p, s_pool, c_pool, layer, slots,
             fresh=None, norm_fn=_rmsnorm_zc, norm_eps=1e-6):
    """The first half of a DeltaNet block over the slot pools ``s_pool (L,
    N, Hv, Dk, Dv)`` f32 and ``c_pool (L, N, (conv_kernel - 1) · channels)``
    (a slot's tail flat on the minor axis: whole tiles, where three rows
    would be padded to a tile's and converted around every access), layer
    ``layer`` of them. ``slots (R,)``: a packed decode step, ``x (R,
    1, d)``, row ``r`` at slot ``slots[r]``, the state updated in place by
    ``gdn_decode``. ``slots ()``: a prefill chunk of one request, ``x (1, C,
    d)``, by the chunked rule; ``fresh`` (a traced bool) starts it from a
    zero state and a zero tail whatever the slot holds. Returns ``(x,
    s_pool, c_pool)``."""
    with jax.named_scope("block/gdn"):
        h = norm_fn(x, p["ln1_g"], None, norm_eps)
        chunk = slots.ndim == 0
        tail = c_pool[layer, slots[None] if chunk else slots].reshape(
            -1, cfg.conv_kernel - 1, cfg.conv_channels)
        if chunk:
            tail = jnp.where(fresh, jnp.zeros((), tail.dtype), tail)
        with jax.named_scope("gdn/in"):
            q, k, v, g, beta, z, tail = gdn_inputs(cfg, p, h, tail)
        tail = tail.reshape(tail.shape[0], -1)
        if chunk:
            S = jnp.where(fresh, 0.0, s_pool[layer, slots])
            with jax.named_scope("gdn/chunk"):
                o, S = gdn_chunk_fwd(q[0], k[0], v[0], g[0], beta[0], S,
                                     cfg.gdn_sub_chunk)
            s_pool = s_pool.at[layer, slots].set(S)
            c_pool = c_pool.at[layer, slots].set(tail[0])
            o = o[None]
        else:
            with jax.named_scope("gdn/decode"):
                o, s_pool = gdn_decode(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                       beta[:, 0], s_pool, layer, slots)
            c_pool = c_pool.at[layer, slots].set(tail)
            o = o[:, None]
        with jax.named_scope("gdn/out"):
            out = gdn_output(cfg, p, o, z, x.dtype)
        return x + out, s_pool, c_pool


# --------------------------------------------------------------------------
# experts
# --------------------------------------------------------------------------
def expert_ffn(cfg: Qwen3NextConfig, p, h):
    """The block's FFN, ``ffn_half``'s ``ffn(h) -> (out, aux)`` once bound
    to a config and a layer: every token routed over all the experts, the
    held ones computed dropless, plus the shared expert behind its sigmoid
    gate. ``aux`` f32 ``(3,)``: pairs computed here, held experts with at
    least one row, heaviest held expert over the mean held expert."""
    with jax.named_scope("block/moe"):
        y, stats, load = moe_ffn_dropless(h, p["moe"], cfg.top_k, 1.0,
                                          cfg.first_expert, route="softmax")
        gate = jax.nn.sigmoid(_matmul(h, p["shared_gate"]).astype(jnp.float32))
        y = y + (_mlp(h, p["shared"], None, use_bias=False)
                 * gate.astype(h.dtype))
    held = load[cfg.first_expert:cfg.first_expert + cfg.experts_held]
    return y, jnp.stack([stats[0], jnp.sum(held > 0).astype(jnp.float32),
                         stats[2]])


# --------------------------------------------------------------------------
# the dense forward
# --------------------------------------------------------------------------
def qwen3_next_apply(params, tokens, cfg: Qwen3NextConfig,
                     recurrent: bool = True):
    """Logits ``(B, S, V)`` f32 of whole sequences from position 0, every
    DeltaNet layer from a zero state: token by token (``recurrent``) or by
    the chunked rule."""
    B, S = tokens.shape
    pos = jnp.arange(S)
    kw = dict(norm_fn=_rmsnorm_zc, norm_eps=cfg.norm_eps)
    rule = gdn_recurrent if recurrent else \
        (lambda *a: gdn_chunk_fwd(*a, cfg.gdn_sub_chunk))
    x = params["wte"][tokens].astype(cfg.dtype)
    for p, kind in zip(params["blocks"], cfg.layer_types):
        if kind == FULL:
            x, _ = full_attn_half(cfg, x, p, cfg.head_dim, lambda: pos,
                                  dense_attend(None), **kw)
        else:
            h = _rmsnorm_zc(x, p["ln1_g"], None, cfg.norm_eps)
            tail = jnp.zeros((B, cfg.conv_kernel - 1, cfg.conv_channels),
                             cfg.dtype)
            q, k, v, g, beta, z, _ = gdn_inputs(cfg, p, h, tail)
            S0 = jnp.zeros((cfg.linear_value_heads, cfg.linear_key_dim,
                            cfg.linear_value_dim), jnp.float32)
            o = jax.vmap(lambda *a: rule(*a, S0)[0])(q, k, v, g, beta)
            x = x + gdn_output(cfg, p, o, z, x.dtype)
        x, _ = ffn_half(x, p, None, lambda h, p=p: expert_ffn(cfg, p, h),
                        use_bias=False, **kw)
    return _readout(params, x, _rmsnorm_zc, cfg.norm_eps)
