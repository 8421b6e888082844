"""Falcon-H1 (``model_type`` ``falcon_h1``): in EVERY layer a Mamba-2 (SSD)
mixer beside grouped-query attention, both on one normed input and summed
into one residual, then a SwiGLU MLP — a dense model whose every branch is
scaled by a published muP multiplier.

With ``rms(v; w) = v / sqrt(mean(v^2) + eps) * w`` (``models/gpt.py::
_rmsnorm``; no bias anywhere but the convolution's)::

    x0 = wte[tokens] * embedding_multiplier
    h  = rms(x; ln1_g);   x <- x + (ssm(h) + attn(h))
    h2 = rms(x; ln2_g);   x <- x + mlp(h2)
    logits = (rms(x; lnf_g) lm_head) * lm_head_multiplier

* **Attention** (:func:`attn_branch`): ``a = h * attention_in_multiplier``;
  ``q = a wq`` (``H`` heads of ``D``), ``k = (a wk) * key_multiplier``, ``v =
  a wv`` (``Hkv`` heads); half-split rotary pairs over the whole head; causal
  softmax attention at ``D^-0.5``, ``H / Hkv`` query heads a k/v head; ``(o
  wo) * attention_out_multiplier``. ``attend(q, k, v) -> (o, carry)`` is the
  caller's, as ``models/gpt.py::attn_half`` has it.
* **SSM** (:func:`ssm_inputs` → a form of the rule in ``ops/ssd.py`` →
  :func:`ssm_output`): ``u = (h * ssm_in_multiplier) in_proj``, its columns
  ``z | x | B | C | dt`` (``d_ssm | d_ssm | G·N | G·N | Hs``), segment ``i``
  times ``ssm_multipliers[i]``; ``x | B | C`` through a causal depthwise
  convolution of ``conv_kernel`` taps with bias, then SiLU; ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the rule, heads ``16 g .. 16
  g + 15`` on group ``g``'s ``B`` and ``C``; ``y <- rms_grouped(y ⊙ silu(z);
  ssm_norm)``, the mean inside each group's channels; ``(y out_proj) *
  ssm_out_multiplier``. Between tokens a sequence carries ``S (Hs, N, P)`` f32
  and the convolution's last ``conv_kernel - 1`` inputs.
* **MLP** (:func:`mlp`): ``(silu((h2 w1) * mlp_multipliers[0]) ⊙ (h2 w3)) w2
  * mlp_multipliers[1]``.

:func:`mixer_half` is the layer's first half over the serve tier's two pools
(``serve/paged_cache.py``: the k/v pool through ``attend``, the slot pool
through ``ops/ssd.py``); :func:`falcon_h1_apply` is the dense forward over
whole sequences (tests). Every multiplier is applied where the equations put
it: none is folded into a weight.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from byteps_tpu.models.gpt import _readout, _rmsnorm, ffn_half, rope_rotate
from byteps_tpu.models.mellum2 import dense_attend
from byteps_tpu.ops.ssd import ssd_chunk_fwd, ssd_decode, ssd_recurrent


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    vocab_size: int = 261120
    max_seq: int = 262144
    d_model: int = 5120
    n_layers: int = 72
    # attention
    n_heads: int = 20
    n_kv_heads: int = 4
    head_dim: int = 128
    rope_base: float = 1e11
    # the Mamba-2 mixer
    ssm_heads: int = 32
    ssm_head_dim: int = 128
    ssm_groups: int = 2
    ssm_state: int = 256
    conv_kernel: int = 4
    ssd_sub_chunk: int = 128          # tokens a step of the chunked rule
    d_ff: int = 21504
    norm_eps: float = 1e-5
    # the published muP multipliers
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_multipliers: Tuple[float, ...] = (        # z, x, B, C, dt
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
        0.3535533905932738)
    ssm_out_multiplier: float = 0.08838834764831845
    mlp_multipliers: Tuple[float, ...] = (        # the gate, the output
        0.1767766952966369, 0.011160714285714284)
    dtype: Any = jnp.bfloat16

    # what the shared block and the paged programs read of a configuration
    # (``GPTConfig``'s names); this model has one answer to each
    pos_embedding = "rope"
    norm = "rmsnorm"
    use_bias = False
    tied_readout = False

    def __post_init__(self):
        # a configuration file gives lists: tuples hash (the programs'
        # factories are cached by configuration)
        for name in ("ssm_multipliers", "mlp_multipliers"):
            object.__setattr__(self, name, tuple(
                float(v) for v in getattr(self, name)))
        if self.n_heads % self.n_kv_heads or \
                self.ssm_heads % self.ssm_groups or self.head_dim % 2:
            raise ValueError(
                "n_heads must be a multiple of n_kv_heads, ssm_heads of "
                "ssm_groups, and head_dim even")
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2 \
                or self.conv_kernel < 2:
            raise ValueError("five ssm_multipliers (z, x, B, C, dt), two "
                             "mlp_multipliers, conv_kernel >= 2")

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads

    @property
    def d_ssm(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def bc_width(self) -> int:
        return self.ssm_groups * self.ssm_state

    @property
    def conv_channels(self) -> int:
        return self.d_ssm + 2 * self.bc_width

    @property
    def in_proj_segments(self) -> Tuple[int, ...]:
        """Columns of ``in_proj``: z, x, B, C, dt."""
        return (self.d_ssm, self.d_ssm, self.bc_width, self.bc_width,
                self.ssm_heads)

    def state_bytes(self, itemsize: int = 2) -> int:
        """What one request holds of one layer's mixer: the f32 state and
        the convolution's tail in ``dtype`` (``itemsize`` bytes)."""
        return (self.ssm_heads * self.ssm_state * self.ssm_head_dim * 4
                + (self.conv_kernel - 1) * self.conv_channels * itemsize)

    @classmethod
    def tiny(cls, **kw) -> "FalconH1Config":
        """Unit-test size: two layers, two query heads a k/v head, two SSM
        heads a group, sub-chunks shorter than a prefill chunk; the
        multipliers as published."""
        base = dict(vocab_size=256, max_seq=64, d_model=64, n_layers=2,
                    n_heads=4, n_kv_heads=2, head_dim=16, rope_base=10000.0,
                    ssm_heads=4, ssm_head_dim=16, ssm_groups=2, ssm_state=32,
                    ssd_sub_chunk=4, d_ff=128, dtype=jnp.float32)
        base.update(kw)
        return cls(**base)


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------
def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def falcon_h1_block_init(rng, cfg: FalconH1Config) -> Dict[str, Any]:
    """One layer's leaves in ``cfg.dtype`` (``A_log``, ``dt_bias`` and ``D``
    f32). A matrix is drawn at ``fan_in^-0.5`` OVER the multiplier its product
    meets (``wk`` over ``key_multiplier``, ``wo`` over
    ``attention_out_multiplier``, each segment of ``in_proj`` over
    ``ssm_in_multiplier`` times its own, ...), so that at the published
    multipliers every product is of unit scale: keys that tell positions
    apart, and three branches that each add a comparable part to the
    residual. (At one std everywhere ``key_multiplier`` 0.011 makes attention
    uniform and a left-out branch passes any tolerance.) Norm weights around
    1; ``A_log = log U(1, 16)``, ``dt_bias`` the inverse softplus of a step
    log-uniform in [1e-3, 1e-1] and ``D = 1``: Mamba-2's own draw."""
    d, dt, ff = cfg.d_model, cfg.dtype, cfg.d_ff
    H, Hkv, D, Hs = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.ssm_heads
    k = jax.random.split(rng, 16)
    step = jnp.exp(jax.random.uniform(k[0], (Hs,), jnp.float32,
                                      math.log(1e-3), math.log(1e-1)))
    in_proj = jnp.concatenate(
        [_normal(kk, (d, w), d ** -0.5 / (cfg.ssm_in_multiplier * m), dt)
         for kk, w, m in zip(jax.random.split(k[1], 5), cfg.in_proj_segments,
                             cfg.ssm_multipliers)], axis=1)
    return {
        "ln1_g": (1.0 + jax.random.normal(k[2], (d,)) * 0.1).astype(dt),
        "ln2_g": (1.0 + jax.random.normal(k[3], (d,)) * 0.1).astype(dt),
        "wq": _normal(k[4], (d, H * D),
                      d ** -0.5 / cfg.attention_in_multiplier, dt),
        "wk": _normal(k[5], (d, Hkv * D), d ** -0.5 / (
            cfg.attention_in_multiplier * cfg.key_multiplier), dt),
        "wv": _normal(k[6], (d, Hkv * D),
                      d ** -0.5 / cfg.attention_in_multiplier, dt),
        "wo": _normal(k[7], (H * D, d),
                      (H * D) ** -0.5 / cfg.attention_out_multiplier, dt),
        "in_proj": in_proj,
        # tap j multiplies the input conv_kernel - 1 - j tokens back
        "conv_w": _normal(k[8], (cfg.conv_kernel, cfg.conv_channels),
                          cfg.conv_kernel ** -0.5, dt),
        "conv_b": _normal(k[9], (cfg.conv_channels,), 0.1, dt),
        "A_log": jnp.log(jax.random.uniform(k[10], (Hs,), jnp.float32,
                                            1.0, 16.0)),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "D": jnp.ones((Hs,), jnp.float32),
        "ssm_norm": (1.0 + jax.random.normal(k[11], (cfg.d_ssm,))
                     * 0.1).astype(dt),
        "out_proj": _normal(k[12], (cfg.d_ssm, d),
                            cfg.d_ssm ** -0.5 / cfg.ssm_out_multiplier, dt),
        "w1": _normal(k[13], (d, ff), d ** -0.5 / cfg.mlp_multipliers[0], dt),
        "w3": _normal(k[14], (d, ff), d ** -0.5, dt),
        "w2": _normal(k[15], (ff, d), ff ** -0.5 / cfg.mlp_multipliers[1],
                      dt),
    }


def falcon_h1_head_init(rng, cfg: FalconH1Config) -> Dict[str, Any]:
    """Embedding (over ``embedding_multiplier``: ``x0`` of unit scale), final
    norm and the untied head (over ``lm_head_multiplier``: logits of unit
    scale)."""
    k = jax.random.split(rng, 3)
    d, dt = cfg.d_model, cfg.dtype
    return {"wte": _normal(k[0], (cfg.vocab_size, d),
                           1.0 / cfg.embedding_multiplier, dt),
            "lm_head": _normal(k[1], (d, cfg.vocab_size),
                               d ** -0.5 / cfg.lm_head_multiplier, dt),
            "lnf_g": (1.0 + jax.random.normal(k[2], (d,)) * 0.1).astype(dt)}


def falcon_h1_init(rng, cfg: FalconH1Config) -> Dict[str, Any]:
    keys = jax.random.split(rng, 1 + cfg.n_layers)
    return {**falcon_h1_head_init(keys[0], cfg),
            "blocks": [falcon_h1_block_init(keys[1 + li], cfg)
                       for li in range(cfg.n_layers)]}


def param_count(cfg: FalconH1Config) -> int:
    shapes = jax.eval_shape(lambda: falcon_h1_init(jax.random.PRNGKey(0),
                                                   cfg))
    return sum(math.prod(a.shape) for a in jax.tree_util.tree_leaves(shapes))


# --------------------------------------------------------------------------
# the three branches
# --------------------------------------------------------------------------
def _matmul(x, w):
    return jnp.einsum("...d,df->...f", x, w.astype(x.dtype))


def _times(x, m: float):
    """``x * m`` in ``x``'s dtype; a multiplier of 1 traces nothing."""
    return x if m == 1.0 else x * jnp.asarray(m, x.dtype)


def attn_branch(cfg: FalconH1Config, p, h, positions, attend, rope=None):
    """``attn(h)`` of the module docstring, ``h (B, T, d)`` the normed input.
    Returns ``(out (B, T, d), carry)``."""
    B, T = h.shape[:2]
    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    with jax.named_scope("block/attn"):
        a = _times(h, cfg.attention_in_multiplier)
        q = _matmul(a, p["wq"]).reshape(B, T, H, D)
        k = _times(_matmul(a, p["wk"]), cfg.key_multiplier) \
            .reshape(B, T, Hkv, D)
        v = _matmul(a, p["wv"]).reshape(B, T, Hkv, D)
        pos = positions()
        base = cfg.rope_base if rope is None else rope
        q, k = rope_rotate(q, pos, base), rope_rotate(k, pos, base)
        o, carry = attend(q, k, v)
        # (the paged kernel returns a decode step's rows without the T axis)
        out = _matmul(o.reshape(B, T, H * D), p["wo"])
        return _times(out, cfg.attention_out_multiplier), carry


def ssm_inputs(cfg: FalconH1Config, p, h, tail):
    """Everything the rule takes, from the normed input ``h (B, T, d)`` and
    the convolution's tail ``(B, conv_kernel - 1, channels)`` (the inputs
    before this call's first token; zeros at a sequence's start). Returns
    ``(x (B, T, Hs, P), dt (B, T, Hs), B, C (B, T, G, N))`` in f32, ``z (B, T,
    d_ssm)`` and the tail after the last token."""
    Bn, T = h.shape[:2]
    K = cfg.conv_kernel
    u = _matmul(_times(h, cfg.ssm_in_multiplier), p["in_proj"])
    ends = list(itertools.accumulate(cfg.in_proj_segments))[:-1]
    z, x, Bm, Cm, dt = (_times(a, m) for a, m in zip(
        jnp.split(u, ends, axis=-1), cfg.ssm_multipliers))
    mixed = jnp.concatenate([x, Bm, Cm], axis=-1)
    win = jnp.concatenate([tail.astype(mixed.dtype), mixed], axis=1)
    w = p["conv_w"].astype(jnp.float32)
    conv = sum(win[:, j:j + T].astype(jnp.float32) * w[j] for j in range(K))
    conv = jax.nn.silu(conv + p["conv_b"].astype(jnp.float32))
    x = conv[..., :cfg.d_ssm].reshape(Bn, T, cfg.ssm_heads, cfg.ssm_head_dim)
    Bm = conv[..., cfg.d_ssm:cfg.d_ssm + cfg.bc_width].reshape(
        Bn, T, cfg.ssm_groups, cfg.ssm_state)
    Cm = conv[..., cfg.d_ssm + cfg.bc_width:].reshape(
        Bn, T, cfg.ssm_groups, cfg.ssm_state)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    return x, dt, Bm, Cm, z, win[:, T:].astype(tail.dtype)


def ssm_output(cfg: FalconH1Config, p, y, z, dtype):
    """``(rms_grouped(y ⊙ silu(z); ssm_norm) out_proj) *
    ssm_out_multiplier``: ``y (B, T, Hs, P)`` f32, gated by ``z (B, T,
    d_ssm)``, the mean of squares taken inside each group's channels."""
    Bn, T = y.shape[:2]
    y = y.reshape(Bn, T, cfg.d_ssm) * jax.nn.silu(z.astype(jnp.float32))
    g = y.reshape(Bn, T, cfg.ssm_groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + cfg.norm_eps)
    y = g.reshape(Bn, T, cfg.d_ssm) * p["ssm_norm"].astype(jnp.float32)
    return _times(_matmul(y.astype(dtype), p["out_proj"]),
                  cfg.ssm_out_multiplier)


def _rule_constants(p):
    return (-jnp.exp(p["A_log"].astype(jnp.float32)),
            p["D"].astype(jnp.float32))


def ssm_branch(cfg: FalconH1Config, p, h, s_pool, c_pool, layer, slots,
               fresh=None):
    """``ssm(h)`` over the slot pools ``s_pool (L, slots, Hs, N, P)`` f32 and
    ``c_pool (L, slots, (conv_kernel - 1) · channels)`` (a slot's tail flat
    on the minor axis, as ``models/qwen3_next.py::gdn_half`` keeps it), layer
    ``layer`` of them. ``slots (R,)``: a packed decode step, ``h (R, 1, d)``,
    row ``r`` at slot ``slots[r]``, the state updated in place by
    ``ssd_decode``. ``slots ()``: a prefill chunk of one request, ``h (1, C,
    d)``, by the chunked rule; ``fresh`` (a traced bool) starts it from a
    zero state and a zero tail whatever the slot holds. Returns ``(out,
    s_pool, c_pool)``."""
    with jax.named_scope("block/ssm"):
        chunk = slots.ndim == 0
        tail = c_pool[layer, slots[None] if chunk else slots].reshape(
            -1, cfg.conv_kernel - 1, cfg.conv_channels)
        if chunk:
            tail = jnp.where(fresh, jnp.zeros((), tail.dtype), tail)
        with jax.named_scope("ssm/in"):
            x, dt, Bm, Cm, z, tail = ssm_inputs(cfg, p, h, tail)
        tail = tail.reshape(tail.shape[0], -1)
        A, D = _rule_constants(p)
        if chunk:
            S = jnp.where(fresh, 0.0, s_pool[layer, slots])
            with jax.named_scope("ssd/chunk"):
                y, S = ssd_chunk_fwd(x[0], dt[0], A, Bm[0], Cm[0], D, S,
                                     cfg.ssd_sub_chunk)
            s_pool = s_pool.at[layer, slots].set(S)
            c_pool = c_pool.at[layer, slots].set(tail[0])
            y = y[None]
        else:
            with jax.named_scope("ssd/decode"):
                y, s_pool = ssd_decode(x[:, 0], dt[:, 0], A, Bm[:, 0],
                                       Cm[:, 0], D, s_pool, layer, slots)
            c_pool = c_pool.at[layer, slots].set(tail)
            y = y[:, None]
        with jax.named_scope("ssm/out"):
            out = ssm_output(cfg, p, y, z, h.dtype)
        return out, s_pool, c_pool


def mixer_half(cfg: FalconH1Config, x, p, head_dim, positions, attend, rope,
               s_pool, c_pool, layer, slots, fresh=None, norm_fn=_rmsnorm,
               norm_eps=1e-5):
    """The first half of a layer over the serve tier's two pools, ``x +
    (ssm(h) + attn(h))`` on one ``h = norm(x)``: ``attend`` is the k/v
    pool's (``models/gpt.py::attn_half``'s contract), the rest
    :func:`ssm_branch`'s. Returns ``(x, carry, s_pool, c_pool)``."""
    del head_dim
    h = norm_fn(x, p["ln1_g"], None, norm_eps)
    y_ssm, s_pool, c_pool = ssm_branch(cfg, p, h, s_pool, c_pool, layer,
                                       slots, fresh)
    y_attn, carry = attn_branch(cfg, p, h, positions, attend, rope)
    return x + (y_ssm + y_attn), carry, s_pool, c_pool


def mlp(cfg: FalconH1Config, p, h):
    """The block's FFN, ``ffn_half``'s ``ffn(h) -> (out, aux)`` once bound to
    a config and a layer (no expert: ``aux`` None)."""
    gate = jax.nn.silu(_times(_matmul(h, p["w1"]), cfg.mlp_multipliers[0]))
    return _times(_matmul(gate * _matmul(h, p["w3"]), p["w2"]),
                  cfg.mlp_multipliers[1]), None


# --------------------------------------------------------------------------
# the dense forward
# --------------------------------------------------------------------------
def falcon_h1_apply(params, tokens, cfg: FalconH1Config,
                    recurrent: bool = True, parts: bool = False):
    """Logits ``(B, S, V)`` f32 of whole sequences from position 0, every
    mixer from a zero state: token by token (``recurrent``) or by the chunked
    rule. ``parts``: also each layer's branches' shares of the residual,
    ``rms(branch) / rms(x)`` for ``(ssm, attn, mlp)`` — what the seeded
    weights make of the multipliers."""
    Bn, S = tokens.shape
    pos = jnp.arange(S)
    kw = dict(norm_fn=_rmsnorm, norm_eps=cfg.norm_eps)
    rule = ssd_recurrent if recurrent else \
        (lambda *a: ssd_chunk_fwd(*a, cfg.ssd_sub_chunk))

    def rms(a):
        return jnp.sqrt(jnp.mean(jnp.square(a.astype(jnp.float32))))

    shares = []
    x = _times(params["wte"][tokens].astype(cfg.dtype),
               cfg.embedding_multiplier)
    for p in params["blocks"]:
        h = _rmsnorm(x, p["ln1_g"], None, cfg.norm_eps)
        tail = jnp.zeros((Bn, cfg.conv_kernel - 1, cfg.conv_channels),
                         cfg.dtype)
        xs, dt, Bm, Cm, z, _ = ssm_inputs(cfg, p, h, tail)
        A, D = _rule_constants(p)
        S0 = jnp.zeros((cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim),
                       jnp.float32)
        y = jax.vmap(lambda a, b, c, d: rule(a, b, A, c, d, D, S0)[0])(
            xs, dt, Bm, Cm)
        y_ssm = ssm_output(cfg, p, y, z, x.dtype)
        y_attn, _ = attn_branch(cfg, p, h, lambda: pos, dense_attend(None))
        mid = x + (y_ssm + y_attn)
        out, _ = ffn_half(mid, p, None, lambda h2, p=p: mlp(cfg, p, h2),
                          use_bias=False, **kw)
        if parts:
            shares.append(jnp.stack([rms(y_ssm), rms(y_attn), rms(out - mid)])
                          / rms(x))
        x = out
    logits = _readout(params, x, _rmsnorm, cfg.norm_eps) \
        * cfg.lm_head_multiplier
    return (logits, jnp.stack(shares)) if parts else logits
