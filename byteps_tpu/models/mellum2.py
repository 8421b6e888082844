"""Mellum2-12B-A2.5B (``model_type`` ``mellum``): grouped-query attention in
two kinds of layer — *sliding* layers that see the last ``window`` keys and
rotate by plain RoPE, *full* layers that see every key and rotate by YaRN —
and in every layer a softmax-routed expert feed-forward (top-k of all the
experts, weights renormalised, no shared expert), all experts held here.

With ``x`` a block's input at position ``t``, RMSNorm eps ``norm_eps``, no
biases, no q/k norm:

* ``q, k, v = RMSNorm(x) (wq, wk, wv)`` in ``n_heads`` / ``n_kv_heads``
  heads of ``head_dim`` (which is NOT ``d_model / n_heads``: 2304 wide, 32
  heads of 128); half-split rotary pairs over all of a head with
  :func:`rope_freqs` of the layer's kind; query head ``j`` reads kv head
  ``j // (n_heads / n_kv_heads)``; key ``s`` is visible iff ``s <= t`` and,
  on a sliding layer, ``t - s < window``; ``x + concat(o) wo``.
* ``p = softmax(RMSNorm(x) wg)`` in f32, the ``top_k`` largest renormalised
  to sum 1, ``x + Σ_j w_j · SwiGLU_{e_j}(RMSNorm(x))``
  (``parallel/moe.py::moe_ffn_dropless`` with ``route="softmax"``).

The block is ``models/gpt.py``'s (``attn_half`` / ``ffn_half``) around this
file's ``attend`` and FFN: :func:`mellum2_apply` here is the dense forward
over whole sequences, the serve tier runs the same halves over k/v pages of
two kinds (``serve/paged_cache.py``, ``serve/families.py``). Weights are
leaves of ``cfg.dtype`` (published in bf16).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from byteps_tpu.models.gpt import (
    RopeFreqs,
    _readout,
    _rmsnorm,
    attn_half,
    ffn_half,
)
from byteps_tpu.parallel.moe import moe_dropless_init, moe_ffn_dropless

_NEG = -1e30
FULL, SLIDING = "full", "sliding"


@dataclasses.dataclass(frozen=True)
class Mellum2Config:
    vocab_size: int = 98304
    max_seq: int = 131072
    d_model: int = 2304
    n_layers: int = 28
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    #: per layer ``"full"`` or ``"sliding"``; None is the published period:
    #: sliding, sliding, sliding, full over and over
    layer_types: Optional[Tuple[str, ...]] = None
    window: int = 1024                # keys a sliding query sees, itself one
    rope_base: float = 500000.0
    # YaRN, on the full layers alone
    yarn_factor: float = 16.0
    yarn_original_max_seq: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.2772588722239782
    n_experts: int = 64
    top_k: int = 8
    d_ff_expert: int = 896
    norm_eps: float = 1e-6
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16

    # what the shared block and the paged programs read of a configuration
    # (``GPTConfig``'s names); this model has one answer to each
    pos_embedding = "rope"
    norm = "rmsnorm"
    use_bias = False
    tied_readout = False

    def __post_init__(self):
        if self.layer_types is None:
            period = (SLIDING, SLIDING, SLIDING, FULL)
            object.__setattr__(self, "layer_types", tuple(
                period[i % 4] for i in range(self.n_layers)))
        else:
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if len(self.layer_types) != self.n_layers or any(
                k not in (FULL, SLIDING) for k in self.layer_types):
            raise ValueError(
                f"layer_types must name {self.n_layers} layers, each "
                f"'{FULL}' or '{SLIDING}'; got {self.layer_types}")
        if self.n_heads % self.n_kv_heads != 0:
            raise ValueError(
                f"n_heads ({self.n_heads}) must be a multiple of "
                f"n_kv_heads ({self.n_kv_heads})")
        if self.window < 1 or not 1 <= self.top_k <= self.n_experts:
            raise ValueError("window must be >= 1 and top_k in "
                             "1..n_experts")

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.layer_types) if k == kind)

    @classmethod
    def tiny(cls, **kw) -> "Mellum2Config":
        """Unit-test size: both layer kinds, a window shorter than the
        contexts, YaRN's ramp inside the head, every expert live."""
        base = dict(vocab_size=128, max_seq=64, d_model=64, n_layers=4,
                    n_heads=4, n_kv_heads=2, head_dim=32, window=9,
                    rope_base=10000.0, yarn_factor=4.0,
                    yarn_original_max_seq=16, n_experts=8, top_k=2,
                    d_ff_expert=32, dtype=jnp.float32)
        base.update(kw)
        return cls(**base)


def rope_freqs(cfg: Mellum2Config, kind: str) -> RopeFreqs:
    """What a layer of ``kind`` rotates by. Sliding: plain RoPE,
    ``inv_freq[i] = base^(-2i/D)``, factor 1. Full: YaRN — with ``d(r) = D ·
    ln(original / (2π r)) / (2 ln base)`` the pair index that turns ``r``
    times over the original context, ``lo = max(floor(d(beta_fast)), 0)``,
    ``hi = min(ceil(d(beta_slow)), D - 1)`` and ``ramp[i] = clip((i - lo) /
    (hi - lo), 0, 1)``: pairs below ``lo`` keep their frequency, pairs above
    ``hi`` turn ``yarn_factor`` times slower, those between are mixed by
    the ramp; cos and sin carry ``yarn_attention_factor``. Nothing depends
    on the sequence length. Computed in float64, once."""
    D, base = cfg.head_dim, cfg.rope_base
    plain = [base ** (-2.0 * i / D) for i in range(D // 2)]
    if kind == SLIDING:
        return RopeFreqs(tuple(plain), 1.0)

    def turns_at(r):
        return D * math.log(cfg.yarn_original_max_seq / (2 * math.pi * r)) \
            / (2 * math.log(base))

    lo = max(math.floor(turns_at(cfg.yarn_beta_fast)), 0)
    hi = min(math.ceil(turns_at(cfg.yarn_beta_slow)), D - 1)
    if hi == lo:
        hi += 0.001                    # the published code's guard
    inv = []
    for i, f in enumerate(plain):
        ramp = min(max((i - lo) / (hi - lo), 0.0), 1.0)
        inv.append(f / cfg.yarn_factor * ramp + f * (1.0 - ramp))
    return RopeFreqs(tuple(inv), float(cfg.yarn_attention_factor))


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------
def mellum2_block_init(rng, cfg: Mellum2Config) -> Dict[str, Any]:
    """One layer's leaves, in ``cfg.dtype``: the attention half under
    ``models/gpt.py``'s names (``_project`` reads them), the experts under
    ``moe`` with no ``router_bias`` leaf (softmax routing has none)."""
    d, dt, std = cfg.d_model, cfg.dtype, cfg.init_std
    hd, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    k = jax.random.split(rng, 5)

    def w(key, shape):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dt)

    moe = moe_dropless_init(k[4], d, cfg.d_ff_expert, cfg.n_experts,
                            cfg.n_experts, std=std)
    return {
        "ln1_g": jnp.ones((d,), dt),
        "wq": w(k[0], (d, hd)), "wk": w(k[1], (d, kv)),
        "wv": w(k[2], (d, kv)), "wo": w(k[3], (hd, d)),
        "ln2_g": jnp.ones((d,), dt),
        "moe": {n: v.astype(dt) for n, v in moe.items()
                if n != "router_bias"},
    }


def mellum2_head_init(rng, cfg: Mellum2Config) -> Dict[str, Any]:
    """Embedding, final norm and the untied head."""
    k = jax.random.split(rng, 2)
    d, dt = cfg.d_model, cfg.dtype
    return {
        "wte": (jax.random.normal(k[0], (cfg.vocab_size, d), jnp.float32)
                * cfg.init_std).astype(dt),
        "lm_head": (jax.random.normal(k[1], (d, cfg.vocab_size), jnp.float32)
                    * cfg.init_std).astype(dt),
        "lnf_g": jnp.ones((d,), dt),
    }


def mellum2_init(rng, cfg: Mellum2Config) -> Dict[str, Any]:
    keys = jax.random.split(rng, 1 + cfg.n_layers)
    return {**mellum2_head_init(keys[0], cfg),
            "blocks": [mellum2_block_init(keys[1 + li], cfg)
                       for li in range(cfg.n_layers)]}


# --------------------------------------------------------------------------
# the block's two halves
# --------------------------------------------------------------------------
def expert_ffn(cfg: Mellum2Config, p, h):
    """The block's FFN, ``ffn_half``'s ``ffn(h) -> (out, aux)`` once bound to
    a config and a layer: every token over all the experts, dropless.
    ``aux`` f32 ``(3,)``: pairs computed, experts with at least one row,
    heaviest expert over the mean expert."""
    with jax.named_scope("block/moe"):
        y, stats, load = moe_ffn_dropless(h, p["moe"], cfg.top_k, 1.0,
                                          route="softmax")
    return y, jnp.stack([stats[0], jnp.sum(load > 0).astype(jnp.float32),
                         stats[2]])


def dense_attend(window: Optional[int]):
    """``attend`` over a whole sequence from position 0: causal, inside
    ``window`` keys where one is given; k and v in their own few heads."""
    def attend(q, k, v):
        B, S, H, D = q.shape
        Hkv = k.shape[2]
        qg = q.reshape(B, S, Hkv, H // Hkv, D)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                       preferred_element_type=jnp.float32) * D ** -0.5
        gap = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]
        ok = gap >= 0 if window is None else (gap >= 0) & (gap < window)
        pr = jax.nn.softmax(jnp.where(ok, s, _NEG), axis=-1)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", pr.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
        return o.reshape(B, S, H, D).astype(q.dtype), None
    return attend


def mellum2_apply(params, tokens, cfg: Mellum2Config):
    """Logits ``(B, S, V)`` f32 of whole sequences from position 0."""
    pos = jnp.arange(tokens.shape[1])
    kw = dict(norm_fn=_rmsnorm, norm_eps=cfg.norm_eps, use_bias=False)
    freqs = {kind: rope_freqs(cfg, kind) for kind in (FULL, SLIDING)}
    x = params["wte"][tokens].astype(cfg.dtype)
    for p, kind in zip(params["blocks"], cfg.layer_types):
        x, _ = attn_half(
            x, p, cfg.head_dim, lambda: pos,
            dense_attend(cfg.window if kind == SLIDING else None), None,
            freqs[kind], **kw)
        x, _ = ffn_half(x, p, None,
                        lambda h, p=p: expert_ffn(cfg, p, h), **kw)
    return _readout(params, x, _rmsnorm, cfg.norm_eps)
