"""DeepSeek-V3.2-Exp (``model_type`` ``deepseek_v32``), for SERVING: the
DeepSeek-V3 block — latent attention (MLA), leading dense layers, then
fine-grained experts under sigmoid bias-corrected top-k routing WITH its
group limit, plus a shared expert — in which EVERY layer attends over the
``index_topk`` keys a learned indexer picks (DeepSeek sparse attention), at
a YaRN-scaled rotation; on the share of the model one chip of an
expert-parallel deployment holds (``experts_held`` of ``n_routed_experts``,
``vocab_size`` rows of the vocabulary; ``models/joyai.py`` says what that
leaves out). The MTP module is not served (Hugging Face's modeling drops it
at load).

With ``x`` a block's normed input at position ``t``, RMSNorm eps
``norm_eps``, no biases — ``models/dots3.py``'s full layer, and where the
equations are the same its functions (:func:`dots3.latents`,
``index_queries``, ``index_keys``, ``absorb_q``, ``latent_attend``,
``unabsorb_v``, ``cache_row``; ``joyai.mla_latents`` under them), with these
differences:

* no variance alignment on the normed latents (``q_scale`` = ``kv_scale`` =
  1), no headwise gate: heads side by side through ``wo``;
* the rotation is YaRN's (:func:`rope_freqs`): with ``d(r) = D · ln(original
  / (2π r)) / (2 ln base)``, ``lo = floor(d(beta_fast))``, ``hi =
  ceil(d(beta_slow))`` clipped to ``0 .. D - 1`` and ``ramp_i = clip((i - lo)
  / (hi - lo), 0, 1)``, pair ``i`` turns at ``f_i / factor · ramp_i + f_i ·
  (1 - ramp_i)``; the frequencies do not depend on the sequence length. cos
  and sin carry ``yarn_get_mscale(factor, mscale) / yarn_get_mscale(factor,
  mscale_all_dim)`` (1 as published) and the SOFTMAX SCALE carries
  ``m²``, ``m = 0.1 · mscale_all_dim · ln(factor) + 1`` (DeepSeek-V3's
  inference code): :func:`latents` hands the queries out times ``m²``, so
  both forms of the attention (and the flash kernel, whose scale is ``1 /
  sqrt(D)``) need know nothing of it. The indexer rotates at the same
  frequencies;
* the router limits a token to ``topk_group`` of ``n_group`` groups of
  experts (``parallel/moe.py::sigmoid_group_topk_route``);
* every layer is a selecting layer: there is no window kind, so every page
  is of the global kind and the prefix index may share it.

:func:`dsv32_apply` materialises k and v and masks densely (whole
sequences; the model as the tests read it); serving runs the absorbed form
over a paged latent cache (``serve/latent_step.py``, through
:data:`MODEL`). Weights are leaves of ``cfg.dtype``; the router's bias is
f32.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from byteps_tpu.models import dots3
from byteps_tpu.models.dots3 import FULL, AttnDims, LatentModel
from byteps_tpu.models.gpt import RopeFreqs, _mlp, _rmsnorm
from byteps_tpu.parallel.moe import moe_dropless_init, moe_ffn_dropless


@dataclasses.dataclass(frozen=True)
class DeepSeekV32Config:
    vocab_size: int = 129280          # rows of the vocabulary held here
    max_seq: int = 163840
    d_model: int = 7168
    n_layers: int = 61
    first_k_dense: int = 3
    d_ff_dense: int = 18432
    d_ff_expert: int = 2048
    n_routed_experts: int = 256
    experts_held: int = 256
    first_expert: int = 0
    top_k: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling: float = 2.5
    n_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    rope_base: float = 10000.0
    yarn_factor: float = 40.0
    yarn_original_max_seq: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 1.0
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    rope_interleave: bool = True
    norm_eps: float = 1e-6
    init_std: float = 0.02
    router_bias_std: float = 0.01
    dtype: Any = jnp.bfloat16
    pos_embedding: str = "rope"

    #: no window kind: what ``serve/families.py`` and the latent programs
    #: ask a latent configuration
    window = None

    def __post_init__(self):
        if not (0 <= self.first_expert and self.first_expert
                + self.experts_held <= self.n_routed_experts):
            raise ValueError(
                f"experts {self.first_expert}..{self.first_expert} + "
                f"{self.experts_held} are not among the "
                f"{self.n_routed_experts} routed experts")
        if self.n_routed_experts % self.n_group \
                or not 1 <= self.topk_group <= self.n_group:
            raise ValueError(
                f"{self.n_routed_experts} experts do not lie in "
                f"{self.n_group} equal groups of which {self.topk_group} "
                "stay")
        if self.index_topk < 1:
            raise ValueError("index_topk must be >= 1")

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return (FULL,) * self.n_layers

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(range(self.n_layers)) if kind == FULL else ()

    def dims(self, kind: str = FULL) -> AttnDims:
        if kind != FULL:
            raise ValueError(f"every layer is '{FULL}'; got {kind!r}")
        return AttnDims(self.n_heads, self.q_lora_rank, self.kv_lora_rank,
                        self.qk_nope_dim, self.qk_rope_dim, self.v_head_dim,
                        rope_freqs(self), 1.0, 1.0, None)

    @classmethod
    def tiny(cls, **kw) -> "DeepSeekV32Config":
        """Unit-test size: every mechanism live, nothing wide. The YaRN
        ramp lies inside the 4 rotary pairs (``lo`` 0, ``hi`` 2) and the
        positions served pass the original context."""
        base = dict(vocab_size=128, max_seq=64, d_model=64, n_layers=4,
                    first_k_dense=1, d_ff_dense=96, d_ff_expert=32,
                    n_routed_experts=16, experts_held=16, top_k=4,
                    n_group=4, topk_group=2, n_heads=4, q_lora_rank=32,
                    kv_lora_rank=24, qk_nope_dim=16, qk_rope_dim=8,
                    v_head_dim=16, yarn_factor=4.0, yarn_original_max_seq=16,
                    yarn_beta_fast=2.0, yarn_beta_slow=0.25,
                    index_n_heads=4, index_head_dim=16, index_topk=12,
                    dtype=jnp.float32)
        base.update(kw)
        return cls(**base)


# --------------------------------------------------------------------------
# the rotation
# --------------------------------------------------------------------------
def yarn_mscale(factor: float, mscale: float) -> float:
    """``yarn_get_mscale``: ``0.1 · mscale · ln(factor) + 1`` (1 at or below
    factor 1)."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_mscale(cfg: DeepSeekV32Config) -> float:
    """``m²``, what the softmax scale ``(nope + rope)^-1/2`` is multiplied
    by: 1.87386 as published (``m`` = 0.1 ln 40 + 1 = 1.36889)."""
    return yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2


@functools.lru_cache(maxsize=16)
def rope_freqs(cfg: DeepSeekV32Config) -> RopeFreqs:
    """The ``qk_rope_dim / 2`` inverse frequencies of the rotation (module
    docstring) and the factor on cos and sin. As published: ``lo`` 10,
    ``hi`` 23 of 32 pairs. Computed in float64, once."""
    D, base = cfg.qk_rope_dim, cfg.rope_base
    plain = [base ** (-2.0 * i / D) for i in range(D // 2)]

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
    return RopeFreqs(tuple(inv),
                     yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
                     / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------
def dsv32_block_init(rng, cfg: DeepSeekV32Config, li: int) -> Dict[str, Any]:
    """One layer's leaves, in ``cfg.dtype`` (the router's bias f32), under
    ``models/dots3.py``'s names less its gate."""
    dense = li < cfg.first_k_dense
    a, d, std, dt = cfg.dims(), cfg.d_model, cfg.init_std, cfg.dtype
    k = iter(jax.random.split(rng, 16))

    def w(shape):
        return (jax.random.normal(next(k), shape, jnp.float32)
                * std).astype(dt)

    def swiglu(ff):
        return {"w1": w((d, ff)), "w3": w((d, ff)), "w2": w((ff, d))}

    Hi, Di = cfg.index_n_heads, cfg.index_head_dim
    p = {
        "ln1_g": jnp.ones((d,), dt),
        "wq_a": w((d, a.q_rank)), "q_norm_g": jnp.ones((a.q_rank,), dt),
        "wq_b": w((a.q_rank, a.heads * (a.nope + a.rope))),
        "wkv_a": w((d, a.row)), "kv_norm_g": jnp.ones((a.kv_rank,), dt),
        "wkv_b": w((a.kv_rank, a.heads * (a.nope + a.v))),
        "wo": w((a.heads * a.v, d)),
        "ln2_g": jnp.ones((d,), dt),
        "idx": {"wq": w((a.q_rank, Hi * Di)), "wk": w((d, Di)),
                "k_norm_g": jnp.ones((Di,), dt),
                "k_norm_b": jnp.zeros((Di,), dt), "ww": w((d, Hi))},
    }
    if dense:
        p["mlp"] = swiglu(cfg.d_ff_dense)
    else:
        moe = moe_dropless_init(
            next(k), d, cfg.d_ff_expert, cfg.n_routed_experts,
            cfg.experts_held, std=std, bias_std=cfg.router_bias_std)
        p["moe"] = {n: (v if n == "router_bias" else v.astype(dt))
                    for n, v in moe.items()}
        p["shared"] = swiglu(cfg.d_ff_expert)
    return p


#: embedding, final norm and the untied head: dots3's
dsv32_head_init = dots3.dots3_head_init


def dsv32_init(rng, cfg: DeepSeekV32Config) -> Dict[str, Any]:
    keys = jax.random.split(rng, 1 + cfg.n_layers)
    return {**dsv32_head_init(keys[0], cfg),
            "blocks": [dsv32_block_init(keys[1 + li], cfg, li)
                       for li in range(cfg.n_layers)]}


# --------------------------------------------------------------------------
# the pieces that are this model's own
# --------------------------------------------------------------------------
def latents(h, p, pos, cfg: DeepSeekV32Config, kind: str = FULL,
            expand: bool = False):
    """:func:`dots3.latents` with the queries times ``m²`` (the softmax
    scale's YaRN factor; the keys, and so the cache, do not carry it)."""
    out = dots3.latents(h, p, pos, cfg, kind, expand)
    q = out[1] * jnp.asarray(softmax_mscale(cfg), out[1].dtype)
    return out[:1] + (q,) + out[2:]


def attn_out(o, h, p):
    """``o (B, S, H, v)``, heads side by side through ``wo``: no gate."""
    del h
    return o.reshape(*o.shape[:2], -1) @ p["wo"].astype(o.dtype)


def ffn(x, p, cfg: DeepSeekV32Config):
    """The block's second half. Returns ``(x, moe stats (4,))``: the
    dropless layer's three and the groups a token's picks lie in, the mean
    (``moe.groups_hit``); a dense layer's are zeros."""
    h = _rmsnorm(x, p["ln2_g"], eps=cfg.norm_eps)
    if "mlp" in p:
        with jax.named_scope("block/mlp"):
            return (x + _mlp(h, p["mlp"], None, use_bias=False),
                    jnp.zeros((4,), jnp.float32))
    with jax.named_scope("block/moe"):
        y, stats, _ = moe_ffn_dropless(
            h, p["moe"], cfg.top_k, cfg.routed_scaling, cfg.first_expert,
            route="sigmoid_bias_groups",
            group_limit=(cfg.n_group, cfg.topk_group))
        # a layer's share of the mean over the expert layers
        stats = stats.at[3].divide(cfg.n_layers - cfg.first_k_dense)
        return x + y + _mlp(h, p["shared"], None, use_bias=False), stats


def fold_moe_stats(total, layer):
    """Pairs add over layers, the load ratio keeps its worst layer, the
    layers' shares of the mean groups hit add."""
    return jnp.concatenate([dots3.fold_moe_stats(total, layer),
                            (total[3] + layer[3])[None]])


def dsv32_apply(params, tokens, cfg: DeepSeekV32Config):
    """Logits ``(B, S, V)`` f32 of whole sequences from position 0, k and v
    materialised, the picked set a dense mask."""
    pos = jnp.arange(tokens.shape[1])
    x = params["wte"][tokens].astype(cfg.dtype)
    for p in params["blocks"]:
        h = _rmsnorm(x, p["ln1_g"], eps=cfg.norm_eps)
        c_q, q, _, _, k, v = latents(h, p, pos, cfg, expand=True)
        qi, w = dots3.index_queries(c_q, h, p["idx"], pos, cfg)
        mask = dots3.selected_mask(dots3.index_scores_dense(
            qi, dots3.index_keys(h, p["idx"], pos, cfg), w), cfg.index_topk)
        x = x + attn_out(dots3._masked_attention(q, k, v, mask), h, p)
        x, _ = ffn(x, p, cfg)
    return dots3.readout(params, x, cfg)


#: this model as the latent programs see it
MODEL = LatentModel(
    latents=latents, index_queries=dots3.index_queries,
    index_keys=dots3.index_keys, attn_out=attn_out, ffn=ffn,
    moe_stats=dots3.MODEL.moe_stats + ("moe.groups_hit",),
    fold=fold_moe_stats, readout=dots3.readout)

__all__ = ["DeepSeekV32Config", "FULL", "MODEL", "dsv32_apply",
           "dsv32_block_init", "dsv32_head_init", "dsv32_init", "rope_freqs",
           "softmax_mscale", "yarn_mscale"]
