"""SDAR-30B-A3B-Chat (``model_type`` ``sdar_moe``): the Qwen3-MoE block —
grouped-query attention with an RMSNorm on q and on k over the head, a
softmax-routed expert feed-forward in every layer — read under a
**block-causal** mask and generated from by diffusion over blocks.

With ``x`` a block's input at position ``t``, RMSNorm eps ``norm_eps``, no
biases, ``B = block_length``:

* ``q, k, v = RMSNorm(x) (wq, wk, wv)`` in ``n_heads`` / ``n_kv_heads``
  heads of ``head_dim``; ``q = RMSNorm(q) · q_norm``, ``k = RMSNorm(k) ·
  k_norm`` over each head; half-split rotary pairs over all of a head at
  base ``rope_base``; query head ``j`` reads kv head ``j // (n_heads /
  n_kv_heads)``; key ``s`` is visible iff ``s // B <= t // B`` (blocks
  counted from position 0: a query sees every earlier block and all of its
  own); ``x + concat(o) wo``.
* ``p = softmax(RMSNorm(x) wg)`` in f32, the ``top_k`` largest renormalised
  to sum 1, ``x + Σ_j w_j · SwiGLU_{e_j}(RMSNorm(x))``
  (``parallel/moe.py::moe_ffn_dropless`` with ``route="softmax"``).
* position ``i``'s logits predict position ``i``'s token (no shift).

Generation fills the next ``B`` positions with ``mask_id``, runs the model
over them against the committed prefix, and fixes from the logits at the
still-masked positions the ``B / T`` most confident (greedy: the argmax
token, confidence its softmax probability, the mask token itself never
picked) — ``T`` passes; a last pass over the ``B`` final tokens writes the
block's k/v, and the next block starts. :func:`fix_positions` is that rule;
the serve tier runs it on the device (``serve/scheduler.py``).

The block is ``models/gpt.py``'s (``ffn_half``, and ``attn_half``'s
``attend`` contract under :func:`sdar_attn_half`): :func:`sdar_apply` here is
the dense forward over whole sequences, the serve tier runs the same halves
over k/v pages (``serve/paged_cache.py``, ``serve/families.py``). Weights are
leaves of ``cfg.dtype`` (published in bf16).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from byteps_tpu.models.gpt import (
    _project,
    _readout,
    _rmsnorm,
    ffn_half,
    rope_rotate,
)
from byteps_tpu.models.mellum2 import expert_ffn, mellum2_head_init
from byteps_tpu.ops.flash_attention import attention_block_causal_jnp
from byteps_tpu.parallel.moe import moe_dropless_init

__all__ = ["SDARConfig", "sdar_init", "sdar_block_init", "sdar_head_init",
           "sdar_apply", "sdar_attn_half", "expert_ffn", "fix_positions",
           "param_count"]


@dataclasses.dataclass(frozen=True)
class SDARConfig:
    vocab_size: int = 151936
    max_seq: int = 32768
    d_model: int = 2048
    n_layers: int = 48
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    rope_base: float = 1000000.0
    n_experts: int = 128
    top_k: int = 8
    d_ff_expert: int = 768
    norm_eps: float = 1e-6
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16
    #: positions a generation step carries a sequence (the mask's block)
    block_length: int = 4
    #: denoising passes a block where a request names none
    denoise_steps: int = 4
    #: the token an open position holds; None is the vocabulary's last row
    mask_id: Any = None

    # what the shared block and the paged programs read of a configuration
    # (``GPTConfig``'s names); this model has one answer to each
    pos_embedding = "rope"
    norm = "rmsnorm"
    use_bias = False
    tied_readout = False

    def __post_init__(self):
        if self.mask_id is None:
            object.__setattr__(self, "mask_id", self.vocab_size - 1)
        if self.n_heads % self.n_kv_heads != 0:
            raise ValueError(
                f"n_heads ({self.n_heads}) must be a multiple of "
                f"n_kv_heads ({self.n_kv_heads})")
        if not 1 <= self.top_k <= self.n_experts:
            raise ValueError("top_k must be in 1..n_experts")
        B = self.block_length
        if B < 1 or B & (B - 1):
            raise ValueError(f"block_length must be a power of two; got {B}")
        if self.denoise_steps < 1 or B % self.denoise_steps:
            raise ValueError(
                f"denoise_steps ({self.denoise_steps}) must divide "
                f"block_length ({B})")
        if not 0 <= self.mask_id < self.vocab_size:
            raise ValueError(f"mask_id {self.mask_id} is not a token id")

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads

    @classmethod
    def tiny(cls, **kw) -> "SDARConfig":
        """Unit-test size: every expert live, a block shorter than a page."""
        base = dict(vocab_size=128, max_seq=64, d_model=64, n_layers=3,
                    n_heads=4, n_kv_heads=2, head_dim=32,
                    rope_base=10000.0, n_experts=8, top_k=2,
                    d_ff_expert=32, dtype=jnp.float32)
        base.update(kw)
        return cls(**base)


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------
def sdar_block_init(rng, cfg: SDARConfig) -> Dict[str, Any]:
    """One layer's leaves, in ``cfg.dtype``: the attention half under
    ``models/gpt.py``'s names (``_project`` reads them) with the two head
    norms' gains drawn around 1, the experts under ``moe`` with no
    ``router_bias`` leaf (softmax routing has none)."""
    d, dt, std = cfg.d_model, cfg.dtype, cfg.init_std
    hd, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    k = jax.random.split(rng, 7)

    def w(key, shape, mean=0.0):
        return (mean + jax.random.normal(key, shape, jnp.float32) * std
                ).astype(dt)

    moe = moe_dropless_init(k[4], d, cfg.d_ff_expert, cfg.n_experts,
                            cfg.n_experts, std=std)
    return {
        "ln1_g": jnp.ones((d,), dt),
        "wq": w(k[0], (d, hd)), "wk": w(k[1], (d, kv)),
        "wv": w(k[2], (d, kv)), "wo": w(k[3], (hd, d)),
        "q_norm": w(k[5], (cfg.head_dim,), 1.0),
        "k_norm": w(k[6], (cfg.head_dim,), 1.0),
        "ln2_g": jnp.ones((d,), dt),
        "moe": {n: v.astype(dt) for n, v in moe.items()
                if n != "router_bias"},
    }


#: embedding, final norm and the untied head: Mellum2's leaves
sdar_head_init = mellum2_head_init


def sdar_init(rng, cfg: SDARConfig) -> Dict[str, Any]:
    keys = jax.random.split(rng, 1 + cfg.n_layers)
    return {**sdar_head_init(keys[0], cfg),
            "blocks": [sdar_block_init(keys[1 + li], cfg)
                       for li in range(cfg.n_layers)]}


def param_count(cfg: SDARConfig) -> int:
    """Parameters of :func:`sdar_init`'s tree, from its shapes alone."""
    shapes = jax.eval_shape(lambda: sdar_init(jax.random.PRNGKey(0), cfg))
    return sum(math.prod(a.shape) for a in jax.tree_util.tree_leaves(shapes))


# --------------------------------------------------------------------------
# the block's first half
# --------------------------------------------------------------------------
def sdar_attn_half(cfg: SDARConfig, x, p, head_dim, positions, attend,
                   tp_axis=None, rope_base=0.0, norm_fn=_rmsnorm,
                   norm_eps=1e-6, use_bias=False, delta=None):
    """The first half of a block, ``x + wo(attend(q, k, v))``:
    ``models/gpt.py::attn_half``'s signature and ``attend`` contract with
    this model's norms on q and k before the rotation (``tp_axis``,
    ``use_bias`` and ``delta`` are the signature's: one answer here).
    Returns ``(x, carry)``."""
    del tp_axis, use_bias, delta
    B, T = x.shape[:2]
    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, head_dim
    with jax.named_scope("block/attn"):
        h = norm_fn(x, p["ln1_g"], None, norm_eps)
        q, k, v = _project(h, p, ("wq", "wk", "wv"), False)
        q = norm_fn(q.reshape(B, T, H, D), p["q_norm"], None, norm_eps)
        k = norm_fn(k.reshape(B, T, Hkv, D), p["k_norm"], None, norm_eps)
        v = v.reshape(B, T, Hkv, D)
        pos = positions()
        q, k = rope_rotate(q, pos, rope_base), rope_rotate(k, pos, rope_base)
        o, carry = attend(q, k, v)
        (out,) = _project(o.reshape(B, T, H * D), p, ("wo",), False)
        return x + out, carry


def dense_attend(block: int):
    """``attend`` over a whole sequence from position 0 under the
    block-causal mask; k and v in their own few heads."""
    return lambda q, k, v: (
        attention_block_causal_jnp(q, k, v, 0, 0, block), None)


def sdar_apply(params, tokens, cfg: SDARConfig):
    """Logits ``(B, S, V)`` f32 of whole sequences from position 0 under the
    block-causal mask."""
    pos = jnp.arange(tokens.shape[1])
    kw = dict(norm_fn=_rmsnorm, norm_eps=cfg.norm_eps)
    x = params["wte"][tokens].astype(cfg.dtype)
    for p in params["blocks"]:
        x, _ = sdar_attn_half(cfg, x, p, cfg.head_dim, lambda: pos,
                              dense_attend(cfg.block_length), None,
                              cfg.rope_base, **kw)
        x, _ = ffn_half(x, p, None, lambda h, p=p: expert_ffn(cfg, p, h),
                        use_bias=False, **kw)
    return _readout(params, x, _rmsnorm, cfg.norm_eps)


# --------------------------------------------------------------------------
# the sampler's rule
# --------------------------------------------------------------------------
def fix_positions(logits, toks, fixed_at, n_fix, pass_no, mask_id: int):
    """One denoising pass over a batch of blocks: of the positions of
    ``toks (R, B)`` that still hold ``mask_id``, the ``n_fix (R,)`` whose
    greedy token is most confident (its softmax probability in f32, the mask
    token left out; ties to the earlier position) take that token, and
    ``fixed_at (R, B)`` takes ``pass_no (R,)`` there. A row with ``n_fix``
    0 — its committing pass — passes through. Returns ``(toks,
    fixed_at)``."""
    # (a select the reductions fuse: no second copy of the logits)
    lg = jnp.where(jnp.arange(logits.shape[-1]) == mask_id, -jnp.inf,
                   logits.astype(jnp.float32))
    best = jnp.argmax(lg, axis=-1).astype(toks.dtype)
    top = jnp.max(lg, axis=-1, keepdims=True)
    conf = 1.0 / jnp.sum(jnp.exp(lg - top), axis=-1)
    masked = toks == mask_id
    # a position's rank among its row's, the masked ones first by confidence
    order = jnp.argsort(jnp.where(masked, -conf, 2.0), axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    fix = masked & (rank < n_fix[:, None])
    return (jnp.where(fix, best, toks),
            jnp.where(fix, pass_no[:, None].astype(fixed_at.dtype), fixed_at))
