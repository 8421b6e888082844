"""JoyAI-LLM-Flash (``model_type`` ``joyai_llm_flash``): the DeepSeek-V3
block — latent attention (MLA), a leading dense layer, then fine-grained
MoE layers with sigmoid bias-corrected top-k routing and a shared expert —
and a multi-token-prediction (MTP) module, for TRAINING, on the share of
the model one chip of an expert-parallel deployment holds.

A sibling of ``models/moe_gpt.py`` and trained by the same factory
(``make_gpt_moe_train_step``); embedding, RMSNorm, the fused readout+CE,
the flash kernels and per-block recomputation are the dense family's.

What a chip holds is part of the config: ``experts_held`` of the
``n_routed_experts`` (experts ``first_expert ..``), and ``vocab_size`` rows
of the vocabulary. Every token is routed over ALL routed experts; this
device computes its own experts' part (``parallel/moe.py``,
:func:`moe_ffn_dropless`) and runs no exchange: what the experts held
elsewhere would add is left out, here and in the plain reference
(``benchmark/configs/joyai_reference.py``) alike.

With ``x`` a block's input, RMSNorm eps ``norm_eps``, no biases, compute
in ``cfg.dtype`` on f32 weights, router and softmax in f32:

* MLA: ``c_q = RMSNorm(x·wq_a)``; ``q = c_q·wq_b`` → heads of ``[q_nope;
  q_rope]``; ``[c_kv; k_rope] = x·wkv_a``, ``c_kv = RMSNorm(c_kv)``;
  ``[k_nope; v]`` per head ``= c_kv·wkv_b``; RoPE on ``q_rope`` and on the
  ONE ``k_rope`` all heads share; ``k = [k_nope; k_rope]``; causal softmax
  of ``q·k / sqrt(nope + rope)``; ``o = concat_heads(p·v)·wo``. k and v
  are materialised (training; the absorbed form and a latent cache are
  serving's).
* Feed-forward: SwiGLU of width ``d_ff_dense`` in the first
  ``first_k_dense`` layers, after them the routed experts held here plus
  the shared expert (SwiGLU of width ``d_ff_expert``, every token).
  The router's correction bias is a buffer: no gradient; after each step
  it moves by ``router_bias_update_rate`` against each expert's load
  (``noaux_tc``'s balancing, :func:`joyai_step_buffers`), or stays fixed
  at rate 0.
* MTP: ``h'_t = eh_proj·[RMSNorm(Emb(tok_{t+1})); RMSNorm(h_t)]`` with
  ``h_t`` the last main layer's output before the final norm, one MoE
  layer, the module's own final RMSNorm, the shared embedding and head; it
  predicts ``tok_{t+2}`` (the last position has no target and is masked).
  ``loss = CE_main + mtp_loss_weight · CE_mtp``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from byteps_tpu.models.gpt import (
    _embed,
    _mlp,
    _positions,
    _readout_nll,
    _rmsnorm,
    rope_rotate,
)
from byteps_tpu.parallel.moe import (
    moe_dropless_init,
    moe_dropless_logical_specs,
    moe_ffn_dropless,
    noaux_bias_step,
)
from byteps_tpu.parallel.remat import maybe_remat
from byteps_tpu.parallel.ring_attention import plain_attention

#: Leaves that are buffers, by key: no gradient, no optimizer state, no
#: weight decay (the train-step factory keeps them out of the optimizer
#: and hands them to :func:`joyai_step_buffers` after each step).
BUFFER_KEYS = ("router_bias",)

#: What the loss returns beside itself, one f32 each, and the registry
#: histogram each is observed into once a step (docs/observability.md).
STEP_STATS = ("moe.pairs_here", "moe.pairs_total", "moe.load_max_over_mean",
              "train.loss_main", "train.loss_mtp")


@dataclasses.dataclass(frozen=True)
class JoyAIConfig:
    vocab_size: int = 129280          # rows of the vocabulary held here
    max_seq: int = 4096
    d_model: int = 2048
    n_heads: int = 32
    n_layers: int = 40                # main layers, the dense ones included
    first_k_dense: int = 1
    d_ff_dense: int = 7168
    d_ff_expert: int = 768
    n_routed_experts: int = 256
    experts_held: int = 256           # of them, computed on this device
    first_expert: int = 0
    top_k: int = 8
    routed_scaling: float = 2.5
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    rope_base: float = 32e6
    rope_interleave: bool = True
    norm_eps: float = 1e-6
    n_mtp: int = 1                    # MTP modules (0 or 1)
    mtp_loss_weight: float = 0.3
    init_std: float = 0.02
    router_bias_std: float = 0.0
    #: ``noaux_tc``'s balancing speed: after each step every routed
    #: expert's correction bias moves by this much against its load
    #: (``parallel/moe.py``, :func:`noaux_bias_step`); 0 holds the bias fixed
    router_bias_update_rate: float = 0.0
    dtype: Any = jnp.float32
    pos_embedding: str = "rope"       # what `_embed` asks: no wpe table

    def __post_init__(self):
        if self.n_mtp not in (0, 1):
            raise ValueError(f"n_mtp must be 0 or 1; got {self.n_mtp}")
        if not (0 <= self.first_expert and self.first_expert
                + self.experts_held <= self.n_routed_experts):
            raise ValueError(
                f"experts {self.first_expert}..{self.first_expert} + "
                f"{self.experts_held} are not among the "
                f"{self.n_routed_experts} routed experts")

    @classmethod
    def tiny(cls, **kw) -> "JoyAIConfig":
        """Unit-test size: every mechanism, nothing wide."""
        base = dict(vocab_size=128, max_seq=32, d_model=64, n_heads=4,
                    n_layers=3, first_k_dense=1, d_ff_dense=96,
                    d_ff_expert=32, n_routed_experts=16, experts_held=16,
                    top_k=4, q_lora_rank=48, kv_lora_rank=32,
                    qk_nope_dim=24, qk_rope_dim=8, v_head_dim=16,
                    router_bias_std=0.01)
        base.update(kw)
        return cls(**base)


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------
def _swiglu_init(rng, d: int, ff: int, std: float):
    k = jax.random.split(rng, 3)
    return {"w1": jax.random.normal(k[0], (d, ff), jnp.float32) * std,
            "w3": jax.random.normal(k[1], (d, ff), jnp.float32) * std,
            "w2": jax.random.normal(k[2], (ff, d), jnp.float32) * std}


def joyai_block_init(rng, cfg: JoyAIConfig, dense: bool) -> Dict[str, Any]:
    d, H, std = cfg.d_model, cfg.n_heads, cfg.init_std
    k = jax.random.split(rng, 8)

    def w(key, shape):
        return jax.random.normal(key, shape, jnp.float32) * std

    p = {
        "ln1_g": jnp.ones((d,), jnp.float32),
        "wq_a": w(k[0], (d, cfg.q_lora_rank)),
        "q_norm_g": jnp.ones((cfg.q_lora_rank,), jnp.float32),
        "wq_b": w(k[1], (cfg.q_lora_rank,
                         H * (cfg.qk_nope_dim + cfg.qk_rope_dim))),
        "wkv_a": w(k[2], (d, cfg.kv_lora_rank + cfg.qk_rope_dim)),
        "kv_norm_g": jnp.ones((cfg.kv_lora_rank,), jnp.float32),
        "wkv_b": w(k[3], (cfg.kv_lora_rank,
                          H * (cfg.qk_nope_dim + cfg.v_head_dim))),
        "wo": w(k[4], (H * cfg.v_head_dim, d)),
        "ln2_g": jnp.ones((d,), jnp.float32),
    }
    if dense:
        p["mlp"] = _swiglu_init(k[5], d, cfg.d_ff_dense, std)
    else:
        p["moe"] = moe_dropless_init(
            k[5], d, cfg.d_ff_expert, cfg.n_routed_experts,
            cfg.experts_held, std=std, bias_std=cfg.router_bias_std)
        p["shared"] = _swiglu_init(k[6], d, cfg.d_ff_expert, std)
    return p


def joyai_init(rng, cfg: JoyAIConfig) -> Dict[str, Any]:
    d, std = cfg.d_model, cfg.init_std
    keys = jax.random.split(rng, 4 + cfg.n_layers)
    params: Dict[str, Any] = {
        "wte": jax.random.normal(keys[0], (cfg.vocab_size, d),
                                 jnp.float32) * std,
        "lm_head": jax.random.normal(keys[1], (d, cfg.vocab_size),
                                     jnp.float32) * std,
        "lnf_g": jnp.ones((d,), jnp.float32),
        "blocks": [joyai_block_init(keys[4 + li], cfg,
                                    dense=li < cfg.first_k_dense)
                   for li in range(cfg.n_layers)],
    }
    if cfg.n_mtp:
        params["mtp"] = {
            "enorm_g": jnp.ones((d,), jnp.float32),
            "hnorm_g": jnp.ones((d,), jnp.float32),
            "eh_proj": jax.random.normal(keys[2], (2 * d, d),
                                         jnp.float32) * std,
            "block": joyai_block_init(keys[3], cfg, dense=False),
            "lnf_g": jnp.ones((d,), jnp.float32),
        }
    return params


def _swiglu_logical():
    return {"w1": ("embed", "mlp"), "w3": ("embed", "mlp"),
            "w2": ("mlp", "embed")}


def _block_logical(dense: bool) -> Dict[str, Any]:
    s = {"ln1_g": ("embed",), "wq_a": ("embed", None), "q_norm_g": (None,),
         "wq_b": (None, "heads"), "wkv_a": ("embed", None),
         "kv_norm_g": (None,), "wkv_b": (None, "heads"),
         "wo": ("heads", "embed"), "ln2_g": ("embed",)}
    if dense:
        s["mlp"] = _swiglu_logical()
    else:
        s["moe"] = moe_dropless_logical_specs()
        s["shared"] = _swiglu_logical()
    return s


def joyai_logical_specs(cfg: JoyAIConfig) -> Dict[str, Any]:
    s: Dict[str, Any] = {
        "wte": ("vocab", "embed"), "lm_head": ("embed", "vocab"),
        "lnf_g": ("embed",),
        "blocks": [_block_logical(li < cfg.first_k_dense)
                   for li in range(cfg.n_layers)],
    }
    if cfg.n_mtp:
        s["mtp"] = {"enorm_g": ("embed",), "hnorm_g": ("embed",),
                    "eh_proj": (None, "embed"),
                    "block": _block_logical(False), "lnf_g": ("embed",)}
    return s


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------
def _swiglu(x, p):
    """The dense family's gated MLP on a ``w1``/``w3``/``w2`` tree, no
    tensor parallelism, no biases."""
    return _mlp(x, p, None, use_bias=False)


def mla_latents(x, p, pos, *, n_heads: int, nope: int, rope: int,
                kv_rank: int, theta: float, interleave: bool, eps: float,
                q_scale: float = 1.0, kv_scale: float = 1.0,
                v_dim: Optional[int] = None):
    """The projections of latent attention, for ``x (B, S, d)`` at positions
    ``pos`` (``(S,)`` or ``(B, S)``, or a callable giving them): ``c_q`` the
    normed query latent, ``q
    (B, S, H, nope + rope)`` with its rope part rotated, ``c_kv (B, S,
    kv_rank)`` the normed key-value latent, ``k_rope (B, S, 1, rope)`` the
    one rotated key part all heads share — ``(c_kv, k_rope)`` is all a
    latent cache keeps of a token — and, with ``v_dim``, k and v
    materialised from them: ``k (B, S, H, nope + rope)`` (every head's own
    nope part beside the shared rope part), ``v (B, S, H, v_dim)``; None
    both without it. ``q_scale`` / ``kv_scale`` multiply the normed latents
    (``models/dots3.py``; 1.0 leaves the program as it was)."""
    B, S, _ = x.shape
    c_q = _rmsnorm(x @ p["wq_a"].astype(x.dtype), p["q_norm_g"], eps=eps)
    if q_scale != 1.0:
        c_q = c_q * q_scale
    q = (c_q @ p["wq_b"].astype(x.dtype)).reshape(B, S, n_heads, nope + rope)
    kv_a = x @ p["wkv_a"].astype(x.dtype)
    c_kv = _rmsnorm(kv_a[..., :kv_rank], p["kv_norm_g"], eps=eps)
    if kv_scale != 1.0:
        c_kv = c_kv * kv_scale
    kv = None if v_dim is None else (
        c_kv @ p["wkv_b"].astype(x.dtype)).reshape(B, S, n_heads,
                                                   nope + v_dim)
    if callable(pos):              # made where they are used, as attn_half's
        pos = pos()
    q_rope = rope_rotate(q[..., nope:], pos, theta, interleaved=interleave)
    k_rope = rope_rotate(kv_a[..., kv_rank:].reshape(B, S, 1, rope), pos,
                         theta, interleaved=interleave)
    q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
    if kv is None:
        return c_q, q, c_kv, k_rope, None, None
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (B, S, n_heads, rope))],
        axis=-1)
    return c_q, q, c_kv, k_rope, k, kv[..., nope:]


def mla_expand(c_kv, k_rope, p, *, n_heads: int, nope: int, v_dim: int):
    """k and v materialised from latents that were cached: ``k (B, S, H,
    nope + rope)`` and ``v (B, S, H, v_dim)`` (what :func:`mla_latents`
    gives with ``v_dim``, from ``c_kv`` and ``k_rope`` alone)."""
    B, S, _ = c_kv.shape
    kv = (c_kv @ p["wkv_b"].astype(c_kv.dtype)).reshape(
        B, S, n_heads, nope + v_dim)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_rope, (B, S, n_heads, k_rope.shape[-1]))],
        axis=-1)
    return k, kv[..., nope:]


def mla_attention(x, p, cfg: JoyAIConfig):
    """Latent attention over ``x (B, S, d)``, k and v materialised; the
    flash kernels take q/k of ``nope + rope`` and v of ``v_head_dim``."""
    B, S, _ = x.shape
    H = cfg.n_heads
    _, q, _, _, k, v = mla_latents(
        x, p, lambda: _positions(S, None, "contiguous"), n_heads=H,
        nope=cfg.qk_nope_dim, rope=cfg.qk_rope_dim, kv_rank=cfg.kv_lora_rank,
        theta=cfg.rope_base, interleave=cfg.rope_interleave,
        eps=cfg.norm_eps, v_dim=cfg.v_head_dim)
    o = plain_attention(q, k, v, causal=True)
    return o.reshape(B, S, H * cfg.v_head_dim) @ p["wo"].astype(x.dtype)


def joyai_block(x, p, cfg: JoyAIConfig):
    """Pre-norm MLA + (dense | routed + shared) feed-forward. Returns
    ``(x, moe_stats (3,), load (n_routed_experts,))``; a dense layer's
    stats and load are zeros."""
    with jax.named_scope("block/mla"):
        x = x + mla_attention(_rmsnorm(x, p["ln1_g"], eps=cfg.norm_eps), p,
                              cfg)
    h = _rmsnorm(x, p["ln2_g"], eps=cfg.norm_eps)
    if "mlp" in p:
        with jax.named_scope("block/mlp"):
            return (x + _swiglu(h, p["mlp"]), jnp.zeros((3,), jnp.float32),
                    jnp.zeros((cfg.n_routed_experts,), jnp.float32))
    with jax.named_scope("block/moe"):
        y, stats, load = moe_ffn_dropless(
            h, p["moe"], cfg.top_k, cfg.routed_scaling, cfg.first_expert)
        return x + y + _swiglu(h, p["shared"]), stats, load


def _fold_stats(total, layer):
    """Pairs add over layers; the load ratio keeps its worst layer."""
    return jnp.stack([total[0] + layer[0], total[1] + layer[1],
                      jnp.maximum(total[2], layer[2])])


def joyai_loss(params, tokens, targets, cfg: JoyAIConfig,
               ep_axis: Optional[str] = None,
               tp_axis: Optional[str] = None,
               sp_axis: Optional[str] = None,
               remat: bool = False,
               seq_layout: str = "contiguous",
               chunked_ce=True):
    """``(loss, (stats, loads))``: next-token CE plus the weighted MTP CE
    over this device's tokens; the ``STEP_STATS`` values as one f32
    vector; and the picks per routed expert of every expert layer, ``(expert
    layers, n_routed_experts)`` in the order :func:`joyai_step_buffers`
    walks them.
    Runs on one device's share without an exchange: a mesh with an ep, tp
    or sp axis is refused (the ep all-to-all is the four-chip follow-up,
    ROADMAP)."""
    for name, axis in (("ep", ep_axis), ("tp", tp_axis), ("sp", sp_axis)):
        if axis is not None:
            raise NotImplementedError(
                f"JoyAIConfig trains on dp meshes only; got a {name} axis "
                "(experts_held says which experts this device computes)")
    block = maybe_remat(lambda x, p: joyai_block(x, p, cfg), remat)
    with jax.named_scope("embed"):
        x = _embed(params, tokens, cfg, None, seq_layout)
    stats = jnp.zeros((3,), jnp.float32)
    loads = []
    for p in params["blocks"]:
        x, layer, load = block(x, p)
        stats = _fold_stats(stats, layer)
        if "moe" in p:
            loads.append(load)
    loss_main = _readout_nll(params, x, targets, _rmsnorm, cfg.norm_eps,
                             chunked=chunked_ce).mean()
    loss_mtp = jnp.zeros((), jnp.float32)
    if cfg.n_mtp:
        m = params["mtp"]
        # Emb(tok_{t+1}) is the embedding of this position's target
        with jax.named_scope("embed"):
            e = _embed(params, targets, cfg, None, seq_layout)
        h = jnp.concatenate(
            [_rmsnorm(e, m["enorm_g"], eps=cfg.norm_eps),
             _rmsnorm(x, m["hnorm_g"], eps=cfg.norm_eps)], axis=-1)
        h, layer, load = block(h @ m["eh_proj"].astype(h.dtype), m["block"])
        stats = _fold_stats(stats, layer)
        loads.append(load)
        # position t predicts tok_{t+2} = targets[t + 1]; the last has none
        # (masked: shapes stay those of the main readout)
        nxt = jnp.concatenate([targets[:, 1:], targets[:, :1]], axis=1)
        nll = _readout_nll(
            {"lnf_g": m["lnf_g"], "lm_head": params["lm_head"]},
            h, nxt, _rmsnorm, cfg.norm_eps, chunked=chunked_ce)
        loss_mtp = nll[:, :-1].mean()
    loss = loss_main + cfg.mtp_loss_weight * loss_mtp
    loads = (jnp.stack(loads) if loads
             else jnp.zeros((0, cfg.n_routed_experts), jnp.float32))
    return loss, (jnp.concatenate([stats, jnp.stack([loss_main, loss_mtp])]),
                  loads)


def joyai_step_buffers(params, loads, cfg: JoyAIConfig):
    """``params`` with every expert layer's correction bias moved one
    ``noaux_tc`` balancing step against that layer's ``loads`` row (the
    step's picks per routed expert, summed over the data-parallel ranks);
    ``params`` itself at ``router_bias_update_rate`` 0."""
    if not cfg.router_bias_update_rate:
        return params
    layers = iter(loads)

    def moved(block):
        if "moe" not in block:
            return block
        moe = dict(block["moe"])
        moe["router_bias"] = noaux_bias_step(
            moe["router_bias"], next(layers), cfg.router_bias_update_rate)
        return {**block, "moe": moe}

    out = {**params, "blocks": [moved(b) for b in params["blocks"]]}
    if cfg.n_mtp:
        out["mtp"] = {**params["mtp"], "block": moved(params["mtp"]["block"])}
    return out
