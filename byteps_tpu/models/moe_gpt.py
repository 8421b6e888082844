"""MoE GPT: dense attention + Switch-style MoE FFN, expert-parallel over ep.

The sparse-FFN sibling of the flagship dense GPT (models/gpt.py — shared
attention/layernorm/readout code, so the families cannot diverge). Each
block's MLP is replaced by :func:`byteps_tpu.parallel.moe.moe_ffn`: top-1
capacity routing, expert weights stacked on a leading expert axis and
sharded ``P('ep')``, token slots shipped to their expert's owner and back
with ``all_to_all`` over ICI. The Switch load-balancing auxiliary loss is
averaged over layers and added with ``aux_coef``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from byteps_tpu.models.gpt import (
    GPTConfig,
    _embed,
    _positions,
    _readout_nll,
    attn_half,
    block_init,
    block_specs,
    ffn_half,
    resolve_norm,
    resolve_rope,
    ring_attend,
)
from byteps_tpu.parallel.moe import moe_ffn, moe_init, moe_specs
from byteps_tpu.parallel.remat import maybe_remat


@dataclasses.dataclass(frozen=True)
class MoEGPTConfig(GPTConfig):
    n_experts: int = 8
    capacity_factor: float = 1.25
    aux_coef: float = 0.01
    router_topk: int = 1  # 1 = Switch, 2 = GShard-style top-2

    @classmethod
    def tiny(cls) -> "MoEGPTConfig":
        return cls(vocab_size=256, max_seq=64, d_model=64, n_heads=4,
                   n_layers=2, d_ff=128, n_experts=4,
                   capacity_factor=4.0)


def moe_block_init(rng, cfg: MoEGPTConfig):
    """Attention half of a dense block + expert-stacked MoE FFN
    (``cfg.mlp="swiglu"`` = llama-style gated experts)."""
    b = block_init(rng, cfg.d_model, cfg.d_ff,
                   cfg.n_heads * cfg.head_dim, cfg.n_layers,
                   kv_hd=cfg.kv_heads * cfg.head_dim,
                   mlp=cfg.mlp, use_bias=cfg.use_bias, norm=cfg.norm)
    for k in ("w1", "b1", "w2", "b2", "w3", "b3"):
        b.pop(k, None)   # bias keys absent under use_bias=False
    b["moe"] = moe_init(jax.random.fold_in(rng, 99), cfg.d_model,
                        cfg.d_ff, cfg.n_experts, mlp=cfg.mlp)
    return b


def moe_gpt_init(rng, cfg: MoEGPTConfig) -> Dict[str, Any]:
    d = cfg.d_model
    keys = jax.random.split(rng, 2 + cfg.n_layers)
    return {
        "wte": jax.random.normal(keys[0], (cfg.vocab_size, d),
                                 jnp.float32) * 0.02,
        "lnf_g": jnp.ones((d,), jnp.float32),
        **({"wpe": jax.random.normal(keys[1], (cfg.max_seq, d),
                                     jnp.float32) * 0.02}
           if cfg.pos_embedding == "learned" else {}),
        **({"lnf_b": jnp.zeros((d,), jnp.float32)}
           if cfg.norm == "layernorm" else {}),
        "blocks": [moe_block_init(keys[2 + li], cfg)
                   for li in range(cfg.n_layers)],
    }


def moe_block_logical_specs(use_bias: bool = True, norm: str = "layernorm",
                            mlp: str = "gelu"):
    # derive from the dense family's logical tree exactly like
    # moe_block_init derives from block_init, so new attention params
    # cannot diverge
    from byteps_tpu.models.gpt import block_logical_specs
    from byteps_tpu.parallel.moe import moe_logical_specs
    s = block_logical_specs(mlp=mlp, use_bias=use_bias, norm=norm)
    for k in ("w1", "b1", "w2", "b2", "w3", "b3"):
        s.pop(k, None)
    s["moe"] = moe_logical_specs(mlp=mlp)
    return s


def moe_block_specs(ep_axis: Optional[str], tp_axis: Optional[str] = None,
                    use_bias: bool = True, norm: str = "layernorm",
                    mlp: str = "gelu"):
    from byteps_tpu.parallel.partitioner import resolve_specs, rules_from_axes
    return resolve_specs(
        moe_block_logical_specs(use_bias=use_bias, norm=norm, mlp=mlp),
        rules_from_axes(tp_axis=tp_axis, ep_axis=ep_axis))


def moe_gpt_logical_specs(cfg: MoEGPTConfig) -> Dict[str, Any]:
    return {
        "wte": ("vocab", "embed"), "lnf_g": ("embed",),
        **({"wpe": (None, "embed")} if cfg.pos_embedding == "learned"
           else {}),
        **({"lnf_b": ("embed",)} if cfg.norm == "layernorm" else {}),
        "blocks": [moe_block_logical_specs(use_bias=cfg.use_bias,
                                           norm=cfg.norm, mlp=cfg.mlp)
                   for _ in range(cfg.n_layers)],
    }


def moe_gpt_param_specs(cfg: MoEGPTConfig, ep_axis: Optional[str],
                        tp_axis: Optional[str] = None) -> Dict[str, Any]:
    from byteps_tpu.parallel.partitioner import resolve_specs, rules_from_axes
    return resolve_specs(moe_gpt_logical_specs(cfg),
                         rules_from_axes(tp_axis=tp_axis, ep_axis=ep_axis))


def moe_transformer_block(x, p, cfg: MoEGPTConfig,
                          ep_axis: Optional[str],
                          tp_axis: Optional[str] = None,
                          sp_axis: Optional[str] = None,
                          seq_layout: str = "contiguous"):
    """Pre-LN attention + MoE FFN; returns (x, aux_loss): the dense
    family's block (``models/gpt.py``) with ``moe_ffn`` at the training
    capacity as its feed-forward."""
    norm_fn, norm_eps = resolve_norm(cfg)
    kw = dict(norm_fn=norm_fn, norm_eps=norm_eps, use_bias=cfg.use_bias)
    x, _ = attn_half(x, p, cfg.head_dim,
                     lambda: _positions(x.shape[1], sp_axis, seq_layout),
                     ring_attend(sp_axis, True, seq_layout), tp_axis,
                     resolve_rope(cfg), **kw)
    ffn = functools.partial(
        moe_ffn, params=p["moe"], capacity_factor=cfg.capacity_factor,
        ep_axis=ep_axis, router_topk=cfg.router_topk, tp_axis=tp_axis)
    return ffn_half(x, p, tp_axis, ffn, **kw)


def moe_gpt_loss(params, tokens, targets, cfg: MoEGPTConfig,
                 ep_axis: Optional[str] = None,
                 tp_axis: Optional[str] = None,
                 sp_axis: Optional[str] = None,
                 remat: bool = False,
                 seq_layout: str = "contiguous",
                 chunked_ce=True) -> jnp.ndarray:
    """Per-device next-token loss + Switch aux loss (local mean over this
    device's tokens, pmean'd over sequence shards — dp/ep averaging is
    the train step's job)."""
    x = _embed(params, tokens, cfg, sp_axis, seq_layout)
    aux_total = jnp.zeros((), jnp.float32)

    def apply_block(x, p):
        return moe_transformer_block(x, p, cfg, ep_axis, tp_axis, sp_axis,
                                     seq_layout)

    apply_block = maybe_remat(apply_block, remat)
    for p in params["blocks"]:
        x, aux = apply_block(x, p)
        aux_total = aux_total + aux
    nll = _readout_nll(params, x, targets, *resolve_norm(cfg),
                       tp_axis=tp_axis, chunked=chunked_ce)
    loss = nll.mean() + cfg.aux_coef * aux_total / cfg.n_layers
    if sp_axis is not None:
        loss = jax.lax.pmean(loss, sp_axis)
    return loss


def moe_gpt_pp_loss(params, tokens, targets, cfg: MoEGPTConfig,
                    pp_axis: str, n_micro: int,
                    ep_axis: Optional[str] = None,
                    tp_axis: Optional[str] = None,
                    sp_axis: Optional[str] = None,
                    remat: bool = False,
                    vma_axes: tuple = (),
                    seq_layout: str = "contiguous",
                    chunked_ce=True) -> jnp.ndarray:
    """Pipelined MoE loss (inside shard_map over pp): ``params["blocks"]``
    is THIS stage's stacked MoE-block slab. Same conventions as
    ``gpt_pp_loss`` — the returned scalar is per-device (masked nll on the
    last stage + this stage's own aux term); never psum it over pp inside
    the grad."""
    from byteps_tpu.parallel.pipeline import pipeline_apply

    B, S_loc = tokens.shape
    if B % n_micro != 0:
        raise ValueError(f"local batch {B} not divisible by {n_micro} "
                         "microbatches")
    x = _embed(params, tokens, cfg, sp_axis, seq_layout)
    x_mb = x.reshape(n_micro, B // n_micro, S_loc, x.shape[-1])

    def blk(h, p):
        return moe_transformer_block(h, p, cfg, ep_axis, tp_axis, sp_axis,
                                     seq_layout)

    y_mb, aux_total = pipeline_apply(
        x_mb, params["blocks"], blk, pp_axis,
        remat=remat, vma_axes=vma_axes, has_aux=True,
    )
    y = y_mb.reshape(B, S_loc, -1)
    nll = _readout_nll(params, y, targets, *resolve_norm(cfg),
                       tp_axis=tp_axis, chunked=chunked_ce).mean()
    stage = jax.lax.axis_index(pp_axis)
    nstages = jax.lax.axis_size(pp_axis)
    masked_nll = jnp.where(stage == nstages - 1, nll, 0.0)
    # aux_total covers THIS stage's layers x all M microbatches; every
    # (layer, microbatch) is counted once across the stages, so the
    # per-device terms sum to the model-wide per-layer mean the dense
    # family uses
    aux_term = cfg.aux_coef * aux_total / (cfg.n_layers * n_micro)
    total = masked_nll + aux_term
    if sp_axis is not None:
        # pmean the WHOLE per-device scalar over sp — pmeaning only the
        # nll would leave the aux term's sp-summed cotangents unscaled,
        # multiplying the load-balancing gradient by sp_size
        total = jax.lax.pmean(total, sp_axis)
    return total
