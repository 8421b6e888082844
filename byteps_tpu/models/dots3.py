"""dots3-note-prev (``model_type`` ``dots3_note``), the text decoder, for
SERVING: latent attention (MLA) in two kinds of layer — *full* layers that
attend over the ``index_topk`` keys a learned indexer picks (DeepSeek-V3.2's
sparse attention) and *sliding* layers with their own ranks and a window —
a headwise sigmoid gate on every attention output, a leading dense layer
and fine-grained experts with sigmoid bias-corrected top-k routing plus a
shared expert, on the share of the model one chip of an expert-parallel
deployment holds (``experts_held`` of ``n_routed_experts``, ``vocab_size``
rows of the vocabulary; ``models/joyai.py`` says what that leaves out).

With ``x`` a block's normed input at position ``t``, RMSNorm eps
``norm_eps``, no biases:

* Latents (both kinds, ``joyai.mla_latents``): ``c_q = a_q·RMSNorm(x
  wq_a)``, ``[q_nope; q_rope]`` per head ``= c_q wq_b``, ``[c_kv; k_rope] =
  x wkv_a``, ``c_kv = a_kv·RMSNorm(c_kv)``, RoPE on ``q_rope`` and on the one
  ``k_rope``; ``a = sqrt(d_model / rank)`` under ``lora_rescale``. A cache
  keeps ``[c_kv; k_rope]`` of a token and nothing else of its keys.
* Full layer: indexer ``qI_j = c_q idx.wq[j]``, ``kI = LayerNorm(x idx.wk)``
  (one head), both rotated on their first ``qk_rope_dim`` dims; ``w_j = (x
  idx.ww)_j · Hi^-1/2 · Di^-1/2``; ``I[t, s] = Σ_j w_j · relu(qI_j[t] ·
  kI[s])``; ``S_t`` = the ``index_topk`` positions ``s <= t`` of largest
  ``I`` (all while ``t < index_topk``); softmax over ``S_t`` of ``q · k /
  sqrt(nope + rope)``.
* Sliding layer: keys ``t - window < s <= t``, no indexer.
* ``o_h`` gated by ``sigmoid(x w_gate)_h``, heads concatenated, ``wo``.
* Feed-forward: SwiGLU ``d_ff_dense`` in the first ``first_k_dense`` layers,
  then ``parallel/moe.py::moe_ffn_dropless`` over the experts held here
  plus the shared expert. The router's bias is a fixed buffer.

Two forms of the same attention: :func:`dots3_apply` materialises k and v
and masks densely (whole sequences; the model as the tests and the plain
reference read it), and the *absorbed* form serving runs
(:func:`absorb_q`, :func:`latent_attend`, :func:`unabsorb_v`: ``q_nope``
through ``wkv_b``'s key part into the latent's width, the mix of latents
through its value part), over rows gathered from a paged latent cache
(``serve/latent_step.py``). Weights are leaves of ``cfg.dtype`` (the model
is published in bf16); the router's bias is f32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from byteps_tpu.models.gpt import (
    RopeFreqs,
    _layernorm,
    _mlp,
    _rmsnorm,
    rope_rotate,
)
from byteps_tpu.models.joyai import mla_latents
from byteps_tpu.parallel.moe import moe_dropless_init, moe_ffn_dropless

_NEG = -1e30
FULL, SLIDING = "full", "sliding"


class AttnDims(NamedTuple):
    """One layer kind's attention shapes."""
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    theta: Union[float, RopeFreqs]    # a base, or frequencies as data
    q_scale: float
    kv_scale: float
    window: Optional[int]

    @property
    def row(self) -> int:
        """Values a token leaves in the latent cache."""
        return self.kv_rank + self.rope

    @property
    def page_row(self) -> int:
        """Width of a cached row as a page stores it: whole 128-lane tiles
        (zeros past ``row``). With a minor axis that is not whole tiles the
        device keeps a page in a dimension order of its own, and every
        program that touches the pool converts all of it on the way in and
        out (PERF.md section 6, PR 28 and PR 35)."""
        return -(-self.row // 128) * 128


@dataclasses.dataclass(frozen=True)
class Dots3Config:
    vocab_size: int = 152064          # rows of the vocabulary held here
    max_seq: int = 524288
    d_model: int = 5120
    n_layers: int = 46
    #: per layer ``"full"`` or ``"sliding"``; None is the published period:
    #: layer 0 full, then full, sliding, sliding, sliding over and over
    layer_types: Optional[Tuple[str, ...]] = None
    first_k_dense: int = 1
    d_ff_dense: int = 13824
    d_ff_expert: int = 1536
    n_routed_experts: int = 256
    experts_held: int = 256
    first_expert: int = 0
    top_k: int = 8
    routed_scaling: float = 1.0
    n_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    rope_base: float = 8e7
    swa_n_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_dim: int = 192
    swa_qk_rope_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_base: float = 5e4
    window: int = 513                 # keys a sliding query sees, itself one
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    lora_rescale: bool = True
    rope_interleave: bool = True
    norm_eps: float = 1e-5
    init_std: float = 0.02
    router_bias_std: float = 0.01
    dtype: Any = jnp.bfloat16
    pos_embedding: str = "rope"

    def __post_init__(self):
        if self.layer_types is None:
            period = (FULL, SLIDING, SLIDING, SLIDING)
            object.__setattr__(self, "layer_types", (FULL,) + tuple(
                period[i % 4] for i in range(self.n_layers - 1)))
        else:
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if len(self.layer_types) != self.n_layers or any(
                k not in (FULL, SLIDING) for k in self.layer_types):
            raise ValueError(
                f"layer_types must name {self.n_layers} layers, each "
                f"'{FULL}' or '{SLIDING}'; got {self.layer_types}")
        if not (0 <= self.first_expert and self.first_expert
                + self.experts_held <= self.n_routed_experts):
            raise ValueError(
                f"experts {self.first_expert}..{self.first_expert} + "
                f"{self.experts_held} are not among the "
                f"{self.n_routed_experts} routed experts")
        if self.window < 1 or self.index_topk < 1:
            raise ValueError("window and index_topk must be >= 1")

    def dims(self, kind: str) -> AttnDims:
        def a(rank):
            return math.sqrt(self.d_model / rank) if self.lora_rescale \
                else 1.0
        if kind == FULL:
            return AttnDims(self.n_heads, self.q_lora_rank,
                            self.kv_lora_rank, self.qk_nope_dim,
                            self.qk_rope_dim, self.v_head_dim, self.rope_base,
                            a(self.q_lora_rank), a(self.kv_lora_rank), None)
        return AttnDims(self.swa_n_heads, self.swa_q_lora_rank,
                        self.swa_kv_lora_rank, self.swa_qk_nope_dim,
                        self.swa_qk_rope_dim, self.swa_v_head_dim,
                        self.swa_rope_base, a(self.swa_q_lora_rank),
                        a(self.swa_kv_lora_rank), self.window)

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.layer_types) if k == kind)

    @classmethod
    def tiny(cls, **kw) -> "Dots3Config":
        """Unit-test size: every mechanism live, nothing wide."""
        base = dict(vocab_size=128, max_seq=64, d_model=64, n_layers=5,
                    d_ff_dense=96, d_ff_expert=32, n_routed_experts=16,
                    experts_held=16, top_k=4, n_heads=4, q_lora_rank=32,
                    kv_lora_rank=24, qk_nope_dim=16, qk_rope_dim=8,
                    v_head_dim=16, swa_n_heads=2, swa_q_lora_rank=32,
                    swa_kv_lora_rank=40, swa_qk_nope_dim=24,
                    swa_qk_rope_dim=8, swa_v_head_dim=16, window=9,
                    index_n_heads=4, index_head_dim=16, index_topk=12,
                    dtype=jnp.float32)
        base.update(kw)
        return cls(**base)


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------
def dots3_block_init(rng, cfg: Dots3Config, li: int) -> Dict[str, Any]:
    """One layer's leaves, in ``cfg.dtype`` (the router's bias f32)."""
    kind, dense = cfg.layer_types[li], li < cfg.first_k_dense
    a, d, std, dt = cfg.dims(kind), cfg.d_model, cfg.init_std, cfg.dtype
    k = iter(jax.random.split(rng, 16))

    def w(shape):
        return (jax.random.normal(next(k), shape, jnp.float32)
                * std).astype(dt)

    def swiglu(ff):
        return {"w1": w((d, ff)), "w3": w((d, ff)), "w2": w((ff, d))}

    p = {
        "ln1_g": jnp.ones((d,), dt),
        "wq_a": w((d, a.q_rank)), "q_norm_g": jnp.ones((a.q_rank,), dt),
        "wq_b": w((a.q_rank, a.heads * (a.nope + a.rope))),
        "wkv_a": w((d, a.row)), "kv_norm_g": jnp.ones((a.kv_rank,), dt),
        "wkv_b": w((a.kv_rank, a.heads * (a.nope + a.v))),
        "w_gate": w((d, a.heads)),
        "wo": w((a.heads * a.v, d)),
        "ln2_g": jnp.ones((d,), dt),
    }
    if kind == FULL:
        Hi, Di = cfg.index_n_heads, cfg.index_head_dim
        p["idx"] = {"wq": w((a.q_rank, Hi * Di)), "wk": w((d, Di)),
                    "k_norm_g": jnp.ones((Di,), dt),
                    "k_norm_b": jnp.zeros((Di,), dt), "ww": w((d, Hi))}
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


def dots3_head_init(rng, cfg: Dots3Config) -> Dict[str, Any]:
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


def dots3_init(rng, cfg: Dots3Config) -> Dict[str, Any]:
    keys = jax.random.split(rng, 1 + cfg.n_layers)
    return {**dots3_head_init(keys[0], cfg),
            "blocks": [dots3_block_init(keys[1 + li], cfg, li)
                       for li in range(cfg.n_layers)]}


# --------------------------------------------------------------------------
# pieces both forms share
# --------------------------------------------------------------------------
def latents(h, p, pos, cfg: Dots3Config, kind: str, expand: bool = False):
    """``joyai.mla_latents`` at this layer kind's shapes: ``(c_q, q, c_kv,
    k_rope)``, and k and v after them with ``expand``."""
    a = cfg.dims(kind)
    out = mla_latents(h, p, pos, n_heads=a.heads, nope=a.nope, rope=a.rope,
                      kv_rank=a.kv_rank, theta=a.theta,
                      interleave=cfg.rope_interleave, eps=cfg.norm_eps,
                      q_scale=a.q_scale, kv_scale=a.kv_scale,
                      v_dim=a.v if expand else None)
    return out if expand else out[:4]


def _index_rope(x, pos, cfg: Dots3Config):
    """The indexer rotates the first ``qk_rope_dim`` dims of a head, in the
    half-split convention (DeepSeek-V3.2's indexer), at the full layers'
    rotation (their base, or their scaled frequencies). ``x (B, S, H,
    Di)``."""
    r = cfg.qk_rope_dim
    return jnp.concatenate(
        [rope_rotate(x[..., :r], pos, cfg.dims(FULL).theta), x[..., r:]],
        axis=-1)


def index_queries(c_q, h, idx, pos, cfg: Dots3Config):
    """``(qI (B, S, Hi, Di), w (B, S, Hi) f32)`` of a full layer."""
    B, S, _ = h.shape
    Hi, Di = cfg.index_n_heads, cfg.index_head_dim
    qi = (c_q @ idx["wq"].astype(h.dtype)).reshape(B, S, Hi, Di)
    w = (h @ idx["ww"].astype(h.dtype)).astype(jnp.float32) \
        * (Hi ** -0.5 * Di ** -0.5)
    return _index_rope(qi, pos, cfg), w


def index_keys(h, idx, pos, cfg: Dots3Config):
    """``kI (B, S, Di)``: the one indexer key a token leaves in the cache."""
    k = _layernorm(h @ idx["wk"].astype(h.dtype), idx["k_norm_g"],
                   idx["k_norm_b"], cfg.norm_eps)
    return _index_rope(k[:, :, None, :], pos, cfg)[:, :, 0]


def headwise_gate(o, h, p):
    """``o (B, S, H, v)`` times one sigmoid scalar a head from the block's
    input, then heads side by side through ``wo``."""
    g = jax.nn.sigmoid((h @ p["w_gate"].astype(h.dtype))
                       .astype(jnp.float32)).astype(o.dtype)
    o = o * g[..., None]
    return o.reshape(*o.shape[:2], -1) @ p["wo"].astype(h.dtype)


def ffn(x, p, cfg: Dots3Config):
    """The block's second half. Returns ``(x, moe stats (3,))``; a dense
    layer's stats are zeros."""
    h = _rmsnorm(x, p["ln2_g"], eps=cfg.norm_eps)
    if "mlp" in p:
        with jax.named_scope("block/mlp"):
            return (x + _mlp(h, p["mlp"], None, use_bias=False),
                    jnp.zeros((3,), jnp.float32))
    with jax.named_scope("block/moe"):
        y, stats, _ = moe_ffn_dropless(
            h, p["moe"], cfg.top_k, cfg.routed_scaling, cfg.first_expert)
        return x + y + _mlp(h, p["shared"], None, use_bias=False), stats


def fold_moe_stats(total, layer):
    """Pairs add over layers; the load ratio keeps its worst layer."""
    return jnp.stack([total[0] + layer[0], total[1] + layer[1],
                      jnp.maximum(total[2], layer[2])])


class LatentModel(NamedTuple):
    """What the two programs of ``serve/latent_step.py`` take from a model
    of latent pages, beside its configuration (``layer_types``,
    ``layers_of``, ``dims``, ``window``, the indexer's sizes): the pieces of
    a block that differ between models, each a function of this module's
    signatures. What a token leaves in the cache and both forms of the
    attention over it (:func:`cache_row`, :func:`absorb_q`,
    :func:`latent_attend`, :func:`unabsorb_v`) are the same for every such
    model and are not in here."""

    #: ``(h, p, pos, cfg, kind) -> (c_q, q, c_kv, k_rope)``
    latents: Callable
    #: ``(c_q, h, idx, pos, cfg) -> (qI, w)`` and ``(h, idx, pos, cfg) -> kI``
    index_queries: Callable
    index_keys: Callable
    #: ``(o (B, S, H, v), h, p) -> (B, S, d)``: a gate or none, then ``wo``
    attn_out: Callable
    #: ``(x, p, cfg) -> (x, moe stats)``; ``moe_stats`` names the stats,
    #: ``fold`` adds a layer's to the program's
    ffn: Callable
    moe_stats: Tuple[str, ...]
    fold: Callable
    #: ``(params, x, cfg) -> logits f32``
    readout: Callable


# --------------------------------------------------------------------------
# the materialised form: whole sequences, dense masks
# --------------------------------------------------------------------------
def index_scores_dense(qi, ki, w):
    """``I (B, S, S)`` f32 from ``qI (B, S, Hi, Di)``, ``kI (B, S, Di)``,
    ``w (B, S, Hi)``; future keys are not masked here."""
    s = jnp.einsum("bthd,bsd->bhts", qi, ki,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("bth,bhts->bts", w, jax.nn.relu(s))


def selected_mask(scores, topk: int):
    """``(B, S, S)`` bool: for query ``t`` the ``topk`` keys ``s <= t`` of
    largest score (all of them while ``t < topk``)."""
    S = scores.shape[-1]
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    if topk >= S:
        return jnp.broadcast_to(causal, scores.shape)
    _, idx = jax.lax.top_k(jnp.where(causal, scores, _NEG), topk)
    hit = jnp.zeros(scores.shape, bool)
    b, t = jnp.meshgrid(jnp.arange(scores.shape[0]), jnp.arange(S),
                        indexing="ij")
    hit = hit.at[b[..., None], t[..., None], idx].set(True)
    return hit & causal


def _masked_attention(q, k, v, mask):
    """softmax over the keys ``mask (B, S, S)`` allows, f32."""
    s = jnp.einsum("bthd,bshd->bhts", q, k,
                   preferred_element_type=jnp.float32) * q.shape[-1] ** -0.5
    p = jax.nn.softmax(jnp.where(mask[:, None], s, _NEG), axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def attention_dense(h, p, pos, cfg: Dots3Config, kind: str):
    """One layer's attention over ``h (B, S, d)``, k and v materialised."""
    a = cfg.dims(kind)
    S = h.shape[1]
    c_q, q, _, _, k, v = latents(h, p, pos, cfg, kind, expand=True)
    if kind == FULL:
        qi, w = index_queries(c_q, h, p["idx"], pos, cfg)
        mask = selected_mask(
            index_scores_dense(qi, index_keys(h, p["idx"], pos, cfg), w),
            cfg.index_topk)
    else:
        gap = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]
        mask = jnp.broadcast_to((gap >= 0) & (gap < a.window),
                                (h.shape[0], S, S))
    return headwise_gate(_masked_attention(q, k, v, mask), h, p)


def dots3_apply(params, tokens, cfg: Dots3Config):
    """Logits ``(B, S, V)`` f32 of whole sequences from position 0."""
    pos = jnp.arange(tokens.shape[1])
    x = params["wte"][tokens].astype(cfg.dtype)
    for li, p in enumerate(params["blocks"]):
        h = _rmsnorm(x, p["ln1_g"], eps=cfg.norm_eps)
        x = x + attention_dense(h, p, pos, cfg, cfg.layer_types[li])
        x, _ = ffn(x, p, cfg)
    return readout(params, x, cfg)


def readout(params, x, cfg: Dots3Config):
    h = _rmsnorm(x, params["lnf_g"], eps=cfg.norm_eps)
    return jax.lax.dot_general(
        h, params["lm_head"].astype(h.dtype),
        (((h.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------
# the absorbed form: queries against rows of a latent cache
# --------------------------------------------------------------------------
def cache_row(c_kv, k_rope, a: AttnDims):
    """What a token leaves in the latent cache: ``[c_kv; k_rope; zeros]``,
    ``page_row`` wide. ``c_kv (..., kv_rank)``, ``k_rope (..., rope)``."""
    pad = jnp.zeros(c_kv.shape[:-1] + (a.page_row - a.row,), c_kv.dtype)
    return jnp.concatenate([c_kv, k_rope, pad], axis=-1)


def absorb_q(q, p, a: AttnDims):
    """``q (..., H, nope + rope)`` into the width of a cached row: ``q_nope``
    through the key part of ``wkv_b`` (``(..., H, kv_rank)``) beside
    ``q_rope`` and zeros to ``page_row`` — its product with a cached row is
    ``q · k``."""
    wk = p["wkv_b"].reshape(a.kv_rank, a.heads, a.nope + a.v)[..., :a.nope]
    qa = jnp.einsum("...hn,rhn->...hr", q[..., :a.nope], wk.astype(q.dtype),
                    preferred_element_type=jnp.float32).astype(q.dtype)
    pad = jnp.zeros(q.shape[:-1] + (a.page_row - a.row,), q.dtype)
    return jnp.concatenate([qa, q[..., a.nope:], pad], axis=-1)


def latent_attend(q_abs, rows, valid, a: AttnDims):
    """``q_abs (N, H, page_row)`` over each query's own ``rows (N, K,
    page_row)``
    (``valid (N, K)`` says which are keys at all): softmax of ``q · k /
    sqrt(nope + rope)`` in f32, the mix of the rows' latents ``(N, H,
    kv_rank)``."""
    s = jnp.einsum("nhf,nkf->nhk", q_abs, rows,
                   preferred_element_type=jnp.float32) \
        * (a.nope + a.rope) ** -0.5
    pr = jax.nn.softmax(jnp.where(valid[:, None, :], s, _NEG), axis=-1)
    return jnp.einsum("nhk,nkr->nhr", pr.astype(rows.dtype),
                      rows[..., :a.kv_rank],
                      preferred_element_type=jnp.float32).astype(q_abs.dtype)


def unabsorb_v(o_lat, p, a: AttnDims):
    """``(..., H, kv_rank)`` through the value part of ``wkv_b``: ``(...,
    H, v)``."""
    wv = p["wkv_b"].reshape(a.kv_rank, a.heads, a.nope + a.v)[..., a.nope:]
    return jnp.einsum("...hr,rhv->...hv", o_lat, wv.astype(o_lat.dtype),
                      preferred_element_type=jnp.float32).astype(o_lat.dtype)


#: dots3 as the latent programs see it
MODEL = LatentModel(
    latents=latents, index_queries=index_queries, index_keys=index_keys,
    attn_out=headwise_gate, ffn=ffn,
    moe_stats=("moe.pairs_here", "moe.pairs_total",
               "moe.load_max_over_mean"),
    fold=fold_moe_stats, readout=readout)
