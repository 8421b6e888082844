"""GPT-style decoder-only transformer — the flagship model.

Functional (params pytree + pure apply), written once for every parallelism
configuration: the same forward runs single-chip (all axes ``None``),
tensor-parallel (Megatron col/row-parallel projections over ``tp``), and
sequence-parallel (ring attention over ``sp``) inside one ``shard_map``.
BASELINE config 4's workload ("GPT-2 medium with topk sparsification") uses
this model at size; tests and the driver dry-run use tiny shapes.

MXU notes: all FLOPs are batched matmuls (einsum/`@`) with static shapes;
activations can run in bfloat16 (``GPTConfig.dtype``) while layernorm,
softmax and the loss accumulate in float32.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from byteps_tpu.parallel.remat import maybe_remat
from byteps_tpu.parallel.ring_attention import (
    ring_attention,
    zigzag_local_positions,
    zigzag_ring_attention,
)
from byteps_tpu.parallel.tp import col_parallel_matmul, row_parallel_matmul


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304
    max_seq: int = 1024
    d_model: int = 768
    n_heads: int = 12
    n_layers: int = 12
    d_ff: int = 3072
    dtype: Any = jnp.float32
    # "learned" = GPT-2 wpe table; "rope" = rotary position embeddings
    # applied to q/k per head (no wpe leaf — the param tree carries
    # exactly the leaves the config trains, so lossy gradient
    # compression can never perturb a structurally-dead parameter)
    pos_embedding: str = "learned"
    rope_base: float = 10000.0
    # grouped-query attention: k/v carry n_kv_heads heads (None = n_heads,
    # plain MHA); queries repeat each kv head n_heads/n_kv_heads times.
    # The KV cache stores only the kv heads — the decode memory lever.
    n_kv_heads: Any = None
    # "gelu" = GPT-2 2-matrix MLP; "swiglu" = gated 3-matrix llama-style
    # FFN (silu(x·w1) ∘ (x·w3)) · w2 — same d_ff hidden width
    mlp: str = "gelu"
    # "layernorm" = GPT-2 LN (mean-centered, affine); "rmsnorm" =
    # llama-style RMS norm (no centering, no bias — the ln*_b / lnf_b
    # leaves are absent from the param tree)
    norm: str = "layernorm"
    norm_eps: float = 1e-5
    # False = llama-style bias-free projections: no b* leaves in the
    # tree. Leaves the config doesn't train must NOT exist — inert
    # zeros would drift under lossy gradient compression (onebit maps
    # a zero gradient to ±scale) and break checkpoint re-export.
    use_bias: bool = True
    # True = GPT-2 weight-tied readout (h @ wte.T); False = separate
    # (d, vocab) "lm_head" leaf (llama-style untied readout)
    tied_readout: bool = True

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def kv_heads(self) -> int:
        kv = self.n_kv_heads if self.n_kv_heads is not None else self.n_heads
        if self.n_heads % kv != 0:
            raise ValueError(
                f"n_heads ({self.n_heads}) must be a multiple of "
                f"n_kv_heads ({kv})")
        return kv

    @classmethod
    def tiny(cls) -> "GPTConfig":
        """Dry-run / unit-test size; dims divisible by tp=2, sp=2, heads=4."""
        return cls(vocab_size=256, max_seq=64, d_model=64, n_heads=4,
                   n_layers=2, d_ff=128)

    @classmethod
    def gpt2_medium(cls) -> "GPTConfig":
        return cls(vocab_size=50304, max_seq=1024, d_model=1024,
                   n_heads=16, n_layers=24, d_ff=4096, dtype=jnp.bfloat16)

    @classmethod
    def llama(cls, **kw) -> "GPTConfig":
        """The llama-family option set (RoPE + GQA + SwiGLU + RMSNorm +
        untied readout); size fields via ``**kw``."""
        defaults = dict(pos_embedding="rope", mlp="swiglu", norm="rmsnorm",
                        tied_readout=False, use_bias=False)
        defaults.update(kw)
        return cls(**defaults)


def gpt_init(rng: jnp.ndarray, cfg: GPTConfig) -> Dict[str, Any]:
    """Initialize full (unsharded) parameters; shard via device_put after."""
    d, ff, hd = cfg.d_model, cfg.d_ff, cfg.n_heads * cfg.head_dim
    kv_hd = cfg.kv_heads * cfg.head_dim
    std = 0.02

    def dense(key, shape):
        return (jax.random.normal(key, shape, jnp.float32) * std)

    keys = jax.random.split(rng, 2 + cfg.n_layers)
    params: Dict[str, Any] = {
        "wte": dense(keys[0], (cfg.vocab_size, d)),
        "lnf_g": jnp.ones((d,), jnp.float32),
        "blocks": [
            block_init(keys[2 + li], d, ff, hd, cfg.n_layers, kv_hd=kv_hd,
                       mlp=cfg.mlp, use_bias=cfg.use_bias, norm=cfg.norm)
            for li in range(cfg.n_layers)
        ],
    }
    if cfg.pos_embedding == "learned":
        params["wpe"] = dense(keys[1], (cfg.max_seq, d))
    if cfg.norm == "layernorm":
        params["lnf_b"] = jnp.zeros((d,), jnp.float32)
    if not cfg.tied_readout:
        params["lm_head"] = dense(jax.random.fold_in(keys[0], 1),
                                  (d, cfg.vocab_size))
    return params


def gpt_logical_specs(cfg: GPTConfig) -> Dict[str, Any]:
    """Logical-axis tree matching :func:`gpt_init`'s structure: one tuple
    of logical names per array dim. The Partitioner's per-family rule
    table decides what (if anything) each name shards over."""
    return {
        "wte": ("vocab", "embed"), "lnf_g": ("embed",),
        **({"wpe": (None, "embed")} if cfg.pos_embedding == "learned"
           else {}),
        **({"lnf_b": ("embed",)} if cfg.norm == "layernorm" else {}),
        **({} if cfg.tied_readout else {"lm_head": ("embed", "vocab")}),
        "blocks": [block_logical_specs(cfg.mlp, use_bias=cfg.use_bias,
                                       norm=cfg.norm)
                   for _ in range(cfg.n_layers)],
    }


def gpt_param_specs(cfg: GPTConfig, tp_axis: Optional[str]) -> Dict[str, Any]:
    """PartitionSpec tree matching :func:`gpt_init`'s structure.

    Column-parallel weights (qkv, w1) split their output dim over tp; the
    matching row-parallel weights (wo, w2) split their input dim; biases of
    column-parallel layers are sharded, everything else replicated (dp/sp
    replication is implicit — those axes never appear in param specs).
    Thin wrapper: the structure lives in :func:`gpt_logical_specs`, the
    tp policy in the partitioner rules.
    """
    from byteps_tpu.parallel.partitioner import resolve_specs, rules_from_axes
    return resolve_specs(gpt_logical_specs(cfg),
                         rules_from_axes(tp_axis=tp_axis))


def resolve_rope(cfg: GPTConfig) -> float:
    """Validate the position scheme and return the rope base to thread to
    the blocks (0.0 = learned/wpe — no rotation)."""
    if cfg.pos_embedding not in ("learned", "rope"):
        raise ValueError(f"unknown pos_embedding {cfg.pos_embedding!r} — "
                         "expected 'learned' or 'rope'")
    if cfg.pos_embedding == "rope":
        if not cfg.rope_base > 0.0:
            raise ValueError(f"rope_base must be > 0; got {cfg.rope_base}")
        return cfg.rope_base
    return 0.0


def _positions(S_loc: int, sp_axis, seq_layout: str) -> jnp.ndarray:
    """This device's global sequence positions (layout-aware) — feeds both
    the learned wpe gather and the RoPE rotations."""
    if seq_layout == "zigzag" and sp_axis is not None:
        return zigzag_local_positions(S_loc, sp_axis)
    off = (jax.lax.axis_index(sp_axis) * S_loc if sp_axis is not None
           else 0)
    return off + jnp.arange(S_loc)


class RopeFreqs(NamedTuple):
    """A rotation given as data: the ``D/2`` inverse frequencies of a head's
    pairs and one factor on cos and sin — what a frequency-scaled scheme
    (YaRN: interpolated low frequencies, an attention factor) needs where
    plain RoPE needs a base. Tuples of Python floats, built once a layer
    kind (``models/mellum2.py::rope_freqs``): hashable, so it can sit in a
    jitted program's static plan."""

    inv_freq: Tuple[float, ...]
    factor: float = 1.0


def rope_rotate(x: jnp.ndarray, pos: jnp.ndarray,
                base: Union[float, RopeFreqs] = 10000.0,
                interleaved: bool = False) -> jnp.ndarray:
    """Rotary position embedding, (B, S, H, D): the half-split convention
    (dims ``i`` and ``i + D/2`` turn together), or with ``interleaved`` the
    adjacent-pair one (dims ``2i`` and ``2i + 1``; ``rope_interleave`` in a
    DeepSeek-family config),
    with global positions ``pos`` — either ``(S,)`` shared across the
    batch (training / single-request decode) or ``(B, S)`` per-row (the
    serve tier's packed decode, where one batch holds requests at
    heterogeneous positions). ``base`` is plain RoPE's base, or a
    :class:`RopeFreqs` whose frequencies are used as they are and whose
    factor multiplies cos and sin. Pure elementwise rotation — composes with
    the flash kernel, ring/zigzag schedules (positions are
    layout-aware), and the KV cache (keys cached post-rotation)."""
    D = x.shape[-1]
    half = D // 2
    factor = 1.0
    if isinstance(base, RopeFreqs):
        inv_freq = jnp.asarray(base.inv_freq, jnp.float32)
        factor = base.factor
    else:
        inv_freq = 1.0 / (base ** (jnp.arange(half, dtype=jnp.float32)
                                   / half))
    if jnp.ndim(pos) == 2:
        ang = pos.astype(jnp.float32)[..., None] * inv_freq  # (B, S, half)
        cos = jnp.cos(ang)[:, :, None, :]
        sin = jnp.sin(ang)[:, :, None, :]
    else:
        ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
        cos = jnp.cos(ang)[None, :, None, :]
        sin = jnp.sin(ang)[None, :, None, :]
    if factor != 1.0:              # a plain base traces no multiply
        cos, sin = cos * factor, sin * factor
    xf = x.astype(jnp.float32)
    if interleaved:
        pairs = xf.reshape(*xf.shape[:-1], half, 2)
        x1, x2 = pairs[..., 0], pairs[..., 1]
        out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                        axis=-1).reshape(xf.shape)
        return out.astype(x.dtype)
    x1, x2 = xf[..., :half], xf[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1)
    return out.astype(x.dtype)


def _layernorm(x: jnp.ndarray, g: jnp.ndarray, b: jnp.ndarray,
               eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * g + b).astype(x.dtype)


def _rmsnorm(x: jnp.ndarray, g: jnp.ndarray, b=None,
             eps: float = 1e-5) -> jnp.ndarray:
    """Llama-style RMS norm. ``b`` is accepted for signature parity with
    layernorm but must be absent (RMSNorm has no bias — rmsnorm trees
    carry no ln*_b leaves)."""
    assert b is None, "rmsnorm trees carry no norm-bias leaf"
    xf = x.astype(jnp.float32)
    ms = (xf * xf).mean(-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * g).astype(x.dtype)


def _rmsnorm_zc(x: jnp.ndarray, g: jnp.ndarray, b=None,
                eps: float = 1e-6) -> jnp.ndarray:
    """The zero-centred RMS norm, ``x̂ · (1 + g)`` in f32 (``g`` is drawn
    and decayed around 0; Qwen3-Next's every norm but one)."""
    assert b is None, "rmsnorm trees carry no norm-bias leaf"
    xf = x.astype(jnp.float32)
    ms = (xf * xf).mean(-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps)
            * (1.0 + g.astype(jnp.float32))).astype(x.dtype)


_NORMS = {"layernorm": _layernorm, "rmsnorm": _rmsnorm,
          "rmsnorm_zero_centred": _rmsnorm_zc}


def resolve_norm(cfg: GPTConfig):
    """Validate cfg.norm and return the (norm_fn, eps) pair to thread to
    the blocks/readout."""
    if cfg.norm not in _NORMS:
        raise ValueError(f"unknown norm {cfg.norm!r} — expected one of "
                         f"{sorted(_NORMS)}")
    if not cfg.norm_eps > 0.0:
        raise ValueError(f"norm_eps must be > 0; got {cfg.norm_eps}")
    return _NORMS[cfg.norm], cfg.norm_eps


def _project(x, p, names, use_bias: bool, tp_axis=None, delta=None):
    """``x`` through the block's frozen projections ``names``: the one place
    a dense-family block weight is read. wq/wk/wv and w1/w3 are
    column-parallel (the outputs stay tp-sharded), wo and w2 row-parallel
    (psum over ``tp_axis``, the bias after it); each weight is cast to the
    activation dtype and the bias is absent under ``use_bias=False``. Beside
    each output go its LoRA deltas, at the same points for every caller: a
    grafted tree's first (``"lora" in p``), then the caller's
    ``delta(name, x)`` (the serve tier's per-row adapter slabs; None for a
    name it does not target). Returns one output per name."""
    from byteps_tpu.models.lora import _ROW_TARGETS, lora_delta

    def matmul(name):
        w = p[name].astype(x.dtype)
        # wq's bias is bq, w1's b1, ...
        b = p["b" + name[1:]].astype(x.dtype) if use_bias else None
        if name in _ROW_TARGETS:
            return row_parallel_matmul(x, w, tp_axis, b)
        return col_parallel_matmul(x, w, b)

    ys = [matmul(name) for name in names]
    if "lora" in p:
        ys = [y + lora_delta(x, p, name, tp_axis)
              for y, name in zip(ys, names)]
    if delta is not None:
        for i, name in enumerate(names):
            d = delta(name, x)
            if d is not None:
                ys[i] = ys[i] + d
    return ys


def _mlp(x, p, tp_axis, use_bias: bool = True, delta=None):
    (h,) = _project(x, p, ("w1",), use_bias, tp_axis, delta)
    if "w3" in p:
        # SwiGLU: silu-gated hidden (w1 value path ∘ w3 gate path); w1/w3
        # col-parallel over tp, w2 row-parallel as in the gelu MLP
        (g,) = _project(x, p, ("w3",), use_bias, tp_axis, delta)
        h = jax.nn.silu(h) * g
    else:
        h = jax.nn.gelu(h)
    return _project(h, p, ("w2",), use_bias, tp_axis, delta)[0]


def ring_attend(sp_axis, causal: bool = True,
                seq_layout: str = "contiguous"):
    """Training's ``attend``: ring attention over ``sp_axis`` in the given
    sequence layout (plain flash / jnp attention when ``sp_axis`` is None);
    no state to thread."""
    if seq_layout not in ("contiguous", "zigzag"):
        raise ValueError(f"unknown seq_layout {seq_layout!r} — expected "
                         "'contiguous' or 'zigzag'")
    ring = (zigzag_ring_attention if seq_layout == "zigzag"
            else ring_attention)
    # GQA: k/v stay NARROW (kv heads) — the flash kernels associate
    # query heads to kv heads by grid-index arithmetic, the jnp lse path
    # by grouped einsum, and the rings rotate the narrow blocks (G× less
    # ICI wire); only the legacy jnp contiguous-ring repeats internally
    return lambda q, k, v: (ring(q, k, v, sp_axis, causal=causal), None)


def attn_half(x, p, head_dim: int, positions, attend, tp_axis=None,
              rope_base: float = 0.0, norm_fn=_layernorm,
              norm_eps: float = 1e-5, use_bias: bool = True, delta=None):
    """First half of THE pre-norm block, ``x + attention(norm(x))``, for
    every caller: training, the static cache, T5's decoder and the paged
    serve step differ only in ``attend(q, k, v) -> (o, carry)``, which gets
    q ``(B, T, h, D)`` and k/v ``(B, T, h_kv, D)`` after RoPE and owns
    where the new keys go and what attends over them; ``carry`` is whatever
    state it threads (None, a layer's cache pair, the KV pool).
    ``positions()`` gives what RoPE rotates by (``(T,)``, or ``(B, T)``
    where every row has its own); it is called under RoPE only, so a
    learned-position program traces no position arithmetic. ``rope_base``: 0
    for none, a base, or a layer kind's :class:`RopeFreqs`.
    ``delta`` as in :func:`_project`. Returns ``(x, carry)``."""
    B, T = x.shape[:2]
    # named scopes are for an operator's xprof op profile; they change no
    # compiled program (docs/observability.md §spans)
    with jax.named_scope("block/attn"):
        h = norm_fn(x, p["ln1_g"], p.get("ln1_b"), norm_eps)
        q, k, v = _project(h, p, ("wq", "wk", "wv"), use_bias, tp_axis, delta)
        h_loc = q.shape[-1] // head_dim     # query heads this tp shard owns
        kv_loc = k.shape[-1] // head_dim    # kv heads (GQA: fewer)
        if kv_loc == 0 or h_loc % kv_loc != 0:
            raise ValueError(
                f"per-shard head split is invalid: {h_loc} query heads vs "
                f"{kv_loc} kv heads — with GQA under tensor parallelism, "
                "n_kv_heads must be divisible by the tp axis size")
        q = q.reshape(B, T, h_loc, head_dim)
        k = k.reshape(B, T, kv_loc, head_dim)
        v = v.reshape(B, T, kv_loc, head_dim)
        if isinstance(rope_base, RopeFreqs) or rope_base > 0.0:
            pos = positions()
            q = rope_rotate(q, pos, rope_base)
            k = rope_rotate(k, pos, rope_base)
        o, carry = attend(q, k, v)
        (out,) = _project(o.reshape(B, T, h_loc * head_dim), p, ("wo",),
                          use_bias, tp_axis, delta)
        return x + out, carry


def ffn_half(x, p, tp_axis=None, ffn=None, norm_fn=_layernorm,
             norm_eps: float = 1e-5, use_bias: bool = True, delta=None):
    """Second half of the block, ``x + ffn(norm(x))``. ``ffn(h) -> (out,
    aux)`` defaults to the dense :func:`_mlp` (aux None); the MoE families
    pass ``moe_ffn`` bound to their capacity rule. Returns ``(x, aux)``."""
    with jax.named_scope("block/mlp"):
        h = norm_fn(x, p["ln2_g"], p.get("ln2_b"), norm_eps)
        out, aux = ((_mlp(h, p, tp_axis, use_bias, delta), None)
                    if ffn is None else ffn(h))
        return x + out, aux


def transformer_block(x, p, head_dim: int, tp_axis=None, sp_axis=None,
                      causal: bool = True, seq_layout: str = "contiguous",
                      rope_base: float = 0.0, norm_fn=_layernorm,
                      norm_eps: float = 1e-5, use_bias: bool = True):
    """The block as training runs it, shared by the GPT (causal) and BERT /
    ViT / T5-encoder (bidirectional) families: :func:`attn_half` around
    ring attention (contiguous or zigzag sequence layout over ``sp_axis``)
    then the dense :func:`ffn_half`; tp col/row-parallel, optional RoPE
    (``rope_base > 0``), layernorm or rmsnorm (``norm_fn``), optional
    llama-style bias-free projections (``use_bias=False``)."""
    kw = dict(norm_fn=norm_fn, norm_eps=norm_eps, use_bias=use_bias)
    x, _ = attn_half(x, p, head_dim,
                     lambda: _positions(x.shape[1], sp_axis, seq_layout),
                     ring_attend(sp_axis, causal, seq_layout), tp_axis,
                     rope_base, **kw)
    return ffn_half(x, p, tp_axis, **kw)[0]


def block_init(rng, d: int, ff: int, hd: int, n_layers: int,
               kv_hd: int = None, mlp: str = "gelu",
               use_bias: bool = True, norm: str = "layernorm"):
    """One transformer block's params (shape shared across families).
    ``kv_hd`` (default ``hd``) narrows the k/v projections for GQA;
    ``mlp="swiglu"`` adds the gate matrix ``w3``; ``use_bias=False``
    omits the projection biases and ``norm="rmsnorm"`` the norm biases
    — absent, not zero, so no optimizer/compression state exists for
    them (see GPTConfig.use_bias)."""
    if mlp not in ("gelu", "swiglu"):
        raise ValueError(f"unknown mlp {mlp!r} — expected 'gelu' or "
                         "'swiglu'")
    std = 0.02
    if kv_hd is None:
        kv_hd = hd
    bk = jax.random.split(rng, 7)

    def dense(key, shape):
        return jax.random.normal(key, shape, jnp.float32) * std

    p = {
        "ln1_g": jnp.ones((d,), jnp.float32),
        "wq": dense(bk[0], (d, hd)),
        "wk": dense(bk[1], (d, kv_hd)),
        "wv": dense(bk[2], (d, kv_hd)),
        "wo": dense(bk[3], (hd, d)) / (2 * n_layers) ** 0.5,
        "ln2_g": jnp.ones((d,), jnp.float32),
        "w1": dense(bk[4], (d, ff)),
        "w2": dense(bk[5], (ff, d)) / (2 * n_layers) ** 0.5,
        **({"w3": dense(bk[6], (d, ff))} if mlp == "swiglu" else {}),
    }
    if norm == "layernorm":
        p["ln1_b"] = jnp.zeros((d,), jnp.float32)
        p["ln2_b"] = jnp.zeros((d,), jnp.float32)
    if use_bias:
        p.update({
            "bq": jnp.zeros((hd,), jnp.float32),
            "bk": jnp.zeros((kv_hd,), jnp.float32),
            "bv": jnp.zeros((kv_hd,), jnp.float32),
            "bo": jnp.zeros((d,), jnp.float32),
            "b1": jnp.zeros((ff,), jnp.float32),
            "b2": jnp.zeros((d,), jnp.float32),
            **({"b3": jnp.zeros((ff,), jnp.float32)} if mlp == "swiglu"
               else {}),
        })
    return p


def block_logical_specs(mlp: str = "gelu", use_bias: bool = True,
                        norm: str = "layernorm") -> Dict[str, Any]:
    """Logical-axis dict for one transformer block: qkv/w1 are
    column-parallel (output dim = heads/kv/mlp), wo/w2 row-parallel
    (input dim likewise), biases follow their weight's output dim."""
    s = {
        "ln1_g": ("embed",),
        "wq": ("embed", "heads"), "wk": ("embed", "kv"),
        "wv": ("embed", "kv"),
        "wo": ("heads", "embed"), "ln2_g": ("embed",),
        "w1": ("embed", "mlp"), "w2": ("mlp", "embed"),
        **({"w3": ("embed", "mlp")} if mlp == "swiglu" else {}),
    }
    if norm == "layernorm":
        s["ln1_b"] = ("embed",)
        s["ln2_b"] = ("embed",)
    if use_bias:
        s.update({
            "bq": ("heads",), "bk": ("kv",), "bv": ("kv",),
            "bo": ("embed",),
            "b1": ("mlp",), "b2": ("embed",),
            **({"b3": ("mlp",)} if mlp == "swiglu" else {}),
        })
    return s


def block_specs(tp_axis, mlp: str = "gelu", use_bias: bool = True,
                norm: str = "layernorm"):
    """PartitionSpec dict for one transformer block (see gpt_param_specs)."""
    from byteps_tpu.parallel.partitioner import resolve_specs, rules_from_axes
    return resolve_specs(block_logical_specs(mlp, use_bias, norm),
                         rules_from_axes(tp_axis=tp_axis))


def _embed(params, tokens: jnp.ndarray, cfg: GPTConfig,
           sp_axis, seq_layout: str = "contiguous") -> jnp.ndarray:
    """Token + position embeddings with the sequence-shard offset, shared
    by the dense and pipelined paths. Under the zigzag layout the local
    tokens are this device's (early, late) chunk pair and the positions
    follow (`zigzag_local_positions`)."""
    S_loc = tokens.shape[1]
    if cfg.pos_embedding == "rope":
        # positions enter through the per-layer q/k rotations instead
        return params["wte"][tokens].astype(cfg.dtype)
    pos = _positions(S_loc, sp_axis, seq_layout)
    return (params["wte"][tokens]
            + jnp.take(params["wpe"], pos, axis=0)).astype(cfg.dtype)


@jax.custom_vjp
def head_dot(h: jnp.ndarray, head: jnp.ndarray) -> jnp.ndarray:
    """Readout matmul in the ACTIVATION dtype with f32 accumulation.

    ``h (..., d) @ head (d, V) → f32 logits``. The head weight casts to
    ``h.dtype`` for the dot — the same per-op cast every block matmul
    does (``p["wq"].astype(x.dtype)``); the readout was the one op that
    upcast to f32 instead, and the round-5 xprof attribution measured
    those f32 MXU passes at ~3× the cost (flagship: 2.4 ms of a 14 ms
    step; gpt2m: 4.0 ms) for no numerics the f32 *accumulation* doesn't
    already provide. With f32 activations (every test/parity config)
    the casts are no-ops and this is bit-identical to the f32 matmul.

    The custom VJP keeps the backward dots in the activation dtype too
    (cotangent rounds to ``h.dtype``, matching what the block weight
    grads already do through their bf16 dot outputs) while the head
    gradient accumulates — and is returned — in f32, so the optimizer
    update on the fp32 master weight loses nothing.
    """
    from byteps_tpu.ops.flash_attention import _unify_vma

    hu, hd = _unify_vma(h, head.astype(h.dtype))
    return jax.lax.dot_general(
        hu, hd, (((h.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _head_dot_fwd(h, head):
    return head_dot(h, head), (h, head)


def _head_dot_bwd(res, g):
    # Cotangent vma must match the primals' (shard_map check_vma): the
    # activation grad keeps h's varying axes; the head grad psums over
    # every axis h varies on that head doesn't — exactly the
    # pvary-transpose adjoint plain AD inserts for a replicated weight
    # used in a varying context (cf. the _novma_collective_fix note in
    # jax/optimizer.py).
    from byteps_tpu.ops.flash_attention import _unify_vma

    h, head = res
    gc = g.astype(h.dtype)
    gcu, hd, hu = _unify_vma(gc, head.astype(h.dtype), h)
    dh = jax.lax.dot_general(
        gcu, hd, (((g.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(h.dtype)
    lead = tuple(range(h.ndim - 1))
    dhead = jax.lax.dot_general(
        hu, gcu, ((lead, lead), ((), ())),
        preferred_element_type=jnp.float32).astype(head.dtype)
    try:
        extra = tuple(jax.typeof(h).vma - jax.typeof(head).vma)
    except (AttributeError, TypeError):
        extra = ()
    if extra:
        dhead = jax.lax.psum(dhead, extra)
    return dh, dhead


head_dot.defvjp(_head_dot_fwd, _head_dot_bwd)


def _readout(params, h: jnp.ndarray, norm_fn=_layernorm,
             norm_eps: float = 1e-5) -> jnp.ndarray:
    """Final norm → f32-accumulated readout in the activation dtype
    (weight-tied ``wte.T`` unless the tree carries an untied
    ``lm_head``), shared by the dense and pipelined paths so their
    numerics cannot diverge. f32 activations (the default config, every
    parity test, the HF bridge) keep the exact f32 matmul."""
    h = norm_fn(h, params["lnf_g"], params.get("lnf_b"), norm_eps)
    head = (params["lm_head"] if "lm_head" in params
            else params["wte"].T)
    return head_dot(h, head.astype(jnp.float32))


def _nll(logits: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def _readout_nll(params, h: jnp.ndarray, targets: jnp.ndarray,
                 norm_fn=_layernorm, norm_eps: float = 1e-5,
                 tp_axis: Optional[str] = None,
                 chunked=True) -> jnp.ndarray:
    """Final norm → per-token next-token NLL, shared by every
    logits-bearing family (GPT dense/pipelined, MoE, T5 decoder).

    ``chunked`` is the tri-state ``chunked_ce`` knob (see
    :func:`gpt_loss`): truthy routes through the fused readout+CE path
    (``ops/chunked_ce.py``) — the f32 (..., V) logits never materialize
    — with ``"vocab_parallel"`` additionally splitting the vocab over
    ``tp_axis`` (V/ntp per device, stats psum'd before the
    log-partition). ``False`` is the dense escape hatch — the
    ``head_dot`` + ``log_softmax`` chain, bit-identical to the chunked
    path on single-device f32 configs and the golden it is pinned
    against."""
    with jax.named_scope("readout_ce"):
        h = norm_fn(h, params["lnf_g"], params.get("lnf_b"), norm_eps)
        head = (params["lm_head"] if "lm_head" in params
                else params["wte"].T).astype(jnp.float32)
        if chunked:
            from byteps_tpu.ops.chunked_ce import chunked_ce_nll

            return chunked_ce_nll(
                h, head, targets,
                tp_axis=tp_axis if chunked == "vocab_parallel" else None)
        return _nll(head_dot(h, head), targets)


def gpt_hidden(params, tokens: jnp.ndarray, cfg: GPTConfig,
               tp_axis: Optional[str] = None,
               sp_axis: Optional[str] = None,
               remat: bool = False,
               seq_layout: str = "contiguous") -> jnp.ndarray:
    """Embeddings → transformer blocks, STOPPING before the final norm +
    readout: the shared trunk of :func:`gpt_forward` (dense logits) and
    :func:`gpt_loss`'s fused readout+CE path (which never materializes
    them)."""
    rope_base = resolve_rope(cfg)
    norm_fn, norm_eps = resolve_norm(cfg)
    with jax.named_scope("embed"):
        x = _embed(params, tokens, cfg, sp_axis, seq_layout)

    def apply_block(x, p):
        return transformer_block(x, p, cfg.head_dim, tp_axis, sp_axis,
                                 causal=True, seq_layout=seq_layout,
                                 rope_base=rope_base, norm_fn=norm_fn,
                                 norm_eps=norm_eps, use_bias=cfg.use_bias)

    # rematerialize per block: activations recomputed in backward — HBM
    # for FLOPs, the long-context lever (see maybe_remat for the tp/sp
    # collective-recompute caveat)
    apply_block = maybe_remat(apply_block, remat)
    for p in params["blocks"]:
        x = apply_block(x, p)
    return x


def gpt_forward(params, tokens: jnp.ndarray, cfg: GPTConfig,
                tp_axis: Optional[str] = None,
                sp_axis: Optional[str] = None,
                remat: bool = False,
                seq_layout: str = "contiguous") -> jnp.ndarray:
    """Per-device forward: tokens (B_local, S_local) → logits (f32).

    Single chip: all axes None, tokens are the whole batch/sequence.
    Inside shard_map: tokens are this device's (dp, sp) block and the
    weights its tp shard; output logits stay tp/dp/sp-local (replicated
    over tp by construction).
    """
    x = gpt_hidden(params, tokens, cfg, tp_axis, sp_axis, remat=remat,
                   seq_layout=seq_layout)
    # f32 logits for a stable softmax/loss
    return _readout(params, x, *resolve_norm(cfg))


def gpt_pp_loss(params, tokens, targets, cfg: GPTConfig,
                pp_axis: str, n_micro: int,
                tp_axis: Optional[str] = None,
                sp_axis: Optional[str] = None,
                remat: bool = False,
                vma_axes: tuple = (),
                seq_layout: str = "contiguous",
                chunked_ce=True) -> jnp.ndarray:
    """Pipeline-parallel next-token loss (inside shard_map over pp).
    ``chunked_ce``: the tri-state fused readout+CE knob — see
    :func:`gpt_loss`.

    ``params["blocks"]`` is THIS stage's stacked layer slab
    ((n_layers/pp, ...) — build with ``stack_blocks`` + ``stacked_specs``);
    embeddings / final LN are pp-replicated. The batch is split into
    ``n_micro`` microbatches and pipelined through the stages
    (:func:`byteps_tpu.parallel.pipeline.pipeline_apply`); the last stage
    computes the readout + loss; the returned value is the MASKED per-stage
    loss (nonzero only on the last stage). Differentiate THIS value —
    grading an already-psum'd replica double-counts through the psum
    transpose under ``check_vma=False`` — and replicate it afterwards for
    reporting (``last_stage_value``). Per-device ``jax.grad`` then yields
    stage-local slab grads plus stage-partial grads for the replicated
    leaves (psum those over pp).
    """
    from byteps_tpu.parallel.pipeline import pipeline_apply

    B, S_loc = tokens.shape
    if B % n_micro != 0:
        raise ValueError(f"local batch {B} not divisible by {n_micro} "
                         "microbatches")
    x = _embed(params, tokens, cfg, sp_axis, seq_layout)
    x_mb = x.reshape(n_micro, B // n_micro, S_loc, x.shape[-1])

    rope_base = resolve_rope(cfg)
    norm_fn, norm_eps = resolve_norm(cfg)

    def blk(h, p):
        return transformer_block(
            h, p, cfg.head_dim, tp_axis, sp_axis, causal=True,
            seq_layout=seq_layout, rope_base=rope_base, norm_fn=norm_fn,
            norm_eps=norm_eps, use_bias=cfg.use_bias)

    y_mb = pipeline_apply(x_mb, params["blocks"], blk, pp_axis,
                          remat=remat, vma_axes=vma_axes)
    y = y_mb.reshape(B, S_loc, -1)
    nll = _readout_nll(params, y, targets, norm_fn, norm_eps,
                       tp_axis=tp_axis, chunked=chunked_ce)
    loss = nll.mean()
    if sp_axis is not None:
        # mean over the sequence shards (inside the grad — VMA types the
        # sp pmean's transpose correctly, unlike the pp axis below)
        loss = jax.lax.pmean(loss, sp_axis)
    # only the last stage's outputs are real; other stages' readout math
    # above is masked dead weight (grads through it are zeroed here)
    stage = jax.lax.axis_index(pp_axis)
    nstages = jax.lax.axis_size(pp_axis)
    return jnp.where(stage == nstages - 1, loss, 0.0)


def gpt_loss(params, tokens, targets, cfg: GPTConfig,
             dp_axis: Optional[str] = None,
             tp_axis: Optional[str] = None,
             sp_axis: Optional[str] = None,
             remat: bool = False,
             seq_layout: str = "contiguous",
             chunked_ce=True) -> jnp.ndarray:
    """Mean next-token cross-entropy, identical (replicated) on every device.

    The replication is what makes per-device ``jax.grad`` correct under
    shard_map: tp-sharded weights then need NO gradient collective, while
    dp/sp-replicated weights need a psum over (dp, sp) — exactly the
    aggregation `DistributedOptimizer` / `sync_grads` provide.

    ``chunked_ce`` is tri-state: ``True`` (default) fuses readout+CE so
    the f32 (B, S, V) logits never materialize (``ops/chunked_ce.py``),
    with the vocab replicated over tp — per-device math identical to the
    single-device path, so every cross-mesh equivalence pin holds
    bit-tight. ``"vocab_parallel"`` additionally splits the readout's
    vocab over tp (V/ntp logit columns per device — ntp× less readout
    GEMM and live logits; the tp stat-combine reassociates the sum-exp,
    so dp×tp drifts from dp-only by f32 roundoff — opt in where the
    memory/FLOPs win outweighs cross-mesh bit-parity). ``False`` is the
    dense golden path.
    """
    x = gpt_hidden(params, tokens, cfg, tp_axis, sp_axis, remat=remat,
                   seq_layout=seq_layout)
    nll = _readout_nll(params, x, targets, *resolve_norm(cfg),
                       tp_axis=tp_axis, chunked=chunked_ce)
    loss = nll.mean()
    axes = tuple(a for a in (dp_axis, sp_axis) if a is not None)
    if axes:
        loss = jax.lax.pmean(loss, axes)
    return loss
