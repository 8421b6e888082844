"""Autoregressive generation with a KV cache for the GPT family.

The reference is a training system (its inference story is "export to the
host framework"); a standalone framework needs the decode path too. The
TPU-idiomatic form: a static-shape KV cache ``(n_layers, B, max_seq, H,
D)`` updated in place with ``dynamic_update_slice`` inside a
``lax.scan`` over positions — one traced XLA program for the whole
generation, no per-token retrace, MXU-friendly (the decode matmuls are
(B·H, 1, D) × (D, S) batched GEMVs that XLA tiles together).

Weights are exactly the training params (`gpt.py`) and so is the block:
``_block_step`` runs ``gpt.py``'s ``attn_half`` / ``ffn_half`` — norms,
Megatron col/row-parallel projections (tp composes: q/k/v/cache shard over
heads, the output projection psums), LoRA deltas, RoPE — and owns only
:func:`cache_attend`, where the new keys go and what attends over them.
Causality is positional masking against the cache fill level, so prefill
and decode share one cached-attention implementation whose numerics are
pinned to ``gpt_forward`` in ``tests/test_generate.py``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from byteps_tpu.models.gpt import (
    GPTConfig,
    _layernorm,
    _readout,
    attn_half,
    ffn_half,
    resolve_norm,
    resolve_rope,
)


class KVCache(NamedTuple):
    """Static-shape per-layer key/value cache.

    k/v: (n_layers, B, max_seq, h_loc, head_dim); ``length`` is the fill
    level (tokens already written). Under tp, h_loc is this shard's head
    count — the cache is a per-device value inside shard_map.

    With ``init_cache(..., quant=True)`` k/v are int8 and ``k_scale`` /
    ``v_scale`` (n_layers, B, max_seq, h_loc) hold per-(position, head)
    fp32 dequantization scales — cache HBM drops to ~(1 + 4/head_dim)
    bytes/element, about half of bf16, the lever that doubles the decode
    batch or context a chip can hold. Dense caches leave the scale
    fields None (the pytree stays scan-carry compatible either way).
    """
    k: jnp.ndarray
    v: jnp.ndarray
    length: jnp.ndarray        # () int32
    k_scale: Optional[jnp.ndarray] = None
    v_scale: Optional[jnp.ndarray] = None


def init_cache(cfg: GPTConfig, batch: int, h_loc: Optional[int] = None,
               max_seq: Optional[int] = None,
               quant: bool = False) -> KVCache:
    h = h_loc if h_loc is not None else cfg.n_heads
    S = max_seq if max_seq is not None else cfg.max_seq
    shape = (cfg.n_layers, batch, S, h, cfg.head_dim)
    if quant:
        return KVCache(
            k=jnp.zeros(shape, jnp.int8),
            v=jnp.zeros(shape, jnp.int8),
            length=jnp.zeros((), jnp.int32),
            k_scale=jnp.zeros(shape[:-1], jnp.float32),
            v_scale=jnp.zeros(shape[:-1], jnp.float32),
        )
    return KVCache(
        k=jnp.zeros(shape, cfg.dtype),
        v=jnp.zeros(shape, cfg.dtype),
        length=jnp.zeros((), jnp.int32),
    )


class _QuantSlot(NamedTuple):
    """One layer's quantized cache side: int8 values + fp32 scales.
    A distinct type (not a bare tuple) so the polymorphic dispatch in
    _cache_write/_cache_read can never mistake another tuple-shaped
    value — KVCache itself is a NamedTuple — for a quantized slot."""
    q: jnp.ndarray
    scale: jnp.ndarray


def _quantize_block(x):
    """(B, T, h, D) → (int8 values, fp32 per-(B,T,h) scales).

    Symmetric absmax scaling over the head_dim axis: exact for inputs
    that already sit on their scale grid, ≤ scale/2 rounding error
    otherwise. A zero block gets scale eps (dequantizes to exact zeros).
    """
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-12)
    q = jnp.round(x.astype(jnp.float32) / scale[..., None])
    return q.astype(jnp.int8), scale


def _cache_write(cache, new, pos0):
    """Append ``new`` (B, T, h, D) at position pos0. ``cache`` is either
    a dense (B, S, h, D) array or a :class:`_QuantSlot` — the quantized
    form flows through _block_step/cache_attend polymorphically so
    the T5/MoE users of the same code path stay untouched."""
    if isinstance(cache, _QuantSlot):
        q, s = _quantize_block(new)
        return _QuantSlot(
            jax.lax.dynamic_update_slice(cache.q, q, (0, pos0, 0, 0)),
            jax.lax.dynamic_update_slice(cache.scale, s, (0, pos0, 0)),
        )
    return jax.lax.dynamic_update_slice(
        cache, new.astype(cache.dtype), (0, pos0, 0, 0))


def _cache_read(cache, dtype):
    """Materialize the attention-ready (B, S, h, D) view in ``dtype``;
    int8 entries dequantize through their scales. On the jnp decode
    path XLA fuses the multiply into the attention dot (reads stay
    int8); the Pallas prefill kernel takes concrete operands, so there
    the dequantized view is materialized once per prefill — the
    *persistent* cache footprint is what halves either way."""
    if isinstance(cache, _QuantSlot):
        return (cache.q.astype(jnp.float32)
                * cache.scale[..., None]).astype(dtype)
    return cache


def _cached_attention(q, k_cache, v_cache, q_pos0, block=None):
    """q: (B, T, H, D) new queries at positions q_pos0..q_pos0+T-1;
    k/v_cache: (B, S_max, H, D) with the new keys already written.
    Causal-masks against global positions, so entries past the fill level
    (zeros) are masked out by construction. Long prefills (tileable T)
    ride the flash kernel — same global-offset masking; single-token
    decode (T=1) stays on the fused-GEMV jnp path automatically.
    ``block``: the mask is block-causal instead (a query sees all of its
    own block of that many positions; the T new queries end on a block's
    boundary, so nothing past the fill level is seen either)."""
    from byteps_tpu.ops.flash_attention import (
        attention_lse, flash_attention_block_causal)

    if block is not None:
        return flash_attention_block_causal(q, k_cache, v_cache, q_pos0, 0,
                                            block)
    o, _ = attention_lse(q, k_cache, v_cache, q_pos0, 0, causal=True)
    return o


def cache_attend(cache_k, cache_v, pos0, block=None):
    """The static cache's ``attend`` for :func:`attn_half`: append the T
    new keys and values (already rotated: cached keys are stored
    post-RoPE, the standard decode convention) to this layer's cache at
    ``pos0``, then attend over the cache. cache_k/v: (B, S_max, h_kv, D)
    arrays or :class:`_QuantSlot`s; the carry is the updated pair.
    Config-agnostic on purpose: the GPT/MoE block step, the serve tier's
    prefill chunk AND the T5 decoder (models/t5.py t5_decode_cached) share
    this one cache-append path. ``block``: as :func:`_cached_attention`
    takes it (a prefill chunk under a block-causal mask)."""
    from byteps_tpu.ops.backend import note_fallback
    from byteps_tpu.ops.flash_decode import (
        decode_supported, flash_decode, use_pallas)

    def attend(q, k, v):
        T, head_dim = q.shape[1], q.shape[-1]
        ck = _cache_write(cache_k, k, pos0)
        cv = _cache_write(cache_v, v, pos0)
        # GQA is native on every path — prefill and decode read the narrow
        # cache directly, no repeat anywhere. The T=1 decode step takes the
        # flash-decode kernel when available: one explicit VMEM online-
        # softmax pass over the stored cache (int8 read directly, dequant
        # per block in VMEM with _cache_read's rounding), dead blocks
        # skipped past the fill level.
        S_max = (ck.q if isinstance(ck, _QuantSlot) else ck).shape[1]
        flash = T == 1 and block is None and use_pallas()
        if flash and not decode_supported(S_max, head_dim):
            note_fallback("flash_decode", (S_max, head_dim),
                          "cache length must tile into 8..256 key blocks "
                          "and head_dim be <= 256")
            flash = False
        if not flash:
            o = _cached_attention(q, _cache_read(ck, q.dtype),
                                  _cache_read(cv, q.dtype), pos0, block)
        elif isinstance(ck, _QuantSlot):
            o = flash_decode(q, ck.q, cv.q, pos0,
                             k_scale=ck.scale, v_scale=cv.scale)
        else:
            o = flash_decode(q, ck, cv, pos0)
        return o, (ck, cv)

    return attend


def _block_step(x, p, cache_k, cache_v, pos0, cfg, tp_axis, ep_axis,
                norm_fn=_layernorm, norm_eps: float = 1e-5, rope=None,
                ffn=None, attn=None, block=None):
    """One transformer block (dense-MLP or MoE, by param structure) over
    T new tokens with cache append: the shared halves of ``models/gpt.py``
    around :func:`cache_attend`. ``rope`` (default: the config's one base)
    and ``ffn`` (``h -> (out, aux)``; default: by param structure) are a
    caller's whose layers differ in either, ``attn`` one whose first half is
    not :func:`attn_half` (same signature and ``attend`` contract), ``block``
    one whose mask is block-causal. Returns ``(x, cache_k, cache_v)``,
    and ``ffn``'s ``aux`` after them where one was given."""
    kw = dict(norm_fn=norm_fn, norm_eps=norm_eps, use_bias=cfg.use_bias)
    x, (cache_k, cache_v) = (attn_half if attn is None else attn)(
        x, p, cfg.head_dim, lambda: pos0 + jnp.arange(x.shape[1]),
        cache_attend(cache_k, cache_v, pos0, block), tp_axis,
        resolve_rope(cfg) if rope is None else rope, **kw)
    if ffn is not None:
        x, aux = ffn_half(x, p, tp_axis, ffn, **kw)
        return x, cache_k, cache_v, aux
    if "moe" in p:
        from byteps_tpu.parallel.moe import moe_ffn

        # inference uses no-drop capacity: the training capacity_factor
        # is a throughput/static-shape lever, and a dropped token at
        # decode time silently corrupts the sample
        ffn = functools.partial(
            moe_ffn, params=p["moe"], ep_axis=ep_axis,
            router_topk=cfg.router_topk, tp_axis=tp_axis, no_drop=True)
    x, _ = ffn_half(x, p, tp_axis, ffn, **kw)
    return x, cache_k, cache_v


def _embed(params, tokens, positions, cfg: GPTConfig):
    """The cached paths' input embedding: ``tokens`` (B, T) at global
    ``positions`` ((T,), or (B, 1) where every row decodes at its own) →
    (B, T, d) in ``cfg.dtype``. One copy for ``gpt_apply_cached`` and the
    serve tier's paged steps, which must stay bit-identical to it."""
    resolve_rope(cfg)   # validate the position scheme decode-side too
    if cfg.pos_embedding == "rope":
        return params["wte"][tokens].astype(cfg.dtype)
    return (params["wte"][tokens]
            + jnp.take(params["wpe"], positions, axis=0)).astype(cfg.dtype)


def gpt_apply_cached(params, tokens: jnp.ndarray, cache: KVCache,
                     cfg: GPTConfig, tp_axis: Optional[str] = None,
                     ep_axis: Optional[str] = None,
                     readout: bool = True
                     ) -> Tuple[Optional[jnp.ndarray], KVCache]:
    """Run T new tokens through the model, appending to the cache.

    tokens: (B, T) continuing at position ``cache.length``. Returns
    (logits (B, T, vocab) f32, updated cache). T=prompt length is the
    prefill; T=1 is one decode step — same code, pinned to
    ``gpt_forward`` numerics either way. Serves both the dense and the
    MoE GPT families (block type detected from the params; ``ep_axis``
    shards the experts inside shard_map).

    ``readout=False`` skips the vocab projection and returns
    ``(None, cache)`` — the serve tier's intermediate prefill chunks
    only need the cache side, and at real vocab sizes the readout is
    the single largest weight stream in the step.
    """
    norm_fn, norm_eps = resolve_norm(cfg)
    B, T = tokens.shape
    pos0 = cache.length
    x = _embed(params, tokens, pos0 + jnp.arange(T), cfg)

    quant = cache.k_scale is not None
    new_k, new_v, new_ks, new_vs = [], [], [], []
    for li, p in enumerate(params["blocks"]):
        ck = (_QuantSlot(cache.k[li], cache.k_scale[li]) if quant
              else cache.k[li])
        cv = (_QuantSlot(cache.v[li], cache.v_scale[li]) if quant
              else cache.v[li])
        x, ck, cv = _block_step(x, p, ck, cv, pos0, cfg, tp_axis, ep_axis,
                                norm_fn=norm_fn, norm_eps=norm_eps)
        if quant:
            new_k.append(ck.q)
            new_ks.append(ck.scale)
            new_v.append(cv.q)
            new_vs.append(cv.scale)
        else:
            new_k.append(ck)
            new_v.append(cv)
    logits = _readout(params, x, norm_fn, norm_eps) if readout else None
    return logits, KVCache(
        k=jnp.stack(new_k), v=jnp.stack(new_v), length=pos0 + T,
        k_scale=jnp.stack(new_ks) if quant else None,
        v_scale=jnp.stack(new_vs) if quant else None,
    )


def make_truncate(top_k: Optional[int], top_p: Optional[float],
                  vocab_size: int):
    """Build the per-step logits filter shared by every sampler (GPT/MoE
    and T5): mask logits outside the top-k set / the top-p nucleus (both
    computed on the raw distribution; with both set, a token must pass
    both filters). top_k-only takes a partial lax.top_k; any top_p pays
    one descending sort that also serves the top_k threshold. Ties at
    the k-th (or nucleus-edge) logit are ALL kept — standard >=-threshold
    behavior, so sampling is not strictly limited to k tokens when the
    boundary value repeats."""
    if top_k is not None and not 1 <= top_k <= vocab_size:
        raise ValueError(f"top_k must be in [1, vocab]; got {top_k}")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1]; got {top_p}")

    def _truncate(logits_t):
        if top_k is None and top_p is None:
            return logits_t
        if top_p is None:
            # top_k only: a partial top-k beats the full vocab sort
            vals = jax.lax.top_k(logits_t, top_k)[0]
            return jnp.where(logits_t >= vals[:, -1:], logits_t, -jnp.inf)
        thresh = jnp.full_like(logits_t[:, :1], -jnp.inf)
        sorted_desc = jnp.sort(logits_t, axis=-1)[:, ::-1]
        if top_k is not None:
            thresh = jnp.maximum(thresh, sorted_desc[:, top_k - 1:top_k])
        if top_p is not None:
            probs = jax.nn.softmax(sorted_desc, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            # keep every token whose PRECEDING cumulative mass < top_p
            # (the nucleus always includes the argmax)
            keep = jnp.concatenate(
                [jnp.zeros_like(cum[:, :1]), cum[:, :-1]], axis=-1) < top_p
            thresh = jnp.maximum(thresh, jnp.min(
                jnp.where(keep, sorted_desc, jnp.inf), axis=-1,
                keepdims=True))
        return jnp.where(logits_t >= thresh, logits_t, -jnp.inf)

    return _truncate


def make_pick(truncate):
    """Per-step token selection shared by every sampler: exact argmax at
    ``temperature == 0``, otherwise categorical over the truncated
    logits at ``temperature`` (floored at 1e-6 so the jitted branchless
    select never divides by zero)."""

    def pick(logits_t, key, temperature):
        greedy = jnp.argmax(logits_t, axis=-1).astype(jnp.int32)
        temp = jnp.maximum(temperature, 1e-6)
        sampled = jax.random.categorical(key, truncate(logits_t) / temp,
                                         axis=-1)
        return jnp.where(temperature > 0.0, sampled.astype(jnp.int32),
                         greedy)

    return pick


def make_generate_fn(cfg: GPTConfig, max_new: int,
                     tp_axis: Optional[str] = None,
                     ep_axis: Optional[str] = None,
                     top_k: Optional[int] = None,
                     top_p: Optional[float] = None,
                     quant_cache: bool = False):
    """Build a jitted sampler: ``gen(params, prompt, rng, temperature)``.

    prompt: (B, T0) int32; returns (B, T0 + max_new) tokens. Greedy when
    ``temperature == 0`` (exact argmax — the equivalence-vs-gpt_forward
    test drives this), categorical sampling otherwise, optionally
    truncated to the ``top_k`` highest-probability tokens and/or the
    ``top_p`` nucleus (smallest set with cumulative probability ≥ top_p,
    computed at temperature 1 then resampled at ``temperature``). One XLA
    program: cached prefill + ``lax.scan`` over max_new decode steps.

    ``quant_cache=True`` stores k/v as int8 with per-(position, head)
    scales (see :class:`KVCache`) — ~half the cache HBM of bf16 at a
    small, bounded numerics cost (symmetric absmax, ≤ scale/2 per
    element).
    """
    _pick = make_pick(make_truncate(top_k, top_p, cfg.vocab_size))

    @functools.partial(jax.jit, static_argnames=())
    def gen(params, prompt, rng, temperature=0.0):
        B, T0 = prompt.shape
        if T0 + max_new > cfg.max_seq:
            # static shapes: past max_seq the cache write offset would
            # clamp (overwriting the last slot) and wpe positions clip —
            # fail at trace time instead of generating garbage
            raise ValueError(
                f"prompt ({T0}) + max_new ({max_new}) exceeds "
                f"cfg.max_seq ({cfg.max_seq})")
        # under tp (inside shard_map) the projections are head-sharded —
        # size the cache from this device's wk shard (GQA: kv heads only,
        # the cache-memory lever)
        kv_loc = params["blocks"][0]["wk"].shape[-1] // cfg.head_dim
        cache = init_cache(cfg, B, h_loc=kv_loc, quant=quant_cache)
        logits, cache = gpt_apply_cached(params, prompt, cache, cfg, tp_axis,
                                         ep_axis)
        last = logits[:, -1]

        def step(carry, key):
            cache, last_logits = carry
            tok = _pick(last_logits, key, temperature)        # (B,)
            logits, cache = gpt_apply_cached(
                params, tok[:, None], cache, cfg, tp_axis, ep_axis)
            return (cache, logits[:, 0]), tok

        keys = jax.random.split(rng, max_new)
        (_, _), toks = jax.lax.scan(step, (cache, last), keys)
        return jnp.concatenate([prompt, toks.T], axis=1)

    return gen
