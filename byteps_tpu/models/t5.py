"""T5-style encoder–decoder with teacher-forced seq2seq loss.

The reference ships no models (SURVEY §1); the zoo's text families so far
are decoder-only (GPT) and encoder-only (BERT). T5 completes the
transformer triptych with the one structural piece neither has:
**cross-attention** — decoder queries over encoder memory. Built from the
same shared parts as the rest of the zoo:

* encoder blocks ARE :func:`byteps_tpu.models.gpt.transformer_block`
  (``causal=False``), so tp col/row sharding and per-block remat carry
  over unchanged;
* decoder blocks add a pre-LN cross-attention sublayer between the
  causal self-attention and the MLP; its q/k/v/o projections use the
  same Megatron col/row-parallel helpers, and the attention core runs
  the flash kernel where supported (``plain_attention`` dispatches);
* embeddings/readout are tied (``wte``), learned absolute positions per
  side, mirroring the GPT family's conventions.

Sequence parallelism (round 4): both sides shard over sp — the encoder
runs the non-causal ring, the decoder the causal ring, and
cross-attention a RECTANGULAR non-causal ring (stationary decoder-query
blocks, rotating encoder-memory k/v blocks — the ring helpers take the
k block's own length for offsets). Positions are sp-aware on both sides.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from byteps_tpu.models.gpt import (
    _layernorm,
    _nll,
    _positions as _gpt_positions,
    _readout,
    attn_half,
    block_init,
    block_specs,
    ffn_half,
    ring_attend,
    transformer_block,
)
from byteps_tpu.parallel.remat import maybe_remat
from byteps_tpu.parallel.ring_attention import (
    plain_attention,
    ring_attention,
)
from byteps_tpu.parallel.tp import col_parallel_matmul, row_parallel_matmul


@dataclasses.dataclass(frozen=True)
class T5Config:
    vocab_size: int = 32128
    max_src: int = 512
    max_tgt: int = 512
    d_model: int = 768
    n_heads: int = 12
    n_enc_layers: int = 12
    n_dec_layers: int = 12
    d_ff: int = 3072
    dtype: Any = jnp.float32

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @classmethod
    def tiny(cls) -> "T5Config":
        return cls(vocab_size=256, max_src=64, max_tgt=64, d_model=64,
                   n_heads=4, n_enc_layers=2, n_dec_layers=2, d_ff=128)

    @classmethod
    def base(cls) -> "T5Config":
        return cls(dtype=jnp.bfloat16)


def _cross_init(rng, d: int, hd: int, n_layers: int) -> Dict[str, Any]:
    """Cross-attention sublayer params (decoder q over encoder k/v)."""
    std = 0.02
    ks = jax.random.split(rng, 4)

    def dense(key, shape):
        return jax.random.normal(key, shape, jnp.float32) * std

    return {
        "lnx_g": jnp.ones((d,), jnp.float32),
        "lnx_b": jnp.zeros((d,), jnp.float32),
        "xwq": dense(ks[0], (d, hd)), "xbq": jnp.zeros((hd,), jnp.float32),
        "xwk": dense(ks[1], (d, hd)), "xbk": jnp.zeros((hd,), jnp.float32),
        "xwv": dense(ks[2], (d, hd)), "xbv": jnp.zeros((hd,), jnp.float32),
        "xwo": dense(ks[3], (hd, d)) / (2 * n_layers) ** 0.5,
        "xbo": jnp.zeros((d,), jnp.float32),
    }


def _cross_logical_specs() -> Dict[str, Any]:
    return {
        "lnx_g": ("embed",), "lnx_b": ("embed",),
        "xwq": ("embed", "heads"), "xbq": ("heads",),
        "xwk": ("embed", "kv"), "xbk": ("kv",),
        "xwv": ("embed", "kv"), "xbv": ("kv",),
        "xwo": ("heads", "embed"), "xbo": ("embed",),
    }


def _cross_specs(tp_axis) -> Dict[str, Any]:
    from byteps_tpu.parallel.partitioner import resolve_specs, rules_from_axes
    return resolve_specs(_cross_logical_specs(),
                         rules_from_axes(tp_axis=tp_axis))


def cross_attention(x, mem, p, head_dim: int, tp_axis, sp_axis=None):
    """Decoder queries over encoder memory; bidirectional (no mask).

    With ``sp_axis`` both sides are sequence-sharded: ``x`` is this
    device's target block and ``mem`` its ENCODER-memory block — the
    ring rotates the memory k/v blocks under the stationary queries
    (rectangular, non-causal ring)."""
    B, Sq = x.shape[:2]
    Sk = mem.shape[1]
    q = col_parallel_matmul(x, p["xwq"].astype(x.dtype), p["xbq"].astype(x.dtype))
    k = col_parallel_matmul(mem, p["xwk"].astype(mem.dtype), p["xbk"].astype(mem.dtype))
    v = col_parallel_matmul(mem, p["xwv"].astype(mem.dtype), p["xbv"].astype(mem.dtype))
    h_loc = q.shape[-1] // head_dim
    q = q.reshape(B, Sq, h_loc, head_dim)
    k = k.reshape(B, Sk, h_loc, head_dim)
    v = v.reshape(B, Sk, h_loc, head_dim)
    o = ring_attention(q, k, v, sp_axis, causal=False)
    o = o.reshape(B, Sq, h_loc * head_dim)
    return row_parallel_matmul(o, p["xwo"].astype(x.dtype), tp_axis,
                               p["xbo"].astype(x.dtype))


def decoder_block(x, mem, p, head_dim: int, tp_axis=None, sp_axis=None):
    """Causal self-attn → cross-attn over ``mem`` → MLP, all pre-LN.

    ``p`` is a GPT ``block_init`` dict (self-attn + MLP) merged with
    :func:`_cross_init`'s cross-attention fields.
    """
    # the shared block's two halves (models/gpt.py) under the same param
    # names: transformer_block is attn-then-mlp, here cross-attn goes
    # between them. T5 has no RoPE, hence no positions
    x, _ = attn_half(x, p, head_dim, None, ring_attend(sp_axis), tp_axis)
    x = x + cross_attention(_layernorm(x, p["lnx_g"], p["lnx_b"]), mem, p,
                            head_dim, tp_axis, sp_axis)
    return ffn_half(x, p, tp_axis)[0]


def t5_init(rng: jnp.ndarray, cfg: T5Config) -> Dict[str, Any]:
    d = cfg.d_model
    hd = cfg.n_heads * cfg.head_dim
    n_total = cfg.n_enc_layers + cfg.n_dec_layers
    keys = jax.random.split(rng, 3 + cfg.n_enc_layers + 2 * cfg.n_dec_layers)
    std = 0.02

    def dense(key, shape):
        return jax.random.normal(key, shape, jnp.float32) * std

    dec_blocks = []
    for li in range(cfg.n_dec_layers):
        p = block_init(keys[3 + cfg.n_enc_layers + 2 * li], d, cfg.d_ff,
                       hd, n_total)
        p.update(_cross_init(keys[4 + cfg.n_enc_layers + 2 * li], d, hd,
                             n_total))
        dec_blocks.append(p)
    return {
        "wte": dense(keys[0], (cfg.vocab_size, d)),
        "wpe_src": dense(keys[1], (cfg.max_src, d)),
        "wpe_tgt": dense(keys[2], (cfg.max_tgt, d)),
        "enc_blocks": [
            block_init(keys[3 + li], d, cfg.d_ff, hd, n_total)
            for li in range(cfg.n_enc_layers)
        ],
        "dec_blocks": dec_blocks,
        "enc_ln_g": jnp.ones((d,), jnp.float32),
        "enc_ln_b": jnp.zeros((d,), jnp.float32),
        "lnf_g": jnp.ones((d,), jnp.float32),
        "lnf_b": jnp.zeros((d,), jnp.float32),
    }


def t5_logical_specs(cfg: T5Config) -> Dict[str, Any]:
    from byteps_tpu.models.gpt import block_logical_specs
    dec = []
    for _ in range(cfg.n_dec_layers):
        s = block_logical_specs()
        s.update(_cross_logical_specs())
        dec.append(s)
    return {
        "wte": ("vocab", "embed"), "wpe_src": (None, "embed"),
        "wpe_tgt": (None, "embed"),
        "enc_blocks": [block_logical_specs()
                       for _ in range(cfg.n_enc_layers)],
        "dec_blocks": dec,
        "enc_ln_g": ("embed",), "enc_ln_b": ("embed",),
        "lnf_g": ("embed",), "lnf_b": ("embed",),
    }


def t5_param_specs(cfg: T5Config, tp_axis: Optional[str]) -> Dict[str, Any]:
    from byteps_tpu.parallel.partitioner import resolve_specs, rules_from_axes
    return resolve_specs(t5_logical_specs(cfg),
                         rules_from_axes(tp_axis=tp_axis))


def _sp_positions(S_loc: int, sp_axis: Optional[str]) -> jnp.ndarray:
    """This device's global positions for its contiguous sequence block
    (the GPT helper, fixed to the contiguous layout — T5 has no zigzag)."""
    return _gpt_positions(S_loc, sp_axis, "contiguous")


def t5_encode(params, src: jnp.ndarray, cfg: T5Config,
              tp_axis: Optional[str] = None,
              sp_axis: Optional[str] = None,
              remat: bool = False) -> jnp.ndarray:
    """(B, S_src) token ids → (B, S_src, d) encoder memory.

    With ``sp_axis``, ``src`` is this device's contiguous sequence block
    and self-attention runs the non-causal ring."""
    S = src.shape[1]
    pos = _sp_positions(S, sp_axis)
    x = (params["wte"][src] + params["wpe_src"][pos]).astype(cfg.dtype)

    def apply_block(x, p):
        return transformer_block(x, p, cfg.head_dim, tp_axis, sp_axis,
                                 causal=False)

    apply_block = maybe_remat(apply_block, remat)
    for p in params["enc_blocks"]:
        x = apply_block(x, p)
    return _layernorm(x, params["enc_ln_g"], params["enc_ln_b"])


def t5_decode(params, mem: jnp.ndarray, tgt_in: jnp.ndarray, cfg: T5Config,
              tp_axis: Optional[str] = None,
              sp_axis: Optional[str] = None,
              remat: bool = False,
              readout: bool = True) -> jnp.ndarray:
    """Teacher-forced decode: (B, S_tgt) shifted ids → f32 logits.

    With ``sp_axis``, the target side is sequence-sharded too: causal
    ring self-attention + rectangular cross-attention ring over the
    sp-sharded encoder memory. ``readout=False`` stops before the final
    norm + tied readout and returns the decoder hidden states —
    :func:`t5_loss`'s fused readout+CE path consumes those directly."""
    S = tgt_in.shape[1]
    pos = _sp_positions(S, sp_axis)
    x = (params["wte"][tgt_in]
         + params["wpe_tgt"][pos]).astype(cfg.dtype)

    def apply_block(x, p):
        return decoder_block(x, mem, p, cfg.head_dim, tp_axis, sp_axis)

    apply_block = maybe_remat(apply_block, remat)
    for p in params["dec_blocks"]:
        x = apply_block(x, p)
    return _readout(params, x) if readout else x


def t5_forward(params, src: jnp.ndarray, tgt_in: jnp.ndarray, cfg: T5Config,
               tp_axis: Optional[str] = None,
               sp_axis: Optional[str] = None,
               remat: bool = False) -> jnp.ndarray:
    mem = t5_encode(params, src, cfg, tp_axis=tp_axis, sp_axis=sp_axis,
                    remat=remat)
    return t5_decode(params, mem, tgt_in, cfg, tp_axis=tp_axis,
                     sp_axis=sp_axis, remat=remat)


def t5_loss(params, src, tgt_in, tgt_out, cfg: T5Config,
            dp_axis: Optional[str] = None,
            tp_axis: Optional[str] = None,
            sp_axis: Optional[str] = None,
            remat: bool = False,
            chunked_ce=True) -> jnp.ndarray:
    """Mean next-token CE over the target side (teacher forcing).

    Replication contract mirrors gpt_loss: identical across tp; pmean
    over sp (each device's local target-chunk mean is one summand of the
    global mean — equal chunks, so mean-of-means is exact); dp-local
    unless ``dp_axis`` is given. ``chunked_ce`` is the tri-state fused
    readout+CE knob (see ``gpt_loss``): truthy fuses the tied readout +
    CE over the decoder hidden states so the f32 (B, S_tgt, V) logits
    never materialize (``ops/chunked_ce.py``; ``"vocab_parallel"`` opts
    into the tp vocab split); ``False`` is the dense golden path."""
    from byteps_tpu.models.gpt import _readout_nll

    mem = t5_encode(params, src, cfg, tp_axis=tp_axis, sp_axis=sp_axis,
                    remat=remat)
    x = t5_decode(params, mem, tgt_in, cfg, tp_axis=tp_axis,
                  sp_axis=sp_axis, remat=remat, readout=False)
    loss = _readout_nll(params, x, tgt_out, tp_axis=tp_axis,
                        chunked=chunked_ce).mean()
    axes = tuple(a for a in (dp_axis, sp_axis) if a is not None)
    if axes:
        loss = jax.lax.pmean(loss, axes)
    return loss


def synthetic_seq2seq_batch(rng: jnp.ndarray, cfg: T5Config, batch: int,
                            src_len: int, tgt_len: int):
    """(src, tgt_in, tgt_out): random ids, target shifted right with BOS=0."""
    k1, k2 = jax.random.split(rng)
    src = jax.random.randint(k1, (batch, src_len), 0, cfg.vocab_size)
    tgt = jax.random.randint(k2, (batch, tgt_len + 1), 0, cfg.vocab_size)
    tgt = tgt.at[:, 0].set(0)
    return src, tgt[:, :-1], tgt[:, 1:]


# ---- cached seq2seq generation ---------------------------------------------
class T5DecCache(NamedTuple):
    """Decoder self-attention KV cache (n_dec, B, max_tgt, H, D) plus the
    fill level. Cross-attention k/v are not cached here — they are a pure
    function of the encoder memory, precomputed ONCE per sample by
    :func:`t5_cross_kv` (the structural win of encoder-decoder decode:
    the source side is encoded and projected exactly once)."""
    k: jnp.ndarray
    v: jnp.ndarray
    length: jnp.ndarray


def t5_init_cache(cfg: T5Config, batch: int,
                  h_loc: Optional[int] = None) -> T5DecCache:
    h = h_loc if h_loc is not None else cfg.n_heads
    shape = (cfg.n_dec_layers, batch, cfg.max_tgt, h, cfg.head_dim)
    return T5DecCache(k=jnp.zeros(shape, cfg.dtype),
                      v=jnp.zeros(shape, cfg.dtype),
                      length=jnp.zeros((), jnp.int32))


def t5_cross_kv(params, mem: jnp.ndarray, cfg: T5Config):
    """Precompute each decoder layer's cross-attention k/v from encoder
    memory: (n_dec, B, S_src, h_loc, D) pair."""
    ks, vs = [], []
    B, Sk = mem.shape[:2]
    for p in params["dec_blocks"]:
        k = col_parallel_matmul(mem, p["xwk"].astype(mem.dtype),
                                p["xbk"].astype(mem.dtype))
        v = col_parallel_matmul(mem, p["xwv"].astype(mem.dtype),
                                p["xbv"].astype(mem.dtype))
        h_loc = k.shape[-1] // cfg.head_dim
        ks.append(k.reshape(B, Sk, h_loc, cfg.head_dim))
        vs.append(v.reshape(B, Sk, h_loc, cfg.head_dim))
    return jnp.stack(ks), jnp.stack(vs)


def t5_decode_cached(params, tgt_tokens: jnp.ndarray, cache: T5DecCache,
                     cross_k: jnp.ndarray, cross_v: jnp.ndarray,
                     cfg: T5Config, tp_axis: Optional[str] = None):
    """Run T new target tokens through the decoder, appending to the cache.

    tgt_tokens: (B, T) continuing at position ``cache.length``; T =
    prompt length is the prefill, T = 1 one decode step — pinned to
    :func:`t5_decode` numerics either way. Returns (logits f32, cache).
    """
    from byteps_tpu.models.generate import cache_attend

    B, T = tgt_tokens.shape
    pos0 = cache.length
    pos = pos0 + jnp.arange(T)
    x = (params["wte"][tgt_tokens]
         + jnp.take(params["wpe_tgt"], pos, axis=0)).astype(cfg.dtype)
    head_dim = cfg.head_dim
    new_k, new_v = [], []
    for li, p in enumerate(params["dec_blocks"]):
        # causal self-attention over the cache — the one shared
        # cache-append path (models/generate.py)
        x, (ck, cv) = attn_half(
            x, p, head_dim, None,
            cache_attend(cache.k[li], cache.v[li], pos0), tp_axis)
        h_loc = ck.shape[-2]    # T5 has no GQA: query heads == kv heads
        # cross-attention over the precomputed encoder k/v
        h = _layernorm(x, p["lnx_g"], p["lnx_b"])
        q = col_parallel_matmul(h, p["xwq"].astype(x.dtype),
                                p["xbq"].astype(x.dtype))
        q = q.reshape(B, T, h_loc, head_dim)
        o = plain_attention(q, cross_k[li].astype(q.dtype),
                            cross_v[li].astype(q.dtype), causal=False)
        x = x + row_parallel_matmul(o.reshape(B, T, h_loc * head_dim),
                                    p["xwo"].astype(x.dtype), tp_axis,
                                    p["xbo"].astype(x.dtype))
        x, _ = ffn_half(x, p, tp_axis)
        new_k.append(ck)
        new_v.append(cv)
    logits = _readout(params, x)
    return logits, T5DecCache(k=jnp.stack(new_k), v=jnp.stack(new_v),
                              length=pos0 + T)


def make_t5_generate_fn(cfg: T5Config, max_new: int,
                        tp_axis: Optional[str] = None,
                        top_k: Optional[int] = None,
                        top_p: Optional[float] = None):
    """Build a jitted seq2seq sampler: ``gen(params, src, rng, temperature)``.

    Encodes the source once, precomputes per-layer cross k/v once, then
    scans ``max_new`` single-token cached decoder steps from BOS (id 0).
    Greedy at ``temperature == 0``; ``top_k``/``top_p`` truncate the
    sampling distribution exactly as in the GPT sampler (shared
    ``make_truncate``). One XLA program end to end; returns (B, max_new)
    generated ids.
    """
    from byteps_tpu.models.generate import make_pick, make_truncate

    if 1 + max_new > cfg.max_tgt:
        # static shapes: past max_tgt the cache write offset would clamp
        # (overwriting the last slot) and wpe_tgt positions clip. The
        # bound depends only on factory args, so fail HERE, not at the
        # first traced call (the GPT sampler's guard needs the runtime
        # prompt length; this one doesn't).
        raise ValueError(f"BOS + max_new ({1 + max_new}) exceeds "
                         f"cfg.max_tgt ({cfg.max_tgt})")
    _pick = make_pick(make_truncate(top_k, top_p, cfg.vocab_size))

    def gen(params, src, rng, temperature=0.0):
        B = src.shape[0]
        mem = t5_encode(params, src, cfg, tp_axis=tp_axis)
        cross_k, cross_v = t5_cross_kv(params, mem, cfg)
        h_loc = cross_k.shape[-2]
        cache = t5_init_cache(cfg, B, h_loc=h_loc)
        bos = jnp.zeros((B, 1), jnp.int32)

        def step(carry, key):
            tok, cache = carry
            logits, cache = t5_decode_cached(
                params, tok, cache, cross_k, cross_v, cfg, tp_axis=tp_axis)
            nxt = _pick(logits[:, -1], key, temperature)[:, None]
            return (nxt, cache), nxt[:, 0]

        keys = jax.random.split(rng, max_new)
        (_, _), toks = jax.lax.scan(step, (bos, cache), keys)
        return toks.T  # (B, max_new)

    return jax.jit(gen, static_argnames=())
