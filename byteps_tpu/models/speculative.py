"""Greedy speculative decoding — exact by construction.

Beyond-reference inference acceleration (the reference has no decode
path at all): a cheap draft model proposes ``spec_len`` tokens per
round; the target model verifies ALL of them in ONE cached forward
(sequence-parallel on the MXU instead of token-serial), keeps the
longest agreeing prefix, and emits its own correction at the first
mismatch. Greedy output is therefore token-for-token IDENTICAL to
plain greedy decoding of the target — the draft affects only speed
(accepted tokens per target forward), never content. Tests pin this
exactness with an adversarial draft.

TPU-first mechanics, all static shapes inside one jitted program:

* One ``lax.while_loop`` round = ``spec_len`` scanned draft steps +
  one target forward over ``spec_len`` fed tokens.
* Rollback is a fill-level rewind: both KV caches append every fed
  token, then ``length`` is reset to the committed prefix — entries
  past the fill level are masked out by construction and overwritten
  by the next round's writes (``generate.py`` cache contract), so no
  scatter/gather cleanup exists.
* Batched: rows accept independently, the round advances by the
  BATCH-MIN accepted count (rows that accepted more simply re-derive
  those tokens next round — correctness is unaffected, the cost is
  the standard batched-speculation tradeoff).

Two draft strategies:

* ``make_speculative_generate_fn`` — a draft MODEL (any GPT-family
  config sharing the target's vocabulary, typically distilled/
  shallower). Wall-clock win ≈ f(draft_cost/target_cost, accept rate);
  with draft == target it measures pure verify overhead (~1×): that
  configuration is an overhead probe, not a ceiling (not measured on
  the chip; no cell).
* ``make_lookup_generate_fn`` — prompt-lookup drafting (the
  "assisted generation" n-gram trick): propose the K tokens that
  followed the most recent occurrence of the current bigram in the
  already-generated context. The draft costs a few vectorized
  compares — no model at all — so ANY nonzero accept rate is pure
  win; repetitive continuations (code, structured text, greedy
  attractors) accept in long runs.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from byteps_tpu.models.generate import gpt_apply_cached, init_cache
from byteps_tpu.models.gpt import GPTConfig


def _verify_commit(d, logits, out, n_emitted, K):
    """The exactness-critical accept/commit arithmetic shared by both
    samplers: compare proposals against the target's greedy choices,
    commit the batch-min agreeing prefix (+ the correction token at the
    first mismatch), and report how many cache entries are committed.

    Returns ``(out, n_emitted, next_tok, committed)`` where
    ``committed`` is the count of newly-valid cache entries past the
    round's starting fill level (``[next_tok, d_1..d_{min(m, K-1)}]``).
    """
    B = d.shape[0]
    preds = jnp.argmax(logits, axis=-1).astype(jnp.int32)   # (B, K)
    acc = (d == preds).astype(jnp.int32)
    m = jnp.min(jnp.cumprod(acc, axis=1).sum(axis=1))       # batch-min
    corr_idx = jnp.minimum(m, K - 1)
    correction = preds[jnp.arange(B), corr_idx]
    full = m == K
    # emit d_1..d_m, plus the correction when a mismatch happened; the
    # stray write at slot m when m == K lands exactly at the next
    # round's offset and is overwritten there
    block = jnp.where(jnp.arange(K + 1)[None, :] == m,
                      correction[:, None],
                      jnp.pad(d, ((0, 0), (0, 1))))
    out = jax.lax.dynamic_update_slice(out, block, (0, n_emitted))
    n_emitted = n_emitted + jnp.where(full, K, m + 1)
    next_tok = jnp.where(full, d[:, K - 1], correction)
    return out, n_emitted, next_tok, 1 + jnp.minimum(m, K - 1)


def make_speculative_generate_fn(cfg: GPTConfig, draft_cfg: GPTConfig,
                                 max_new: int, spec_len: int = 4,
                                 tp_axis: Optional[str] = None):
    """Build a jitted greedy speculative sampler.

    ``gen(params, draft_params, prompt) -> (tokens (B, T0+max_new),
    rounds)`` — ``rounds`` is the number of verify forwards the run
    took (== target forwards after prefill; plain greedy decoding would
    take ``max_new``). Output tokens are exactly plain greedy's.
    """
    if spec_len < 1:
        raise ValueError(f"spec_len must be >= 1; got {spec_len}")
    if draft_cfg.vocab_size != cfg.vocab_size:
        raise ValueError(
            f"draft vocab {draft_cfg.vocab_size} != target vocab "
            f"{cfg.vocab_size} — speculation compares token ids")
    K = spec_len

    @jax.jit
    def gen(params, draft_params, prompt):
        B, T0 = prompt.shape
        if T0 + max_new + K > cfg.max_seq:
            raise ValueError(
                f"prompt ({T0}) + max_new ({max_new}) + spec_len ({K}) "
                f"exceeds cfg.max_seq ({cfg.max_seq})")
        if T0 + max_new + K > draft_cfg.max_seq:
            raise ValueError(
                f"draft max_seq ({draft_cfg.max_seq}) too small for "
                f"prompt ({T0}) + max_new ({max_new}) + spec_len ({K})")

        kv_t = params["blocks"][0]["wk"].shape[-1] // cfg.head_dim
        kv_d = draft_params["blocks"][0]["wk"].shape[-1] // draft_cfg.head_dim
        cache_t = init_cache(cfg, B, h_loc=kv_t)
        cache_d = init_cache(draft_cfg, B, h_loc=kv_d)

        logits_t, cache_t = gpt_apply_cached(params, prompt, cache_t, cfg,
                                             tp_axis)
        _, cache_d = gpt_apply_cached(draft_params, prompt, cache_d,
                                      draft_cfg, tp_axis)
        # first committed token: target's greedy choice after the prompt
        # (emitted, not yet in either cache)
        next_tok = jnp.argmax(logits_t[:, -1], axis=-1).astype(jnp.int32)

        out = jnp.zeros((B, max_new + K + 1), jnp.int32)
        out = out.at[:, 0].set(next_tok)

        draft_step = functools.partial(gpt_apply_cached, cfg=draft_cfg,
                                       tp_axis=tp_axis)

        def round_body(state):
            out, n_emitted, next_tok, cache_t, cache_d, rounds = state
            len0 = cache_t.length

            # -- draft proposes K tokens (K cached single steps) -------
            def dstep(carry, _):
                tok, cd = carry
                lg, cd = draft_step(draft_params, tok[:, None], cd)
                nxt = jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)
                return (nxt, cd), nxt

            (_, cache_d), d = jax.lax.scan(
                dstep, (next_tok, cache_d), None, length=K)
            d = jnp.moveaxis(d, 0, 1)                     # (B, K)

            # -- target verifies in ONE forward of K fed tokens --------
            feed = jnp.concatenate([next_tok[:, None], d[:, :K - 1]],
                                   axis=1)                # (B, K)
            logits, cache_t = gpt_apply_cached(params, feed, cache_t, cfg,
                                               tp_axis)
            out, n_emitted, next_tok, committed = _verify_commit(
                d, logits, out, n_emitted, K)
            # fill-level rewind on BOTH caches (they appended the same
            # K fed positions)
            cache_t = cache_t._replace(length=len0 + committed)
            cache_d = cache_d._replace(length=len0 + committed)
            return out, n_emitted, next_tok, cache_t, cache_d, rounds + 1

        def cond(state):
            return state[1] < max_new

        out, n_emitted, *_rest = jax.lax.while_loop(
            cond, round_body,
            (out, jnp.int32(1), next_tok, cache_t, cache_d, jnp.int32(0)))
        rounds = _rest[-1]
        return jnp.concatenate([prompt.astype(jnp.int32),
                                out[:, :max_new]], axis=1), rounds

    return gen


def make_lookup_generate_fn(cfg: GPTConfig, max_new: int,
                            spec_len: int = 4,
                            tp_axis: Optional[str] = None):
    """Prompt-lookup speculative greedy sampler (model-free draft).

    ``gen(params, prompt) -> (tokens (B, T0+max_new), rounds)``. Each
    round proposes the ``spec_len`` tokens that followed the most
    recent earlier occurrence of the current (prev, last) bigram in
    the committed context (per batch row), then verifies them with one
    target forward exactly like the model-draft sampler. Output is
    token-for-token plain greedy at any accept rate; ``rounds`` counts
    the verify forwards (plain decoding would take ``max_new``).
    """
    if spec_len < 1:
        raise ValueError(f"spec_len must be >= 1; got {spec_len}")
    K = spec_len

    @jax.jit
    def gen(params, prompt):
        B, T0 = prompt.shape
        if T0 < 2:
            raise ValueError("prompt must hold at least the seed bigram "
                             f"(2 tokens); got {T0}")
        if T0 + max_new + K > cfg.max_seq:
            raise ValueError(
                f"prompt ({T0}) + max_new ({max_new}) + spec_len ({K}) "
                f"exceeds cfg.max_seq ({cfg.max_seq})")
        kv_t = params["blocks"][0]["wk"].shape[-1] // cfg.head_dim
        cache_t = init_cache(cfg, B, h_loc=kv_t)
        logits_t, cache_t = gpt_apply_cached(params, prompt, cache_t, cfg,
                                             tp_axis)
        next_tok = jnp.argmax(logits_t[:, -1], axis=-1).astype(jnp.int32)

        W = T0 + max_new + K + 1
        out = jnp.zeros((B, max_new + K + 1), jnp.int32)
        out = out.at[:, 0].set(next_tok)

        def propose(out, n_emitted, next_tok):
            """Latest-bigram continuation from the committed context."""
            ctx = jnp.concatenate([prompt.astype(jnp.int32), out], axis=1)
            pos_last = T0 + n_emitted - 1          # next_tok's position
            prev = ctx[jnp.arange(B), pos_last - 1]
            pos = jnp.arange(W - 1)
            match = ((ctx[:, :-1] == prev[:, None])
                     & (ctx[:, 1:] == next_tok[:, None])
                     & (pos[None, :] <= pos_last - 2))
            # latest match; rows with none propose clamped-gather junk
            # (a junk proposal just means accept 0 for that row)
            p_star = jnp.argmax(
                jnp.where(match, pos[None, :], -1), axis=1)
            idx = jnp.clip(p_star[:, None] + 2 + jnp.arange(K)[None, :],
                           0, W - 1)
            return jnp.take_along_axis(ctx, idx, axis=1)   # (B, K)

        def round_body(state):
            out, n_emitted, next_tok, cache_t, rounds = state
            len0 = cache_t.length
            d = propose(out, n_emitted, next_tok)
            feed = jnp.concatenate([next_tok[:, None], d[:, :K - 1]],
                                   axis=1)
            logits, cache_t = gpt_apply_cached(params, feed, cache_t, cfg,
                                               tp_axis)
            out, n_emitted, next_tok, committed = _verify_commit(
                d, logits, out, n_emitted, K)
            cache_t = cache_t._replace(length=len0 + committed)
            return out, n_emitted, next_tok, cache_t, rounds + 1

        out, n_emitted, _nt, _c, rounds = jax.lax.while_loop(
            lambda s: s[1] < max_new, round_body,
            (out, jnp.int32(1), next_tok, cache_t, jnp.int32(0)))
        return jnp.concatenate([prompt.astype(jnp.int32),
                                out[:, :max_new]], axis=1), rounds

    return gen
