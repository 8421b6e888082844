"""The plain reference of the GPT-2 family (gpt2-medium, gpt2-large).

The published architecture (Radford et al. 2019; ``modeling_gpt2``): learned
token and position embeddings, pre-LayerNorm blocks of causal multi-head
attention and a 4x GELU MLP (``gelu_new``, the tanh form), a final LayerNorm
and a readout tied to the token embedding. Straight ``jax.numpy`` in float32
with ``precision=highest`` on every product (a TPU otherwise multiplies
float32 in bf16 passes): no kernel, no cache, no batching tricks, nothing
imported from the program. It reads the parameter tree ``gpt_init`` makes
(``wte``, ``wpe``, ``lnf_*``, ``blocks[i]`` with ``ln1_*``, ``wq/wk/wv/wo``,
``ln2_*``, ``w1/w2`` and their biases), which is the one thing it shares
with the system under test.

Departures from the source: the vocabulary is padded 50257 -> 50304 (rows
the traffic never names; their logits exist and are compared like any
other).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def _ln(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def hidden(params, tokens, n_heads: int, eps: float = 1e-5):
    """tokens (B, S) int -> final-LayerNorm'd hidden states (B, S, d) f32."""
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    B, S = tokens.shape
    x = p32["wte"][tokens] + p32["wpe"][:S]
    d = x.shape[-1]
    hd = d // n_heads
    causal = jnp.tril(jnp.ones((S, S), bool))
    for p in p32["blocks"]:
        h = _ln(x, p["ln1_g"], p["ln1_b"], eps)
        q = (jnp.matmul(h, p["wq"], precision=_HI) + p["bq"]
             ).reshape(B, S, n_heads, hd)
        k = (jnp.matmul(h, p["wk"], precision=_HI) + p["bk"]
             ).reshape(B, S, n_heads, hd)
        v = (jnp.matmul(h, p["wv"], precision=_HI) + p["bv"]
             ).reshape(B, S, n_heads, hd)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=_HI) / hd ** 0.5
        s = jnp.where(causal, s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", a, v, precision=_HI
                       ).reshape(B, S, d)
        x = x + jnp.matmul(o, p["wo"], precision=_HI) + p["bo"]
        h = _ln(x, p["ln2_g"], p["ln2_b"], eps)
        h = _gelu_new(jnp.matmul(h, p["w1"], precision=_HI) + p["b1"])
        x = x + jnp.matmul(h, p["w2"], precision=_HI) + p["b2"]
    return _ln(x, p32["lnf_g"], p32["lnf_b"], eps), p32["wte"]


def logits_at(params, tokens, position, n_heads: int, eps: float = 1e-5):
    """Next-token logits (vocab,) f32 after ``tokens[0, :position + 1]``.
    Causal, so whatever pads ``tokens`` to the right cannot reach them."""
    h, wte = hidden(params, tokens, n_heads, eps)
    return jnp.matmul(h[0, position], wte.T, precision=_HI)


def mean_nll(params, tokens, targets, n_heads: int, eps: float = 1e-5):
    """Mean next-token negative log-likelihood over (B, S), f32."""
    h, wte = hidden(params, tokens, n_heads, eps)
    logp = jax.nn.log_softmax(jnp.matmul(h, wte.T, precision=_HI), axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1).mean()
