"""The plain reference of JoyAI-LLM-Flash (``joyai_llm_flash``), cut to the
share of the model one chip of an expert-parallel deployment holds.

The published architecture is the DeepSeek-V3 block (``config.json`` of the
source; DeepSeek-V3 technical report, sections 2.1 and 2.2): RMSNorm,
multi-head latent attention with a decoupled rotary key, ``first_k_dense``
leading layers with a dense SwiGLU, then layers of ``n_routed_experts``
fine-grained SwiGLU experts routed by sigmoid scores with a correction bias
(``noaux_tc``, ``n_group`` = ``topk_group`` = 1: no group limit) plus a
shared expert, an untied readout, and one multi-token-prediction module.
Straight ``jax.numpy`` in float32 with ``precision=highest`` on every
product: no kernel, no sorting, no cache, a Python loop over the experts
held, nothing imported from the program. It reads the parameter tree
``joyai_init`` makes, which is the one thing it shares with the system
under test, and takes every size from the shapes of that tree and from the
keyword arguments (all of them the configuration file's numbers).

``experts_held``/``first_expert``: the routed experts whose weights the tree
holds. Every token is routed over ALL experts; only the held experts' terms
are added. What the absent experts would add is left out (their owners add
it in the deployment). The vocabulary is the tree's rows.

Departures from the published description, each deliberate:

* ``eh_proj``'s input is ``[RMSNorm(Emb(tok_{t+1})); RMSNorm(h_t)]``, the
  embedding half first (DeepSeek-V3's released code; the report's equation
  21 writes the hidden half first).
* The correction bias takes no gradient. Between steps it follows the
  source's balancing rule (:func:`bias_update`: down by ``rate`` for an
  expert picked by more tokens than the mean expert, up by ``rate`` for one
  picked by fewer), from the loads of THIS chip's tokens alone, where the
  deployment sums them over its data-parallel ranks; the speed ``rate`` is
  the configuration file's assumption (DeepSeek-V3 trains at 0.001 with
  batches of 15,360 sequences; the source gives none).
* ``mtp_weight`` (lambda) is the configuration file's assumption; the source
  gives none.
* Rotary pairs are adjacent dims ``(2i, 2i+1)`` and stay where they are
  (``rope_interleave``); the released code moves them to the half-split
  layout first, which permutes q and k alike and changes no score.
* The MTP module sees positions ``0..S-1`` like the main model.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
_mm = functools.partial(jnp.matmul, precision=_HI)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _swiglu(x, w1, w3, w2):
    return _mm(jax.nn.silu(_mm(x, w1)) * _mm(x, w3), w2)


def _rope(x, theta):
    """x (B, S, H, D): adjacent pairs rotated by ``pos * theta**(-2i/D)``."""
    S, D = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def mla(x, p, n_heads, nope, rope, v_dim, theta, eps):
    B, S, _ = x.shape
    kv_rank = p["kv_norm_g"].shape[0]
    q = _mm(_rms(_mm(x, p["wq_a"]), p["q_norm_g"], eps), p["wq_b"]
            ).reshape(B, S, n_heads, nope + rope)
    kv_a = _mm(x, p["wkv_a"])
    kv = _mm(_rms(kv_a[..., :kv_rank], p["kv_norm_g"], eps), p["wkv_b"]
             ).reshape(B, S, n_heads, nope + v_dim)
    q_r = _rope(q[..., nope:], theta)
    k_r = _rope(kv_a[..., kv_rank:][:, :, None, :], theta)     # one, shared
    s = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :nope], kv[..., :nope],
                    precision=_HI)
         + jnp.einsum("bqhd,bkd->bhqk", q_r, k_r[:, :, 0], precision=_HI)
         ) / (nope + rope) ** 0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1),
                   kv[..., nope:], precision=_HI)
    return _mm(o.reshape(B, S, n_heads * v_dim), p["wo"])


def routed(x, p, top_k, scale, first_expert):
    """``(y, idx)``: the sum over the held experts of gate_weight *
    SwiGLU_e(x) — a dense mask per expert, every expert applied to every
    token — and the experts each token picked, ``(..., top_k)``."""
    s = jax.nn.sigmoid(_mm(x, p["wg"]))                       # (..., E)
    _, idx = jax.lax.top_k(s + p["router_bias"], top_k)
    picked = jnp.take_along_axis(s, idx, axis=-1)
    w = scale * picked / picked.sum(-1, keepdims=True)        # (..., k)
    y = jnp.zeros_like(x)
    for j in range(p["w1"].shape[0]):
        gate = jnp.sum(jnp.where(idx == first_expert + j, w, 0.0), axis=-1)
        y = y + gate[..., None] * _swiglu(x, p["w1"][j], p["w3"][j],
                                          p["w2"][j])
    return y, idx


def block(x, p, *, n_heads, nope, rope, v_dim, theta, eps, top_k, scale,
          first_expert):
    """``(x, route)``: ``route`` is ``(router input, picks)`` of an expert
    layer, None of a dense one."""
    x = x + mla(_rms(x, p["ln1_g"], eps), p, n_heads, nope, rope, v_dim,
                theta, eps)
    h = _rms(x, p["ln2_g"], eps)
    if "mlp" in p:
        return x + _swiglu(h, p["mlp"]["w1"], p["mlp"]["w3"],
                           p["mlp"]["w2"]), None
    sh = p["shared"]
    y, idx = routed(h, p["moe"], top_k, scale, first_expert)
    return x + y + _swiglu(h, sh["w1"], sh["w3"], sh["w2"]), (h, idx)


def bias_update(bias, idx, rate):
    """The correction bias after a step whose tokens picked the experts
    ``idx (..., top_k)``: each expert's load is the number of tokens that
    picked it, and its bias moves by ``rate`` towards the mean load."""
    load = jnp.stack([jnp.sum(idx == e) for e in range(bias.shape[0])]
                     ).astype(jnp.float32)
    return bias + rate * jnp.sign(load.mean() - load)


def _ce(h, g, head, targets, eps):
    logp = jax.nn.log_softmax(_mm(_rms(h, g, eps), head), axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def forward(params, tokens, targets, *, n_heads, nope, rope, v_dim, theta,
            eps, top_k, scale, first_expert=0, all_picks=False):
    """``(CE_main, CE_mtp, pairs_held, first_route)`` of tokens/targets
    ``(B, S)`` (targets the next tokens). Both losses are means over the
    positions that have a target; ``CE_mtp`` is 0 for a tree without an MTP
    module. ``pairs_held`` counts the (token, expert) pairs of all expert
    layers whose expert the tree holds; ``first_route`` is the first expert
    layer's ``(router input, picks)``, None where there is no such layer.
    With ``all_picks``, instead: every expert layer's picks, main layers
    first and the MTP module's last (what :func:`bias_update` reads)."""
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    blk = functools.partial(block, n_heads=n_heads, nope=nope, rope=rope,
                            v_dim=v_dim, theta=theta, eps=eps, top_k=top_k,
                            scale=scale, first_expert=first_expert)
    routes = []
    x = p32["wte"][tokens]
    for p in p32["blocks"]:
        x, route = blk(x, p)
        routes.append((p, route))
    main = _ce(x, p32["lnf_g"], p32["lm_head"], targets, eps).mean()
    mtp = jnp.zeros((), jnp.float32)
    if "mtp" in p32:
        m = p32["mtp"]
        h = jnp.concatenate([_rms(p32["wte"][targets], m["enorm_g"], eps),
                             _rms(x, m["hnorm_g"], eps)], axis=-1)
        h, route = blk(_mm(h, m["eh_proj"]), m["block"])
        routes.append((m["block"], route))
        # position t predicts token t+2 = targets[t+1]; the last has no
        # target
        mtp = _ce(h[:, :-1], m["lnf_g"], p32["lm_head"], targets[:, 1:],
                  eps).mean()
    routes = [(p["moe"]["w1"].shape[0], r) for p, r in routes
              if r is not None]
    held = sum(jnp.sum((idx >= first_expert) & (idx < first_expert + n))
               for n, (_, idx) in routes)
    if all_picks:
        return [idx for _, (_, idx) in routes]
    return main, mtp, held, (routes[0][1] if routes else None)


def losses(params, tokens, targets, **kw):
    """``(CE_main, CE_mtp)`` of :func:`forward`."""
    return forward(params, tokens, targets, **kw)[:2]


def loss(params, tokens, targets, *, mtp_weight, **kw):
    main, mtp = losses(params, tokens, targets, **kw)
    return main + mtp_weight * mtp


def moe_layer(x, p, *, top_k, scale, first_expert=0):
    """One layer's routed part over the experts ``p`` holds (no shared
    expert): what the share test sums over all shares."""
    return routed(x.astype(jnp.float32),
                  jax.tree.map(lambda a: a.astype(jnp.float32), p),
                  top_k, scale, first_expert)[0]
