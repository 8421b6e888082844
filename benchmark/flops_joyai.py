"""Operations and bytes the two kernels of the JoyAI-LLM-Flash training cell
need per step, from the cell's shapes and the program's own pair counter.
``benchmark/flops.py``'s conventions: a roofline share may not pass 100%, so
nothing is counted that the algorithm does not need — no recomputed forward
pass, no padding rows, no tile that a kernel visits and the routing left
empty."""

from __future__ import annotations


def mla_attention_train(batch: int, seq: int, n_heads: int, qk_dim: int,
                        v_dim: int, n_layers: int,
                        bytes_per_el: int = 2) -> dict:
    """Causal attention, forward and backward, with q and k ``qk_dim`` wide
    and v ``v_dim`` wide (latent attention with k and v materialised: 192 /
    128), over ``n_layers`` attention layers.

    FLOPs, as ``flops.causal_attention_train`` counts them (two a
    multiply-add, half of each product under the causal mask): forward
    ``q k^T`` (qk) and ``p v`` (v); backward dV (v), dP (v), dQ (qk), dK
    (qk): ``B H S^2 (qk + v)`` forward and ``B H S^2 (2 v + 2 qk)``
    backward a layer — ``960 B H S^2`` at 192 / 128. The backward's
    recomputed scores are not counted, nor is the forward pass that block
    recomputation runs a second time.
    Bytes: q, k, v read and o written forward; q, k, v, o, do read and dq,
    dk, dv written backward, each once, in the activation type."""
    bhs2 = batch * n_heads * seq * seq
    per_token_head = (2 * qk_dim + 2 * v_dim) + (4 * qk_dim + 4 * v_dim)
    return {
        "flops": n_layers * bhs2 * ((qk_dim + v_dim)
                                    + (2 * v_dim + 2 * qk_dim)),
        "bytes": n_layers * batch * seq * n_heads * per_token_head
        * bytes_per_el,
    }


def moe_grouped_products_train(pairs: float, d_model: int, d_expert: int,
                               experts_held: int, n_moe_layers: int,
                               bytes_per_el: int = 2) -> dict:
    """The grouped products of the routed experts for ``pairs`` (token,
    expert) pairs computed here in one step, all layers together (the
    program's ``moe.pairs_here``).

    FLOPs: three matrices a pair (gate and up ``d x f``, down ``f x d``),
    each in three products — forward, the gradient to its rows, the gradient
    to the matrix — of ``2 d f`` FLOPs: ``9 * 2 d f`` a pair.
    Bytes: each product reads its rows and writes its result once (``d + f``
    elements a pair and matrix; the weight gradient reads two row arrays),
    and touches its matrix once (read in the forward and row-gradient
    products, written in the weight-gradient one) for each of the
    ``experts_held`` experts of each layer."""
    df = d_model * d_expert
    return {
        "flops": pairs * 9 * 2 * df,
        "bytes": (pairs * 9 * (d_model + d_expert)
                  + n_moe_layers * experts_held * 9 * df) * bytes_per_el,
    }
