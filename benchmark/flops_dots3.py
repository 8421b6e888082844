"""Operations and bytes the kernels of the dots3 serving cell need, from the
cell's shapes (``g``, the configuration file's ``gpt_config``) and the
program's own counters. ``benchmark/flops.py``'s conventions: a roofline
share may not pass 100%, so nothing is counted that the algorithm does not
need — no key after its query, no key that was not picked, no tile a kernel
visits past the diagonal, no padding."""

from __future__ import annotations


def index_scores(pairs: float, queries: float, g: dict,
                 bytes_per_el: int = 2) -> dict:
    """The indexer's scores ``I[t, s] = sum_j w_j relu(qI_j[t] . kI[s])`` for
    ``pairs`` (query, key) pairs with the key at or before the query (the
    program's ``serve.dsa.prefill_scored_pairs``: ``C * pos0 + C (C + 1) / 2``
    a chunk and full layer) of ``queries`` queries.

    FLOPs: one product of ``index_head_dim`` multiply-adds a head and pair,
    two FLOPs each; the relu, the weight and the sum over heads are left out
    (the MXU does none of them). Bytes: every query's ``index_n_heads x
    index_head_dim`` values and weights read once, every pair's score written
    once in f32; the keys, read once a query tile, are left out."""
    heads, dim = g["index_n_heads"], g["index_head_dim"]
    return {
        "flops": pairs * 2 * heads * dim,
        "bytes": queries * heads * (dim * bytes_per_el + 4) + pairs * 4,
    }


def selected_attention(pairs: float, queries: float, g: dict,
                       bytes_per_el: int = 2) -> dict:
    """A full layer's attention over the picked keys alone: ``pairs`` (query,
    picked key) pairs (the program's ``serve.dsa.prefill_selected_keys``:
    ``index_topk`` a query once the context passes it) of ``queries``
    queries, k and v materialised.

    FLOPs: ``q . k`` over ``nope + rope`` and ``p . v`` over ``v`` a head and
    pair, two FLOPs a multiply-add. The kernel visits every key up to the
    diagonal and masks the ones not picked, which the algorithm does not
    need: at 2,048 picked of 16k keys the share cannot pass an eighth.
    Bytes: q read and o written once a query and head; k and v, shared by the
    queries that picked them, are left out."""
    heads = g["n_heads"]
    qk, v = g["qk_nope_dim"] + g["qk_rope_dim"], g["v_head_dim"]
    return {
        "flops": pairs * heads * 2 * (qk + v),
        "bytes": queries * heads * (qk + v) * bytes_per_el,
    }
