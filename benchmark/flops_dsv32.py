"""Operations and bytes the kernels of the DeepSeek-V3.2-Exp serving cell
need, from the cell's shapes (``g``, the configuration file's ``gpt_config``)
and the program's own counts (``done``: what each counter or histogram sum
moved by in the traced iterations). ``benchmark/flops.py``'s conventions: a
roofline share may not pass 100%, so nothing is counted that the algorithm
does not need — no key after its query, no key that was not picked, no tile
a kernel visits past the diagonal, no padding — and no token the prefix
index spared: ``serve.prefill_tokens`` counts COMPUTED tokens, a hit's
adopted positions are keys of the queries that follow and queries of
nobody."""

from __future__ import annotations


def _queries(done: dict, g: dict) -> float:
    """(computed token, layer) pairs: every layer is a selecting layer."""
    return done["serve.prefill_tokens"] * g["n_layers"]


def index_scores(done: dict, g: dict, bytes_per_el: int = 2) -> dict:
    """The indexer's scores ``I[t, s] = sum_j w_j relu(qI_j[t] . kI[s])`` for
    the (query, key) pairs with the key at or before the query (the program's
    ``serve.dsa.prefill_scored_pairs``: ``C * pos0 + C (C + 1) / 2`` a chunk
    and layer — a later ask's chunk scores its 256 or 512 queries against the
    whole adopted document).

    FLOPs: one product of ``index_head_dim`` multiply-adds a head and pair,
    two FLOPs each; the relu, the weight and the sum over heads are left out
    (the MXU does none of them). Bytes: every query's ``index_n_heads x
    index_head_dim`` values and weights read once, every pair's score written
    once in f32; the keys, read once a query tile, are left out."""
    pairs = done["serve.dsa.prefill_scored_pairs"]
    heads, dim = g["index_n_heads"], g["index_head_dim"]
    return {
        "flops": pairs * 2 * heads * dim,
        "bytes": _queries(done, g) * heads * (dim * bytes_per_el + 4)
        + pairs * 4,
    }


def selected_attention(done: dict, g: dict, bytes_per_el: int = 2) -> dict:
    """A layer's attention over the picked keys alone: the (query, picked
    key) pairs of the chunks (the program's
    ``serve.dsa.prefill_selected_keys``: ``index_topk`` a query once the
    context passes it), k and v materialised.

    FLOPs: ``q . k`` over ``nope + rope`` and ``p . v`` over ``v`` a head and
    pair, two FLOPs a multiply-add. The kernel visits every key up to the
    diagonal and masks the ones not picked, which the algorithm does not
    need: at 2,048 picked of 16k keys the share cannot pass an eighth.
    Bytes: q read and o written once a query and head; k and v, shared by the
    queries that picked them, are left out."""
    pairs = done["serve.dsa.prefill_selected_keys"]
    heads = g["n_heads"]
    qk, v = g["qk_nope_dim"] + g["qk_rope_dim"], g["v_head_dim"]
    return {
        "flops": pairs * heads * 2 * (qk + v),
        "bytes": _queries(done, g) * heads * (qk + v) * bytes_per_el,
    }
