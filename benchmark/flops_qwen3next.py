"""Operations and bytes the kernels of the Qwen3-Next serving cell need, from
the cell's shapes (``g``, the configuration file's ``gpt_config``) and the
program's own counts (``done``: what each counter or histogram sum moved by in
the traced iterations). ``benchmark/flops.py``'s conventions: a roofline share
may not pass 100%, so nothing is counted that the algorithm does not need — no
scratch row, no expert without a row, no padding of a row tile, no key past a
fill level."""

from __future__ import annotations


def gdn_decode(done: dict, g: dict) -> dict:
    """The packed decode step's state update: ``rows`` (live row, DeltaNet
    layer) pairs (the program's ``serve.gdn.decode_rows``), each over
    ``linear_value_heads`` states of ``linear_key_dim x linear_value_dim``
    f32.

    Bytes: a state read once and written once (2 x 65,536 B a row, head and
    layer at the published sizes) and the row's q, k (``Dk``), v and o
    (``Dv``) in f32. FLOPs an element of the state: the decay (1), ``S^T k``
    (2), the rank-one update (2), ``S^T q`` (2)."""
    heads = done["serve.gdn.decode_rows"] * g["linear_value_heads"]
    dk, dv = g["linear_key_dim"], g["linear_value_dim"]
    return {"flops": heads * 7 * dk * dv,
            "bytes": heads * (2 * dk * dv + 2 * dk + 2 * dv) * 4}


def paged_attention(done: dict, g: dict, bytes_per_el: int = 2) -> dict:
    """The packed decode step's attention over the full layers' pages:
    ``keys`` (row, layer, key) triples a step's queries must read (the
    program's ``serve.kv.decode_keys_read.full``: a live row's length on each
    full layer).

    FLOPs: ``q . k`` and ``p . v`` over ``head_dim`` for each of ``n_heads``
    query heads and key (the kernel's block-diagonal layout does
    ``n_kv_heads`` times that, which the algorithm does not need). Bytes: a
    key's k and v rows of ``n_kv_heads x head_dim`` read once for the whole
    group of query heads; q and o left out."""
    keys = done["serve.kv.decode_keys_read.full"]
    return {"flops": keys * 2 * 2 * g["n_heads"] * g["head_dim"],
            "bytes": keys * 2 * g["n_kv_heads"] * g["head_dim"]
            * bytes_per_el}


def expert_products(done: dict, g: dict, bytes_per_el: int = 2) -> dict:
    """The three grouped products of the expert layers over the experts held
    here: ``pairs`` (token, held expert) pairs computed (the sum of
    ``moe.pairs_here``) and ``hits`` (program, layer, held expert) triples in
    which the expert had at least one row (the sum of ``moe.experts_hit``, a
    mean over a program's layers, times ``n_layers``).

    FLOPs: gate, up and down, ``d_model x d_ff_expert`` multiply-adds each a
    pair. Bytes: an expert's three matrices read once a program and layer in
    which it has a row; a pair's row read once and its output written once;
    the ``d_ff_expert``-wide intermediates are left out. The shared expert is
    a dense product outside these kernels and is not counted."""
    d, ff = g["d_model"], g["d_ff_expert"]
    pairs = done["moe.pairs_here"]
    hits = done["moe.experts_hit"] * g["n_layers"]
    return {"flops": pairs * 3 * 2 * d * ff,
            "bytes": (hits * 3 * d * ff + pairs * 2 * d) * bytes_per_el}
