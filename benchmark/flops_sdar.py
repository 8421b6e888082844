"""Operations and bytes the kernels of the SDAR serving cell need, from the
cell's shapes (``g``, the configuration file's ``gpt_config``) and the
program's own counts (``done``: what each counter or histogram sum moved by in
the traced iterations). ``benchmark/flops.py``'s conventions: a roofline share
may not pass 100%, so nothing is counted that the algorithm does not need — no
expert without a row, no padding of a row tile, no tile a kernel visits past
the mask, none of the products the block-diagonal layout does on zeros."""

from __future__ import annotations


def paged_attention(done: dict, g: dict, bytes_per_el: int = 2) -> dict:
    """A pass's attention: ``keys`` (row, layer, key) triples a row's block of
    queries must read (the program's ``serve.kv.decode_keys_read.full``: a
    live row's fill level and its block, a layer each).

    FLOPs: ``q . k`` and ``p . v`` over ``head_dim`` for each of ``n_heads``
    query heads of each of the ``block_length`` queries and key, two FLOPs a
    multiply-add (the kernel's block-diagonal layout does ``n_kv_heads`` times
    that, which the algorithm does not need). Bytes: a key's k and v rows of
    ``n_kv_heads x head_dim`` read once for the whole block of queries; q and
    o, ``block_length`` rows a head, left out."""
    keys = done["serve.kv.decode_keys_read.full"]
    return {
        "flops": keys * 2 * 2 * g["n_heads"] * g["head_dim"]
        * g["block_length"],
        "bytes": keys * 2 * g["n_kv_heads"] * g["head_dim"] * bytes_per_el,
    }


def prefill_attention(done: dict, g: dict, bytes_per_el: int = 2) -> dict:
    """A chunk's attention under the flash forward kernel and the block-causal
    mask: ``pairs`` visible (query, key) pairs (the program's
    ``serve.attn.prefill_pairs.full``, a layer each: a query sees to the end
    of its block) of ``serve.prefill_tokens`` queries in each of ``n_layers``
    layers.

    FLOPs: ``q . k`` and ``p . v`` over ``head_dim`` a query head and pair.
    Bytes: q read and o written once a query, head and layer; k and v, shared
    by a tile of queries and eight query heads a kv head, are left out."""
    pairs = done["serve.attn.prefill_pairs.full"]
    queries = done["serve.prefill_tokens"] * g["n_layers"]
    return {
        "flops": pairs * 2 * 2 * g["n_heads"] * g["head_dim"],
        "bytes": queries * 2 * g["n_heads"] * g["head_dim"] * bytes_per_el,
    }


def expert_products(done: dict, g: dict, bytes_per_el: int = 2) -> dict:
    """The three grouped products of the expert layers: ``pairs`` (token,
    expert) pairs computed (the sum of ``moe.pairs_here``) and ``hits``
    (program, layer, expert) triples in which the expert had at least one row
    (the sum of ``moe.experts_hit``, a mean over a program's layers, times
    ``n_layers``).

    FLOPs: gate, up and down, ``d_model x d_ff_expert`` multiply-adds each a
    pair. Bytes: an expert's three matrices read once a program and layer in
    which it has a row; a pair's row read once (gate and up share it) and its
    output written once; the ``d_ff_expert``-wide intermediates between the
    products, which a fused kernel would keep on the chip, are left out."""
    d, ff = g["d_model"], g["d_ff_expert"]
    pairs = done["moe.pairs_here"]
    hits = done["moe.experts_hit"] * g["n_layers"]
    return {
        "flops": pairs * 3 * 2 * d * ff,
        "bytes": (hits * 3 * d * ff + pairs * 2 * d) * bytes_per_el,
    }
