"""Operations and bytes the kernels of the Falcon-H1 serving cell need, from
the cell's shapes (``g``, the configuration file's ``gpt_config``) and the
program's own counts (``done``: what each counter moved by in the traced
iterations). ``benchmark/flops.py``'s conventions: a roofline share may not
pass 100%, so nothing is counted that the algorithm does not need — no
scratch row, no padding of a column to a tile, no key past a fill level."""

from __future__ import annotations


def ssd_decode(done: dict, g: dict) -> dict:
    """The packed decode step's state update: ``rows`` (live row, layer)
    pairs (the program's ``serve.ssd.decode_rows``), each over ``ssm_heads``
    states of ``ssm_state x ssm_head_dim`` f32.

    Bytes: a state read once and written once (2 x 131,072 B a row, head and
    layer at the published sizes), the head's x and y (``ssm_head_dim`` f32
    each) and its dt, A and D; B and C (``ssm_state`` f32 each) ONCE a group,
    not once a head. FLOPs an element of the state: the decay (1), the
    rank-one update (2), ``S^T C`` (2)."""
    rows = done["serve.ssd.decode_rows"]
    heads = rows * g["ssm_heads"]
    n, p = g["ssm_state"], g["ssm_head_dim"]
    return {"flops": heads * 5 * n * p,
            "bytes": (heads * (2 * n * p + 2 * p + 3)
                      + rows * g["ssm_groups"] * 2 * n) * 4}


def paged_attention(done: dict, g: dict, bytes_per_el: int = 2) -> dict:
    """The packed decode step's attention over the layers' pages: ``keys``
    (row, layer, key) triples a step's queries must read (the program's
    ``serve.kv.decode_keys_read.full``: a live row's length on each layer).

    FLOPs: ``q . k`` and ``p . v`` over ``head_dim`` for each of ``n_heads``
    query heads and key (the kernel's block-diagonal layout does
    ``n_kv_heads`` times that, which the algorithm does not need). Bytes: a
    key's k and v rows of ``n_kv_heads x head_dim`` read once for the whole
    group of query heads; q and o left out."""
    keys = done["serve.kv.decode_keys_read.full"]
    return {"flops": keys * 2 * 2 * g["n_heads"] * g["head_dim"],
            "bytes": keys * 2 * g["n_kv_heads"] * g["head_dim"]
            * bytes_per_el}
