"""Operations and bytes the kernels of the Phi-4-mini-flash serving cell
need, from the cell's shapes (``g``, the configuration file's ``gpt_config``)
and the program's own counts (``done``: what each counter moved by in the
traced iterations). ``benchmark/flops.py``'s conventions: a roofline share may
not pass 100%, so nothing is counted that the algorithm does not need — no
scratch row, no zero half of a padded query, no key before a window or past a
fill level, no query whose output nothing reads."""

from __future__ import annotations


def _per_pair(g: dict) -> int:
    """FLOPs a (query head, key) pair of differential attention needs: ``q .
    k`` over ``d_head`` (a head meets ONE component of its k/v pair; the
    padded query's zero half is the kernel's, not the algorithm's) and ``p .
    V`` over the pair's ``2 x d_head`` values."""
    return g["n_heads"] * (2 * g["d_head"] + 2 * 2 * g["d_head"])


def paged_attention(done: dict, g: dict, bytes_per_el: int = 2) -> dict:
    """The packed decode step's attention: ``keys`` (row, layer, key) triples
    a step's queries must read (the program's ``serve.kv.decode_keys_read.
    full`` + ``.window``). **The full layer's pages count once for EACH of
    the layers that read them** — the layer that wrote them and every
    cross-attention layer, eight at the published depth: each is a kernel
    call of its own that streams the pages again (``.full`` is a live row's
    length times those layers) — and a window layer its last ``window`` keys.

    Bytes: a key's k and v rows of ``n_kv_heads x d_head`` read once a
    reading layer for all its query heads; q and o, one row a head, left
    out."""
    keys = done["serve.kv.decode_keys_read.full"] \
        + done["serve.kv.decode_keys_read.window"]
    return {"flops": keys * _per_pair(g),
            "bytes": keys * 2 * g["n_kv_heads"] * g["d_head"] * bytes_per_el}


def prefill_attention(done: dict, g: dict, bytes_per_el: int = 2) -> dict:
    """A chunk's attention under the flash forward kernel: ``pairs`` visible
    (query, key) pairs (``serve.attn.prefill_pairs.window`` of the window
    layers + ``.full``, which holds the full layer's pairs in chunks that
    read out alone — in a chunk that stops, its queries are not run — and the
    cross layers' one query each, which the paged kernel serves, a few
    thousand pairs in millions).

    Bytes: q read (``n_heads x d_head``) and o written (``n_heads x 2
    d_head``) once a query and window layer; k and v, shared by a tile of
    queries, and the full layer's queries are left out (fewer bytes: a lower
    share)."""
    window_layers = g["n_layers"] // 4     # the odd layers below the middle
    pairs = done["serve.attn.prefill_pairs.full"] \
        + done["serve.attn.prefill_pairs.window"]
    queries = done["serve.prefill_tokens"] * window_layers
    return {"flops": pairs * _per_pair(g),
            "bytes": queries * 3 * g["n_heads"] * g["d_head"] * bytes_per_el}


def selective_scan_decode(done: dict, g: dict) -> dict:
    """The packed decode step's state update: ``rows`` (live row, layer)
    pairs (the program's ``serve.sscan.decode_rows``), each over a state of
    ``ssm_state x d_inner`` f32.

    Bytes: the state read once and written once, a row's ``delta``, ``delta
    * u`` and ``y`` (``d_inner`` f32 each) and its ``B`` and ``C``
    (``ssm_state`` each); ``A``, fetched once a kernel call, is left out.
    FLOPs an element of the state: ``delta * A`` and its exponential (2), the
    decay (1), the rank-one update (2), ``S C`` (2)."""
    rows = done["serve.sscan.decode_rows"]
    n, dn = g["ssm_state"], g["d_inner"]
    return {"flops": rows * 7 * n * dn,
            "bytes": rows * (2 * n * dn + 3 * dn + 2 * n) * 4}
