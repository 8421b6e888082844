"""The second model step of the paged serve tier: a model of latent
attention (MLA) over a *latent* paged cache of up to two layer kinds. One
pair of programs for every such model: what differs between models — the
projections, the indexer, a gate on the attention output or none, the
feed-forward half, the readout — comes in a ``models/dots3.py::LatentModel``
(``dots3.MODEL``, ``deepseek_v32.MODEL``; ``families.latent_model`` finds a
configuration's), as ``paged_cache.py``'s programs take a ``StepPlan``; the
layer kinds, their shapes and the window come from the configuration
(``layer_types``, ``layers_of``, ``dims``, ``window``).

Same contract as ``paged_cache.make_paged_decode_fn`` /
``make_paged_prefill_fn`` — ``(params, pool, toks, pos, tables) -> (logits,
pool)``, the pool donated — with a pool of another shape
(:class:`LatentPool`). A configuration with sliding layers has table rows
of two lines: line 0 the request's blocks of the *global* kind (full layers
keep every block), line 1 those of the *window* kind (sliding layers keep
the blocks that hold the last ``window`` positions; the cache hands the
others back while the request runs, and their entries read 0, the scratch
block). One with full layers alone (DeepSeek-V3.2-Exp) has the one line
every family has, no window pool, and pages the prefix index may share.

A token leaves in the cache, per full layer, its latent row ``[c_kv; k_rope]``
and one indexer key; per sliding layer its (wider) latent row. Nothing is
ever materialised into per-head keys in the cache. The decode step attends in
the absorbed form over rows gathered through the tables (the picked 2,048 on
full layers, the last ``window`` on sliding ones). A prefill chunk
materialises k and v from the request's latent rows, transiently: a full
layer's for the live key buckets, a group of heads at a time, under the flash
kernel with the indexer's pick as its mask; a sliding layer's for the chunk
and the ``window - 1`` positions before it, under the flash kernel with the
window in its mask. Every write is ``pool.at[layer, block, offset].set`` on the donated
pool, per layer, as PR 31 made the rule.

What the programs count reaches the host in ``pool.stats`` with the step's
other outputs, a step late (:class:`LateStats`): no sync is added.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from byteps_tpu.common.metrics import get_registry
from byteps_tpu.common.tracing import traced_program
from byteps_tpu.models import dots3
from byteps_tpu.models.dots3 import FULL, SLIDING, LatentModel
from byteps_tpu.models.gpt import _rmsnorm
from byteps_tpu.models.joyai import mla_expand
# select_mask: the name under which the drivers and the tests take the
# program's indexer pick from here
from byteps_tpu.ops.dsa_index import (  # noqa: F401
    index_scores,
    select_mask,
    select_mask_counted,
)
from byteps_tpu.ops.flash_attention import (
    flash_attention_masked,
    flash_attention_window,
)
from byteps_tpu.serve import families
from byteps_tpu.serve.paged_cache import gather_rows as _gather_rows

_NEG = -1e30
#: a chunk's full layers work on the keys of the first ``ceil((pos0 + C) /
#: _KEY_BUCKET)`` buckets alone, not on the table's whole width: the table is
#: a power of two wide (a 16,385-token context reads 32,768 keys) and a
#: request's chunks see half its final context on average. One statically
#: shaped branch a bucket count: a branch is traced, lowered and compiled
#: like a program of its own, so 8,192 and not less (4,096 read 46 ms of
#: selected attention an iteration where this reads more, and twice the
#: branches in every chunk program's set-up)
_KEY_BUCKET = 8192
#: heads whose materialised k and v exist at once in a chunk's full layer:
#: 32 of 128 heads x 32,768 keys x (192 + 128) values is 0.67 GB in bf16
_HEAD_GROUP = 32

#: ``pool.stats``: what one program counted, f32 — the model's own
#: ``moe_stats`` and after them these
DSA_STATS = ("dsa.scored_pairs", "dsa.selected_keys", "dsa.queries",
             "dsa.prefill_scored_pairs", "dsa.prefill_selected_keys",
             "dsa.select_tie_tiles")


def stats_names(model: LatentModel) -> tuple:
    return tuple(model.moe_stats) + DSA_STATS


class LatentPool(NamedTuple):
    """The device half of the latent cache.

    kv: ``(full layers, blocks, block_size, page_row)`` latent rows of the
    full layers (``[c_kv; k_rope]`` and zeros to whole lane tiles); ki: ``(full layers, blocks, block_size,
    index_head_dim)`` their indexer keys (same blocks, same tables); wkv:
    ``(sliding layers, window blocks, block_size, page_row)`` the sliding
    layers' rows, in a pool of its own, far smaller (None without sliding
    layers); stats: :func:`stats_names` of the program that last wrote the
    pool."""

    kv: jnp.ndarray
    ki: jnp.ndarray
    wkv: Optional[jnp.ndarray]
    stats: jnp.ndarray

    #: the leaves a block of the global kind has a page in, each ``(layers,
    #: blocks, ...)``: what a copy of one block copies
    #: (``PagedKVCache.ensure_writable``)
    block_leaves = ("kv", "ki")


def init_pool(cfg, block_size: int, pool_blocks: int,
              window_blocks: int) -> LatentPool:
    model = families.latent_model(cfg)
    nf, nw = len(cfg.layers_of(FULL)), len(cfg.layers_of(SLIDING))
    return LatentPool(
        kv=jnp.zeros((nf, pool_blocks, block_size, cfg.dims(FULL).page_row),
                     cfg.dtype),
        ki=jnp.zeros((nf, pool_blocks, block_size, cfg.index_head_dim),
                     cfg.dtype),
        wkv=jnp.zeros((nw, window_blocks, block_size,
                       cfg.dims(SLIDING).page_row), cfg.dtype) if nw
        else None,
        stats=jnp.zeros((len(stats_names(model)),), jnp.float32))


class LateStats(families.LateStats):
    """:func:`stats_names` of each dispatched program into the ``moe.*``
    and ``serve.dsa.*`` series."""

    def __init__(self, model: LatentModel):
        super().__init__()
        self.names = stats_names(model)
        reg = get_registry()
        self._pairs_here = reg.histogram("moe.pairs_here")
        self._load = reg.histogram("moe.load_max_over_mean")
        self._groups = reg.histogram("moe.groups_hit") \
            if "moe.groups_hit" in self.names else None
        self._per_query = reg.histogram("serve.dsa.selected_per_query")
        self._scored = reg.counter("serve.dsa.scored_pairs")
        self._selected = reg.counter("serve.dsa.selected_keys")
        self._prefill_scored = reg.counter("serve.dsa.prefill_scored_pairs")
        self._prefill_selected = reg.counter(
            "serve.dsa.prefill_selected_keys")
        self._tie_tiles = reg.counter("serve.dsa.select_tie_tiles")

    def observe(self, s: dict) -> None:
        self._pairs_here.observe(s["moe.pairs_here"])
        self._load.observe(s["moe.load_max_over_mean"])
        if self._groups is not None:
            self._groups.observe(s["moe.groups_hit"])
        self._scored.inc(int(s["dsa.scored_pairs"]))
        self._selected.inc(int(s["dsa.selected_keys"]))
        self._prefill_scored.inc(int(s["dsa.prefill_scored_pairs"]))
        self._prefill_selected.inc(int(s["dsa.prefill_selected_keys"]))
        self._tie_tiles.inc(int(s["dsa.select_tie_tiles"]))
        if s["dsa.queries"] > 0:
            self._per_query.observe(
                s["dsa.selected_keys"] / s["dsa.queries"])


def _pick_rows(scores, topk: int):
    """``(sel (N, K), valid (N, K))``: the ``topk`` keys of largest score a
    query (every key while there are no more than ``topk``); ``valid`` is
    False where a query has fewer live keys than K."""
    L = scores.shape[-1]
    if topk >= L:
        return (jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32),
                                 scores.shape), scores > _NEG / 2)
    top, sel = jax.lax.top_k(scores, topk)
    return sel.astype(jnp.int32), top > _NEG / 2


def _full_decode_scores(qi, w, keys, pos):
    """``(R, L)`` indexer scores of one query a row against its own keys
    ``(R, L, Di)``; a key after the row's position reads -1e30."""
    s = jnp.einsum("rhd,rld->rhl", qi, keys,
                   preferred_element_type=jnp.float32)
    sc = jnp.einsum("rh,rhl->rl", w, jax.nn.relu(s))
    live = jnp.arange(keys.shape[1])[None, :] <= pos[:, None]
    return jnp.where(live, sc, _NEG)


def _lines(tables, windowed: bool):
    """``(global line, window line)`` of a table or a batch of tables: the
    second axis from the end holds the two lines of a configuration with
    sliding layers; one without has the one line and no such axis."""
    if windowed:
        return tables[..., 0, :], tables[..., 1, :]
    return tables, None


def _window_rows(pool, wi, table, pos, P: int, block_size: int):
    """``(rows (N, P + 1, row), valid)`` of the positions ``pos - P .. pos``
    of each of N requests (``table (N, W)``)."""
    at = pos[:, None] - P + jnp.arange(P + 1)[None, :]
    rows = _gather_rows(pool.wkv, wi, table, jnp.maximum(at, 0), block_size)
    return rows, at >= 0


def _stats(moe, scored, selected, queries, prefill_scored, prefill_selected,
           tie_tiles):
    return jnp.concatenate([moe, jnp.stack([
        jnp.asarray(v, jnp.float32) for v in (
            scored, selected, queries, prefill_scored, prefill_selected,
            tie_tiles)])])


@functools.lru_cache(maxsize=64)
def make_latent_decode_fn(cfg, block_size: int):
    """The jitted packed decode step: R requests feed one token each at
    their own positions. ``tables (R, 2, W)``, or ``(R, W)`` without
    sliding layers."""
    bs = block_size
    model = families.latent_model(cfg)
    full_of = {li: i for i, li in enumerate(cfg.layers_of(FULL))}
    win_of = {li: i for i, li in enumerate(cfg.layers_of(SLIDING))}
    P = cfg.window - 1 if win_of else None

    @functools.partial(jax.jit, donate_argnums=(1,))
    def step(params, pool, toks, pos, tables):
        g_tab, w_tab = _lines(tables, bool(win_of))
        R, W = g_tab.shape
        off = pos % bs
        blk_g = jnp.take_along_axis(g_tab, (pos // bs)[:, None], 1)[:, 0]
        if win_of:
            blk_w = jnp.take_along_axis(w_tab, (pos // bs)[:, None], 1)[:, 0]
        with jax.named_scope("embed"):
            x = params["wte"][toks][:, None].astype(cfg.dtype)  # (R, 1, d)
        moe = jnp.zeros((len(model.moe_stats),), jnp.float32)
        selected = jnp.zeros((), jnp.float32)
        for li, p in enumerate(params["blocks"]):
            kind = cfg.layer_types[li]
            a = cfg.dims(kind)
            with jax.named_scope("block/mla"):
                h = _rmsnorm(x, p["ln1_g"], eps=cfg.norm_eps)
                c_q, q, c_kv, k_rope = model.latents(h, p, pos[:, None], cfg,
                                                     kind)
                row = dots3.cache_row(c_kv, k_rope[:, :, 0], a)[:, 0]
                q_abs = dots3.absorb_q(q[:, 0], p, a)           # (R, H, row)
                if kind == FULL:
                    fi = full_of[li]
                    ki = model.index_keys(h, p["idx"], pos[:, None],
                                          cfg)[:, 0]
                    with jax.named_scope("latent/scatter"):
                        pool = pool._replace(
                            kv=pool.kv.at[fi, blk_g, off].set(row),
                            ki=pool.ki.at[fi, blk_g, off].set(ki))
                    qi, w = model.index_queries(c_q, h, p["idx"],
                                                pos[:, None], cfg)
                    with jax.named_scope("latent/index_scores"):
                        keys = pool.ki[fi, g_tab].reshape(R, W * bs, -1)
                        sel, valid = _pick_rows(_full_decode_scores(
                            qi[:, 0], w[:, 0], keys, pos), cfg.index_topk)
                    with jax.named_scope("latent/gather"):
                        rows = _gather_rows(pool.kv, fi, g_tab, sel, bs)
                    selected = selected + jnp.sum(valid)
                else:
                    wi = win_of[li]
                    with jax.named_scope("latent/scatter"):
                        pool = pool._replace(
                            wkv=pool.wkv.at[wi, blk_w, off].set(row))
                    with jax.named_scope("latent/gather"):
                        rows, valid = _window_rows(pool, wi, w_tab, pos, P,
                                                   bs)
                with jax.named_scope("latent/attention"):
                    o = dots3.unabsorb_v(
                        dots3.latent_attend(q_abs, rows, valid, a), p, a)
                x = x + model.attn_out(o[:, None], h, p)
            x, layer = model.ffn(x, p, cfg)
            moe = model.fold(moe, layer)
        nf = len(full_of)
        pool = pool._replace(stats=_stats(
            moe, jnp.sum(pos + 1) * nf, selected, R * nf, 0.0, 0.0, 0.0))
        with jax.named_scope("readout"):
            logits = model.readout(params, x, cfg)[:, 0]
        return logits, pool

    return traced_program(
        "serve.decode", step,
        key=lambda params, pool, toks, pos, tables: f"W={tables.shape[-1]}")


@functools.lru_cache(maxsize=256)
def make_latent_prefill_fn(cfg, block_size: int, chunk_len: int,
                           with_readout: bool = True):
    """The jitted prefill chunk of one request: ``C`` tokens at ``pos0``,
    ``table (2, W)``, or ``(W,)`` without sliding layers.
    ``with_readout=False`` returns ``(None, pool)``."""
    bs, C = block_size, chunk_len
    model = families.latent_model(cfg)
    full_of = {li: i for i, li in enumerate(cfg.layers_of(FULL))}
    win_of = {li: i for i, li in enumerate(cfg.layers_of(SLIDING))}
    P = cfg.window - 1 if win_of else None
    a_full = cfg.dims(FULL)

    def full_attend(p, q, qi, w, pool, fi, g_tab, pos0):
        """A full layer's attention for the chunk: ``(o (1, C, H, v), keys
        picked, row tiles whose pick had ties to place)``. Indexer scores
        against the cached keys and the picked set as a mask, found a row
        tile at a time in VMEM (``ops/dsa_index.py``: ``dsa_index_scores``,
        ``dsa_select_mask``), k and v materialised from the request's
        latent rows a group of heads at a time, and the flash forward kernel
        over exactly the picked pairs (``mla_sparse_attn``). No row is
        gathered one by one: XLA's gather of 2,048 rows a query ran at a
        tenth of the memory's rate and, with the sort behind ``top_k``, was
        three fifths of an iteration (PERF.md section 6, PR 35)."""
        a = a_full
        Lw, G = g_tab.shape[0] * bs, _KEY_BUCKET
        H, Hg = a.heads, min(_HEAD_GROUP, a.heads)
        if H % Hg:
            Hg = H
        wkv = p["wkv_b"].reshape(a.kv_rank, H // Hg, Hg, a.nope + a.v)
        qg = q.reshape(1, C, H // Hg, Hg, a.nope + a.rope)

        def over(n_keys):
            def attend(kv_pool, ki_pool):
                tab = g_tab[:n_keys // bs]
                keys = ki_pool[fi, tab].reshape(n_keys, -1)
                mask, picked, tie_tiles = select_mask_counted(
                    index_scores(qi, keys, w, pos0), cfg.index_topk)
                rows = kv_pool[fi, tab].reshape(1, n_keys, -1)
                c_kv, k_rope = rows[..., :a.kv_rank], rows[..., a.kv_rank:a.row]

                def group(args):
                    wg, qq = args                 # (r, Hg, e), (1, C, Hg, d)
                    kv = jnp.einsum(
                        "blr,rhe->blhe", c_kv, wg.astype(c_kv.dtype),
                        preferred_element_type=jnp.float32).astype(c_kv.dtype)
                    k = jnp.concatenate([kv[..., :a.nope], jnp.broadcast_to(
                        k_rope[:, :, None, :], (1, n_keys, Hg, a.rope))], -1)
                    return flash_attention_masked(
                        qq, k, kv[..., a.nope:], mask, pos0, 0,
                        name="mla_sparse_attn")

                o = jax.lax.map(group, (jnp.moveaxis(wkv, 1, 0),
                                        jnp.moveaxis(qg, 2, 0)))
                # (groups, 1, C, Hg, v) -> (1, C, H, v)
                return (jnp.moveaxis(o, 0, 2).reshape(1, C, H, a.v),
                        picked.astype(jnp.float32),
                        tie_tiles.astype(jnp.float32))
            return attend

        if Lw <= G or Lw % G or G % bs:
            return over(Lw)(pool.kv, pool.ki)
        n = Lw // G
        return jax.lax.switch(jnp.minimum((pos0 + C - 1) // G, n - 1),
                              [over((i + 1) * G) for i in range(n)],
                              pool.kv, pool.ki)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def chunk(params, pool, tokens, pos0, table):
        g_tab, w_tab = _lines(table, bool(win_of))
        positions = pos0 + jnp.arange(C)
        off = positions % bs
        blk_g = jnp.take(g_tab, positions // bs)
        if win_of:
            blk_w = jnp.take(w_tab, positions // bs)
        with jax.named_scope("embed"):
            x = params["wte"][tokens].astype(cfg.dtype)         # (1, C, d)
        moe = jnp.zeros((len(model.moe_stats),), jnp.float32)
        selected = tie_tiles = jnp.zeros((), jnp.float32)
        for li, p in enumerate(params["blocks"]):
            kind = cfg.layer_types[li]
            a = cfg.dims(kind)
            with jax.named_scope("block/mla"):
                h = _rmsnorm(x, p["ln1_g"], eps=cfg.norm_eps)
                c_q, q, c_kv, k_rope = model.latents(h, p, positions, cfg,
                                                     kind)
                row = dots3.cache_row(c_kv, k_rope[:, :, 0], a)[0]
                if kind == FULL:
                    fi = full_of[li]
                    ki = model.index_keys(h, p["idx"], positions, cfg)[0]
                    with jax.named_scope("latent/scatter"):
                        pool = pool._replace(
                            kv=pool.kv.at[fi, blk_g, off].set(row),
                            ki=pool.ki.at[fi, blk_g, off].set(ki))
                    qi, w = model.index_queries(c_q, h, p["idx"], positions,
                                                cfg)
                    with jax.named_scope("latent/sparse_attention"):
                        o, picked, ties = full_attend(p, q, qi[0], w[0],
                                                      pool, fi, g_tab, pos0)
                    selected, tie_tiles = selected + picked, tie_tiles + ties
                else:
                    wi = win_of[li]
                    with jax.named_scope("latent/scatter"):
                        pool = pool._replace(
                            wkv=pool.wkv.at[wi, blk_w, off].set(row))
                    with jax.named_scope("latent/window_attention"):
                        # the window - 1 rows before the chunk (those before
                        # position 0 are padding the mask never lets
                        # through) and the chunk's own, k and v materialised
                        before = pos0 - P + jnp.arange(P)
                        prev = _gather_rows(pool.wkv, wi, w_tab,
                                            jnp.maximum(before, 0), bs)
                        lat = jnp.concatenate([prev, row])[None]
                        k, v = mla_expand(
                            lat[..., :a.kv_rank],
                            lat[:, :, None, a.kv_rank:a.row], p,
                            n_heads=a.heads, nope=a.nope, v_dim=a.v)
                        o = flash_attention_window(q, k, v, pos0, pos0 - P,
                                                   cfg.window)
                x = x + model.attn_out(o, h, p)
            x, layer = model.ffn(x, p, cfg)
            moe = model.fold(moe, layer)
        nf = len(full_of)
        scored = (C * pos0 + C * (C + 1) // 2) * nf
        pool = pool._replace(stats=_stats(
            moe, scored, selected, C * nf, scored, selected, tie_tiles))
        logits = None
        if with_readout:
            with jax.named_scope("readout"):
                logits = model.readout(params, x, cfg)
        return logits, pool

    return traced_program(
        "serve.prefill", chunk,
        key=lambda params, pool, tokens, pos0, table:
        f"C={C},W={table.shape[-1]},readout={int(with_readout)}")
