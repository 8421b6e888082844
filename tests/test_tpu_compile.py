"""The main path's Pallas kernels compile for the chip — without the chip.

The TPU compiler is installed here and compiles for a v5e that is
described, not attached (``jax.experimental.topologies``): what Mosaic
refuses on the chip — a slice off the tiling, too much VMEM, a kernel it
cannot partition — it refuses here, at no chip time. Interpret mode, which
every other kernel test uses on CPU, shows none of that. Shapes are
``chip_smoke.py``'s: GPT-2-medium attention (16 heads of 64, S=1024, batch
8, bf16), the serve tier's 32-token prefill chunk, solo generate's decode
step, and 4 MB gradient partitions. Two whole programs are held too: the
serve cells' prefill chunk and packed decode step at GPT-2-large, which must
not touch the KV pool as a whole.

A compile is not a run: nothing here says a kernel is right or fast.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from byteps_tpu.ops import backend
from byteps_tpu.ops import onebit_kernels as ob
from byteps_tpu.ops import ring_collective_kernels as rk
from byteps_tpu.ops import topk_kernels as tk
# (the package re-exports functions named like these two modules)
from byteps_tpu.ops.flash_attention import _flash_core, flash_attention
from byteps_tpu.ops.flash_decode import flash_decode
from byteps_tpu.ops.grouped_matmul import grouped_matmul
from byteps_tpu.ops.paged_attention import paged_attention_decode

BF16, F32, I8, U32, I32 = (jnp.bfloat16, jnp.float32, jnp.int8, jnp.uint32,
                           jnp.int32)
PART = (4 << 20) // 4            # elements of one 4 MB f32 partition


@pytest.fixture(scope="module")
def topo():
    """A described v5e 2x2 host, or skip. The persistent compile cache is
    off around these tests: a compile for a described device is written to
    it but cannot be read back without a chip, so a later run would warn
    (on-chip-measurement guide, section 2)."""
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this image
        pytest.skip(f"cannot describe a v5e topology here: {e!r}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def as_on_tpu(monkeypatch):
    """The dispatchers ask ops/backend.py one platform question; here the
    backend is the CPU, so the test answers it (the guide: steer such code
    in the test, not through an option of the program)."""
    monkeypatch.setattr(backend, "_on_tpu", lambda: True)
    monkeypatch.delenv("BYTEPS_KERNEL_BACKEND", raising=False)


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _qkv(bh, sq, sk, d, kv_bh=None):
    kv = _sds((kv_bh or bh, sk, d), BF16)
    return _sds((bh, sq, d), BF16), kv, kv


def _flash_fwd(heads, kv_heads):
    def f(q, k, v):
        off = jnp.zeros((1, 1), F32)
        return _flash_core(q, k, v, off, off, True, backend.interpret(),
                           heads, kv_heads)
    return f


def _flash_fwd_bwd(heads, kv_heads):
    fwd = _flash_fwd(heads, kv_heads)

    def loss(q, k, v):
        o, lse = fwd(q, k, v)
        return o.astype(F32).sum() + lse.sum()
    return jax.grad(loss, argnums=(0, 1, 2))


def _attention_bshd(q, k, v):
    # through the dispatcher, (B, S, H, D) layout, as models/gpt.py calls it
    return flash_attention(q, k, v, causal=True)


def _attention_grad(q, k, v):
    return jax.grad(lambda *a: _attention_bshd(*a).astype(F32).sum(),
                    argnums=(0, 1, 2))(q, k, v)


def _decode(quant):
    def f(q, k, v, *scales):
        return flash_decode(q, k, v, jnp.int32(100), *scales)
    B, S, H, D = 1, 1024, 16, 64
    kv = _sds((B, S, H, D), I8 if quant else BF16)
    args = [_sds((B, 1, H, D), BF16), kv, kv]
    if quant:
        args += [_sds((B, S, H), F32)] * 2
    return f, args


def _paged_decode(W):
    # the serve cells' packed decode step: GPT-2-large's pool (36 layers,
    # 513 blocks of 16 tokens x 20 heads x 64), batch 8, table width W
    def f(q, k, v, tables, lengths):
        return paged_attention_decode(q, k, v, tables, lengths, 35)
    pool = _sds((36, 513, 16, 20 * 64), BF16)
    return f, [_sds((8, 20, 64), BF16), pool, pool, _sds((8, W), I32),
               _sds((8,), I32)]


def _paged_decode_cell(q_tail, pool, W, windowed=False, R=128):
    # a drawn cell's decode attention: pages of 128 rows x 512 values, a
    # chunk of 4 of them; ``q_tail`` the query's shape after the rows
    def f(q, k, v, tables, lengths, *first):
        return paged_attention_decode(q, k, v, tables, lengths, 1,
                                      first=first[0] if first else None)
    pool = _sds(pool + (128, 512), BF16)
    return f, [_sds((R,) + q_tail, BF16), pool, pool, _sds((R, W), I32),
               _sds((R,), I32)] + [_sds((R,), I32)] * windowed


def _gmm_fwd_bwd(lhs, w_up, w_down, sizes):
    # one expert stack up and one down, as parallel/moe.py chains them
    def loss(lhs, w_up, w_down):
        h = grouped_matmul(lhs, w_up, sizes)
        return grouped_matmul(h, w_down, sizes).astype(F32).sum()
    return jax.grad(loss, argnums=(0, 1, 2))(lhs, w_up, w_down)


def _select_mask(topk, scores):
    from byteps_tpu.ops.dsa_index import select_mask

    return select_mask(scores, topk)


def _gmm_up_down(lhs, w_up, w_down, sizes, tm=256):
    # a serve program's expert layer: forward alone, up then down
    return grouped_matmul(grouped_matmul(lhs, w_up, sizes, tm), w_down,
                          sizes, tm)


def _gmm_shapes(rows, held, d, ff):
    """A cell's worst-case row buffer (pairs / tile + a tile an expert held,
    the tile ``parallel/moe.py::dropless_row_tile`` gives the program) and
    its expert stacks up and down, bf16."""
    return [_sds((rows, d), BF16), _sds((held, d, ff), BF16),
            _sds((held, ff, d), BF16), _sds((held,), I32)]


def _row_moves(tokens, d, k, rows):
    """The dropless layer's row kernels (ops/moe_rows.py) at a program's
    shapes, bf16: ``[(id suffix, function, argument shapes, Pallas calls)]``
    — each call packs its source first, so two calls a move."""
    from byteps_tpu.ops import moe_rows as mr

    live = _sds((1,), I32)
    slots = [_sds((tokens,), I32), _sds((tokens, k), I32)]
    return [
        ("to_buffer", lambda x, row_pair, n: mr.moe_rows_to_buffer(
            x, row_pair, k, None, n),
         [_sds((tokens, d), BF16), _sds((rows,), I32), live], 2),
        ("to_buffer_weighted", lambda x, row_pair, w, n:
         mr.moe_rows_to_buffer(x, row_pair, k, w, n),
         [_sds((tokens, d), BF16), _sds((rows,), I32),
          _sds((tokens, k), F32), live], 2),
        ("to_tokens", mr.moe_rows_to_tokens,
         [_sds((rows, d), BF16), *slots, _sds((tokens, k), F32), live], 2),
        ("dweight", mr.moe_rows_dweight,
         [_sds((rows, d), BF16), *slots, _sds((tokens, d), BF16), live], 2),
    ]


# the JoyAI-LLM-Flash cell's routed experts: 16 held, 2048 -> 768 -> 2048,
# the worst-case row buffer of 4 x 4096 tokens x top-8 (+ a tile a group)
_GMM = _gmm_shapes(135168, 16, 2048, 768)

# (id, function, argument shapes, Pallas calls expected in the program)
ONE_CHIP = [
    ("flash_fwd_gpt2m", _flash_fwd(16, 16), _qkv(128, 1024, 1024, 64), 1),
    # the backward is ONE kernel beside the forward where a head's dq can
    # stay in VMEM; a grouped-query shape keeps dq and dkv apart
    ("flash_fwd_bwd_gpt2m", _flash_fwd_bwd(16, 16),
     _qkv(128, 1024, 1024, 64), 2),
    ("flash_fwd_bwd_gqa32_4_d128_s2048", _flash_fwd_bwd(32, 4),
     _qkv(32, 2048, 2048, 128, kv_bh=4), 3),
    # latent attention with k and v materialised: q/k 192 wide, v 128
    # (JoyAI-LLM-Flash: 4 sequences x 32 heads, S 4096)
    ("flash_fwd_bwd_mla_qk192_v128_s4096", _flash_fwd_bwd(32, 32),
     [_sds((128, 4096, 192), BF16)] * 2 + [_sds((128, 4096, 128), BF16)], 2),
    # the training cells' two shapes as the models call them, (B, S, H, D)
    # through the dispatcher under jax.grad: forward + the fused backward
    # (JoyAI's live set is over the scoped default and states its limit)
    ("flash_grad_fused_gpt2m_b8_h16", _attention_grad,
     [_sds((8, 1024, 16, 64), BF16)] * 3, 2),
    ("flash_grad_fused_joyai_b4_h32", _attention_grad,
     [_sds((4, 4096, 32, 192), BF16)] * 2 + [_sds((4, 4096, 32, 128), BF16)],
     2),
    ("moe_gmm_fwd_joyai", lambda lhs, w, _, sizes:
     grouped_matmul(lhs, w, sizes), _GMM, 1),
    # the first product forward (the second's output is not needed for
    # its gradient), then dx and dw of each
    ("moe_gmm_fwd_bwd_joyai", _gmm_fwd_bwd, _GMM, 5),
    # the row moves round them under an expert share: JoyAI's step (16,384
    # tokens of 2,048, top-8, the 135,168-row buffer) and dots3's chunk
    # (2,048 tokens of 5,120, top-8 over 32 held: 96 tiles), whose packed row
    # is 20 lane blocks of words, not a multiple of the 8 sublanes
    *[(f"moe_rows_{what}_joyai", fn, args, n)
      for what, fn, args, n in _row_moves(16384, 2048, 8, 135168)],
    *[(f"moe_rows_{what}_dots3_chunk", fn, args, n)
      for what, fn, args, n in _row_moves(2048, 5120, 8, 96 * 256)],
    # blocks as wide as an expert's matrix must fit v5e's scoped VMEM:
    # Mellum2's 64 experts of 2304 x 896 under a 24-row decode step (192
    # pairs: 65 tiles) and a 2,048-token chunk (128 tiles), whole matrices;
    # dots3's 32 held experts of 5120 x 1536 under its chunk (96 tiles),
    # the contraction axis split
    ("moe_gmm_fwd_mellum2_decode", _gmm_up_down,
     _gmm_shapes(65 * 256, 64, 2304, 896), 2),
    # the decode steps at the tiles their pairs give (an expert's even share
    # rounded up to a power of two, 16 rows at the least in bf16): Mellum2's
    # 192 pairs over 64 experts in 12 + 64 tiles of 16, Qwen3-Next's 1,280
    # over 64 held (2048 x 512) in 40 + 64 of 32, dots3's 128 over 32 held
    # in 8 + 32 of 16; the 1,024-token tail chunk of Mellum2 at 128
    ("moe_gmm_fwd_mellum2_decode_tm16", functools.partial(
        _gmm_up_down, tm=16), _gmm_shapes(76 * 16, 64, 2304, 896), 2),
    ("moe_gmm_fwd_qwen3next_decode_tm32", functools.partial(
        _gmm_up_down, tm=32), _gmm_shapes(104 * 32, 64, 2048, 512), 2),
    ("moe_gmm_fwd_dots3_decode_tm16", functools.partial(
        _gmm_up_down, tm=16), _gmm_shapes(40 * 16, 32, 5120, 1536), 2),
    ("moe_gmm_fwd_mellum2_tail_tm128", functools.partial(
        _gmm_up_down, tm=128), _gmm_shapes(128 * 128, 64, 2304, 896), 2),
    ("moe_gmm_fwd_mellum2_chunk", _gmm_up_down,
     _gmm_shapes(128 * 256, 64, 2304, 896), 2),
    ("moe_gmm_fwd_dots3_chunk", _gmm_up_down,
     _gmm_shapes(96 * 256, 32, 5120, 1536), 2),
    # widths off the lanes have one legal block, the whole axis: 17.6 MB,
    # over the budget, so the call asks for the VMEM it needs
    ("moe_gmm_fwd_whole_axes_over_budget", _gmm_up_down,
     _gmm_shapes(4 * 256, 4, 3000, 1000), 2),
    # the paged prefill chunk: 32 new tokens against the widest and the
    # narrowest gathered view
    ("flash_fwd_prefill_chunk_wide", _flash_fwd(16, 16),
     _qkv(16, 32, 1024, 64), 1),
    ("flash_fwd_prefill_chunk_narrow", _flash_fwd(16, 16),
     _qkv(16, 32, 32, 64), 1),
    ("flash_attention_dispatch", _attention_bshd,
     [_sds((8, 1024, 16, 64), BF16)] * 3, 1),
    ("flash_decode_dense", *_decode(False), 1),
    ("flash_decode_int8", *_decode(True), 1),
    ("paged_attn_decode_gpt2l_w64", *_paged_decode(64), 1),
    ("paged_attn_decode_gpt2l_w2", *_paged_decode(2), 1),
    # heads of 128: a k/v head's rows with that head's columns (grouped).
    # SDAR's block of 4 queries a row, 32 rows a k/v head, the batch in 4
    # grid steps; Mellum2's window layers, 8 rows a head and a first key
    ("paged_attn_decode_sdar_b4_w64",
     *_paged_decode_cell((4, 32, 128), (6, 2305), 64), 1),
    ("paged_attn_decode_sdar_b4_w2",
     *_paged_decode_cell((4, 32, 128), (6, 2305), 2), 1),
    ("paged_attn_decode_mellum2_window_w256",
     *_paged_decode_cell((32, 128), (6, 318), 256, windowed=True, R=24), 1),
    # the indexer's pick of a prefill chunk: a row tile of f32 scores, its
    # integer image and the int8 mask, over the scoped default at the widest
    # table, so the call states its limit; the full chunk, the two ends of a
    # final chunk and the narrowest key bucket
    *[(f"dsa_select_mask_c{c}_l{l}", functools.partial(_select_mask, 2048),
       [_sds((c, l), F32)], 1)
      for c, l in ((2048, 32768), (1280, 32768), (512, 32768), (256, 8192))],
    ("onebit_pack", ob.onebit_pack, [_sds((PART,), F32)], 1),
    *[(f"onebit_unpack_sum_k{k}",
       functools.partial(ob.onebit_unpack_sum, n=PART),
       [_sds((k, ob.packed_words(PART)), U32), _sds((k,), F32)], 1)
      for k in (1, 4, 8, 16)],
    ("topk_select", tk.block_select, [_sds((128, 8192), F32)], 1),
    ("topk_reconstruct_k4",
     functools.partial(tk.block_reconstruct_sum, block=128),
     [_sds((4, 8192), I32), _sds((4, 8192), F32)], 1),
    ("topk_roundtrip_ef",
     lambda x, e: tk.block_roundtrip(x, 64, 128, e=e),
     [_sds((PART,), F32)] * 2, 1),
]

# the ring transport kernels issue remote DMAs: four chips, inside shard_map
RING = [
    ("ring_collect", lambda x: rk._rotate_pallas(
        x[0], 4, "dp", gather=False, interpret=backend.interpret())[None],
     (4, 4, 8, 128)),
    # what the ring TIER actually hands the kernel: onebit's (n, words)
    # payload stack of a 1 MB segment. Mosaic refuses the row slice of a
    # 2-D stack ("Slice shape along dimension 0 must be aligned to tiling
    # (4), but is 1") — seen on four real chips in PR 21, so
    # BYTEPS_ICI_TIER=ring does not compile there (ROADMAP A6). Strict:
    # the day the kernel takes this shape, this entry says so.
    pytest.param(
        ("ring_collect_onebit_payload", lambda x: rk._rotate_pallas(
            x[0], 4, "dp", gather=False,
            interpret=backend.interpret())[None], (4, 4, 8192)),
        marks=pytest.mark.xfail(strict=True, reason="ROADMAP A6")),
    ("ring_allgather", lambda x: rk._rotate_pallas(
        x[0], 4, "dp", gather=True, interpret=backend.interpret())[None],
     (4, 8, 128)),
    ("ring_presum", lambda x: rk._presum_pallas(
        x[0], 4, "dp", interpret=backend.interpret())[None],
     (4, 4, 8, 128)),
]


def _n_pallas(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def _case_id(case):
    return case[0] if isinstance(case, tuple) else None


@pytest.mark.parametrize("case", ONE_CHIP + RING, ids=_case_id)
def test_kernel_compiles_for_v5e(topo, as_on_tpu, case):
    if len(case) == 4:
        _, fn, args, n_calls = case
        one = SingleDeviceSharding(topo.devices[0])
        args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)
                for a in args]
    else:
        _, body, shape = case
        n_calls = 1
        mesh = Mesh(topo.devices, ("dp",))
        fn = jax.shard_map(body, mesh=mesh, in_specs=P("dp"),
                           out_specs=P("dp"), check_vma=False)
        args = [jax.ShapeDtypeStruct(shape, F32,
                                     sharding=NamedSharding(mesh, P("dp")))]
    compiled = jax.jit(fn).lower(*args).compile()
    assert _n_pallas(compiled) == n_calls, compiled.as_text()[:2000]


def test_row_kernels_compile_under_a_checked_shard_map(topo, as_on_tpu):
    """The training factories run the layer under ``shard_map(check_vma=
    True)``: a value read from a ref varies there, and an array constant
    met with it inside a kernel needs a ``pvary`` that Mosaic does not lower
    (jnp's floor division makes such constants; PR 62 met it on the chip).
    The layer's forward and gradient under an expert share, four chips."""
    from byteps_tpu.parallel.moe import moe_ffn_dropless

    T, d, ff, held, k = 8192, 2048, 768, 16, 8      # 256 + 16 tiles a chip
    mesh = Mesh(topo.devices, ("dp",))
    p = {"wg": _sds((d, 256), F32), "router_bias": _sds((256,), F32),
         "w1": _sds((held, d, ff), BF16), "w3": _sds((held, d, ff), BF16),
         "w2": _sds((held, ff, d), BF16)}

    def loss(x, p):
        y = moe_ffn_dropless(x, p, k, 2.5, first_expert=held)[0]
        return (y.astype(F32) ** 2).sum()

    def body(x, p):
        dx, dp = jax.grad(loss, (0, 1))(x, p)
        return dx, jax.tree.map(lambda a: jax.lax.psum(a, "dp"), dp)

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P("dp"), P()),
                       out_specs=(P("dp"), P()), check_vma=True)
    args = (jax.ShapeDtypeStruct((4 * T, d), BF16,
                                 sharding=NamedSharding(mesh, P("dp"))),
            {name: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                        sharding=NamedSharding(mesh, P()))
             for name, a in p.items()})
    compiled = jax.jit(fn).lower(*args).compile()
    # 3 + 6 grouped products; a pack and a move each for x and ys forward,
    # dout, ys and dxs back, the two packs of ys merged by the compiler
    assert _n_pallas(compiled) == 9 + 2 * 5 - 1, _n_pallas(compiled)


# ---- whole programs: the serve cells' prefill chunk and decode step ---------
_POOL = (36, 513, 16, 20 * 64)        # GPT-2-large's KV pool, 756 MB in bf16


def _top_level(hlo: str):
    """The instructions outside the fused computations, one line each."""
    fused = False
    for line in hlo.splitlines():
        if line[:1] not in ("", " "):          # a computation's header
            fused = line.startswith("%fused_computation")
        elif not fused:
            yield line


def _ops_with_result(hlo: str, shape: str):
    """``(op, aliased)`` of every instruction outside the fused computations
    whose result has ``shape``. A fusion that updates an operand in place
    says so in its ``aliasing_operands``."""
    for line in _top_level(hlo):
        if f" = {shape}{{" in line:
            op = re.search(r"\} ([\w-]+)\(", line).group(1)
            yield op, '"aliasing_operands":{"lists":[{' in line


def _gpt2_large_on(topo):
    """GPT-2-large's parameters and KV pool as shapes on the described
    chip, and the maker of further arguments there."""
    from byteps_tpu.models import GPTConfig, gpt_init
    from byteps_tpu.serve.paged_cache import PoolState

    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    cfg = GPTConfig(vocab_size=50304, max_seq=1024, d_model=1280, n_heads=20,
                    n_layers=_POOL[0], d_ff=5120, dtype=BF16)
    params = jax.tree.map(
        lambda a: on_chip(a.shape, a.dtype),
        jax.eval_shape(lambda: gpt_init(jax.random.PRNGKey(0), cfg)))
    pool = PoolState(on_chip(_POOL, BF16), on_chip(_POOL, BF16))
    return cfg, params, pool, on_chip


def _compile_chunk(cfg, tree, pool, on_chip, C, W, with_readout):
    from byteps_tpu.serve.paged_cache import make_paged_prefill_fn

    chunk = make_paged_prefill_fn(cfg, _POOL[2], C, None, with_readout)
    return chunk.lower(tree, pool, on_chip((1, C), I32), on_chip((), I32),
                       on_chip((W,), I32)).compile()


def _compile_decode(cfg, tree, pool, on_chip, W):
    from byteps_tpu.serve.paged_cache import make_paged_decode_fn

    step = make_paged_decode_fn(cfg, _POOL[2])
    return step.lower(tree, pool, on_chip((8,), I32), on_chip((8,), I32),
                      on_chip((8, W), I32)).compile()


def _assert_pool_in_place(compiled, n_pallas):
    """No instruction makes a second pool (a ``copy`` re-laying it out, a
    scatter that is not in place): K and V of every layer are scattered
    once and in place, and the temporaries are far under a pool."""
    shape = "bf16[%s]" % ",".join(map(str, _POOL))
    made = [(op, aliased)
            for op, aliased in _ops_with_result(compiled.as_text(), shape)
            if op not in ("parameter", "tuple", "get-tuple-element",
                          "bitcast")]
    assert made == [("fusion", True)] * (2 * _POOL[0]), made
    assert compiled.memory_analysis().temp_size_in_bytes < 0.25e9
    assert _n_pallas(compiled) == n_pallas


@pytest.mark.parametrize("C,W,with_readout", [
    (32, 8, False), (16, 64, True)],
    ids=["c32_w8_no_readout", "c16_w64_readout"])
def test_prefill_chunk_program_leaves_the_pool_in_place(
        topo, as_on_tpu, C, W, with_readout):
    """The whole chunk program of the serve cells: no instruction makes a
    second pool, and the program's temporaries are a layer's views, not
    pools — the parent of PR 31 gathered and scattered all layers at once
    and compiled to four pool-sized copies, two pool-sized scatters and
    0.9 GB of temporaries. The flash calls are the parent's count: one a
    layer, less the last layer's where no readout asks for its output."""
    compiled = _compile_chunk(*_gpt2_large_on(topo), C, W, with_readout)
    _assert_pool_in_place(compiled, _POOL[0] - (not with_readout))


@pytest.mark.parametrize("W", [8, 64], ids=["w8", "w64"])
def test_decode_step_program_leaves_the_pool_in_place(topo, as_on_tpu, W):
    """The whole packed decode step of the serve cells (batch 8, table width
    W): each layer scatters the new token's K and V in place and attends
    through one ``paged_attn_decode`` call over the pool where it lies."""
    compiled = _compile_decode(*_gpt2_large_on(topo), W)
    _assert_pool_in_place(compiled, _POOL[0])


# a projection or bias of GPT-2-large's block: what serve_operands casts
_WEIGHT = r"\[(1280|5120|1280,1280|1280,5120|5120,1280)\]"


@pytest.mark.parametrize("program", ["decode_step", "chunk_c32"])
def test_serve_program_reads_prepared_operands_as_they_are(
        topo, as_on_tpu, program):
    """Both serve programs on the tree ``serve_operands`` returns: nothing
    is left of the per-call casts. On the caller's f32 tree the decode step
    compiles to 216 top-level ``convert``s (a layer's six biases; the
    weights' rounding sits inside the matmul fusions) and the chunk to 212,
    each weight travels in f32 (slices and prefetches), and the cost
    analysis reads 9.19 and 9.71 GB accessed; prepared, 6.16 and 6.82."""
    from byteps_tpu.serve.paged_cache import _PROJECTED, serve_operands

    cfg, params, pool, on_chip = _gpt2_large_on(topo)
    operands = jax.tree.map(
        lambda a: on_chip(a.shape, a.dtype),
        jax.eval_shape(lambda p: serve_operands(p, cfg), params))
    assert operands["wte"].dtype == F32 and operands["lm_head"].dtype == BF16
    if program == "decode_step":
        compiled = _compile_decode(cfg, operands, pool, on_chip, 8)
    else:
        compiled = _compile_chunk(cfg, operands, pool, on_chip, 32, 8, False)
    top = list(_top_level(compiled.as_text()))
    casts = [ln for ln in top
             if re.search(r" = bf16%s\S* convert\(" % _WEIGHT, ln)]
    assert not casts, casts[:3]
    # the async moves (prefetches, slices) of a projected leaf: none in f32
    moves = [ln for ln in top if re.search(
        r"-start\(%%params__blocks___\d+___(%s)__" % "|".join(_PROJECTED), ln)]
    assert moves and not [ln for ln in moves if "f32[" in ln], moves[:3]
    assert compiled.cost_analysis()["bytes accessed"] < 7e9


# --- the dots3 serving cell's two programs at the cut configuration ---------
_DOTS_BS, _DOTS_BATCH, _DOTS_CHUNK = 128, 16, 2048


def _dots3_on(topo):
    """The cut dots3-note-prev (``benchmark/configs/dots3-note-ep8.json``:
    5 layers, 32 of 256 experts, 19,072 vocabulary rows, bf16), its
    parameters and latent pool as shapes on the described chip."""
    from byteps_tpu.models.dots3 import Dots3Config, dots3_init
    from byteps_tpu.serve.families import serve_family

    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    cfg = Dots3Config(vocab_size=19072, max_seq=32768, n_layers=5,
                      experts_held=32)
    shapes = jax.eval_shape(lambda: dots3_init(jax.random.PRNGKey(0), cfg))
    params = jax.tree.map(lambda a: on_chip(a.shape, a.dtype), shapes)
    pool = jax.eval_shape(lambda: serve_family(cfg).layout(
        shapes, cfg, block_size=_DOTS_BS, pool_blocks=1 + 20 * 256,
        max_batch=_DOTS_BATCH, prefill_chunk=_DOTS_CHUNK,
        quant=False).state)
    pool = jax.tree.map(lambda a: on_chip(a.shape, a.dtype), pool)
    return cfg, params, pool, on_chip


def _bytes(tree) -> int:
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))


@pytest.mark.parametrize("program", ["chunk_c2048_w256", "decode_r16_w256"])
def test_dots3_serve_program_fits_and_leaves_the_pool_in_place(
        topo, as_on_tpu, program):
    """The proof of Mosaic lowering that needs no chip: the 2,048-token
    chunk program and the 16-row decode step at the table width of 32,768
    positions compile for the described v5e. The weights are 8.17 GB in bf16
    (within 5%), arguments are the weights, the pool and a few vectors and
    nothing else, the pool is updated in place (no instruction makes a second
    one), no ``(64, C, L)`` indexer intermediate exists, and weights + pool +
    temporaries fit the chip's 16 GB."""
    from byteps_tpu.serve.latent_step import (
        make_latent_decode_fn,
        make_latent_prefill_fn,
    )

    cfg, params, pool, on_chip = _dots3_on(topo)
    W = 32768 // _DOTS_BS
    if program.startswith("chunk"):
        compiled = make_latent_prefill_fn(cfg, _DOTS_BS, _DOTS_CHUNK, False)\
            .lower(params, pool, on_chip((1, _DOTS_CHUNK), I32),
                   on_chip((), I32), on_chip((2, W), I32)).compile()
    else:
        compiled = make_latent_decode_fn(cfg, _DOTS_BS).lower(
            params, pool, on_chip((_DOTS_BATCH,), I32),
            on_chip((_DOTS_BATCH,), I32),
            on_chip((_DOTS_BATCH, 2, W), I32)).compile()
    weights, pages = _bytes(params), _bytes(pool)
    assert abs(weights / 8.17e9 - 1) < 0.05, weights
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pages - 64            # donated, in place
    assert mem.argument_size_in_bytes <= weights + pages + (1 << 20)
    assert weights + pages + mem.temp_size_in_bytes < 15.5e9
    hlo = compiled.as_text()
    for name, a in zip(pool._fields, pool):
        shape = "bf16[%s]" % ",".join(map(str, a.shape))
        made = [op for op, aliased in _ops_with_result(hlo, shape)
                if op not in ("parameter", "tuple", "get-tuple-element",
                              "bitcast") and not aliased]
        assert not made, (name, made)
    # the indexer's scores never exist a head at a time: no (Hi, C, L)
    assert not re.search(r"\[64,2048,32768\]|\[2048,64,32768\]", hlo)
    n = _n_pallas(compiled)
    if program.startswith("chunk"):
        # an indexer-score call, a selection call and a selected-attention
        # call for each of the 4 key-bucket branches of each full layer, 3
        # window-flash calls, 3 grouped products for each expert layer but
        # the last (no readout asks for its output, so its second half is
        # not in the program)
        assert n == 3 * 4 * 2 + 3 + 9, n
        for name in ("dsa_index_scores", "dsa_select_mask",
                     "mla_sparse_attn", "flash_fwd", "moe_gmm_fwd"):
            assert name in hlo, name
        # the selection's image and its masks stay in the kernel's VMEM
        # (PR 55's program held 88, 70 and 98 instructions of these shapes
        # and 1,965,927,424 bytes of temporaries)
        assert not re.search(r"(u32|s32|pred)\[2048,32768\]", hlo)
        assert mem.temp_size_in_bytes <= 1_965_927_424
    else:
        assert n == 12, n                   # the grouped products alone


# --- the Mellum2 serving cell's two programs at the cut configuration -------
_MEL_BS, _MEL_BATCH, _MEL_CHUNK = 128, 24, 2048


def _mellum2_on(topo):
    """The cut Mellum2 (``benchmark/configs/mellum2-12b-a2.5b-l8.json``: 8
    of 28 layers, every layer whole, bf16), its parameters and pools of two
    kinds as shapes on the described chip."""
    from byteps_tpu.models.mellum2 import Mellum2Config, mellum2_init
    from byteps_tpu.serve.families import serve_family

    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    cfg = Mellum2Config(max_seq=32768, n_layers=8)
    shapes = jax.eval_shape(lambda: mellum2_init(jax.random.PRNGKey(0), cfg))
    params = jax.tree.map(lambda a: on_chip(a.shape, a.dtype), shapes)
    family = serve_family(cfg)
    pool = jax.eval_shape(lambda: family.layout(
        shapes, cfg, block_size=_MEL_BS, pool_blocks=1 + _MEL_BATCH * 257,
        max_batch=_MEL_BATCH, prefill_chunk=_MEL_CHUNK, quant=False).state)
    pool = jax.tree.map(lambda a: on_chip(a.shape, a.dtype), pool)
    return cfg, family, params, pool, on_chip


@pytest.mark.parametrize("program", ["chunk_c2048_w256", "decode_r24_w256"])
def test_mellum2_serve_program_fits_and_leaves_the_pools_in_place(
        topo, as_on_tpu, program):
    """The 2,048-token chunk program and the 24-row decode step at the table
    width of 32,768 positions compile for the described v5e at the published
    widths: 3,794,966,784 parameters (7.59 GB in bf16), a global pool of
    6,169 blocks for the 2 full layers and a window pool of 318 for the 6
    sliding ones, both donated and updated in place; weights + pools +
    temporaries fit the chip's 16 GB. A chunk: 2 causal + 6 window flash
    calls and 3 grouped products a layer (the last layer's second half is
    not in a program without readout); a decode step: 8 paged-attention
    calls — 2 as they were, 6 with a first key — and 24 grouped products."""
    cfg, family, params, pool, on_chip = _mellum2_on(topo)
    W = 32768 // _MEL_BS
    if program.startswith("chunk"):
        compiled = family.prefill_fn(cfg, _MEL_BS, _MEL_CHUNK, None, False)\
            .lower(params, pool, on_chip((1, _MEL_CHUNK), I32),
                   on_chip((), I32), on_chip((2, W), I32)).compile()
    else:
        assert family.decode_reads_pool_in_place(
            cfg, type("C", (), dict(block_size=_MEL_BS, kv_heads=4,
                                    quant=False)))
        compiled = family.decode_fn(cfg, _MEL_BS, None, None).lower(
            params, pool, on_chip((_MEL_BATCH,), I32),
            on_chip((_MEL_BATCH,), I32),
            on_chip((_MEL_BATCH, 2, W), I32)).compile()
    weights, pages = _bytes(params), _bytes(pool)
    assert weights == 2 * 3794966784, weights
    assert pool.k.shape == (2, 6169, 128, 512)
    assert pool.wk.shape == (6, 318, 128, 512)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pages - 64            # donated, in place
    assert mem.argument_size_in_bytes <= weights + pages + (1 << 20)
    assert weights + pages + mem.temp_size_in_bytes < 15.5e9, \
        mem.temp_size_in_bytes
    hlo = compiled.as_text()
    for name in ("k", "v", "wk", "wv"):
        shape = "bf16[%s]" % ",".join(map(str, getattr(pool, name).shape))
        made = [op for op, aliased in _ops_with_result(hlo, shape)
                if op not in ("parameter", "tuple", "get-tuple-element",
                              "bitcast") and not aliased]
        assert not made, (name, made)
    n = _n_pallas(compiled)
    if program.startswith("chunk"):
        assert n == 8 + 3 * 7, n
        for name in ("flash_fwd", "moe_gmm_fwd"):
            assert name in hlo, name
    else:
        assert n == 8 + 3 * 8, n
        assert "paged_attn_decode" in hlo


# --- the Qwen3-Next serving cell's two programs at the cut configuration ----
_QN_BS, _QN_BATCH, _QN_CHUNK, _QN_BLOCKS = 128, 128, 2048, 8193


def _qwen3next_on(topo):
    """The cut Qwen3-Next (``benchmark/configs/qwen3-next-80b-a3b-ep8-l8
    .json``: 8 of 48 layers — two whole periods —, 64 of 512 experts held,
    an eighth of the vocabulary, bf16), its parameters, its k/v pool and its
    state pool as shapes on the described chip."""
    from byteps_tpu.models.qwen3_next import Qwen3NextConfig, qwen3_next_init
    from byteps_tpu.serve.families import serve_family

    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    cfg = Qwen3NextConfig(max_seq=32768, n_layers=8, experts_held=64,
                          vocab_size=19072)
    shapes = jax.eval_shape(
        lambda: qwen3_next_init(jax.random.PRNGKey(0), cfg))
    params = jax.tree.map(lambda a: on_chip(a.shape, a.dtype), shapes)
    family = serve_family(cfg)
    pool = jax.eval_shape(lambda: family.layout(
        shapes, cfg, block_size=_QN_BS, pool_blocks=_QN_BLOCKS,
        max_batch=_QN_BATCH, prefill_chunk=_QN_CHUNK, quant=False).state)
    pool = jax.tree.map(lambda a: on_chip(a.shape, a.dtype), pool)
    return cfg, family, params, pool, on_chip


def _gdn_decode_case():
    from byteps_tpu.ops.gated_delta import gdn_decode

    def f(q, k, v, g, beta, pool, slots):
        return gdn_decode(q, k, v, g, beta, pool, 5, slots)
    R, H, D = _QN_BATCH, 32, 128
    row = _sds((R, H, D), F32)
    return f, [row, row, row, _sds((R, H), F32), _sds((R, H), F32),
               _sds((6, 161, H, D, D), F32), _sds((R,), I32)]


def _qn_paged_decode(q, k, v, tables, lengths):
    return paged_attention_decode(q, k, v, tables, lengths, 1)


_QN_KERNELS = [
    # the decode step's state update in place in the slot pool
    ("gdn_decode_r128_h32_d128", *_gdn_decode_case(), 1),
    # head size 256 with 8 query heads a k/v head: the packed decode step's
    # attention over the 2-layer pool, and a 2,048-token chunk's forward
    # against a 16k-key view
    ("paged_attn_decode_qwen3next_d256_w256", _qn_paged_decode,
     [_sds((_QN_BATCH, 16, 256), BF16)]
     + [_sds((2, _QN_BLOCKS, _QN_BS, 512), BF16)] * 2
     + [_sds((_QN_BATCH, 256), I32), _sds((_QN_BATCH,), I32)], 1),
    ("flash_fwd_qwen3next_d256_gqa16_2", _flash_fwd(16, 2),
     _qkv(16, 2048, 16384, 256, kv_bh=2), 1),
]


@pytest.mark.parametrize("case", _QN_KERNELS, ids=_case_id)
def test_qwen3next_kernel_compiles_for_v5e(topo, as_on_tpu, case):
    test_kernel_compiles_for_v5e(topo, as_on_tpu, case)


def test_qwen3next_chunked_rule_compiles_for_v5e(topo, as_on_tpu):
    """A prefill chunk's chunked gated delta rule (plain XLA, f32 at the
    highest precision) compiles for the chip with temporaries far under a
    state pool."""
    from byteps_tpu.ops.gated_delta import gdn_chunk_fwd

    one = SingleDeviceSharding(topo.devices[0])
    T, H, D = _QN_CHUNK, 32, 128
    args = [jax.ShapeDtypeStruct(s, F32, sharding=one) for s in
            [(T, H, D)] * 3 + [(T, H)] * 2 + [(H, D, D)]]
    compiled = jax.jit(gdn_chunk_fwd).lower(*args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9


@pytest.mark.parametrize("program", ["chunk_c2048_w128", "decode_r128_w256",
                                     "params"])
def test_qwen3next_serve_program_fits_and_leaves_the_pools_in_place(
        topo, as_on_tpu, program):
    """The cut configuration counts 1,979,175,040 parameters (3.96 GB in
    bf16); its 2,048-token chunk program and its 128-row decode step compile
    for the described v5e with the k/v pool (2 layers x 8,193 blocks) and
    the state pool (6 layers x 161 slots x 2 MB, f32) donated and updated in
    place, and weights + pools + temporaries fit the chip's 16 GB. A decode
    step: 6 state updates, 2 paged-attention calls, 24 grouped products."""
    cfg, family, params, pool, on_chip = _qwen3next_on(topo)
    weights, pages = _bytes(params), _bytes(pool)
    # bf16 but A_log and dt_bias (32 + 32 f32 a DeltaNet layer)
    assert sum(a.size for a in jax.tree.leaves(params)) == 1979175040
    assert weights == 2 * 1979175040 + 6 * 64 * 2, weights
    assert pool.k.shape == (2, _QN_BLOCKS, 128, 512)
    assert pool.s.shape == (6, 161, 32, 128, 128) and pool.s.dtype == F32
    assert pool.conv.shape == (6, 161, 3 * 8192)
    if program == "params":
        return
    if program.startswith("chunk"):
        W = 128
        compiled = family.prefill_fn(cfg, _QN_BS, _QN_CHUNK, None, False)\
            .lower(params, pool, on_chip((1, _QN_CHUNK), I32),
                   on_chip((), I32), on_chip((1 + W,), I32)).compile()
    else:
        W = 256
        assert family.decode_reads_pool_in_place(
            cfg, type("C", (), dict(block_size=_QN_BS, kv_heads=2,
                                    quant=False)))
        compiled = family.decode_fn(cfg, _QN_BS, None, None).lower(
            params, pool, on_chip((_QN_BATCH,), I32),
            on_chip((_QN_BATCH,), I32),
            on_chip((_QN_BATCH, 1 + W), I32)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pages - 64            # donated, in place
    # (narrow leaves — in_ba, shared_gate — are padded to the lanes)
    assert mem.argument_size_in_bytes <= weights + pages + (8 << 20)
    assert weights + pages + mem.temp_size_in_bytes < 15.5e9, \
        mem.temp_size_in_bytes
    hlo = compiled.as_text()
    for name in ("k", "v", "s", "conv"):
        a = getattr(pool, name)
        shape = "%s[%s]" % ("f32" if a.dtype == F32 else "bf16",
                            ",".join(map(str, a.shape)))
        made = [op for op, aliased in _ops_with_result(hlo, shape)
                if op not in ("parameter", "tuple", "get-tuple-element",
                              "bitcast") and not aliased]
        assert not made, (name, made)
    n = _n_pallas(compiled)
    if program.startswith("chunk"):
        assert n == 2 + 3 * 7, n
        for name in ("flash_fwd", "moe_gmm_fwd"):
            assert name in hlo, name
    else:
        assert n == 6 + 2 + 3 * 8, n
        for name in ("gdn_decode", "paged_attn_decode"):
            assert name in hlo, name


# --- the SDAR serving cell's two programs at the cut configuration ----------
_SD_BS, _SD_BATCH, _SD_CHUNK, _SD_BLOCKS = 128, 128, 2048, 2305


def _sdar_on(topo):
    """The cut SDAR (``benchmark/configs/sdar-30b-a3b-chat-l6.json``: 6 of 48
    layers, every layer whole, bf16), its parameters and its k/v pool as
    shapes on the described chip."""
    from byteps_tpu.models.sdar import SDARConfig, sdar_init
    from byteps_tpu.serve.families import serve_family

    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    cfg = SDARConfig(max_seq=8192, n_layers=6)
    shapes = jax.eval_shape(lambda: sdar_init(jax.random.PRNGKey(0), cfg))
    params = jax.tree.map(lambda a: on_chip(a.shape, a.dtype), shapes)
    family = serve_family(cfg)
    pool = jax.eval_shape(lambda: family.layout(
        shapes, cfg, block_size=_SD_BS, pool_blocks=_SD_BLOCKS,
        max_batch=_SD_BATCH, prefill_chunk=_SD_CHUNK, quant=False).state)
    pool = jax.tree.map(lambda a: on_chip(a.shape, a.dtype), pool)
    return cfg, family, params, pool, on_chip


@pytest.mark.parametrize("program", ["chunk_c2048_w64", "decode_r128_b4_w64",
                                     "decode_r128_b4_w4"])
def test_sdar_serve_program_fits_and_leaves_the_pool_in_place(
        topo, as_on_tpu, program):
    """The 2,048-token block-causal chunk program and the 128-row pass over
    blocks of 4 positions compile for the described v5e at the published
    widths: 4,361,055,744 parameters (8.72 GB in bf16) and a pool of 2,305
    pages over 6 layers (3.6 GB), donated and updated in place — a pass
    scatters 4 rows a sequence and layer over what the pass before left;
    weights + pool + temporaries fit the chip's 16 GB. A chunk: a flash call
    and 3 grouped products a layer (the last layer's second half is not in a
    program without readout); a pass: 6 paged-attention calls, each over
    ``4 x 32`` query rows a sequence in 4 grid steps, and 18 grouped
    products."""
    cfg, family, params, pool, on_chip = _sdar_on(topo)
    if program.startswith("chunk"):
        compiled = family.prefill_fn(cfg, _SD_BS, _SD_CHUNK, None, False)\
            .lower(params, pool, on_chip((1, _SD_CHUNK), I32),
                   on_chip((), I32), on_chip((64,), I32)).compile()
    else:
        assert family.decode_reads_pool_in_place(
            cfg, type("C", (), dict(block_size=_SD_BS, kv_heads=4,
                                    quant=False)))
        W = int(program.rsplit("w", 1)[1])
        compiled = family.decode_fn(cfg, _SD_BS, None, None).lower(
            params, pool, on_chip((_SD_BATCH, cfg.block_length), I32),
            on_chip((_SD_BATCH,), I32),
            on_chip((_SD_BATCH, W), I32)).compile()
    weights, pages = _bytes(params), _bytes(pool)
    assert weights == 2 * 4361055744, weights
    assert pool.k.shape == (6, _SD_BLOCKS, 128, 512)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pages - 64            # donated, in place
    assert mem.argument_size_in_bytes <= weights + pages + (1 << 20)
    assert weights + pages + mem.temp_size_in_bytes < 15.5e9, \
        mem.temp_size_in_bytes
    hlo = compiled.as_text()
    for name in ("k", "v"):
        shape = "bf16[%s]" % ",".join(map(str, getattr(pool, name).shape))
        made = [op for op, aliased in _ops_with_result(hlo, shape)
                if op not in ("parameter", "tuple", "get-tuple-element",
                              "bitcast") and not aliased]
        assert not made, (name, made)
    n = _n_pallas(compiled)
    if program.startswith("chunk"):
        assert n == 6 + 3 * 5, n
        for name in ("flash_fwd", "moe_gmm_fwd"):
            assert name in hlo, name
    else:
        assert n == 6 + 3 * 6, n
        assert "paged_attn_decode" in hlo


# --- the Falcon-H1 serving cell's two programs at the cut configuration -----
_FH_BS, _FH_BATCH, _FH_CHUNK, _FH_BLOCKS = 128, 128, 2048, 2049


def _falconh1_on(topo):
    """The cut Falcon-H1 (``benchmark/configs/falcon-h1-34b-instruct-l4
    .json``: 4 of 72 identical layers, every layer whole, the whole
    vocabulary, bf16), its parameters, its k/v pool and its state pool as
    shapes on the described chip."""
    from byteps_tpu.models.falcon_h1 import FalconH1Config, falcon_h1_init
    from byteps_tpu.serve.families import serve_family

    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    cfg = FalconH1Config(max_seq=8192, n_layers=4)
    shapes = jax.eval_shape(
        lambda: falcon_h1_init(jax.random.PRNGKey(0), cfg))
    params = jax.tree.map(lambda a: on_chip(a.shape, a.dtype), shapes)
    family = serve_family(cfg)
    pool = jax.eval_shape(lambda: family.layout(
        shapes, cfg, block_size=_FH_BS, pool_blocks=_FH_BLOCKS,
        max_batch=_FH_BATCH, prefill_chunk=_FH_CHUNK, quant=False).state)
    pool = jax.tree.map(lambda a: on_chip(a.shape, a.dtype), pool)
    return cfg, family, params, pool, on_chip


def _ssd_decode_case():
    from byteps_tpu.ops.ssd import ssd_decode

    def f(x, dt, A, B, C, D, pool, slots):
        return ssd_decode(x, dt, A, B, C, D, pool, 3, slots)
    R, H, G, N, P_ = _FH_BATCH, 32, 2, 256, 128
    return f, [_sds((R, H, P_), F32), _sds((R, H), F32), _sds((H,), F32),
               _sds((R, G, N), F32), _sds((R, G, N), F32), _sds((H,), F32),
               _sds((4, 161, H, N, P_), F32), _sds((R,), I32)]


def _fh_paged_decode(q, k, v, tables, lengths):
    return paged_attention_decode(q, k, v, tables, lengths, 1)


_FH_KERNELS = [
    # the decode step's state update in place in the slot pool: 32 heads of
    # 256 x 128 f32 in 2 groups, 8 heads a grid step
    ("ssd_decode_r128_h32_n256_p128", *_ssd_decode_case(), 1),
    # 20 query heads (not a multiple of 8 sublanes) on 4 k/v heads of 128
    ("paged_attn_decode_falconh1_h20_kv4_w64", _fh_paged_decode,
     [_sds((_FH_BATCH, 20, 128), BF16)]
     + [_sds((4, _FH_BLOCKS, _FH_BS, 512), BF16)] * 2
     + [_sds((_FH_BATCH, 64), I32), _sds((_FH_BATCH,), I32)], 1),
]


@pytest.mark.parametrize("case", _FH_KERNELS, ids=_case_id)
def test_falconh1_kernel_compiles_for_v5e(topo, as_on_tpu, case):
    test_kernel_compiles_for_v5e(topo, as_on_tpu, case)


@pytest.mark.parametrize("program", ["chunk_c2048_w32", "decode_r128_w64",
                                     "params"])
def test_falconh1_serve_program_fits_and_leaves_the_pools_in_place(
        topo, as_on_tpu, program):
    """The cut configuration counts 4,394,354,048 parameters (8.79 GB in
    bf16); its 2,048-token final chunk (ONE position read out) and its
    128-row decode step compile for the described v5e with the k/v pool (4
    layers x 2,049 blocks) and the state pool (4 layers x 161 slots x 4 MB,
    f32) donated and updated in place, and weights + pools + temporaries fit
    the chip's 16 GB. A decode step: 4 state updates, 4 paged-attention
    calls; a chunk: 4 flash forwards."""
    cfg, family, params, pool, on_chip = _falconh1_on(topo)
    weights, pages = _bytes(params), _bytes(pool)
    # bf16 but A_log, dt_bias and D (3 x 32 f32 a layer)
    assert sum(a.size for a in jax.tree.leaves(params)) == 4394354048
    assert weights == 2 * 4394354048 + 4 * 96 * 2, weights
    assert pool.k.shape == (4, _FH_BLOCKS, 128, 512)
    assert pool.s.shape == (4, 161, 32, 256, 128) and pool.s.dtype == F32
    assert pool.conv.shape == (4, 161, 3 * 5120)
    if program == "params":
        return
    if program.startswith("chunk"):
        W = 32
        compiled = family.prefill_fn(cfg, _FH_BS, _FH_CHUNK, None, True)\
            .lower(params, pool, on_chip((1, _FH_CHUNK), I32),
                   on_chip((), I32), on_chip((1 + W,), I32)).compile()
    else:
        W = 64
        assert family.decode_reads_pool_in_place(
            cfg, type("C", (), dict(block_size=_FH_BS, kv_heads=4,
                                    quant=False)))
        compiled = family.decode_fn(cfg, _FH_BS, None, None).lower(
            params, pool, on_chip((_FH_BATCH,), I32),
            on_chip((_FH_BATCH,), I32),
            on_chip((_FH_BATCH, 1 + W), I32)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pages - 64            # donated, in place
    assert mem.argument_size_in_bytes <= weights + pages + (8 << 20)
    assert weights + pages + mem.temp_size_in_bytes < 15.5e9, \
        mem.temp_size_in_bytes
    # the logits are the output beside the pools: one position of a chunk,
    # one a row of a decode step — never the chunk's 2,048
    rows = 1 if program.startswith("chunk") else _FH_BATCH
    assert mem.output_size_in_bytes - mem.alias_size_in_bytes \
        <= rows * cfg.vocab_size * 4 + (1 << 20)
    hlo = compiled.as_text()
    # (the chunk program's 19.8 MB tail pool is small enough for the compiler
    # to stage through its fast memory, with copies of its own: PERF.md
    # section 7; the three pools that size the chip stay where they lie)
    for name in ("k", "v", "s") + (() if program.startswith("chunk")
                                   else ("conv",)):
        a = getattr(pool, name)
        shape = "%s[%s]" % ("f32" if a.dtype == F32 else "bf16",
                            ",".join(map(str, a.shape)))
        made = [op for op, aliased in _ops_with_result(hlo, shape)
                if op not in ("parameter", "tuple", "get-tuple-element",
                              "bitcast") and not aliased]
        assert not made, (name, made)
    n = _n_pallas(compiled)
    if program.startswith("chunk"):
        assert n == 4, n
        assert "flash_fwd" in hlo
    else:
        assert n == 4 + 4, n
        for name in ("ssd_decode", "paged_attn_decode"):
            assert name in hlo, name


# --- the Phi-4-mini-flash serving cell's programs at the published sizes -----
_P4_BS, _P4_BATCH, _P4_CHUNK, _P4_BLOCKS = 64, 96, 2048, 6401


def _phi4flash_on(topo):
    """Phi-4-mini-flash-reasoning whole (``benchmark/configs/
    phi-4-mini-flash-reasoning.json``: 32 layers, the whole vocabulary,
    bf16), its parameters and its three pools as shapes on the described
    chip."""
    from byteps_tpu.models.phi4_flash import Phi4FlashConfig, phi4_flash_init
    from byteps_tpu.serve.families import serve_family

    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    cfg = Phi4FlashConfig(max_seq=8192)
    shapes = jax.eval_shape(
        lambda: phi4_flash_init(jax.random.PRNGKey(0), cfg))
    params = jax.tree.map(lambda a: on_chip(a.shape, a.dtype), shapes)
    family = serve_family(cfg)
    pool = jax.eval_shape(lambda: family.layout(
        shapes, cfg, block_size=_P4_BS, pool_blocks=_P4_BLOCKS,
        max_batch=_P4_BATCH, prefill_chunk=_P4_CHUNK, quant=False).state)
    pool = jax.tree.map(lambda a: on_chip(a.shape, a.dtype), pool)
    return cfg, family, params, pool, on_chip


def _sscan_decode_case():
    from byteps_tpu.ops.selective_scan import sscan_decode

    def f(u, delta, A, B, C, D, pool, slots):
        return sscan_decode(u, delta, A, B, C, D, pool, 8, slots)
    R, N, Dn = _P4_BATCH, 16, 5120
    return f, [_sds((R, Dn), F32), _sds((R, Dn), F32), _sds((N, Dn), F32),
               _sds((R, N), F32), _sds((R, N), F32), _sds((Dn,), F32),
               _sds((9, 121, N, Dn), F32), _sds((R,), I32)]


def _p4_paged_decode(q, k, v, tables, lengths):
    return paged_attention_decode(q, k, v, tables, lengths, 0)


def _p4_window_decode(q, k, v, tables, lengths):
    return paged_attention_decode(q, k, v, tables, lengths, 7,
                                  first=jnp.maximum(lengths - 512, 0))


_P4_KERNELS = [
    # the decode step's state update in place in the slot pool: 16 x 5120
    # f32 a row and layer, one row a grid step
    ("sscan_decode_r96_n16_dn5120", *_sscan_decode_case(), 1),
    # 40 padded query heads on 10 k/v PAIRS of 128: the full layer's pages,
    # read by it and by each cross layer
    ("paged_attn_decode_phi4flash_h40_kv10_w128", _p4_paged_decode,
     [_sds((_P4_BATCH, 40, 128), BF16)]
     + [_sds((1, _P4_BLOCKS, _P4_BS, 1280), BF16)] * 2
     + [_sds((_P4_BATCH, 128), I32), _sds((_P4_BATCH,), I32)], 1),
    # the same heads over a window layer's last 512 keys
    ("paged_attn_decode_phi4flash_window512_w128", _p4_window_decode,
     [_sds((_P4_BATCH, 40, 128), BF16)]
     + [_sds((8, 1235, _P4_BS, 1280), BF16)] * 2
     + [_sds((_P4_BATCH, 128), I32), _sds((_P4_BATCH,), I32)], 1),
]


@pytest.mark.parametrize("case", _P4_KERNELS, ids=_case_id)
def test_phi4flash_kernel_compiles_for_v5e(topo, as_on_tpu, case):
    test_kernel_compiles_for_v5e(topo, as_on_tpu, case)


@pytest.mark.parametrize("program", [
    "chunk_c2048_w64_stops", "chunk_c2048_w64_reads_out", "decode_r96_w64",
    "params"])
def test_phi4flash_serve_program_fits_and_leaves_the_pools_in_place(
        topo, as_on_tpu, program):
    """The whole model counts 3,852,562,944 parameters (7.71 GB in bf16); its
    2,048-token chunks and its 96-row decode step compile for the described
    v5e with the three pools (one layer of 6,401 pages, 8 window layers, 9
    layers of 121 f32 slots) donated and updated in place, and weights +
    pools + temporaries fit the chip's 16 GB. A decode step: 9 state updates
    and 16 paged-attention calls (the full layer, 8 window layers, 7 readers
    of the full layer's pages). A chunk that reads nothing out ENDS with
    layer 17's k and v rows: 8 flash forwards, the window layers'; one that
    reads out runs layer 17's own and adds 7 one-query paged reads."""
    cfg, family, params, pool, on_chip = _phi4flash_on(topo)
    weights, pages = _bytes(params), _bytes(pool)
    n_const = 16                                   # lambda_init, f32
    assert sum(a.size for a in jax.tree.leaves(params)) - n_const \
        == 3852562944
    assert pool.k.shape == (1, _P4_BLOCKS, 64, 1280)
    assert pool.wk.shape[0] == 8 and pool.wk.shape[2:] == (64, 1280)
    assert pool.s.shape == (9, 121, 16, 5120) and pool.s.dtype == F32
    assert pool.conv.shape == (9, 121, 3 * 5120)
    assert 12e9 < weights + pages < 14.5e9, (weights, pages)
    if program == "params":
        return
    W = 64
    if program.startswith("chunk"):
        compiled = family.prefill_fn(
            cfg, _P4_BS, _P4_CHUNK, None, program.endswith("reads_out"))\
            .lower(params, pool, on_chip((1, _P4_CHUNK), I32),
                   on_chip((), I32), on_chip((2, 1 + W), I32)).compile()
    else:
        assert family.decode_reads_pool_in_place(
            cfg, type("C", (), dict(block_size=_P4_BS, kv_heads=10,
                                    quant=False)))
        compiled = family.decode_fn(cfg, _P4_BS, None, None).lower(
            params, pool, on_chip((_P4_BATCH,), I32),
            on_chip((_P4_BATCH,), I32),
            on_chip((_P4_BATCH, 2, 1 + W), I32)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pages - 64            # donated, in place
    assert weights + pages + mem.temp_size_in_bytes < 15.5e9, \
        mem.temp_size_in_bytes
    rows = {"chunk_c2048_w64_stops": 0, "chunk_c2048_w64_reads_out": 1}.get(
        program, _P4_BATCH)
    assert mem.output_size_in_bytes - mem.alias_size_in_bytes \
        <= rows * cfg.vocab_size * 4 + (1 << 20)
    hlo = compiled.as_text()
    for name in ("k", "v", "wk", "wv", "s"):
        a = getattr(pool, name)
        shape = "%s[%s]" % ("f32" if a.dtype == F32 else "bf16",
                            ",".join(map(str, a.shape)))
        made = [op for op, aliased in _ops_with_result(hlo, shape)
                if op not in ("parameter", "tuple", "get-tuple-element",
                              "bitcast") and not aliased]
        assert not made, (name, made)
    # the tied readout contracts the embedding as it lies: no transposed or
    # upcast copy of the 200,064 x 2,560 table
    assert not [op for op, _ in _ops_with_result(hlo, "f32[200064,2560]")]
    assert not [op for op, _ in _ops_with_result(hlo, "bf16[2560,200064]")]
    n = _n_pallas(compiled)
    if program == "chunk_c2048_w64_stops":
        # the 8 window layers' flash forwards: layer 17 writes its k and v
        # and nothing reads what its attention or its MLP would give
        assert n == 8, n
        assert "paged_attn_decode" not in hlo
    elif program.startswith("chunk"):
        assert n == 9 + 7, n
    else:
        assert n == 9 + 16, n
        for name in ("sscan_decode", "paged_attn_decode"):
            assert name in hlo, name


# ---- the training readout's backward: which array the loop carries ----------
def _readout_loss_and_grads(topo, B, S, d, V):
    from byteps_tpu.ops.chunked_ce import chunked_ce_nll

    one = SingleDeviceSharding(topo.devices[0])
    fn = jax.value_and_grad(
        lambda h, head, t: chunked_ce_nll(h, head, t).mean(), argnums=(0, 1))
    args = (jax.ShapeDtypeStruct((B, S, d), BF16, sharding=one),
            jax.ShapeDtypeStruct((d, V), F32, sharding=one),
            jax.ShapeDtypeStruct((B, S), I32, sharding=one))
    return jax.jit(fn).lower(*args).compile().as_text()


def _while_bodies(hlo: str):
    """``(carried types, body text)`` of each ``while`` of a compiled
    program."""
    out = []
    for m in re.finditer(r"= (\(.*?\)) while\(.*?body=(%[\w.\-]+)", hlo):
        start = hlo.index(f"\n{m.group(2)} (")
        out.append((m.group(1), hlo[start:hlo.index("\n}\n", start)]))
    return out


def test_readout_backward_carries_the_shorter_side(topo):
    """GPT-2-medium's training cell (8 × 1,024 rows, V = 50,304 > N): the
    backward scans vocab blocks, so the whole-array carry that a block
    reads and adds to is dh, f32 (8192, 1024) — and the (1024, 50304) f32
    dhead, 206 MB, is only written a slice at a time (a plain
    dynamic-update-slice of the carried buffer, no add over it)."""
    hlo = _readout_loss_and_grads(topo, 8, 1024, 1024, 50304)
    assert "readout_ce.bwd_vocab" in hlo and "readout_ce.bwd_rows" not in hlo
    loops = _while_bodies(hlo)
    assert any("f32[8192,1024]" in carried for carried, _ in loops)
    for carried, body in loops:
        makers = {op for op, _ in _ops_with_result(body, "f32[1024,50304]")}
        assert makers <= {"get-tuple-element", "dynamic-update-slice"}, (
            makers, carried)


def test_readout_backward_keeps_rows_where_they_are_shorter(topo):
    """JoyAI's training shape (4 × 4,096 rows ≥ V = 16,256 as run): 16 row
    blocks of a 133.2 MB carry against 16 vocab blocks of a 134.2 MB one —
    the row loop stays."""
    hlo = _readout_loss_and_grads(topo, 4, 4096, 2048, 16256)
    assert "readout_ce.bwd_rows" in hlo and "readout_ce.bwd_vocab" not in hlo


# ---- the raw gradient aggregation across four chips --------------------------
def _chained_aggregation_hlo(topo, chained: bool):
    """A two-layer tied backward at width 2,048 and its dp=4 aggregation,
    compiled for the 2x2 host from shapes; returns the entry computation's
    instruction names in scheduled order."""
    from byteps_tpu.jax.optimizer import (
        backward_order, push_pull_inside, value_and_grad_in_order)

    d, rows = 2048, 1024
    mesh = Mesh(topo.devices, ("dp",))

    def loss(p, x):
        h = x @ p["emb"]
        for blk in p["blocks"]:
            h = jnp.tanh(h @ blk["w"] + blk["b"])
        return jnp.mean((h @ p["emb"].T) ** 2)       # the tied readout

    def per_device(p, x):
        p = jax.tree.map(lambda l: jax.lax.pcast(l, ("dp",), to="varying"), p)
        out, grads, order = value_and_grad_in_order(
            jax.value_and_grad(loss), p, x)
        with backward_order(order if chained else None):
            agg = push_pull_inside(grads, axis="dp", n=4)
        return jax.lax.pmean(out, "dp"), agg

    rep, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("dp"))
    params = {"emb": _sds_on((d, d), F32, rep),
              "blocks": [{"w": _sds_on((d, d), F32, rep),
                          "b": _sds_on((d,), F32, rep)} for _ in range(2)]}
    fn = jax.jit(jax.shard_map(per_device, mesh=mesh, in_specs=(P(), P("dp")),
                               out_specs=(P(), P())))
    hlo = fn.lower(params, _sds_on((4 * rows, d), F32, split)) \
        .compile().as_text()
    entry = hlo[hlo.index("\nENTRY "):]
    return re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\(",
                      entry, re.M)


def _sds_on(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _gradient_all_reduces(ops):
    return [typ for _, typ, op in ops
            if op == "all-reduce" and "2048,2048" in typ]


def test_chained_buckets_stay_apart_and_the_tied_leaf_goes_last(
        topo, monkeypatch):
    """dp=4 on the described 2x2 host: the three 16.8 MB buckets (a block's
    b + w twice, the tied emb) stay three all-reduces — the combiner does
    not merge a chain — in the order the traced backward yields them, emb
    last; by reversed tree order emb would go first and every bucket wait
    behind it; without the barrier the combiner makes ONE tuple all-reduce
    of every leaf, behind the whole backward. No compile option is passed:
    the TPU compiler's async all-reduce options hid nothing net on the chip
    (PERF.md §6, PR 49)."""
    def has_bias(typ):          # a block's bucket; emb has none beside it
        return "f32[2048]{" in typ

    in_order = _gradient_all_reduces(_chained_aggregation_hlo(topo, True))
    assert [has_bias(t) for t in in_order] == [True, True, False], in_order
    reversed_tree = _gradient_all_reduces(_chained_aggregation_hlo(topo, False))
    assert [has_bias(t) for t in reversed_tree] == [False, True, True]
    monkeypatch.setattr(jax.lax, "optimization_barrier", lambda x: x)
    merged = _gradient_all_reduces(_chained_aggregation_hlo(topo, True))
    assert len(merged) == 1 and merged[0].count("f32[2048,2048]") == 3, merged
