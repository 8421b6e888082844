"""Regions on the device (docs/observability.md): ``tracing.scope_table``
on compiled text, ``traced_program`` and ``program_scopes``."""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.common import tracing
from byteps_tpu.common.tracing import (
    program_scopes,
    scope_of,
    scope_table,
    traced_program,
)


@pytest.mark.parametrize("op_name, scope", [
    ("jit(step)/transpose(jvp(block/attn))/paged/attention/dot_general",
     "block/attn/paged/attention"),
    ("jit(step)/jvp(block/attn)/paged/attention/dot_general",
     "block/attn/paged/attention"),
    ("jit(step)/shard_map/transpose(jvp())/while/body/closed_call/block/mlp/"
     "block/mlp/checkpoint/rematted_computation/tanh",
     "block/mlp/block/mlp"),
    ("jit(f)/block/mlp/jit(relu)/max", "block/mlp"),
    ("jit(step)/shard_map/jvp(readout_ce)/reduce_max", "readout_ce"),
    ("jit(step)/transpose(jvp(readout_ce))/readout_ce.bwd_vocab/while/body/"
     "dot_general", "readout_ce/readout_ce.bwd_vocab"),
    ("jit(step)/cond/branch_1_fun/block/moe/moe/route/top_k",
     "block/moe/moe/route"),
    ("jit(step)/block/attn/paged/attention/bqhd,bkhd->bhqk/dot_general",
     "block/attn/paged/attention"),
    ("jit(pick)/vmap()/vmap(jit(_fold))/f.<locals>.g.<locals>.<lambda>/add",
     ""),
    ("jit(step)/vmap(block/gdn)/gdn/decode/pallas_call",
     "block/gdn/gdn/decode"),
    ("jit(step)/add", ""),
    ("add", ""),
    ("", ""),
])
def test_scope_of(op_name, scope):
    assert scope_of(op_name) == scope


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _layer(w, x):
    with jax.named_scope("block/attn"):
        x = jnp.tanh(x @ w)
        with jax.named_scope("paged/attention"):
            x = jnp.sin(x) @ w
    with jax.named_scope("block/mlp"):
        return jax.nn.relu(x @ w)


W = jnp.ones((32, 32), jnp.float32)
X = jnp.ones((8, 32), jnp.float32)


def test_scope_table_of_nested_scopes_and_an_unscoped_op():
    def f(w, x):
        return jnp.cumsum(_layer(w, x), axis=0)      # in no region

    table = scope_table(_compiled_text(f, W, X))
    scopes = set(table.values())
    assert {"block/attn", "block/attn/paged/attention", "block/mlp",
            ""} <= scopes
    # nothing but the regions the source names, and whole components only
    assert scopes <= {"block/attn", "block/attn/paged/attention",
                      "block/mlp", "block", ""}
    # every instruction that runs is in the table: the text's dots are
    dots = [n for n in table if n.startswith("dot")]
    assert len(dots) == 3 and all(table[n] for n in dots)


def test_forward_and_backward_of_a_region_land_on_one_scope():
    def loss(w, x):
        return jnp.sum(_layer(w, x) ** 2)

    text = _compiled_text(jax.grad(loss), W, X)
    assert "transpose(jvp(" in text
    table = scope_table(text)
    assert not [s for s in table.values() if "jvp" in s or "transpose" in s]
    dots = [table[n] for n in table if n.startswith("dot")]
    # three forward products and their backward ones, by region
    assert len(dots) >= 6
    assert set(dots) == {"block/attn", "block/attn/paged/attention",
                         "block/mlp"}


def test_the_instructions_of_loop_bodies_are_in_the_table():
    def f(w, x):
        def body(c, _):
            with jax.named_scope("block/ssm"):
                return jnp.tanh(c @ w), None
        x, _ = jax.lax.scan(body, x, None, length=3)

        def more(c):
            with jax.named_scope("readout"):
                return c[0] @ w, c[1] + 1
        return jax.lax.while_loop(lambda c: c[1] < 2, more, (x, 0))[0]

    text = _compiled_text(f, W, X)
    table = scope_table(text)
    assert "while" in text
    by_scope = {}
    for name, s in table.items():
        by_scope.setdefault(s, []).append(name)
    assert any(n.startswith("dot") for n in by_scope["block/ssm"])
    assert any(n.startswith("dot") for n in by_scope["readout"])
    # the containers themselves are instructions too (their events nest)
    assert any(n.startswith("while") for n in table)


HLO = """\
HloModule jit_step, is_scheduled=true, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %bitcast.1 = f32[8]{0} bitcast(%param_0), metadata={op_name="jit(step)/reshape"}
  %exp.1 = f32[8]{0} exponential(%bitcast.1), metadata={op_name="jit(step)/block/moe/moe/route/exp"}
  ROOT %add.1 = f32[8]{0} add(%exp.1, %exp.1), metadata={op_name="jit(step)/block/moe/moe/plan/add"}
}

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  %neg.1 = f32[8]{0} negate(%param_0.1), metadata={op_name="jit(step)/block/attn/neg"}
  ROOT %mul.1 = f32[8]{0} multiply(%neg.1, %neg.1), metadata={op_name="jit(step)/readout/mul"}
}

%fused_computation.2 (param_0.2: f32[8]) -> f32[8] {
  %param_0.2 = f32[8]{0} parameter(0)
  ROOT %copy.9 = f32[8]{0} copy(%param_0.2)
}

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b), metadata={op_name="jit(step)/embed/reduce_sum"}
}

%body.3 (p: (f32[8], s32[])) -> (f32[8], s32[]) {
  %p = (f32[8]{0}, s32[]) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%p), index=0
  %fusion.7 = f32[8]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/while/body/readout/mul"}
  %gte.2 = s32[] get-tuple-element(%p), index=1
  ROOT %tuple.4 = (f32[8]{0}, s32[]) tuple(%fusion.7, %gte.2)
}

%cond.3 (p.1: (f32[8], s32[])) -> pred[] {
  %p.1 = (f32[8]{0}, s32[]) parameter(0)
  %gte.3 = s32[] get-tuple-element(%p.1), index=1
  %c.1 = s32[] constant(3)
  ROOT %lt.1 = pred[] compare(%gte.3, %c.1), direction=LT, metadata={op_name="jit(step)/while/cond/lt"}
}

ENTRY %main.5 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0)
  %fusion.12 = f32[8]{0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/block/moe/moe/plan/add"}
  %c.2 = s32[] constant(0)
  %tuple.1 = (f32[8]{0}, s32[]) tuple(%fusion.12, %c.2)
  %while.4 = (f32[8]{0}, s32[]) while(%tuple.1), condition=%cond.3, body=%body.3, metadata={op_name="jit(step)/while"}
  %gte.5 = f32[8]{0} get-tuple-element(%while.4), index=0
  %fusion.13 = f32[8]{0} fusion(%gte.5), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(step)/embed/copy"}
  %copy-start.3 = (f32[8]{0:S(1)}, f32[8]{0}, u32[]) copy-start(%Arg_0.1)
  %copy-done.3 = f32[8]{0:S(1)} copy-done(%copy-start.3)
  %copy.5 = f32[8]{0} copy(%Arg_0.1)
  %custom-call.2 = f32[8]{0} custom-call(%fusion.13, %copy-done.3, %copy.5), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/block/attn/paged/attention/jit(paged_attn_decode)/pallas_call"}
  %neg.7 = f32[8]{0} negate(%copy.5), metadata={op_name="jit(step)/block/attn/neg"}
  ROOT %reduce.1 = f32[8]{0} reduce-window(%custom-call.2, %c.2), to_apply=%region_0.1
}
"""


def test_scope_table_on_written_text():
    """What a compile here cannot be made to produce on demand: a fusion
    across two sub-regions (their common prefix), across two regions
    (""), one whose fused instructions carry no scope (its own), a kernel's
    custom call, an instruction without metadata, loop bodies."""
    found = tracing._scopes_and_order(HLO)
    module, table, order, mixed = (found[k] for k in (
        "module", "scopes", "order", "mixed"))
    assert mixed == {"fusion.12": ["block/moe/moe/plan",
                                   "block/moe/moe/route"],
                     "fusion.7": ["block/attn", "readout"]}
    assert module == "jit_step"
    assert table["fusion.12"] == "block/moe/moe"       # route + plan
    assert table["fusion.7"] == ""                     # attn + readout
    assert table["fusion.13"] == "embed"               # its own
    assert table["custom-call.2"] == "block/attn/paged/attention"
    assert table["reduce.1"] == "" and table["while.4"] == ""
    # the compiler's own instructions go where their results go: a
    # prefetch pair to its one user, a copy two regions read to what those
    # share, a tuple that feeds the loop to nothing
    assert table["copy-start.3"] == table["copy-done.3"] == \
        "block/attn/paged/attention"
    assert table["copy.5"] == "block/attn"
    assert table["tuple.1"] == ""
    assert table["lt.1"] == ""                         # the condition's
    # fused and applied computations' instructions run as no event
    assert not {"exp.1", "add.1", "neg.1", "copy.9", "add.9"} & set(table)
    assert order == ["fusion.12", "while.4", "fusion.13", "copy-start.3",
                     "copy-done.3", "copy.5", "custom-call.2", "neg.7",
                     "reduce.1"]
    assert scope_table(HLO) == table


class _CompileCount:
    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


_COMPILES = _CompileCount()


def test_traced_program_keeps_one_signature_an_executable():
    @functools.partial(jax.jit, donate_argnums=(1,), static_argnames="k")
    def f(a, pool, scale, k=2):
        with jax.named_scope("block/attn"):
            y = jnp.tanh(a @ pool)
        return y.sum() * scale * k, pool + 1

    prog = traced_program("test.prog", f, statics=("k",))
    a = jnp.ones((16, 16))
    before = _COMPILES.n
    _, pool = prog(a, jnp.ones((16, 16)), 2.0, k=3)
    assert list(prog.signatures) == ["0"]
    compiled = _COMPILES.n - before
    assert compiled >= 1
    for _ in range(3):                      # repeats: nothing kept, no compile
        _, pool = prog(a, pool, 2.0, k=3)
    assert list(prog.signatures) == ["0"] and _COMPILES.n - before == compiled
    # the donated argument's shape was still readable; the static as given
    args, kwargs = prog.signatures["0"]
    assert args[1].shape == (16, 16) and args[2].weak_type
    assert kwargs == {"k": 3}
    # another static value, another shape: an executable and a signature each
    _, pool = prog(a, pool, 2.0, k=4)
    prog(jnp.ones((8, 16)), pool, np.float32(2.0), k=4)
    assert list(prog.signatures) == ["0", "1", "2"]
    assert prog._cache_size() == f._cache_size() == 3   # attributes pass
    assert prog.lower(a, pool, 2.0, k=3).compile() is not None

    # asking builds the tables, of the asked-for programs only, once
    asked = _COMPILES.n
    tables = program_scopes(only=["test.prog[1]"])
    assert list(tables) == ["test.prog[1]"]
    assert list(prog._tables) == ["1"]
    t = tables["test.prog[1]"]
    assert t["module"] == "jit_f" and t["signature"] == "1"
    assert "block/attn" in t["scopes"].values()
    assert t["order"] and set(t["order"]) <= set(t["scopes"])
    assert set(t["mixed"]) <= set(t["scopes"])
    assert program_scopes(only=["test.prog"])["test.prog[1]"] is t
    assert sorted(program_scopes(only=["test.prog"])) == [
        "test.prog[0]", "test.prog[1]", "test.prog[2]"]
    assert prog._cache_size() == 3          # asking adds no executable
    assert _COMPILES.n == asked                         # and no compile


def test_traced_program_labels_an_executable_by_its_key():
    f = jax.jit(lambda x, t: x + t.sum())
    prog = traced_program("test.keyed", f,
                          key=lambda x, t: f"W={t.shape[-1]}")
    for w in (2, 4, 2, 4, 8):
        prog(jnp.ones(3), np.zeros((3, w), np.int32))
    assert list(prog.signatures) == ["W=2", "W=4", "W=8"]
    assert sorted(program_scopes(only=["test.keyed[W=4]", "nothing"])) == [
        "test.keyed[W=4]"]


def test_traced_program_inside_another_trace_keeps_nothing():
    prog = traced_program("test.inner", jax.jit(lambda x: x * 2))
    assert float(jax.jit(lambda x: prog(x) + 1)(jnp.ones(()))) == 3.0
    assert prog.signatures == {}


def test_the_wrapper_costs_under_a_microsecond_a_call():
    """A program call pays one ``_cache_size()`` and one comparison: the
    wrapper around a stand-in whose call costs nothing, against the stand-in
    alone, best of seven. If this fails someone made the hot path build a
    signature, take a lock or read a clock."""
    real = jax.jit(lambda x: x)
    real(1.0)

    class Stub:
        _cache_size = real._cache_size

        def __call__(self, *args, **kwargs):
            return None

    stub = Stub()
    prog = traced_program("test.budget", stub)
    N = 100000
    x, y = object(), object()

    def per_call(fn):
        best = float("inf")
        for _ in range(7):
            t0 = time.perf_counter()
            for _ in range(N):
                fn(x, y)
            best = min(best, (time.perf_counter() - t0) / N)
        return best

    extra = per_call(prog) - per_call(stub)
    assert extra < 1e-6, f"wrapper adds {extra * 1e9:.0f} ns a call"
    assert prog.signatures == {}


def test_program_scopes_of_a_scheduler_run_names_both_programs():
    from byteps_tpu.models import GPTConfig, gpt_init
    from byteps_tpu.serve import Request, Scheduler

    cfg = GPTConfig.tiny()
    sched = Scheduler(gpt_init(jax.random.PRNGKey(0), cfg), cfg,
                      max_batch=3, prefill_chunk=8, block_size=4,
                      pool_blocks=64)
    rng = np.random.default_rng(0)
    before = _COMPILES.n
    sched.serve([Request(rid=i, max_new=m,
                         prompt=rng.integers(0, cfg.vocab_size, n)
                         .astype(np.int32))
                 for i, (n, m) in enumerate([(5, 6), (13, 4), (9, 8)])])
    served = _COMPILES.n - before
    # this scheduler's own programs (a worker's earlier tests left theirs
    # in the factories' caches, under the same names)
    spans = tracing.get_tracer().spans()
    chunks = {(e[5][1], e[5][2], bool(e[5][3])) for e in spans
              if e[0] == "serve.prefill_dispatch"}
    decode = sched._decode_fn
    prefill = {(c, r): sched._prefill_fn(c, r) for c, _, r in chunks}
    asked = program_scopes(only=[decode, sched._pick, sched._pick_last,
                                 *prefill.values()])
    assert {k.split("[")[0] for k in asked} == {
        "serve.decode", "serve.prefill", "serve.pick", "serve.pick_last"}
    for prog in (decode, *prefill.values()):
        assert isinstance(prog, tracing._TracedProgram) and prog._tables
    assert {p.name for p in (decode, sched._pick, sched._pick_last)} == {
        "serve.decode", "serve.pick", "serve.pick_last"}
    # a program's label is what its span's args say of it
    widths = {e[5][1] for e in spans if e[0] == "serve.decode_dispatch"}
    assert {f"W={w}" for w in widths} <= set(decode._tables)
    for c, w, r in chunks:
        assert f"C={c},W={w},readout={int(r)}" in prefill[c, r]._tables
    for prog in (decode, *prefill.values()):
        for label, t in prog._tables.items():
            found = set(t["scopes"].values())
            assert {"block/attn", "block/mlp"} <= found, (prog.name, label)
            assert any(s.startswith(("block/attn/paged/", "paged/"))
                       for s in found), (prog.name, label)
    for t in decode._tables.values():
        assert {"embed", "readout"} <= set(t["scopes"].values())
    # asking compiled nothing: the executables are the process's own
    assert _COMPILES.n - before == served


def test_program_scopes_of_a_train_step():
    import optax

    from byteps_tpu.models import GPTConfig
    from byteps_tpu.models.train import make_gpt_train_step
    from byteps_tpu.parallel import MeshAxes, make_mesh

    cfg = GPTConfig.tiny()
    mesh = make_mesh(MeshAxes(dp=1), devices=jax.devices()[:1])
    step, params, opt_state, _ = make_gpt_train_step(
        cfg, mesh, optax.adamw(1e-3))
    tok = jnp.zeros((2, 16), jnp.int32)
    for _ in range(2):
        loss, params, opt_state = step(params, opt_state, tok, tok)
    assert np.isfinite(float(loss))
    mine = [p for p in tracing._programs
            if p.name == "train.step" and p._jitted is step._jitted._jitted]
    assert len(mine) == 1 and mine[0].signatures
    tables = {k: t for k, t in program_scopes(only=["train.step"]).items()
              if t in mine[0]._tables.values()}
    assert tables
    for t in tables.values():
        found = set(t["scopes"].values())
        assert {"embed", "block/attn", "block/mlp", "readout_ce",
                "optimizer_update"} <= {s.split("/")[0] + (
                    "/" + s.split("/")[1] if s.startswith("block/") else "")
                    for s in found}


def test_a_callable_that_is_no_jit_wrapper_comes_back_as_it_is():
    def plain(x):
        return x + 1

    assert traced_program("test.plain", plain) is plain


def test_a_signature_that_cannot_be_kept_does_not_fail_the_call():
    @functools.partial(jax.jit, static_argnums=(1,))
    def f(x, mode):
        return x * 2 if mode == "double" else x

    prog = traced_program("test.static", f)
    assert float(prog(jnp.ones(()), "double")) == 2.0   # a str has no aval
    assert prog.signatures == {}
