"""Iteration-level request scheduler — Orca's continuous batching over
the block-paged KV cache.

One :class:`Scheduler` is one model replica: it owns a
:class:`~byteps_tpu.serve.paged_cache.PagedKVCache` pool and drives a
four-phase iteration (``step()``):

1. **Admission** — requests whose arrival time has passed join the
   running set as soon as a decode slot AND enough free KV blocks
   exist. Per-tenant FIFO in arrival order, deficit-weighted fair
   queuing ACROSS tenants (``serve_fair_queue``; single-tenant
   traffic reduces exactly to the historical global FIFO); preempted
   requests re-queue at the FRONT (they are the oldest work). With the prefix cache on
   (``BYTEPS_SERVE_PREFIX_CACHE``, default), admission first consults
   the pool's radix index: a hit maps the request's leading table
   entries to shared read-only pages (committed by earlier prefills),
   CoWs the divergence block when the match ends mid-block, and starts
   chunked prefill at the divergence — the shared chunks are skipped
   entirely, which is where a shared prefix saves time to first token
   (streams held bit-identical by tests/test_serve_prefix.py).
2. **Prefill** — one prompt chunk (``serve_prefill_chunk`` tokens) per
   iteration through the per-request paged prefill, so a long prompt
   interleaves with everyone else's decode steps instead of stalling
   them (the Orca observation). The final chunk's last-position logits
   yield the request's first generated token — that commit is TTFT.
3. **Speculative lane** — every spec-policy request runs one
   draft-propose/verify round per iteration instead of a plain decode
   step: ``spec_len`` proposed tokens verified in ONE forward,
   committed through ``speculative._verify_commit`` (the same
   exactness-critical arithmetic as ``make_speculative_generate_fn``
   — greedy output is identical to plain greedy decoding at any
   accept rate, the draft only moves speed). Spec requests never join
   the packed batch: a plain decode step would commit tokens the
   per-request draft cache never saw, silently desyncing it and
   collapsing acceptance. Fill-level rewind is the paged twin of the
   dense cache rewind: ``cache_len`` advances only by the committed
   count, later writes overwrite the rest.
4. **Packed decode** — every non-speculative decoding request joins
   ONE jitted device batch (static ``serve_max_batch`` rows, padded
   rows scatter into the reserved scratch block): one token per
   request per iteration at heterogeneous positions.

**The order of an iteration** — a step's tokens are read one step late.
The packed step is issued first, its input tokens taken on the device
from the picks of the step before it (``_take``), and only then does the
host read that earlier step's tokens and commit them; the first token of
a final chunk is read in its own iteration, after the decode step that
already feeds on it was issued. So the device always has the next program
queued while the host reads, commits, admits and packs. What the host
needs before a token's value it knows without it: positions and table
growth from ``cache_len`` (advanced at issue), whether a request goes on
from the COUNT of its picked tokens. Only ``eos_id`` needs the value: a
request that ends at eos has one row of the step already issued dropped.
At most one decode step is ever unread; an iteration with no row to
decode reads it before it returns, and everything that reads or rewrites
a request from outside the lanes (preemption, migration either way,
``drain_incomplete``, a kill) calls ``_drain_in_flight`` first. Tokens,
their order and the cache contents are what a read-at-once loop gives.
The two reads are also where the host learns that a program is done: the
time between two of them it waited in is the device's time for what was
issued between the programs read, a ``serve.device_step.*`` span on the
ring (``_wait``; docs/serving.md §Scheduler iteration).

**Preemption** — when a block allocation fails, the youngest admitted
request is evicted: its blocks free immediately, its committed tokens
are kept, and it re-queues with ``prompt + emitted`` as the recompute
prefill input (recompute-on-resume; the vLLM policy that beats
swapping when recompute is one chunked prefill). Continuation tokens
are unchanged — the resume prefill's last logits ARE the logits the
uninterrupted decode step would have produced at that position.

**Exactness contract** — greedy (``temperature == 0``) requests emit
token-for-token what a solo ``make_generate_fn`` run emits, regardless
of batch composition, admission order, chunking, preemption, or
speculation (pinned in tests/test_serve.py). Sampled requests draw
per-request fold_in keys — deterministic per (seed, position) but
intentionally NOT the solo sampler's batched key sequence.

Replica death is deterministic chaos: a ``worker:kill`` (or
serve-scoped ``replica<N>:kill``) rule in the request's
:class:`~byteps_tpu.common.faults.FaultPlan` kills the replica at an
exact step; the router's lease sweep then evicts it — the same
death-by-silence semantics the PR 5 membership layer pins.

**Multi-tenant LoRA multiplexing** (docs/serving.md §multi-tenant) —
with an :class:`~byteps_tpu.serve.adapter_pool.AdapterPool` attached,
one replica serves MANY fine-tuned variants of its base model:
adapter-tagged requests pin their adapter's pool slot at admission
(all-or-nothing with the KV blocks), single-request forwards (chunked
prefill, spec verify) run on the tenant's grafted tree, and the packed
decode step gathers each row's A/B slabs by slot inside one jitted
program (the S-LoRA/Punica shape; ``ops/segmented_lora.py``) — every
tenant's greedy tokens bit-identical to a solo run on its grafted
params. Per-tenant KV quotas make a flooding tenant preempt ITS OWN
youngest runs and queue behind its own wall instead of starving
siblings; ``serve.tenant<T>.*`` metrics carry the per-tenant view.

**Disaggregation** (docs/serving.md §disaggregation) — a Scheduler
can be a dedicated ``role="prefill"`` or ``role="decode"`` replica:
prefill replicas run chunked prefill only, stream committed KV blocks
to their decode target over the ``serve/kv_wire.py`` transport as each
chunk fills them, and park finished requests for the router to
migrate; decode replicas adopt migrated requests through the
refcount/radix path (``submit_migrated``), so prefix sharing survives
the wire. The same transport gives migrate-don't-evict preemption
(``extract_for_migration``): a pressured victim's blocks move to a
sibling instead of being freed and recomputed.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from byteps_tpu.common.config import get_config
from byteps_tpu.common.faults import FaultPlan, WorkerKilledError, plan_from_env
from byteps_tpu.common.flight_recorder import get_flight_recorder
from byteps_tpu.common.logging import get_logger
from byteps_tpu.common.metrics import get_registry
from byteps_tpu.common.tracing import get_tracer, traced_program
from byteps_tpu.models.generate import gpt_apply_cached, init_cache
from byteps_tpu.models.gpt import GPTConfig
from byteps_tpu.models.speculative import _verify_commit
from byteps_tpu.serve.families import admitted_at_once, serve_family
from byteps_tpu.serve.paged_cache import PagedKVCache, PoolExhausted

log = get_logger("serve.scheduler")

# why an unread decode step was read with no step queued behind it: the
# iteration had no row to decode; a preemption needed every run's tokens on
# the host; a run was leaving for, or arriving from, another replica; the
# fault plan killed the replica
_DRAIN_CAUSES = ("idle", "preempt", "migrate", "kill")

# a device step by what the device ran between two reads the host waited in
# (``Scheduler._wait``): a decode step alone, a final chunk alone, a
# non-final chunk and the decode step behind it; ``unseen`` is any of them
# one of whose ends the host did not wait for
_DEVICE_STEP = {k: "serve.device_step." + k
                for k in ("decode", "chunk", "chunk_decode", "unseen")}

# global replica instance sequence for per-replica gauge series (the
# PR 6 scheduler.s<N> pattern — replica_id is caller-chosen and two
# fresh replicas may both say 0)
_REPLICA_SEQ = itertools.count()


@functools.lru_cache(maxsize=16)
def _make_pick_fn(vocab_size: int):
    """Process-wide jitted token pick, one per vocab size (jit's own
    shape cache handles the batch dimension). The greedy/sampled select
    arm IS generate.make_pick — the serve layer only adds per-row keys
    (fold_in by absolute position, invariant to batch packing), so the
    bit-exact greedy contract can never drift from make_generate_fn's.
    lru-cached like the paged-step factories: fresh replicas (bench
    reps, failover respawns) must reuse the compiled programs. Returns
    ``(pick, pick_last)``: over a decode step's rows, and over the last
    position of a final chunk's ``(1, C, vocab)`` logits."""
    from byteps_tpu.models.generate import make_pick, make_truncate

    pick1 = make_pick(make_truncate(None, None, vocab_size))

    def pick(logits, seeds, pos, temps):
        keys = jax.vmap(
            lambda s, p: jax.random.fold_in(jax.random.PRNGKey(s), p))(
                seeds, pos)
        return jax.vmap(lambda l, k, t: pick1(l[None], k, t)[0])(
            logits, keys, temps)

    # the same pick on a chunk's last position, sliced on the device inside
    # the one call: only vocab floats would cross to host, never the whole
    # (1, C, vocab) chunk, and no eager slice is dispatched for it
    return traced_program("serve.pick", jax.jit(pick)), traced_program(
        "serve.pick_last", jax.jit(
            lambda logits, seeds, pos, temps: pick(logits[:, -1], seeds, pos,
                                                   temps)))


# columns of the one host array a packed decode step is issued with (a
# transfer costs the host ~0.25 ms on a v5e machine whatever its size,
# PERF.md §6, PR 36: seven of them were most of an iteration); the row's
# block table follows them, flattened
_TOK, _SRC, _POS, _SEED, _TEMP, _SLOT, _N_COLS = range(7)


@functools.partial(jax.jit, static_argnames="table_shape")
def _take(picked, first, host, table_shape):
    """The packed step's operands from ONE host array, and its input tokens
    taken on the device: row ``i``'s token is element ``src[i]`` of the
    unread decode step's picks, the unread first token of the request whose
    final chunk was just issued, and the tokens the host already holds,
    laid end to end. So a step can be issued before the tokens of the one
    before it are read. Returns ``(toks, pos, tables, seeds, pos + 1,
    temps, slots)``; temperatures cross as their bits."""
    toks = jnp.concatenate([picked, first, host[:, _TOK]])[host[:, _SRC]]
    pos = host[:, _POS]
    return (toks, pos,
            host[:, _N_COLS:].reshape((host.shape[0],) + table_shape),
            host[:, _SEED], pos + 1,
            jax.lax.bitcast_convert_type(host[:, _TEMP], jnp.float32),
            host[:, _SLOT])


_take = traced_program("serve.take", _take, statics=("table_shape",))


# a block-diffusion step's columns: what a pass fixes of the row's block and
# which pass of the block it is (a greedy schedule needs neither the token
# column nor a seed); the row's block state follows its table
_NFIX, _PASS = _TOK, _SEED


@functools.partial(jax.jit, static_argnames="table_shape")
def _take_block(state, host, table_shape):
    """:func:`_take` for a step of block diffusion: row ``i``'s block state
    ``(2B,)`` — its B tokens, the mask token where open, and the pass each was
    fixed at — is row ``src[i]`` of the unread step's states and of the ones
    the host holds (a block's first pass, a step read already), laid end to
    end. Returns ``(toks, state, pos, tables, n_fix, pass_no)``."""
    n = _N_COLS + int(np.prod(table_shape))
    state = jnp.concatenate([state, host[:, n:]])[host[:, _SRC]]
    return (state[:, :state.shape[1] // 2], state, host[:, _POS],
            host[:, _N_COLS:n].reshape((host.shape[0],) + table_shape),
            host[:, _NFIX], host[:, _PASS])


_take_block = traced_program("serve.take", _take_block,
                             statics=("table_shape",))


class _Block:
    """The block of B positions a run of a block-diffusion family is
    denoising, at ``[cache_len, cache_len + B)``: ``given`` leading positions
    the host knew (a prompt's last ``len % B`` tokens; generated tokens start
    behind them) and ``open`` ones it did not, ``per`` positions a denoising
    pass fixes and ``need`` such passes before the one that commits,
    ``issued`` passes issued so far, and
    ``state (2B,)`` as the host last held it (the tokens, the mask token
    where open, then the pass each was fixed at) — what a pass feeds on where
    the pass before it is not the unread step."""

    __slots__ = ("given", "open", "per", "need", "issued", "state")

    def __init__(self, given, B: int, steps: int, mask_id: int):
        self.given = len(given)
        self.open = B - self.given
        self.per = B // steps
        self.need = -(-self.open // self.per)
        self.issued = 0
        self.state = np.zeros(2 * B, np.int32)
        self.state[:B] = mask_id
        self.state[:self.given] = given

    def fixes(self) -> int:
        """Positions the next pass fixes (0: it commits the block)."""
        return max(0, min(self.per, self.open - self.issued * self.per))


class _InFlight:
    """The packed decode step whose tokens the host has not read: the
    device array ``_pick`` returned, the runs of its rows, the position
    each row wrote, the row of each request in it, and the device step
    that reading it ends (``Scheduler._wait``). Of a block-diffusion step
    also each row's :class:`_Block` and, by request, the ``given`` count of
    the rows whose pass commits their block."""

    __slots__ = ("picked", "runs", "pos", "rows", "step", "blocks", "commit")

    def __init__(self, picked, runs: List["_Run"], pos: np.ndarray, step,
                 blocks: Optional[List[_Block]] = None):
        self.picked = picked
        self.runs = runs
        self.pos = pos
        self.rows = {run.req.rid: i for i, run in enumerate(runs)}
        self.step = step
        self.blocks = blocks
        self.commit = {} if blocks is None else {
            run.req.rid: b.given for run, b in zip(runs, blocks)
            if b.issued > b.need}


@dataclasses.dataclass
class SpecPolicy:
    """Per-request speculative decoding policy.

    ``kind="lookup"`` — prompt-lookup drafting (model-free): propose
    the ``spec_len`` tokens that followed the most recent earlier
    occurrence of the current bigram in the committed context (the
    ``make_lookup_generate_fn`` trick, host-side).
    ``kind="draft"`` — a draft MODEL (any GPT-family config sharing
    the target's vocab): ``spec_len`` greedy draft steps against a
    per-request dense draft cache, the
    ``make_speculative_generate_fn`` proposal semantics in-loop.
    Greedy-only (verification compares greedy argmax)."""

    kind: str = "lookup"
    spec_len: int = 0              # 0 = BYTEPS_SERVE_SPEC_LEN
    draft_params: Any = None
    draft_cfg: Optional[GPTConfig] = None

    def __post_init__(self):
        if self.kind not in ("lookup", "draft"):
            raise ValueError(f"unknown spec kind {self.kind!r}")
        if self.kind == "draft" and (self.draft_params is None
                                     or self.draft_cfg is None):
            raise ValueError("draft policy needs draft_params + draft_cfg")


@dataclasses.dataclass
class Request:
    """One generation request. ``prompt`` is a 1-D int32 token array;
    the scheduler emits up to ``max_new`` tokens (stopping early at
    ``eos_id`` when set). ``temperature == 0`` is the bit-pinned greedy
    path; sampled requests use per-request ``seed``."""

    rid: Any
    prompt: np.ndarray
    max_new: int
    temperature: float = 0.0
    seed: int = 0
    eos_id: Optional[int] = None
    spec: Optional[SpecPolicy] = None
    arrival_s: float = 0.0
    # multi-tenant multiplexing (docs/serving.md §multi-tenant):
    # ``tenant`` keys fair queuing, KV quotas, and the per-tenant
    # metric series (None = untenanted legacy traffic, exempt from
    # quotas); ``adapter`` names a LoRA adapter registered in the
    # replica's AdapterPool — the request decodes through that
    # adapter's pool slot, bit-identical to a solo run on its grafted
    # params (None = the bare base model).
    tenant: Any = None
    adapter: Any = None
    # a block-diffusion family's: denoising passes a block (it divides the
    # block; 0: the configuration's), B / denoise_steps positions fixed a
    # pass, one committing pass on top. Quality is traded against passes
    # here; other families ignore it
    denoise_steps: int = 0


class _Run:
    """Scheduler-internal per-request state."""

    __slots__ = ("req", "full_input", "emitted", "pending", "cache_len",
                 "prefill_done", "state", "t_submit", "t_origin", "t_admit",
                 "t_first", "t_last", "t_phase", "preemptions", "spec_rounds",
                 "draft_cache", "tok_s", "idx_seq", "prefix_hit", "streamed",
                 "tenant", "slot", "blk", "given", "fixed_at")

    def __init__(self, req: Request, resume_tokens: List[int],
                 t_submit: float):
        self.req = req
        self.emitted: List[int] = list(resume_tokens)
        self.full_input = np.concatenate(
            [np.asarray(req.prompt, np.int32),
             np.asarray(self.emitted, np.int32)])
        self.pending: Optional[int] = None
        self.cache_len = 0
        self.prefill_done = 0
        self.state = "queued"
        self.t_submit = t_submit
        # latency origin: the request's ARRIVAL, not the (possibly
        # earlier) submit call — offered-load benches submit ahead of
        # time and TTFT must not credit queue-building as waiting
        self.t_origin = max(t_submit, req.arrival_s)
        self.t_admit = 0.0
        self.t_first: Optional[float] = None
        self.t_last = self.t_origin
        # start of the lifecycle phase the run is in (queued → prefill →
        # decode): each transition closes it as a serve.request.* span
        self.t_phase = self.t_origin
        self.preemptions = 0
        self.spec_rounds = 0
        self.draft_cache = None
        self.tok_s: List[float] = []
        # prefix-index version this run last matched against: the
        # mid-prefill re-match is skipped until a new commit bumps it
        self.idx_seq = -1
        # positions of its input this admission mapped from shared pages
        # instead of computing (at admission and mid-prefill together)
        self.prefix_hit = 0
        # full blocks already streamed to the decode target (prefill
        # replicas only): the stream callback sends [streamed, full)
        # after each chunk, so each block crosses the wire exactly once
        self.streamed = 0
        self.tenant = req.tenant
        # adapter-pool slot held while admitted (None = base model or
        # not admitted); acquired at admission, released on finish,
        # preempt, drain, and migration — mirrors the KV block table
        self.slot: Optional[int] = None
        # a block-diffusion family's: the block being denoised (None between
        # blocks), the input's last ``len % B`` tokens (the first block's
        # given positions; cut off at admission), and the pass each emitted
        # token was fixed at (0: it came with the request)
        self.blk: Optional[_Block] = None
        self.given: np.ndarray = self.full_input[:0]
        self.fixed_at: List[int] = [0] * len(self.emitted)


class NoProgressError(RuntimeError):
    """The drain loop spun without any request advancing — a scheduler
    bug or an impossible pool configuration; raised instead of hanging
    (the serve twin of the PR 5 StallError philosophy)."""


class Scheduler:
    """One serving replica: continuous admission, chunked prefill,
    packed decode, preemption, per-request speculation. See the module
    docstring for the iteration anatomy and docs/serving.md for the
    operator view."""

    def __init__(self, params, cfg: GPTConfig, *,
                 tp_axis: Optional[str] = None,
                 max_batch: Optional[int] = None,
                 block_size: Optional[int] = None,
                 pool_blocks: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 quant_cache: Optional[bool] = None,
                 prefix_cache: Optional[bool] = None,
                 fault_plan: Optional[FaultPlan] = None,
                 replica_id: int = 0,
                 role: str = "both",
                 adapter_pool=None,
                 tenant_quota_blocks: Optional[int] = None,
                 fair_queue: Optional[bool] = None,
                 tenant_weights: Optional[Dict[Any, float]] = None,
                 clock=time.monotonic):
        """``role`` (disaggregation, docs/serving.md §disaggregation):
        ``"both"`` — the colocated default, admission through decode on
        one replica. ``"prefill"`` — a dedicated prefill replica: runs
        chunked prefill only, streams committed KV blocks to its decode
        target as they fill (router-installed ``stream_blocks``
        callback), parks a finished request in the ``handoff`` state
        (first token already committed — TTFT is stamped HERE) for the
        router to migrate, and never touches the packed decode step.
        ``"decode"`` — receives migrated requests (``submit_migrated``)
        and decodes; it can still prefill (short prompts routed
        directly, recompute-on-resume fallbacks), but in the pure
        migration flow it never builds a prefill chunk program. The
        jit factories are built LAZILY per role so a dedicated replica
        never compiles — or holds HBM for — the other role's step."""
        c = get_config()
        if role not in ("both", "prefill", "decode"):
            raise ValueError(f"unknown scheduler role {role!r} "
                             "(expected both|prefill|decode)")
        self.params = params
        self.cfg = cfg
        self.tp_axis = tp_axis
        self.role = role
        self.replica_id = replica_id
        self.max_batch = max_batch if max_batch is not None \
            else c.serve_max_batch
        self.prefill_chunk = prefill_chunk if prefill_chunk is not None \
            else c.serve_prefill_chunk
        self.default_spec_len = c.serve_spec_len
        self._prefix_on = prefix_cache if prefix_cache is not None \
            else c.serve_prefix_cache
        # multi-tenant plane (docs/serving.md §multi-tenant): the
        # AdapterPool is caller-built and caller-shared (one pool per
        # replica; the router wires it), quotas/fair-queue default from
        # config so env knobs reach bench/tests
        self.adapter_pool = adapter_pool
        self._quota = tenant_quota_blocks if tenant_quota_blocks \
            is not None else c.serve_tenant_quota_blocks
        if self._quota < 0:
            raise ValueError(
                f"tenant_quota_blocks must be >= 0; got {self._quota}")
        self._fair = fair_queue if fair_queue is not None \
            else c.serve_fair_queue
        self._weights: Dict[Any, float] = dict(tenant_weights or {})
        for t, w in self._weights.items():
            if w <= 0:
                raise ValueError(
                    f"tenant weight must be > 0; got {w} for {t!r}")
        # DWFQ deficit credits, one per tenant with waiting work; the
        # max over active tenants is renormalized to 0 after every
        # admission so an idle tenant can't bank credit while away
        self._credits: Dict[Any, float] = {}
        self._tm: Dict[Any, Dict[str, Any]] = {}
        quant = quant_cache if quant_cache is not None \
            else c.serve_quant_cache
        bs = block_size if block_size is not None else c.serve_block_size
        nb = pool_blocks if pool_blocks is not None else c.serve_pool_blocks
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1; got {self.max_batch}")
        if self.prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1; got {self.prefill_chunk}")
        if cfg.max_seq % bs != 0:
            log.warning(
                "serve: block_size %d does not divide max_seq %d — the "
                "gathered views carry a zero tail past max_seq (correct, "
                "slightly wasteful)", bs, cfg.max_seq)
        # the one seam to the model: pools by layer kind, the two programs
        # and the operand tree come from the family the configuration's
        # type names, and what its cache layout cannot carry is refused
        # here, by name
        self._family = serve_family(cfg)
        self._family.validate(params, cfg, dict(
            prefix_cache=prefix_cache, adapter_pool=adapter_pool is not None,
            quant_cache=quant, role=role != "both",
            tp_axis=tp_axis is not None))
        if self._prefix_on and not self._family.shares_prefixes:
            log.info("serve: the configured prefix cache is not applied: "
                     "the %s family's pages are not shared", self._family.name)
        self._prefix_on = self._prefix_on and self._family.shares_prefixes
        self.cache = PagedKVCache(
            cfg, block_size=bs, pool_blocks=nb, max_batch=self.max_batch,
            quant=quant, layout=lambda bs_, nb_: self._family.layout(
                params, cfg, block_size=bs_, pool_blocks=nb_,
                max_batch=self.max_batch, prefill_chunk=self.prefill_chunk,
                quant=quant))
        self._late = self._family.late_stats()
        # generation by diffusion over blocks: positions a decode row carries
        # (None: a token a row a step, and nothing below differs)
        self._blk = self._family.block(cfg)
        # the packed decode step is built LAZILY (first decode touch):
        # a prefill-only replica must never trace/compile it — that is
        # the dedicated replica's cold-start and HBM win, asserted in
        # tests/test_serve_disagg.py
        self._decode_fn = None
        self._decode_paged_attn = False   # set with _decode_fn
        if self._blk is None:
            self._pick, self._pick_last = _make_pick_fn(cfg.vocab_size)
        else:
            self._pick, self._pick_last = self._family.block_pick(cfg), None
        self._draft_steps: Dict[int, Any] = {}
        self._plan = fault_plan if fault_plan is not None \
            else plan_from_env(worker_id=replica_id)
        self._dead = False
        self._clock = clock
        # disaggregation hooks (router-installed; None = colocated):
        # stream_blocks(sched, run, {block_idx: BlockPayload}) pushes
        # newly committed prefill blocks onto the migration wire;
        # migrate_out(sched, run) -> bool moves a preemption victim's
        # blocks to a sibling instead of evicting (True = extracted)
        self.stream_blocks = None
        self.migrate_out = None
        # wire-delivered block payloads staged until adoption, keyed
        # (rid -> {block_idx: BlockPayload}); written by KVWire push
        # threads via ingest_block, drained on this thread at adoption
        self._staging: Dict[Any, Dict[int, Any]] = {}
        self._staging_lock = threading.Lock()
        self._kv_codec = None
        self._prefill_built = False
        self._waiting: deque = deque()
        self._running: List[_Run] = []
        self._runs: Dict[Any, _Run] = {}
        # what the device has picked and the host has not read: the packed
        # decode step issued last, and the first token of the request whose
        # final chunk this iteration issued. A step is issued before the
        # one before it is read, so the device never waits for the host to
        # commit, admit and pack (module docstring, "The order of an
        # iteration")
        self._flight: Optional[_InFlight] = None
        # (run, (1,) device array, the device step its read ends)
        self._first: Optional[tuple] = None
        # device steps (_wait): the non-final chunk issued and not yet
        # followed by a program whose result the host reads, as (clock at
        # its launch, tokens, table width); the last read's (clock when it
        # returned, whether the host waited in it); the open
        # serve.iteration's span id
        self._ahead: Optional[tuple] = None
        self._done = (0.0, False)
        self._iter = 0
        self.results: Dict[Any, Dict[str, Any]] = {}
        # admit a little past the decode-slot count so a finished
        # request's slot refills from a PREFILLED standby instead of
        # waiting a prompt's worth of prefill chunks with the batch
        # underfull (the pool pressure valve is preemption either way)
        self._admit_cap = admitted_at_once(self.max_batch)
        self._iteration = 0            # the serve.iteration span's number
        _reg = get_registry()
        self._m = {
            "admitted": _reg.counter("serve.admitted"),
            "completed": _reg.counter("serve.completed"),
            "preempted": _reg.counter("serve.preempted"),
            "prefill_tokens": _reg.counter("serve.prefill_tokens"),
            "decode_tokens": _reg.counter("serve.decode_tokens"),
            "decode_steps_paged_attn": _reg.counter(
                "serve.decode_steps_paged_attn"),
            # the pipeline of one (docs/observability.md): decode steps
            # issued while the step before them was unread; unread steps
            # read with no decode step queued behind them, in all and by
            # cause; rows of an issued step whose run had ended at eos by
            # the time they were read
            "decode_steps_overlapped": _reg.counter(
                "serve.decode_steps_overlapped"),
            "pipeline_drains": _reg.counter("serve.pipeline_drains"),
            **{f"pipeline_drains.{c}": _reg.counter(
                f"serve.pipeline_drains.{c}") for c in _DRAIN_CAUSES},
            "decode_rows_dropped": _reg.counter("serve.decode_rows_dropped"),
            "spec_rounds": _reg.counter("serve.spec_rounds"),
            "spec_tokens": _reg.counter("serve.spec_tokens"),
            "prefix_hits": _reg.counter("serve.prefix_hits"),
            "prefix_misses": _reg.counter("serve.prefix_misses"),
            "prefix_saved": _reg.counter("serve.prefix_saved_tokens"),
            # migration plane (docs/observability.md): requests that
            # left/arrived over the KV wire, KV tokens that moved
            # instead of being recomputed, and the recompute bill the
            # evict path still charges — migrate-vs-recompute reads
            # straight off these two
            "migrated_out": _reg.counter("serve.migration.out_requests"),
            "migrated_in": _reg.counter("serve.migration.in_requests"),
            "migrated_tokens": _reg.counter("serve.migration.tokens"),
            "recompute_tokens": _reg.counter(
                "serve.migration.recompute_tokens"),
            "ttft_ms": _reg.histogram("serve.ttft_ms"),
            # the two parts of TTFT, observed where the phases' spans are
            # emitted: waiting for a slot, and chunked prefill behind
            # older prompts
            "queue_wait_ms": _reg.histogram("serve.queue_wait_ms"),
            "prefill_ms": _reg.histogram("serve.prefill_ms"),
            "token_ms": _reg.histogram("serve.token_ms"),
            "request_ms": _reg.histogram("serve.request_ms"),
            "batch_occupancy": _reg.histogram("serve.batch_occupancy"),
            **self._block_metrics(_reg),
            # per-replica series (global instance sequence): two
            # replicas' queues must not mask each other
            "queue_depth": _reg.gauge(
                f"serve.r{next(_REPLICA_SEQ)}.queue_depth"),
        }
        # every serve program is called with the PREPARED tree: its
        # matmul operands cast to cfg.dtype once, here, not in each decode
        # step and prefill chunk. self.params stays the caller's tree
        # (shapes, adapters' registration); a draft model's tree
        # (SpecPolicy.draft_params) is not prepared
        with get_tracer().span("serve.prepare_operands", "SERVE"):
            self._operands = jax.block_until_ready(
                self._family.operands(params, cfg))
        theirs = {id(w) for w in jax.tree_util.tree_leaves(params)}
        cast = [w for w in jax.tree_util.tree_leaves(self._operands)
                if id(w) not in theirs]
        _reg.gauge("serve.operand_leaves_cast").set(len(cast))
        _reg.gauge("serve.operand_bytes").set(sum(w.nbytes for w in cast))

    # -- client surface -----------------------------------------------------
    def submit(self, req: Request,
               resume_tokens: Optional[List[int]] = None) -> None:
        """Enqueue a request (idempotence is the caller's problem: rids
        must be unique per replica lifetime). ``resume_tokens`` is the
        router's failover path — tokens already committed on a dead
        replica, kept verbatim and recomputed into fresh KV."""
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must hold at least one token")
        if req.max_new < 1:
            raise ValueError(f"max_new must be >= 1; got {req.max_new}")
        spec_k = 0
        self._family.validate_request(req, self.cfg)
        if req.spec is not None:
            if req.temperature != 0.0:
                raise ValueError(
                    "speculative policies are greedy-only "
                    "(verification compares greedy argmax)")
            spec_k = req.spec.spec_len or self.default_spec_len
            if spec_k < 1:
                raise ValueError(
                    f"effective spec_len must be >= 1; got {spec_k} "
                    "(policy spec_len or BYTEPS_SERVE_SPEC_LEN)")
        total = prompt.size + req.max_new + spec_k
        if self._blk:                  # its last block is written whole
            total = -(-total // self._blk) * self._blk
        if total > self.cfg.max_seq:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new ({req.max_new})"
                + (f" + spec_len ({spec_k})" if spec_k else "")
                + f" exceeds cfg.max_seq ({self.cfg.max_seq})")
        if self.cache.blocks_for(total) > self.cache.pool_blocks - 1:
            raise ValueError(
                f"request needs {self.cache.blocks_for(total)} KV blocks "
                f"but the pool holds {self.cache.pool_blocks - 1} — it "
                "could never be scheduled")
        if (self._quota and req.tenant is not None
                and self.cache.blocks_for(total) > self._quota):
            raise ValueError(
                f"request needs {self.cache.blocks_for(total)} KV blocks "
                f"but tenant {req.tenant!r}'s quota is {self._quota} — "
                "it could never run under the quota")
        if req.adapter is not None:
            if self.adapter_pool is None:
                raise ValueError(
                    f"request names adapter {req.adapter!r} but this "
                    "replica has no adapter pool "
                    "(BYTEPS_SERVE_ADAPTER_SLOTS=0)")
            if not self.adapter_pool.registered(req.adapter):
                raise ValueError(
                    f"adapter {req.adapter!r} is not registered in the "
                    "pool")
        if req.rid in self._runs:
            raise ValueError(f"duplicate request id {req.rid!r}")
        if req.adapter is not None:
            # prefetch-on-admission: warm a FREE slot now (never evicts
            # a cached sibling) so the admission-time acquire is a
            # residency hit instead of a host->device load on the
            # critical path
            self.adapter_pool.prefetch(req.adapter)
        run = _Run(req, list(resume_tokens or []), self._clock())
        self._runs[req.rid] = run
        if resume_tokens:
            self._waiting.appendleft(run)   # failover work is oldest
        else:
            self._waiting.append(run)
        self._m["queue_depth"].set(len(self._waiting))

    @property
    def load(self) -> int:
        """Routing weight: queued + running requests."""
        return len(self._waiting) + len(self._running)

    @property
    def finished(self) -> bool:
        """Nothing queued, nothing running and nothing unread: a request
        whose last token the device has picked is not finished until a
        ``step()`` has read and committed it."""
        return (not self._waiting and not self._running
                and self._flight is None and self._first is None)

    @property
    def dead(self) -> bool:
        return self._dead

    def result(self, rid) -> Dict[str, Any]:
        return self.results[rid]

    def drain_incomplete(self):
        """Pop every unfinished request (queued AND running), freeing
        their blocks; returns ``[(Request, emitted_tokens), ...]`` for
        the router to re-queue on a survivor. Completed results stay
        readable — they were already delivered. An unread step is read
        first: ``emitted`` is every token the device picked."""
        self._drain_in_flight("migrate")
        out = []
        for run in list(self._running):
            self.cache.release(run.req.rid)
            self._release_adapter(run)
            out.append((run.req, list(run.emitted)))
            del self._runs[run.req.rid]
        self._running.clear()
        while self._waiting:
            run = self._waiting.popleft()
            out.append((run.req, list(run.emitted)))
            del self._runs[run.req.rid]
        self._m["queue_depth"].set(0)
        return out

    # -- disaggregation / migration surface (docs/serving.md) ---------------
    def ingest_block(self, rid, block_idx: int, buf) -> None:
        """KV-wire delivery (called on KVWire PUSH threads): decode the
        frame (CRC verified — corruption raises back into the wire's
        stage retry) and stage the payload until adoption. Idempotent
        per (rid, block): a retried delivery overwrites the identical
        payload. Device state is never touched here — adoption scatters
        on the scheduler's own thread."""
        payload = self.kv_codec.decode(buf)
        with self._staging_lock:
            self._staging.setdefault(rid, {})[int(block_idx)] = payload

    def staged_blocks(self, rid) -> set:
        with self._staging_lock:
            return set(self._staging.get(rid, ()))

    def pop_staged(self, rid) -> Dict[int, Any]:
        with self._staging_lock:
            return self._staging.pop(rid, {})

    def drop_staged(self, rid) -> None:
        with self._staging_lock:
            self._staging.pop(rid, None)

    def _cut_ticket(self, run: _Run, nb: int, payloads):
        from byteps_tpu.serve.kv_wire import MigrationTicket

        return MigrationTicket(
            req=run.req, emitted=list(run.emitted), pending=run.pending,
            cache_len=run.cache_len,
            full_input=np.concatenate(
                [np.asarray(run.req.prompt, np.int32),
                 np.asarray(run.emitted, np.int32)]),
            n_blocks=nb, payloads=payloads, t_origin=run.t_origin,
            t_submit=run.t_submit, t_first=run.t_first,
            tok_s=list(run.tok_s), preemptions=run.preemptions,
            spec_rounds=run.spec_rounds)

    def pop_handoffs(self):
        """Prefill replicas: cut a :class:`MigrationTicket` for every
        request whose prefill (and first token) completed. The ticket
        carries the blocks NOT yet streamed (the partial tail); the run
        parks in the ``migrating`` state — blocks pinned — until the
        router confirms adoption via :meth:`finish_handoff` (so a
        mid-migration failure can always re-stream from live pages)."""
        out = []
        for run in self._running:
            if run.state != "handoff":
                continue
            nb = self.cache.blocks_for(run.cache_len)
            out.append(self._cut_ticket(
                run, nb,
                self.cache.snapshot_blocks(run.req.rid, run.streamed,
                                           nb)))
            run.state = "migrating"
        return out

    def finish_handoff(self, rid) -> None:
        """Adoption confirmed on the decode target: release the parked
        run's blocks (shared prefix pages stay resident for the next
        sharer — the refcount path, as everywhere)."""
        run = self._runs.pop(rid)
        self._running.remove(run)
        self.cache.release(rid)
        self._release_adapter(run)

    def extract_for_migration(self, rid):
        """Migrate-don't-evict: pull a decoding victim OUT of this
        replica — snapshot ALL its committed blocks, free them, and
        return the ticket the router ships to a sibling. Unlike
        :meth:`_preempt` nothing is recomputed: the tokens move, the
        pool pressure drops NOW. An unread step is read first (the ticket
        carries every token the device picked); a request that read
        finishes is no longer here to extract (``KeyError``, its result
        is in ``results``)."""
        self._drain_in_flight("migrate")
        run = self._runs.pop(rid)
        self._running.remove(run)
        nb = self.cache.blocks_for(run.cache_len)
        ticket = self._cut_ticket(
            run, nb, self.cache.snapshot_blocks(rid, 0, nb))
        self.cache.release(rid)
        self._release_adapter(run)
        run.state = "migrated"
        self._m["migrated_out"].inc()
        get_flight_recorder().record_event(
            "serve.migrate_out",
            {"replica": self.replica_id, "rid": str(rid),
             "blocks": nb, "tokens": run.cache_len})
        return ticket

    def submit_migrated(self, ticket, payloads) -> bool:
        """Adopt a migrated request: its KV blocks (delivered over the
        wire into ``payloads``) enter THIS pool through the refcount/
        radix path — leading blocks the local index already holds are
        shared instead of duplicated (prefix sharing survives
        migration), the rest scatter bit-exact, and the whole context
        is committed to the index so later sharers (and this request's
        own preemption resume) hit it. Returns False — allocating
        nothing — when the pool cannot fit the request even after
        preemption (the router then falls back to recompute-on-resume
        via a plain ``submit``)."""
        req = ticket.req
        rid = req.rid
        if rid in self._runs:
            raise ValueError(f"duplicate request id {rid!r}")
        if req.adapter is not None and (
                self.adapter_pool is None
                or not self.adapter_pool.registered(req.adapter)):
            raise ValueError(
                f"migrated request {rid!r} names adapter {req.adapter!r} "
                "but this replica's pool does not hold it — the router "
                "must register every adapter on every decode-capable "
                "replica")
        missing = [bi for bi in range(ticket.n_blocks)
                   if bi not in payloads]
        if missing:
            raise ValueError(
                f"migration for {rid!r} is missing block(s) {missing}")
        run = _Run(req, list(ticket.emitted),
                   ticket.t_submit or self._clock())
        ctx = run.full_input           # prompt + emitted == rows [0, len)
        self.cache.register(rid)
        hit_blocks: List[int] = []
        if self._prefix_on:
            hit_blocks, hit_tokens = self.cache.match_prefix(
                ctx[:ticket.cache_len], full_blocks_only=True)
            if hit_blocks:
                self.cache.adopt_prefix(rid, hit_blocks)
                self._m["prefix_hits"].inc()
                self._m["prefix_saved"].inc(hit_tokens)
        hit_n = len(hit_blocks)
        while True:
            try:
                self.cache.ensure(rid, ticket.cache_len + 1)
                break
            except PoolExhausted:
                if self._drain_in_flight("migrate"):
                    continue       # what it finished gave its blocks back
                victim = None
                for cand in reversed(self._running):
                    if cand.state in ("prefill", "decode"):
                        victim = cand
                        break
                if victim is None:
                    # cannot fit even with the pool drained: roll back
                    # losslessly; the router recomputes instead
                    self.cache.release(rid)
                    return False
                if (self.migrate_out is not None
                        and victim.state == "decode"
                        and victim.req.spec is None
                        and self.migrate_out(self, victim)):
                    continue
                self._preempt(victim)
        if req.adapter is not None:
            try:
                run.slot = self.adapter_pool.acquire(req.adapter, rid)
            except PoolExhausted:
                # every adapter slot is pinned by live requests: roll
                # back losslessly, the router falls back to recompute
                # (or a sibling) exactly like the block-fit failure
                self.cache.release(rid)
                return False
        row = self.cache.table_row(rid)
        self.cache.write_payloads(
            [int(b) for b in row[hit_n:ticket.n_blocks]],
            [payloads[bi] for bi in range(hit_n, ticket.n_blocks)])
        if self._prefix_on:
            self.cache.commit_prefix(rid, ctx, ticket.cache_len)
        run.cache_len = ticket.cache_len
        run.prefill_done = ticket.cache_len
        run.pending = ticket.pending
        run.t_origin = ticket.t_origin
        run.t_first = ticket.t_first
        run.t_last = ticket.tok_s[-1] if ticket.tok_s else ticket.t_origin
        run.t_phase = self._clock()        # its decode phase here starts now
        run.tok_s = list(ticket.tok_s)
        run.preemptions = ticket.preemptions
        run.spec_rounds = ticket.spec_rounds
        run.state = "decode"
        if req.spec is not None and req.spec.kind == "draft":
            # rebuild the per-request draft cache over everything but
            # the pending token (the draft proposes FROM pending) —
            # drafts only move speed, never content, so the rebuild
            # cannot touch exactness
            self._build_draft_cache(run, tokens=ctx[:-1])
        self._runs[rid] = run
        self._running.append(run)
        self._m["migrated_in"].inc()
        self._m["migrated_tokens"].inc(ticket.cache_len)
        get_flight_recorder().record_event(
            "serve.migrate_in",
            {"replica": self.replica_id, "rid": str(rid),
             "blocks": ticket.n_blocks, "shared": hit_n,
             "tokens": ticket.cache_len})
        return True

    # -- jit caches ---------------------------------------------------------
    def _prefill_fn(self, C: int, with_readout: bool = True):
        # the factory is lru-cached process-wide — every replica shares
        # one jit wrapper per (cfg, block_size, C, readout)
        self._prefill_built = True
        return self._family.prefill_fn(self.cfg, self.cache.block_size, C,
                                       self.tp_axis, with_readout)

    def _decode_step(self):
        """The packed decode step, built on first decode touch. A
        prefill-only replica must never get here — reaching it would
        mean the role split leaked decode work onto the prefill tier
        (and would silently re-grow its cold-start/HBM bill)."""
        if self._decode_fn is None:
            if self.role == "prefill":
                raise RuntimeError(
                    "prefill-only replica asked for the packed decode "
                    "step — the router's role split is broken")
            lora_sig = None
            if self.adapter_pool is not None:
                # (targets, rank bucket, n_slots) joins the factory's
                # lru key: two replicas with different pool shapes get
                # different compiled steps instead of silently
                # retracing each other's per iteration (the compile-
                # count pin in tests/test_serve_multitenant.py)
                ap = self.adapter_pool
                lora_sig = (tuple(ap.targets), ap.rank_bucket,
                            ap.n_slots)
            self._decode_fn = self._family.decode_fn(
                self.cfg, self.cache.block_size, self.tp_axis, lora_sig)
            # the same question the step asks when it is traced: does
            # its attention read the pool in place (the Pallas kernel)
            # or gather a dense view (serve.decode_steps_paged_attn)
            self._decode_paged_attn = \
                self._family.decode_reads_pool_in_place(self.cfg, self.cache)
            # what _take reads where nothing is unread
            self._no_picked = jnp.zeros(
                self.max_batch if self._blk is None
                else (self.max_batch, 2 * self._blk), jnp.int32)
            self._no_first = jnp.zeros(1, jnp.int32)
        return self._decode_fn

    def _params_for(self, run: _Run):
        """The parameter tree a single-request forward (chunked
        prefill, spec verify) runs on: the tenant's grafted tree —
        built from the pool's canonical padded host slabs and cached
        per adapter — when the request carries one, else the bare
        base. Grafting from the SAME rank-bucket-padded slabs the
        packed decode gathers is what keeps prefill logits, packed
        decode logits, and the solo baseline bit-identical."""
        if run.req.adapter is None:
            return self._operands
        return self.adapter_pool.graft(self._operands, run.req.adapter)

    @property
    def kv_codec(self):
        """This replica's KV-block wire codec (lazy; both ends of a
        migration must agree — KVBlockCodec.decode validates)."""
        if self._kv_codec is None:
            from byteps_tpu.serve.kv_wire import KVBlockCodec

            self._kv_codec = KVBlockCodec.from_pool(self.cache)
        return self._kv_codec

    def _width(self, rid) -> int:
        """Power-of-two bucket of the request's live table: the jitted
        steps retrace once per bucket instead of once per length, and a
        short request never pays a max_seq-wide gather."""
        n = self.cache.table_len(rid)
        w = 1
        while w < n:
            w <<= 1
        return min(w, self.cache.blocks_per_req)


    def _draft_step(self, draft_cfg: GPTConfig):
        key = id(draft_cfg)
        fn = self._draft_steps.get(key)
        if fn is None:
            fn = jax.jit(_make_draft_apply(draft_cfg, self.tp_axis))
            self._draft_steps[key] = fn
        return fn

    # -- multi-tenant policy (docs/serving.md §multi-tenant) ----------------
    def _tenant_m(self, tenant) -> Dict[str, Any]:
        """Lazy per-tenant metric family (``serve.tenant<T>.*``) —
        only tenanted requests pay the extra series, so legacy
        single-model traffic keeps its historical metric surface."""
        m = self._tm.get(tenant)
        if m is None:
            _reg = get_registry()
            p = f"serve.tenant{tenant}"
            m = {
                "admitted": _reg.counter(f"{p}.admitted"),
                "tokens": _reg.counter(f"{p}.tokens"),
                "quota_hits": _reg.counter(f"{p}.quota_hits"),
                "ttft_ms": _reg.histogram(f"{p}.ttft_ms"),
            }
            self._tm[tenant] = m
        return m

    def _tenant_usage(self, tenant) -> int:
        """KV blocks the tenant's admitted requests hold right now
        (table lengths — shared prefix pages charge every sharer,
        which is conservative and keeps the accounting O(running))."""
        return sum(self.cache.table_len(r.req.rid)
                   for r in self._running if r.tenant == tenant)

    def _quota_blocked(self, run: _Run) -> bool:
        """Would admitting ``run`` push its tenant past the KV quota?
        Untenanted requests are exempt (the quota is tenant isolation,
        not a pool limit — the pool has its own)."""
        if not self._quota or run.tenant is None:
            return False
        L = len(run.full_input)
        reserve = L if self.role == "prefill" else L + 1
        return (self._tenant_usage(run.tenant)
                + self.cache.blocks_for(reserve) > self._quota)

    def _next_admission(self, now: float, deferred=()) -> Optional[_Run]:
        """The admission selector. Candidates are each tenant's OLDEST
        waiting request (per-tenant order is always FIFO) that has
        arrived, is not quota-blocked, and whose tenant is not
        fault-deferred — a blocked tenant is skipped WITHOUT
        head-blocking its siblings. With fair queuing off, or when
        every candidate is the same (possibly None) tenant, the
        earliest queue position wins — exactly the historical FIFO.
        With it on, the max-credit tenant wins (deficit-weighted fair
        queuing; ties break to the earliest queue position)."""
        seen = set()
        cands = []                       # (queue position, run)
        for pos, run in enumerate(self._waiting):
            t = run.tenant
            if t in seen:
                continue
            seen.add(t)                  # younger same-tenant work waits
            if run.req.arrival_s > now:
                continue
            if t is not None and str(t) in deferred:
                continue
            if self._quota_blocked(run):
                self._tenant_m(t)["quota_hits"].inc()
                continue
            cands.append((pos, run))
        if not cands:
            return None
        if not self._fair:
            return min(cands)[1]
        for _, run in cands:
            self._credits.setdefault(run.tenant, 0.0)
        return max(cands, key=lambda pr: (self._credits[pr[1].tenant],
                                          -pr[0]))[1]

    def _charge_admission(self, run: _Run, reserve: int) -> None:
        """DWFQ accounting for one successful admission: the winner's
        tenant pays its block reservation over its weight, then the
        max credit over tenants that still have waiting work (plus the
        payer) renormalizes to 0 — a tenant idle for an hour returns
        at credit 0, equal to the current leaders, instead of having
        banked an hour of unfairness."""
        if not self._fair:
            return
        t = run.tenant
        w = float(self._weights.get(t, 1.0))
        self._credits[t] = (self._credits.get(t, 0.0)
                            - self.cache.blocks_for(reserve) / w)
        active = {r.tenant for r in self._waiting}
        active.add(t)
        mx = max(self._credits.get(a, 0.0) for a in active)
        self._credits = {a: self._credits.get(a, 0.0) - mx
                         for a in active}

    def _release_adapter(self, run: _Run) -> None:
        """Unpin the run's adapter slot (idempotent). The adapter
        stays RESIDENT at refcount 0 — cached-but-idle, LRU — so the
        tenant's next request is a residency hit."""
        if run.slot is not None:
            self.adapter_pool.release(run.req.adapter, run.req.rid)
            run.slot = None

    # -- internals ----------------------------------------------------------
    def _phase(self, run: _Run, name: str, now: float,
               cut: bool = False) -> float:
        """Close the lifecycle phase ``run`` is in (queued → prefill →
        decode, and again after a preemption) as one
        ``serve.request.<name>`` span ending ``now``, from the stamps the
        results are computed from: an unpreempted request's queued +
        prefill is its ``ttft_s`` to the last bit. Returns its length in
        ms."""
        dur = now - run.t_phase
        tag = ("preempted",) if cut else \
            ("resumed",) if run.preemptions else ()
        get_tracer().emit(f"serve.request.{name}", "SERVE", run.t_phase,
                          dur, (run.req.rid, *tag))
        run.t_phase = now
        return dur * 1e3

    def _commit_token(self, run: _Run, tok: int, now: float) -> None:
        """Append one generated token, stamp latencies, finish when the
        request is done (max_new reached or eos emitted)."""
        run.emitted.append(tok)
        run.pending = tok
        run.tok_s.append(now)
        if run.tenant is not None:
            self._tenant_m(run.tenant)["tokens"].inc()
        if run.t_first is None:
            run.t_first = now
            self._m["ttft_ms"].observe((now - run.t_origin) * 1e3)
            if run.tenant is not None:
                self._tenant_m(run.tenant)["ttft_ms"].observe(
                    (now - run.t_origin) * 1e3)
        else:
            self._m["token_ms"].observe((now - run.t_last) * 1e3)
        run.t_last = now
        if (len(run.emitted) >= run.req.max_new
                or (run.req.eos_id is not None
                    and tok == run.req.eos_id)):
            self._finish(run, now)

    def _commit_tokens(self, run: _Run, toks, now: float) -> None:
        """Commit the tokens one step yielded for ``run``, in order, one
        :meth:`_commit_token` each — so ``max_new`` and ``eos_id`` stop the
        request mid-way exactly as the dense sampler's output truncation
        does (a speculative round's accepted block, a denoised block)."""
        for t in toks:
            if run.state != "decode":
                return                       # finished mid-way
            self._commit_token(run, int(t), now)

    def _finish(self, run: _Run, now: float) -> None:
        self._phase(run, "decode", now)
        self.cache.release(run.req.rid)
        self._release_adapter(run)
        self._running.remove(run)
        # the run record is done — drop it so a long-lived replica's
        # memory tracks its LIVE load, not its lifetime request count
        # (results stay until the caller/router consumes them)
        del self._runs[run.req.rid]
        run.state = "done"
        prompt = np.asarray(run.req.prompt, np.int32).reshape(-1)
        emitted = np.asarray(run.emitted[:run.req.max_new], np.int32)
        self.results[run.req.rid] = {
            "tokens": np.concatenate([prompt, emitted]),
            "emitted": emitted,
            "ttft_s": (run.t_first - run.t_origin
                       if run.t_first is not None else None),
            "total_s": now - run.t_origin,
            "token_s": np.asarray(run.tok_s[:run.req.max_new]),
            "preemptions": run.preemptions,
            "spec_rounds": run.spec_rounds,
            "prefix_hit_tokens": run.prefix_hit,
        }
        if self._blk:
            # the pass of its block each token was fixed at (1 ..): what a
            # reference replays the sampler from
            self.results[run.req.rid]["fixed_at"] = np.asarray(
                run.fixed_at[:len(emitted)], np.int32)
        self._m["completed"].inc()
        self._m["request_ms"].observe((now - run.t_origin) * 1e3)

    def _preempt(self, run: _Run) -> None:
        """Evict ``run`` under pool pressure: free its blocks, keep its
        committed tokens, re-queue at the FRONT for recompute-on-resume
        (its next prefill input is prompt + emitted). Nothing is unread
        here: whoever picks a victim has called ``_drain_in_flight``, so
        ``emitted`` is whole."""
        # the recompute bill: every committed KV row thrown away here
        # must be re-prefilled on resume (the request's own prefix
        # commits may refund part of it if they survive the pressure
        # that caused this evict) — the migrate-vs-recompute comparison's
        # "recompute" side (tests/test_serve_disagg.py holds both sides)
        self._m["recompute_tokens"].inc(run.cache_len)
        self._phase(run, "prefill" if run.state == "prefill" else "decode",
                    self._clock(), cut=True)
        self.cache.release(run.req.rid)
        self._release_adapter(run)
        run.state = "queued"
        run.preemptions += 1
        run.pending = None
        run.cache_len = 0
        run.prefill_done = 0
        run.streamed = 0
        run.draft_cache = None
        run.blk = None             # an open block is denoised again
        run.full_input = np.concatenate(
            [np.asarray(run.req.prompt, np.int32),
             np.asarray(run.emitted, np.int32)])
        self._running.remove(run)
        self._waiting.appendleft(run)
        self._m["preempted"].inc()
        self._m["queue_depth"].set(len(self._waiting))
        get_flight_recorder().record_event(
            "serve.preempt",
            {"replica": self.replica_id, "rid": str(run.req.rid),
             "emitted": len(run.emitted)})

    def _ensure_or_preempt(self, run: _Run, n_tokens: int,
                           write_lo: Optional[int] = None,
                           write_hi: Optional[int] = None) -> bool:
        """Grow ``run``'s block table to ``n_tokens`` — and, when a
        write span is given, CoW any shared page inside it — preempting
        the youngest admitted request as often as needed. Returns False
        when ``run`` itself became the victim (the caller skips it).
        The write span is belt-and-braces: scheduler writes only ever
        target fresh or admission-CoW'd private blocks, but a shared
        page must NEVER be scattered into, so the invariant is enforced
        here rather than assumed."""
        # per-tenant KV quota: growth past the tenant's cap preempts
        # the OFFENDER's own youngest run — never a sibling's — so a
        # noisy tenant pays its own recompute bill. Terminates: each
        # preempt frees at least one same-tenant table, and submit()
        # guarantees a single request fits the quota alone.
        if self._quota and run.tenant is not None:
            while True:
                need = (self.cache.blocks_for(n_tokens)
                        - self.cache.table_len(run.req.rid))
                if (need <= 0 or self._tenant_usage(run.tenant) + need
                        <= self._quota):
                    break
                if self._drain_in_flight("preempt"):
                    if run.state not in ("prefill", "decode"):
                        return False         # the read tokens ended it
                    continue
                self._tenant_m(run.tenant)["quota_hits"].inc()
                victim = None
                for cand in reversed(self._running):
                    if (cand.tenant == run.tenant and cand is not run
                            and cand.state in ("prefill", "decode")):
                        victim = cand
                        break
                if victim is None:
                    victim = run             # its own youngest is itself
                self._preempt(victim)
                if victim is run:
                    return False
        while True:
            try:
                self.cache.ensure(run.req.rid, n_tokens)
                self.cache.ensure_window(run.req.rid, n_tokens)
                if write_lo is not None:
                    self.cache.ensure_writable(run.req.rid, write_lo,
                                               write_hi)
                return True
            except PoolExhausted:
                # a victim's recompute input is prompt + emitted, and a
                # run whose last token is unread still holds its blocks:
                # read what is unread, then look again
                if self._drain_in_flight("preempt"):
                    if run.state not in ("prefill", "decode"):
                        return False         # the read tokens ended it
                    continue
                victim = None
                for cand in reversed(self._running):
                    if cand.state in ("prefill", "decode"):
                        victim = cand
                        break
                if victim is None:
                    raise RuntimeError(
                        "KV pool exhausted with no preemptible request — "
                        "pool sizing bug (submit() validates single-"
                        "request fit)")
                # migrate-don't-evict: a decoding victim's committed
                # blocks can MOVE to a sibling replica over the KV wire
                # instead of being freed and recomputed — the router's
                # hook extracts it (blocks freed here, adopted there).
                # The victim may be the REQUESTER itself (symmetric
                # pressure grows every table in lockstep, so the
                # youngest decoder is usually the one asking): that is
                # cross-replica load shedding, and the caller's False
                # return already means "this run is no longer mine".
                # Mid-prefill and spec victims take the classic evict
                # path (their partial/draft state doesn't travel).
                if (self.migrate_out is not None
                        and victim.state == "decode"
                        and victim.req.spec is None
                        and self.migrate_out(self, victim)):
                    if victim is run:
                        return False
                    continue
                self._preempt(victim)
                if victim is run:
                    return False

    # -- speculative lane ---------------------------------------------------
    def _lookup_propose(self, run: _Run, K: int) -> np.ndarray:
        """Host-side prompt-lookup draft: the continuation of the most
        recent earlier occurrence of the committed context's last
        bigram (speculative.make_lookup_generate_fn's propose(), numpy).
        No match → junk proposals (they just accept 0)."""
        ctx = np.concatenate(
            [np.asarray(run.req.prompt, np.int32),
             np.asarray(run.emitted, np.int32)])
        n = ctx.size
        if n < 2:
            return np.zeros(K, np.int32)
        prev, last = int(ctx[-2]), int(ctx[-1])
        match = np.flatnonzero(
            (ctx[:-1] == prev) & (ctx[1:] == last))
        match = match[match <= n - 3]   # strictly earlier than the bigram
        if match.size == 0:
            return np.zeros(K, np.int32)
        p = int(match[-1])
        idx = np.clip(p + 2 + np.arange(K), 0, n - 1)
        return ctx[idx].astype(np.int32)

    def _draft_propose(self, run: _Run, K: int):
        """K greedy draft-model steps (make_speculative_generate_fn's
        dstep scan, in-loop with a per-request dense draft cache).
        Returns ``(proposals (K,), draft fill level before the round)``
        — the rewind anchor."""
        pol = run.req.spec
        step = self._draft_step(pol.draft_cfg)
        dc = run.draft_cache
        len0 = int(dc.length)
        tok = run.pending
        d = []
        for _ in range(K):
            lg, dc = step(pol.draft_params,
                          jnp.asarray([[tok]], jnp.int32), dc)
            tok = int(np.argmax(np.asarray(lg)[0, -1]))
            d.append(tok)
        run.draft_cache = dc
        return np.asarray(d, np.int32), len0

    def _spec_round(self, run: _Run, now: float) -> None:
        """One propose→verify→commit round for a spec-policy request.
        Exactness rides on speculative._verify_commit — the identical
        accept/commit arithmetic of make_speculative_generate_fn."""
        pol = run.req.spec
        K = pol.spec_len or self.default_spec_len
        pos0 = run.cache_len
        if not self._ensure_or_preempt(run, pos0 + K, pos0, pos0 + K):
            return
        draft_len0 = None
        if pol.kind == "draft":
            d, draft_len0 = self._draft_propose(run, K)
        else:
            d = self._lookup_propose(run, K)
        feed = np.concatenate([[run.pending], d[:K - 1]]).astype(np.int32)
        logits, self.cache.state = self._prefill_fn(K)(
            self._params_for(run), self.cache.state,
            jnp.asarray(feed)[None],
            jnp.int32(pos0),
            jnp.asarray(self.cache.table_row(run.req.rid,
                                             self._width(run.req.rid))))
        out = jnp.zeros((1, K + 1), jnp.int32)
        out, n_emitted, next_tok, committed = _verify_commit(
            jnp.asarray(d)[None], logits, out, jnp.int32(0), K)
        n = int(n_emitted)
        block = np.asarray(out)[0, :n]
        committed = int(committed)
        run.cache_len = pos0 + committed
        if pol.kind == "draft":
            run.draft_cache = run.draft_cache._replace(
                length=jnp.asarray(draft_len0 + committed, jnp.int32))
        run.spec_rounds += 1
        self._m["spec_rounds"].inc()
        self._m["spec_tokens"].inc(n)
        # the round emits [d_1..d_m (, correction)] then the NEXT round's
        # pending token; commit them one by one so eos/max_new stop
        # mid-block exactly like the dense sampler's output truncation
        self._commit_tokens(run, block, now)
        if run.state == "decode":
            run.pending = int(np.asarray(next_tok)[0])

    # -- the iteration ------------------------------------------------------
    def step(self) -> bool:
        """One scheduler iteration; returns True when any request made
        progress (admission, a prefill chunk, a spec round, a decode step
        issued, or a token read and committed)."""
        if self._dead:
            raise WorkerKilledError(
                f"serve replica {self.replica_id} is dead")
        if self._plan is not None:
            inj = self._plan.intercept("serve", -1)
            if inj is not None:
                if inj.kind == "kill":
                    # what the device has picked was served: the router's
                    # drain_incomplete hands it to the survivor
                    self._drain_in_flight("kill")
                    self._dead = True
                    get_flight_recorder().record_event(
                        "serve.replica_killed",
                        {"replica": self.replica_id,
                         "step": self._plan.step})
                    raise WorkerKilledError(
                        f"serve replica {self.replica_id} killed by fault "
                        f"plan at op {self._plan.step}")
                if inj.kind == "hang":
                    time.sleep(inj.rule.latency_ms / 1e3)
        tr = get_tracer()
        self._iteration += 1
        with tr.span("serve.iteration", "SERVE", (self._iteration,)) as it:
            self._iter = it.sid
            with tr.span("serve.admit", "SERVE"):
                progress = self._admit(self._clock())
            progress = self._lanes(tr) or progress
        self._iter = 0
        return progress

    def _admit(self, now: float) -> bool:
        """§1 of an iteration: admission. True when a request was
        admitted."""
        progress = False

        # tenant-scoped fault rules (tenant<T>:slow|hang): one
        # attributed intercept per waiting tenant per iteration —
        # made ONLY when the plan carries tenant rules, so tenant-free
        # specs keep their historical step-window alignment. A slow
        # rule sleeps inline inside intercept (the tenant's admission
        # pays the latency); a hang defers the tenant's admission for
        # the iteration without sleeping.
        deferred: set = set()
        if (self._plan is not None and self._plan.has_tenant_rules()
                and self._waiting):
            for t in sorted({str(r.tenant) for r in self._waiting
                             if r.tenant is not None}):
                inj = self._plan.intercept("serve", -1, tenant=t)
                if inj is not None and inj.kind == "hang":
                    deferred.add(t)

        # 1. admission (per-tenant FIFO in arrival order, DWFQ across
        # tenants when fair queuing is on — single-tenant traffic is
        # exactly the historical global FIFO; head-blocked on blocks so
        # latecomers can't starve the selected request, but a quota- or
        # fault-blocked tenant is skipped, never head-blocking
        # siblings)
        while self._waiting and len(self._running) < self._admit_cap:
            run = self._next_admission(now, deferred)
            if run is None:
                break
            if self._blk:
                # whole blocks are prefilled; what is left of the input is
                # the first block's given positions (from prompt + emitted
                # each time: an admission rolled back cuts again)
                full = np.concatenate(
                    [np.asarray(run.req.prompt, np.int32).reshape(-1),
                     np.asarray(run.emitted, np.int32)])
                cut = len(full) // self._blk * self._blk
                run.full_input, run.given = full[:cut], full[cut:]
            L = len(run.full_input)
            # a prefill-only replica writes exactly L rows (the decode
            # slot L+1 belongs to the decode target's pool)
            reserve = L if self.role == "prefill" else L + 1
            hit_blocks: List[int] = []
            hit_tokens = 0
            if self._prefix_on:
                # consult the radix index — capped at L-1 tokens so the
                # final prefill chunk always runs (its last-position
                # logits yield the first generated token / TTFT commit)
                with get_tracer().span("serve.prefix.match", "SERVE",
                                       (run.req.rid, "admit")):
                    hit_blocks, hit_tokens = self.cache.match_prefix(
                        run.full_input[:L - 1])
                run.idx_seq = self.cache.index_version
            partial = 1 if hit_tokens % self.cache.block_size else 0
            need = (self.cache.blocks_for(reserve) - len(hit_blocks)
                    + partial)
            if partial and need > (self.cache.free_blocks
                                   + self.cache.reclaimable_blocks(
                                       exclude=hit_blocks)):
                # a partial-divergence hit costs one extra block (the
                # CoW copy) AND pins an otherwise-evictable page — on a
                # tight pool that can make admission infeasible where a
                # cold admission would fit, forever (nothing running to
                # free blocks). Drop the partial adoption; the
                # full-block hit alone is never worse than cold.
                hit_blocks = hit_blocks[:-1]
                hit_tokens -= hit_tokens % self.cache.block_size
                partial = 0
                need = self.cache.blocks_for(reserve) - len(hit_blocks)
            if need > (self.cache.free_blocks
                       + self.cache.reclaimable_blocks(
                           exclude=hit_blocks)):
                break
            self._waiting.remove(run)
            self.cache.register(run.req.rid, resumed=run.preemptions > 0)
            try:
                if hit_blocks:
                    self.cache.adopt_prefix(run.req.rid, hit_blocks)
                self.cache.ensure(run.req.rid, reserve)
                if partial:
                    # the match ends mid-block: CoW the divergence
                    # block so the request owns a private copy carrying
                    # the shared KV below hit_tokens
                    self.cache.ensure_writable(run.req.rid, hit_tokens,
                                               hit_tokens + 1)
                if run.req.adapter is not None:
                    # pin the tenant's adapter slot for the run's
                    # lifetime (all-or-nothing with the KV blocks: a
                    # PoolExhausted here — every slot pinned by live
                    # requests — rolls the whole admission back)
                    run.slot = self.adapter_pool.acquire(
                        run.req.adapter, run.req.rid)
            except PoolExhausted:
                # the reclaimable estimate can be beaten by pathological
                # tree shapes (and the adapter pool can be pinned out);
                # roll the admission back losslessly and retry next
                # iteration
                self.cache.release(run.req.rid)
                self._waiting.appendleft(run)
                break
            if self._prefix_on:
                if hit_tokens:
                    self._m["prefix_hits"].inc()
                    self._m["prefix_saved"].inc(hit_tokens)
                else:
                    self._m["prefix_misses"].inc()
            # a hit starts chunked prefill at the divergence — the
            # shared chunks are never recomputed
            run.prefill_done = hit_tokens
            run.cache_len = hit_tokens
            run.prefix_hit = hit_tokens
            run.state = "prefill"
            run.t_admit = now
            self._m["queue_wait_ms"].observe(self._phase(run, "queued", now))
            self._running.append(run)
            self._charge_admission(run, reserve)
            self._m["admitted"].inc()
            if run.tenant is not None:
                self._tenant_m(run.tenant)["admitted"].inc()
            self._m["queue_depth"].set(len(self._waiting))
            progress = True
        return progress

    def _lanes(self, tr) -> bool:
        """§2–4 of an iteration, in the order the device is kept busy by:
        one prefill chunk issued, the speculative rounds, the packed decode
        step issued, and only then the tokens of the step BEFORE it read
        and committed (and the chunk's first token after them). True when
        anything was issued or committed."""
        progress = self._prefill_lane(tr)
        progress = self._spec_lane(tr) or progress
        unread, issued = self._issue_decode(tr)
        if unread is not None and not issued:
            self._note_drain("idle")
        progress = self._read_decode(tr, unread) or progress
        return self._read_first(tr) or issued or progress

    def _prefill_lane(self, tr) -> bool:
        """§2: ONE chunk for the oldest prefilling request. A final chunk
        leaves the request's first token picked and unread in
        ``self._first``. True when a chunk was issued."""
        progress = False
        for run in list(self._running):
            if run.state != "prefill":
                continue
            L = len(run.full_input)
            if L == run.prefill_done:
                # an input shorter than a block (a block-diffusion family's:
                # all of it is the first block's given positions)
                self._m["prefill_ms"].observe(
                    self._phase(run, "prefill", self._clock()))
                run.state = "decode"
                continue
            if (self._prefix_on and run.prefill_done < L - 1
                    and run.idx_seq != self.cache.index_version):
                # re-consult the index mid-prefill: at saturation every
                # request admits before ANY has committed the shared
                # prefix, so the admission lookup misses — but the
                # oldest sibling prefills first and commits, and this
                # jump maps its pages instead of recomputing them. The
                # block at the watermark swaps too when matched (its
                # written-so-far rows are content-identical by
                # construction); prefill resumes at the match end.
                # Gated on the index VERSION (bumped per commit) and
                # matched full-blocks-only, so an unchanged index costs
                # nothing and a re-match never pays the divergence scan.
                bs = self.cache.block_size
                run.idx_seq = self.cache.index_version
                with tr.span("serve.prefix.match", "SERVE",
                             (run.req.rid, "prefill")):
                    hit_blocks, hit_tokens = self.cache.match_prefix(
                        run.full_input[:L - 1], full_blocks_only=True)
                jump = hit_tokens
                if jump > run.prefill_done:
                    bp = run.prefill_done // bs
                    self.cache.readopt_prefix(
                        run.req.rid, hit_blocks[bp:jump // bs], bp)
                    self._m["prefix_hits"].inc()
                    self._m["prefix_saved"].inc(jump - run.prefill_done)
                    run.prefix_hit += jump - run.prefill_done
                    run.prefill_done = jump
                    run.cache_len = jump
            C = min(self.prefill_chunk,
                    len(run.full_input) - run.prefill_done)
            toks = run.full_input[run.prefill_done:run.prefill_done + C]
            final = run.prefill_done + C == len(run.full_input)
            # a final chunk's last position yields the first token — but not
            # where generation starts from a masked block: no readout, no
            # pick, and the chunk is read behind the decode step after it
            reads = final and self._blk is None
            if self.cache.window is not None:
                # the window kind grows chunk by chunk (and shrinks behind
                # it): its blocks for this chunk's rows, or a preemption
                if not self._ensure_or_preempt(run, run.prefill_done + C):
                    break
            W = self._width(run.req.rid)
            with tr.span("serve.prefill_dispatch", "SERVE",
                         (run.req.rid, C, W, final)
                         + self.cache.kind_widths(run.req.rid)) as sp:
                # the chunk scatters C rows — CoW any shared page in its
                # span (a no-op by construction: admission already CoW'd
                # the divergence block; enforced, not assumed)
                self.cache.ensure_writable(run.req.rid, run.prefill_done,
                                           run.prefill_done + C)
                # intermediate chunks skip the vocab readout — only the
                # final chunk's last-position logits are ever read. Host
                # arrays go in as they are: the call transfers them, at
                # less than half of what a jnp.asarray each costs
                launch = tr.clock()
                logits, self.cache.state = self._prefill_fn(C, reads)(
                    self._params_for(run), self.cache.state,
                    toks[None], np.int32(run.prefill_done),
                    self.cache.table_row(run.req.rid, W))
                tr.emit("serve.issue.chunk_call", "SERVE", launch,
                        tr.clock() - launch, parent=sp.sid)
            if self._ahead is not None:
                # no row decoded since the last chunk: two chunks in one
                # device step, whose time is then no one kind's
                launch = self._ahead[0]
                self._done = (self._done[0], False)
            self._ahead = None if reads else (launch, C, W)
            run.prefill_done += C
            run.cache_len = run.prefill_done
            self.cache.release_behind(run.req.rid, run.cache_len)
            if self._late is not None:
                self._late.note(self.cache.state)
            self._m["prefill_tokens"].inc(C)
            if self._prefix_on:
                # publish the newly fully-written leading blocks so the
                # NEXT request sharing this prefix maps them instead of
                # recomputing (refcount +1 per node keeps them resident
                # after this request finishes — cached-but-idle, LRU)
                self.cache.commit_prefix(run.req.rid, run.full_input,
                                         run.prefill_done)
            if self.role == "prefill" and self.stream_blocks is not None:
                # disaggregation: newly FULL blocks stream to the decode
                # target NOW, so their wire time (codec + pacer on the
                # KVWire's stage threads) overlaps the next chunk's
                # compute on this thread — the partial tail travels
                # with the handoff ticket
                full = run.prefill_done // self.cache.block_size
                if full > run.streamed:
                    self.stream_blocks(
                        self, run,
                        self.cache.snapshot_blocks(run.req.rid,
                                                   run.streamed, full))
                    run.streamed = full
            progress = True
            if final and not reads:
                self._m["prefill_ms"].observe(
                    self._phase(run, "prefill", self._clock()))
                run.state = "decode"
            elif final:
                run.state = "decode"
                if (run.req.spec is not None
                        and run.req.spec.kind == "draft"
                        and self.role != "prefill"):
                    self._build_draft_cache(run)
                # the pick is issued here and read in _read_first
                self._first = (run, self._pick_last(
                    logits, np.asarray([run.req.seed], np.int32),
                    np.asarray([run.cache_len], np.int32),
                    np.asarray([run.req.temperature], np.float32)),
                    ("chunk", launch, (C, W)))
                self._first[1].copy_to_host_async()
                if run.req.spec is not None or self.role == "prefill":
                    # its round of this iteration proposes from the token
                    # on the host; a prefill replica issues nothing more
                    self._read_first(tr)
            break                                 # one chunk per iteration
        return progress

    def _spec_lane(self, tr) -> bool:
        """§3: one round per spec request — they never take plain decode
        steps (a token committed outside the round would desync the
        per-request draft cache). True when a round ran."""
        progress = False
        for run in [r for r in self._running
                    if r.state == "decode" and r.req.spec is not None]:
            if run.state == "decode":   # an earlier round may preempt
                with tr.span("serve.spec_round", "SERVE", (run.req.rid,)):
                    self._spec_round(run, self._clock())
                progress = True
        return progress

    def _tokens_picked(self, run: _Run) -> int:
        """Tokens the device has picked for ``run``, read or not: whether
        it goes on is known from this count before the last one's value
        is."""
        if self._blk:
            # the unread pass commits its block: the positions behind the
            # given ones
            given = None if self._flight is None \
                else self._flight.commit.get(run.req.rid)
            return len(run.emitted) + (0 if given is None
                                       else self._blk - given)
        return (len(run.emitted)
                + (self._flight is not None
                   and run.req.rid in self._flight.rows)
                + (self._first is not None and self._first[0] is run))

    def _issue_decode(self, tr):
        """§4: the packed decode step for the non-speculative decoders,
        issued with its input tokens taken on the device where the host has
        not read them yet. Returns ``(unread, issued)``: the step that was
        in flight before (None when there was none, or packing had to
        drain it), now the caller's to read, and whether a step was
        issued behind it."""
        B = self._blk or 1             # positions a row writes
        with tr.span("serve.decode_pack", "SERVE"):
            packed: List[_Run] = []
            for run in list(self._running):
                if run.state != "decode" or run.req.spec is not None:
                    continue
                if len(packed) >= self.max_batch:
                    break
                if self._tokens_picked(run) >= run.req.max_new:
                    continue               # its unread token is its last
                if self._ensure_or_preempt(run, run.cache_len + B,
                                           run.cache_len,
                                           run.cache_len + B):
                    if run.state == "decode":  # survived any preemptions
                        packed.append(run)
            packed = [r for r in packed if r.state == "decode"]
            unread, self._flight = self._flight, None
            if not packed:
                return unread, False
            R = self.max_batch
            W = max(self._width(r.req.rid) for r in packed)
            rows = [self.cache.table_row(r.req.rid, W) for r in packed]
            host = np.zeros((R, _N_COLS + rows[0].size
                             + (2 * B if self._blk else 0)), np.int32)
            # where row i's input token is: a row of the unread step, the
            # unread first token (R), or the host's own (R + 1 + i); a block's
            # state likewise, without a first token (R + i)
            host[:, _SRC] = np.arange(R + 1, 2 * R + 1) - (self._blk
                                                           is not None)
            temps = np.zeros(R, np.float32)
            blocks = self._pack_blocks(packed, unread, host, rows) \
                if self._blk else None
            for i, run in enumerate(() if self._blk else packed):
                if unread is not None and run.req.rid in unread.rows:
                    host[i, _SRC] = unread.rows[run.req.rid]
                elif self._first is not None and self._first[0] is run:
                    host[i, _SRC] = R
                else:
                    host[i, _TOK] = run.pending
                host[i, _POS] = run.cache_len
                host[i, _SEED] = run.req.seed
                temps[i] = run.req.temperature
                # heterogeneous-adapter decode: each row gathers its
                # adapter's A/B slabs by pool slot inside the ONE jitted
                # step (ops/segmented_lora.py); padded rows and base-model
                # runs ride slot 0, the reserved all-zero slot, so batch
                # composition never branches the program
                host[i, _SLOT] = run.slot or 0
                host[i, _N_COLS:] = rows[i].reshape(-1)
            host[:, _TEMP] = temps.view(np.int32)
        shape = (len(packed), W) + ((B,) if self._blk else ())
        with tr.span("serve.decode_dispatch", "SERVE", shape) as sp:
            step = self._decode_step()
            t0 = tr.clock()
            was = unread.picked if unread is not None else self._no_picked
            if self._blk:
                toks, state, pos, tables, n_fix, pass_no = _take_block(
                    was, host, table_shape=rows[0].shape)
                extra = ()
            else:
                toks, pos, tables, seeds, pos1, temps, slots = _take(
                    was, self._first[1] if self._first is not None
                    else self._no_first, host, table_shape=rows[0].shape)
                extra = () if self.adapter_pool is None \
                    else (self.adapter_pool.slabs, slots)
            t1 = tr.clock()
            logits, self.cache.state = step(
                self._operands, self.cache.state, toks, pos, tables, *extra)
            t2 = tr.clock()
            picked = self._pick(logits, state, n_fix, pass_no) if self._blk \
                else self._pick(logits, seeds, pos1, temps)
            picked.copy_to_host_async()
            t3 = tr.clock()
            if self._late is not None:
                self._late.note(self.cache.state)
            # the host's issue by its parts: a clock read and a ring
            # append each, no profiler annotation
            tr.emit("serve.issue.take", "SERVE", t0, t1 - t0, parent=sp.sid)
            tr.emit("serve.issue.decode_call", "SERVE", t1, t2 - t1,
                    parent=sp.sid)
            tr.emit("serve.issue.pick", "SERVE", t2, t3 - t2, parent=sp.sid)
        # what the host knows without the tokens' values: each row wrote
        # its position — a block's row its block, which becomes cache only
        # with the pass that commits it
        for run in packed:
            if not self._blk:
                run.cache_len += 1
            elif run.blk.issued > run.blk.need:
                run.cache_len += B
                run.blk = None
        if self._ahead is None:
            dstep = ("decode", t0, shape)
        else:
            dstep = ("chunk_decode", self._ahead[0], self._ahead[1:] + shape)
            self._ahead = None
        self._flight = _InFlight(picked, packed, host[:len(packed), _POS],
                                 dstep, blocks)
        if unread is not None:
            self._m["decode_steps_overlapped"].inc()
        if self._decode_paged_attn:
            self._m["decode_steps_paged_attn"].inc()
        self._m["batch_occupancy"].observe(len(packed))
        return unread, True

    def _read_decode(self, tr, flight: Optional[_InFlight]) -> bool:
        """Read a decode step's tokens and commit them: the host waits in
        ``serve.decode_sync`` (with a step queued behind this one, the
        device does not), ``serve.commit`` is host work alone. A row
        whose run ended at eos after the step was issued is dropped: its
        write went to a block that run released, and whoever owns the
        block next writes after it, in issue order."""
        if flight is None:
            return False
        with tr.span("serve.decode_sync", "SERVE"):
            picked = self._wait(tr, flight.picked, flight.step)
        with tr.span("serve.commit", "SERVE"):
            now = self._clock()
            live = tokens = 0
            for i, run in enumerate(flight.runs):
                if run.state != "decode":
                    continue
                live += 1
                if flight.blocks is None:
                    self.cache.release_behind(run.req.rid,
                                              int(flight.pos[i]) + 1)
                    self._commit_token(run, int(picked[i]), now)
                elif run.req.rid in flight.commit:
                    tokens += self._commit_block(run, flight.blocks[i],
                                                 picked[i], now)
                else:
                    # a denoising pass commits nothing: the host keeps the
                    # block as it stands, for a pass issued after a drain
                    flight.blocks[i].state = picked[i]
        self._m["decode_tokens"].inc(live if flight.blocks is None
                                     else tokens)
        self._m["decode_rows_dropped"].inc(len(flight.runs) - live)
        return True

    # -- generation by diffusion over blocks ---------------------------------
    def _block_metrics(self, reg) -> Dict[str, Any]:
        """The series of a block-diffusion family (docs/observability.md
        §serve.block): none for the others."""
        if self._blk is None:
            return {}
        return {
            # row-passes issued, those of them that commit a block, blocks
            # committed (at the read), positions the passes fixed
            "block_row_passes": reg.counter("serve.block.row_passes"),
            "block_commit_row_passes": reg.counter(
                "serve.block.commit_row_passes"),
            "block_commits": reg.counter("serve.block.commits"),
            "block_positions_fixed": reg.counter(
                "serve.block.positions_fixed"),
            # rows of k/v (a layer each) that passes scattered and a later
            # pass of the same block overwrote
            "block_rows_rewritten": reg.counter(
                "serve.kv.block_rows_rewritten"),
            # passes a committed block took; what a client waits between two
            # blocks of one request
            "block_passes": reg.histogram("serve.block.passes"),
            "block_ms": reg.histogram("serve.block_ms"),
        }

    def _pack_blocks(self, packed: List[_Run], unread, host,
                     rows) -> List[_Block]:
        """Each packed row's block and its columns of ``host`` (position and
        table ``rows[i]`` as a token's row has them): a run between
        blocks starts one (its state is the host's: the given tokens, the
        rest masked); the next pass of an open block feeds on the unread
        step's row of it where there is one, else on the state the host read.
        The schedule is static, so which pass a row is at, what it fixes and
        whether it commits are known here without any token's value."""
        B, m = self._blk, self._m
        blocks, fixed, commits = [], 0, 0
        for i, run in enumerate(packed):
            blk = run.blk
            if blk is None:
                blk = run.blk = _Block(
                    run.given, B,
                    run.req.denoise_steps or self.cfg.denoise_steps,
                    self.cfg.mask_id)
                run.given = run.given[:0]
            if (blk.issued and unread is not None
                    and run.req.rid in unread.rows):
                host[i, _SRC] = unread.rows[run.req.rid]
            else:
                host[i, -2 * B:] = blk.state
            n = blk.fixes()
            host[i, _NFIX] = n
            blk.issued += 1
            host[i, _PASS] = blk.issued
            host[i, _POS] = run.cache_len
            host[i, _N_COLS:-2 * B] = rows[i].reshape(-1)
            fixed += n
            commits += n == 0
            blocks.append(blk)
        m["block_row_passes"].inc(len(packed))
        m["block_commit_row_passes"].inc(commits)
        m["block_positions_fixed"].inc(fixed)
        m["block_rows_rewritten"].inc(
            (len(packed) - commits) * B * self.cfg.n_layers)
        return blocks

    def _commit_block(self, run: _Run, blk: _Block, state: np.ndarray,
                      now: float) -> int:
        """The read of the pass that committed ``blk``: its tokens behind the
        given ones are the run's next, in order (``max_new`` or ``eos_id``
        may end the request inside the block). One latency observation a
        token as everywhere — the block's first carries the whole gap, the
        others 0 — and the gap between blocks on its own. Returns the tokens
        committed."""
        B, before = self._blk, len(run.emitted)
        if run.t_first is not None:
            self._m["block_ms"].observe((now - run.t_last) * 1e3)
        self._m["block_passes"].observe(blk.issued)
        self._m["block_commits"].inc()
        run.fixed_at.extend(state[B + blk.given:].tolist())
        self._commit_tokens(run, state[blk.given:B], now)
        return len(run.emitted) - before

    def _read_first(self, tr) -> bool:
        """Read and commit the first token of the request whose final
        chunk this iteration issued (TTFT is stamped here): after the
        iteration's decode step was issued, so that step is queued behind
        the chunk while the host waits in ``serve.prefill_sync``."""
        if self._first is None:
            return False
        (run, picked, step), self._first = self._first, None
        with tr.span("serve.prefill_sync", "SERVE"):
            tok = int(self._wait(tr, picked, step)[0])
        now = self._clock()
        self._m["prefill_ms"].observe(self._phase(run, "prefill", now))
        self._commit_token(run, tok, now)
        if run.state == "decode" and self.role == "prefill":
            # prefill is this replica's whole job: the request parks
            # (blocks pinned) until the router migrates it — its first
            # token is already committed, so TTFT was stamped here,
            # untouched by wire time
            run.state = "handoff"
        return True

    def _wait(self, tr, picked, step) -> np.ndarray:
        """``picked`` on the host (the host blocks on the device here), and
        the device step its arrival ends as a span on the ring. The device
        runs what it is handed in issue order, so between two reads the host
        WAITED in it ran exactly what was issued between the two programs
        read: ``step`` = (kind, clock at the first launch of those, args).
        The span runs from the previous read's return to this one's. It
        carries its kind's name when the host waited in both reads and the
        step was queued before the previous read returned; else (the result
        was ready already, nothing was queued behind the previous step: a
        drain, an idle wait, the first step) it is ``unseen``, from the
        launch if that is later, its kind the first of its args: counted,
        never averaged."""
        waited = not picked.is_ready()
        out = np.asarray(picked)
        now = tr.clock()
        kind, launch, args = step
        prev, seen = self._done
        start = max(prev, launch)
        if not (seen and waited and launch <= prev):
            kind, args = "unseen", (kind,) + args
        tr.emit(_DEVICE_STEP[kind], "SERVE", start, now - start, args,
                self._iter)
        self._done = (now, waited)
        return out

    def _note_drain(self, cause: str) -> None:
        self._m["pipeline_drains"].inc()
        self._m[f"pipeline_drains.{cause}"].inc()

    def _drain_in_flight(self, cause: str) -> bool:
        """Read and commit whatever the device has picked and the host has
        not read, with nothing issued behind it: for everything that reads
        or rewrites a run from outside the lanes (a preemption, a
        migration either way, ``drain_incomplete``, the fault plan's
        kill). True when there was anything to read."""
        flight, self._flight = self._flight, None
        if flight is not None:
            self._note_drain(cause)
        tr = get_tracer()
        read = self._read_decode(tr, flight)
        return self._read_first(tr) or read

    def _build_draft_cache(self, run: _Run,
                           tokens: Optional[np.ndarray] = None) -> None:
        """Prefill the per-request dense draft cache over the full
        committed context (prompt + resumed tokens; a migrated-in run
        passes its context minus the pending token explicitly)."""
        pol = run.req.spec
        kv_d = (pol.draft_params["blocks"][0]["wk"].shape[-1]
                // pol.draft_cfg.head_dim)
        dc = init_cache(pol.draft_cfg, 1, h_loc=kv_d)
        _, dc = self._draft_step(pol.draft_cfg)(
            pol.draft_params,
            jnp.asarray(run.full_input if tokens is None
                        else tokens)[None], dc)
        run.draft_cache = dc

    def flush_stats(self) -> None:
        """Observe what the dispatched programs counted and the registry has
        not seen yet (a latent family's ``moe.*`` / ``serve.dsa.*``, a step
        late otherwise), waiting for the device: for a reading at a fixed
        point, outside the hot loop."""
        if self._late is not None:
            self._late.drain(block=True)

    def serve(self, requests: List[Request], max_idle_iters: int = 10000):
        """Submit + drain convenience for tests/bench: runs ``step()``
        until every request finished. Arrival times are honored against
        this scheduler's clock."""
        for r in requests:
            self.submit(r)
        idle = 0
        while not self.finished:
            if self.step():
                idle = 0
            else:
                idle += 1
                if self._waiting and all(
                        r.req.arrival_s > self._clock()
                        for r in self._waiting):
                    time.sleep(1e-4)
                elif idle > max_idle_iters:
                    raise NoProgressError(
                        f"{len(self._waiting)} queued / "
                        f"{len(self._running)} running requests made no "
                        f"progress for {max_idle_iters} iterations")
        return self.results


def _make_draft_apply(draft_cfg: GPTConfig, tp_axis):
    """A named closure (not functools.partial) so jit caches by draft
    config identity and the traceback names the draft step."""
    def _draft_apply(p, t, c):
        return gpt_apply_cached(p, t, c, draft_cfg, tp_axis)
    return _draft_apply
