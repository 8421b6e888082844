"""Telemetry-driven autoscaling: ONE policy for train workers and serve
replicas.

PR 5 built scale-DOWN (leases, eviction, quorum sums, rejoin), this PR's
``kJoin`` builds scale-UP — this module closes the loop with the policy
that DECIDES. The always-on registry (PR 6) already exports everything a
Pollux-style goodput policy needs: per-worker goodput trend, the
``server.staleness`` histogram, the ``psworker.*.rounds_ahead`` straggler
gauges on the train side; queue depth and the ``serve.ttft_ms`` histogram
on the serve side. :class:`ScalingPolicy` reads a domain-agnostic
:class:`Sample` distilled from those and emits ``admit``/``evict``/
``hold`` with hysteresis, a sustain requirement, and a cooldown — the
same class drives worker admission in a training loop and replica
spawn/drain in ``serve/router.py``, so train and serve share one
elasticity story.

Every consequential decision — whether it came from this policy, the
serve router's lease sweep, or an operator-driven ``join()`` — flows
through :func:`record_decision`: the ``autoscaler.decisions`` counter,
a chrome-trace FAULT instant, and a flight-recorder event, so a
post-mortem shows WHY a worker/replica was admitted or evicted
(docs/observability.md).

Decision semantics (pinned by a deterministic trace test):

* **admit** — ``load`` held above ``scale_up_load × (1 + hysteresis)``
  for ``sustain`` consecutive samples (sustained headroom/demand, not
  one lucky step) and the unit count is below ``max_units``.
* **evict** — either a straggler was detected (``straggler`` above
  ``straggler_limit`` for ``sustain`` samples — evict it rather than let
  it set the step time) or ``load`` held below
  ``scale_down_load × (1 − hysteresis)`` (sustained idleness), and the
  unit count is above ``min_units``.
* **hold** — inside the hysteresis band, during the post-decision
  cooldown, or pinned at a min/max bound.

``load`` is the domain's demand/efficiency signal, HIGH = the pool is
earning its keep: per-worker goodput as a fraction of the clean
per-worker baseline (train, :func:`train_sample`), or per-replica queue
depth plus TTFT-SLO pressure (serve, :func:`serve_sample`).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional

from byteps_tpu.common.config import get_config
from byteps_tpu.common.flight_recorder import get_flight_recorder
from byteps_tpu.common.logging import get_logger
from byteps_tpu.common.metrics import get_registry
from byteps_tpu.common.tracing import get_tracer

log = get_logger("autoscaler")

__all__ = [
    "Sample", "Decision", "ScalingPolicy", "record_decision",
    "train_sample", "serve_sample",
]


def record_decision(domain: str, action: str, reason: str,
                    target: Optional[int] = None,
                    live: Optional[int] = None,
                    predicted: Optional[Dict[str, Any]] = None) -> None:
    """The ONE event path for every scale decision: counters
    (``autoscaler.decisions`` + ``autoscaler.<domain>.<action>``), a
    chrome-trace FAULT instant, and a flight-recorder event. The serve
    router's lease sweep and the policy loop both land here, so a
    post-mortem's event ring answers "why was this worker/replica
    admitted/evicted" uniformly. ``predicted`` carries the what-if
    simulator's payoff estimate when an ``estimator`` was consulted —
    the post-mortem then also answers "what did the decision EXPECT"."""
    reg = get_registry()
    reg.counter("autoscaler.decisions").inc()
    reg.counter(f"autoscaler.{domain}.{action}").inc()
    args = {"domain": domain, "action": action, "reason": reason,
            "target": target, "live": live}
    if predicted is not None:
        args["predicted"] = predicted
    get_tracer().instant(f"autoscaler_{action}", "FAULT", args)
    get_flight_recorder().record_event("autoscaler.decision", args)
    log.info("autoscaler[%s]: %s (%s)%s", domain, action, reason,
             f" target={target}" if target is not None else "")


@dataclasses.dataclass(frozen=True)
class Sample:
    """One domain-agnostic policy observation (see module docstring)."""

    live: int               # current live unit count (workers/replicas)
    load: float             # demand/efficiency signal, HIGH = earning keep
    straggler: float = 0.0  # straggler severity (rounds_ahead spread /
    #                         staleness p99 / replica load imbalance)


@dataclasses.dataclass(frozen=True)
class Decision:
    action: str   # 'admit' | 'evict' | 'hold'
    reason: str
    step: int     # the policy step this decision was made at
    live: int     # unit count observed when deciding
    # the estimator's payoff prediction, when one was consulted:
    # {"goodput_live", "goodput_target", "target"} — recorded on the
    # decision event for post-mortems (ROADMAP item 4's remainder)
    predicted: Optional[Dict[str, float]] = None


class ScalingPolicy:
    """Hysteresis/sustain/cooldown admit-evict-hold policy — one class
    for both elasticity domains (constructor thresholds carry the
    domain's units; the dynamics come from the shared
    ``BYTEPS_AUTOSCALE_*`` defaults)."""

    def __init__(self, scale_up_load: float, scale_down_load: float,
                 straggler_limit: Optional[float] = None,
                 hysteresis: Optional[float] = None,
                 cooldown: Optional[int] = None,
                 sustain: Optional[int] = None,
                 min_units: Optional[int] = None,
                 max_units: Optional[int] = None,
                 domain: str = "train",
                 estimator: Optional[Callable[[int], float]] = None):
        """``estimator(n_units) -> predicted aggregate goodput`` (the
        what-if simulator's ``sim.search.goodput_estimator``, or any
        model): when set, an ADMIT must predict its own payoff before
        spending capacity — the marginal unit must add at least
        ``hysteresis`` of an average live unit's current contribution
        (a per-unit margin: perfect linear scaling always passes), else
        the decision degrades to a hold that says so and arms the
        cooldown like the admit it replaced. Every estimator
        consultation is recorded on the decision
        (``Decision.predicted``) and rides the shared event path, so
        post-mortems show expectation beside outcome."""
        cfg = get_config()
        if scale_down_load >= scale_up_load:
            raise ValueError(
                f"scale_down_load ({scale_down_load}) must sit below "
                f"scale_up_load ({scale_up_load}) — an inverted band "
                "admits and evicts at once")
        self.scale_up_load = float(scale_up_load)
        self.scale_down_load = float(scale_down_load)
        self.straggler_limit = straggler_limit
        self.hysteresis = (hysteresis if hysteresis is not None
                           else cfg.autoscale_hysteresis)
        self.cooldown = (cooldown if cooldown is not None
                         else cfg.autoscale_cooldown)
        self.sustain = max(1, sustain if sustain is not None
                           else cfg.autoscale_sustain)
        self.min_units = (min_units if min_units is not None
                          else cfg.autoscale_min)
        self.max_units = (max_units if max_units is not None
                          else cfg.autoscale_max)
        self.domain = domain
        self.estimator = estimator
        self._step = 0
        self._last_change = -(10 ** 9)
        self._up_streak = 0
        self._down_streak = 0
        self._straggler_streak = 0
        # full decision history — what the deterministic-trace pin
        # (tests/test_join.py) reads back
        self.trace: List[Decision] = []
        self._m_hold = get_registry().counter(
            f"autoscaler.{domain}.hold")

    # -- core ---------------------------------------------------------------
    def observe(self, sample: Sample) -> Decision:
        """Feed one sample; returns (and records) the decision. Non-hold
        decisions reset the streaks and arm the cooldown; the CALLER
        executes them (join a worker / spawn a replica / drain one) —
        the policy only decides."""
        self._step += 1
        d = self._decide(sample)
        self.trace.append(d)
        if d.action == "hold" and not (d.predicted is not None
                                       and "veto" in d.reason):
            # holds are counted but not traced/ring-recorded: one event
            # per policy tick would drown the post-mortem ring
            self._m_hold.inc()
        elif d.action == "hold":
            # an estimator VETO is a consequential decision (capacity
            # was declined on a predicted non-payoff) and must be
            # explicable post-mortem like the admit it replaced — and it
            # arms the cooldown + resets the streaks exactly like one,
            # so a sustained veto state records once per cooldown window
            # instead of once per tick (which would drown the ring).
            # record_decision counts autoscaler.<domain>.hold itself.
            record_decision(self.domain, "hold", d.reason,
                            live=sample.live, predicted=d.predicted)
            self._last_change = self._step
            self._up_streak = self._down_streak = 0
            self._straggler_streak = 0
        else:
            record_decision(self.domain, d.action, d.reason,
                            live=sample.live, predicted=d.predicted)
            self._last_change = self._step
            self._up_streak = self._down_streak = 0
            self._straggler_streak = 0
        return d

    def _decide(self, s: Sample) -> Decision:
        up_at = self.scale_up_load * (1.0 + self.hysteresis)
        down_at = self.scale_down_load * (1.0 - self.hysteresis)
        # streaks advance even during the cooldown so a persistent
        # condition acts the moment the cooldown opens
        if (self.straggler_limit is not None
                and s.straggler > self.straggler_limit):
            self._straggler_streak += 1
        else:
            self._straggler_streak = 0
        self._up_streak = self._up_streak + 1 if s.load >= up_at else 0
        self._down_streak = (self._down_streak + 1 if s.load <= down_at
                             else 0)
        if self._step - self._last_change <= self.cooldown:
            return Decision("hold", "cooldown", self._step, s.live)
        if self._straggler_streak >= self.sustain:
            if s.live > self.min_units:
                return Decision(
                    "evict",
                    f"straggler detected ({s.straggler:.3g} > "
                    f"{self.straggler_limit:.3g} for "
                    f"{self._straggler_streak} samples)",
                    self._step, s.live)
            return Decision("hold", "straggler but at min_units",
                            self._step, s.live)
        if self._up_streak >= self.sustain:
            if s.live < self.max_units:
                reason = (f"sustained load headroom ({s.load:.3g} >= "
                          f"{up_at:.3g} for {self._up_streak} samples)")
                pred = self._predict(s.live, s.live + 1)
                if pred is not None and not pred["pays_off"]:
                    # ROADMAP item 4's remainder: the admit predicts its
                    # own payoff (simulated goodput at live+1) BEFORE
                    # spending capacity — a sublinear step (round-close
                    # barriers, server contention) turns into a hold
                    return Decision(
                        "hold",
                        f"estimator veto: goodput({s.live + 1}) "
                        f"{pred['goodput_target']:.3g} adds under "
                        f"{self.hysteresis:.3g}x of an average "
                        f"worker's share at live {s.live} "
                        f"({pred['goodput_live']:.3g})",
                        self._step, s.live, predicted=pred)
                return Decision("admit", reason, self._step, s.live,
                                predicted=pred)
            return Decision("hold", "demand but at max_units",
                            self._step, s.live)
        if self._down_streak >= self.sustain:
            if s.live > self.min_units:
                return Decision(
                    "evict",
                    f"sustained idle ({s.load:.3g} <= {down_at:.3g} "
                    f"for {self._down_streak} samples)",
                    self._step, s.live,
                    # recorded, never vetoing: an idle evict SAVES
                    # capacity — the prediction is for the post-mortem
                    predicted=self._predict(s.live, s.live - 1))
            return Decision("hold", "idle but at min_units",
                            self._step, s.live)
        return Decision("hold", "in-band", self._step, s.live)

    def _predict(self, live: int, target: int,
                 ) -> Optional[Dict[str, float]]:
        """Consult the estimator (None when none attached; a failing
        estimator is treated as absent — the policy must keep deciding
        without its advisor). ``pays_off`` applies the policy's
        hysteresis as the margin an extra unit must clear."""
        if self.estimator is None:
            return None
        try:
            cur = float(self.estimator(live))
            tgt = float(self.estimator(target))
        except Exception as e:  # noqa: BLE001 — advisory, never fatal
            log.warning("autoscaler estimator failed (%s); deciding "
                        "without prediction", e)
            return None
        # an admit pays off when the MARGINAL unit delivers at least
        # `hysteresis` of an average live unit's current contribution —
        # relative to the per-unit gain, NOT the aggregate (a flat
        # aggregate margin would veto perfect linear scaling the moment
        # live exceeds 1/hysteresis)
        per_unit = cur / max(1, live)
        return {
            "goodput_live": cur,
            "goodput_target": tgt,
            "target": target,
            "pays_off": ((tgt - cur) > self.hysteresis * per_unit
                         if target > live else tgt >= 0.0),
        }


# -- domain samplers ----------------------------------------------------------
def train_sample(snapshot: Dict[str, Any], live: int,
                 goodput_per_worker: float,
                 baseline_per_worker: float) -> Sample:
    """Distill the TRAIN-domain :class:`Sample` from a
    ``byteps_tpu.metrics_snapshot()`` dict plus the caller's goodput
    trend: ``load`` = per-worker goodput as a fraction of the clean
    per-worker baseline (≈1.0 means adding capacity still pays
    linearly); ``straggler`` = the spread of the per-NIC
    ``rounds_ahead`` gauges (how far the fastest pipeline runs ahead of
    the round it consumes vs the slowest) with the ``server.staleness``
    p99 folded in — both are zero on a healthy strict-sync tier."""
    m = snapshot.get("metrics", snapshot)
    gauges = m.get("gauges", {})
    ahead = [
        float(v["value"] if isinstance(v, dict) else v)
        for k, v in gauges.items()
        if k.startswith("psworker.") and k.endswith(".rounds_ahead")
    ]
    spread = (max(ahead) - min(ahead)) if len(ahead) > 1 else 0.0
    hist = m.get("histograms", {}).get("server.staleness", {})
    stale_p99 = float(hist.get("p99", 0.0) or 0.0)
    load = (goodput_per_worker / baseline_per_worker
            if baseline_per_worker > 0 else 0.0)
    return Sample(live=int(live), load=load,
                  straggler=max(spread, stale_p99))


def serve_sample(live: int, queue_depth: float,
                 ttft_p99_ms: float = 0.0,
                 ttft_slo_ms: Optional[float] = None) -> Sample:
    """Distill the SERVE-domain :class:`Sample`: ``load`` = per-replica
    queue depth, plus SLO pressure (how far the recent TTFT overshoots
    the SLO) when an SLO is configured — a saturated-but-short queue
    with blown latency must still scale up. The TTFT figure should be a
    WINDOWED reading (the router passes the per-tick delta mean of the
    ``serve.ttft_ms`` histogram — a process-lifetime percentile would
    carry a cold-start spike forever). ``straggler`` stays 0:
    replica-level stragglers are the router's LEASE sweep's job
    (silence, not slowness)."""
    load = float(queue_depth) / max(1, int(live))
    if ttft_slo_ms and ttft_p99_ms:
        load += max(0.0, float(ttft_p99_ms) / float(ttft_slo_ms) - 1.0)
    return Sample(live=int(live), load=load, straggler=0.0)
