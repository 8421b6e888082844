"""What every cell shares: the device check, the compile cache, the window
with its compile counter, the profiler trace, the by-name rules and the
result line. Nothing here names a cell, a configuration, a mix or a metric.
"""

from __future__ import annotations

import glob
import importlib
import json
import os
import shutil
import tempfile
import time
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_json(*parts: str) -> Dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def merged(base: Dict, over: Dict) -> Dict:
    """``base`` with ``over`` laid on top, dicts merged key by key."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merged(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def layer_metrics_for(sets: List[str], reports: List[str], chips: int,
                      metrics: Optional[List[Dict]] = None) -> List[Dict]:
    """The rule that picks a cell's per-layer metrics: every file in
    ``benchmark/layer_metrics/`` whose ``set`` is ``any`` or among ``sets``
    (the ``metric_sets`` of the cell's traffic file), whose ``moves`` metric
    the cell's traffic reports, and whose ``min_chips`` (default 1) the cell
    has. The binding is on the cell's side, so a new cell joins a shared
    metric from files of its own."""
    if metrics is None:
        metrics = [load_json(p) for p in sorted(
            glob.glob(os.path.join(HERE, "layer_metrics", "*.json")))]
    return [m for m in metrics
            if (m["set"] == "any" or m["set"] in sets)
            and m["moves"] in reports and chips >= m.get("min_chips", 1)]


class Run:
    """One run of one cell. Drivers get this and nothing else."""

    def __init__(self, args, cell: Dict, config: Dict, traffic: Dict,
                 units: Dict[str, str], t_process: float):
        self.workload = cell["name"]
        self.chips = int(cell["chips"])
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.traced = bool(args.trace)
        self.rehearse = bool(args.rehearse)
        if self.rehearse:
            config = merged(config, {
                "gpt_config": {k: v for k, v in config["rehearsal"].items()
                               if k in config["gpt_config"]},
                "source_vocab_size":
                    config["rehearsal"]["source_vocab_size"],
                "assumed": {k: v for k, v in config["rehearsal"].items()
                            if k == "serve"}})
            traffic = merged(traffic, traffic.get("rehearsal", {}))
        self.config, self.traffic, self.units = config, traffic, units
        self.t_process = t_process
        self.setup_s: Optional[float] = None
        self.compiles = 0
        self._armed = False
        self._trace_dir: Optional[str] = None
        self._win = None
        self.reduced = None
        self.device: Dict = {}

    # -- device ---------------------------------------------------------
    def claim_device(self) -> Dict:
        """Import jax, refuse anything but the chips the cell asks for
        (a rehearsal takes what it finds and says so), place the compile
        cache inside the checkout. Returns the device record."""
        import jax

        from byteps_tpu.common.compile_cache import enable_compile_cache

        devs = jax.devices()
        self.device = {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs)}
        if not self.rehearse and self.device["platform"] != "tpu":
            raise SystemExit(
                f"benchmark: cell {self.workload!r} needs a TPU, jax found "
                f"{self.device}; --rehearse runs tiny sizes on what is here")
        if len(devs) < self.chips:
            raise SystemExit(
                f"benchmark: cell {self.workload!r} needs {self.chips} "
                f"chip(s), jax found {len(devs)}")
        self.cache_dir = enable_compile_cache()
        # every program, however quick to compile, comes from the cache
        # after a checkout's first run: set-up is then the same every run
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        return self.device

    def _on_duration(self, event: str, secs: float, **kw) -> None:
        if self._armed and event == COMPILE_EVENT:
            self.compiles += 1

    def gpt_config(self):
        """The configuration file's ``gpt_config`` as the program's
        dataclass."""
        import jax.numpy as jnp

        from byteps_tpu.models import GPTConfig

        kw = dict(self.config["gpt_config"])
        kw["dtype"] = jnp.dtype(kw["dtype"]).type
        return GPTConfig(**kw)

    # -- the measured window ----------------------------------------------
    def open_window(self) -> float:
        """Set-up ends here; programs compiled or loaded from now on are
        counted. Returns the host clock."""
        now = time.monotonic()
        self.setup_s = now - self.t_process
        self._armed = True
        return now

    def close_window(self) -> None:
        self._armed = False
        self.stop_trace()

    def span(self, name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)

    def trace_due(self, t0: float, now: float) -> bool:
        """True once, when a traced run reaches the last
        ``trace_window_s`` of its window."""
        if not self.traced or self._trace_dir is not None:
            return False
        return now - t0 >= self.seconds - min(
            self.seconds, float(self.traffic["trace_window_s"]))

    def start_trace(self) -> None:
        import jax

        from benchmark import trace_reduce

        self._trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(self._trace_dir)
        self._win = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
        self._win.__enter__()

    def stop_trace(self) -> None:
        import jax

        if self._win is None:
            return
        self._win.__exit__(None, None, None)
        self._win = None
        jax.profiler.stop_trace()

    def reduce_trace(self, span_names) -> None:
        from benchmark import trace_reduce

        if self._trace_dir is None:
            return
        try:
            trace = trace_reduce.load(
                trace_reduce.find_xplane(self._trace_dir), span_names)
            keep = os.environ.get("BENCH_KEEP_TRACE")
            if keep:                     # a builder's own look at a trace
                os.makedirs(os.path.dirname(keep) or ".", exist_ok=True)
                with open(keep, "w") as f:
                    json.dump(trace, f)
            self.reduced = trace_reduce.Reduced(trace, self.chips)
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)

    def memory_peak_bytes(self) -> Optional[int]:
        import jax

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.devices()]
        peaks = [p for p in peaks if p is not None]
        return max(peaks) if peaks else None

    # -- the result line --------------------------------------------------
    def result_line(self, observed: Dict) -> Dict:
        """The one JSON object of a run. ``observed`` is the driver's:
        ``correct``, ``attempted``, ``failed``, ``end_to_end`` (name ->
        value) and whatever its readers read (``notes``, for a reader of the
        log, is printed on a line of its own before this one)."""
        device = dict(self.device,
                      memory_peak_bytes=observed.get("memory_peak_bytes"))
        metrics: Dict[str, Dict] = {}
        line = {"correct": bool(observed["correct"]),
                "attempted": int(observed["attempted"]),
                "failed": int(observed["failed"])}
        if self.traced:
            if self.reduced is not None:
                device["busy_s"] = self.reduced.busy_s
                device["window_s"] = self.reduced.window_s
                line["breakdown"] = self.reduced.breakdown()
            observed = dict(observed, compiles=self.compiles)
            for m in layer_metrics_for(self.traffic["metric_sets"],
                                       self.traffic["reports"], self.chips):
                reader = importlib.import_module(
                    f"benchmark.readers.{m['reader']}")
                value = reader.read(self, observed, **m.get("params", {}))
                if value is not None:
                    metrics[m["name"]] = {"value": float(value),
                                          "unit": m["unit"]}
        else:
            values = dict(observed["end_to_end"], setup_s=self.setup_s)
            for name in self.traffic["reports"]:
                metrics[name] = {"value": float(values[name]),
                                 "unit": self.units[name]}
        if self.rehearse:
            # a rehearsal's numbers are counts and correctness only: none
            # is printed under the name of a device metric
            line["rehearsal"] = {"device": device, "would_report":
                                 sorted(metrics)}
            metrics, device = {}, dict(device, rehearsal=True)
        line.update(metrics=metrics, device=device)
        return line
