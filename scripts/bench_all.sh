#!/usr/bin/env bash
# Run every BASELINE-named bench config on the current device and collect
# the JSON lines. On a healthy single TPU chip this produces the four
# single-chip workloads (flagship GPT, ResNet-50, BERT+onebit,
# GPT-2-medium+topk) plus the DCN tier and its component profile; each
# line carries MFU/calibration/linearity accountability fields
# (absolute_trusted=false + warnings when the numbers are physically
# impossible — see docs/performance.md).
#
# Usage: scripts/bench_all.sh [outfile]
set -u
cd "$(dirname "$0")/.."
OUT="${1:-bench_all.jsonl}"
: > "$OUT"

run() {
  echo "== bench $* ==" >&2
  timeout 1800 python bench.py "$@" 2>&2 | tail -1 >> "$OUT"
}

# Trend-relevant legs rewrite the BENCH_*.json artifacts the gate reads:
# a leg that crashes or times out leaves the CHECKED-IN artifact behind,
# and gating against it would pass a real regression (fail-open). Track
# their exit codes and refuse to run the gate on stale artifacts.
TREND_LEGS_RC=0
run_trend_leg() {
  echo "== bench $* ==" >&2
  timeout 1800 python bench.py "$@" 2>&2 | tail -1 >> "$OUT"
  local rc=${PIPESTATUS[0]}
  if [ "$rc" -ne 0 ]; then
    echo "trend-relevant leg '$*' failed (rc=$rc) — its artifact is stale" >&2
    TREND_LEGS_RC=1
  fi
}

run                                      # flagship GPT (or all-reduce if >1 dev)
run --model resnet50                     # BASELINE config 2
run --model bert --compressor onebit     # BASELINE config 3
run --model gpt2m --compressor topk      # BASELINE config 4
run --model gpt2m                        # MFU-honest large config (uncompressed)
run --model vit                          # beyond-reference families
run --model t5
run --model moe                          # Switch-MoE routing overhead vs dense
run --ce dense                           # flagship w/o fused CE (A/B attribution)
run --mode generate                      # KV-cache decode vs full recompute (+BENCH_generate.json)
run_trend_leg --mode serve               # continuous-batching serve vs sequential + shared-prefix TTFT race + disaggregated-vs-colocated race + migrate-don't-evict + multi-tenant LoRA race/flood (+BENCH_serve.json; floors: value, prefix_ttft_p50_speedup, disagg_ttft_p99_speedup, migrate_recompute_saved, multitenant_goodput_speedup, multitenant_fairness)
run --mode dcn                           # DCN summation tier
run --mode dcn-profile                   # host component ceilings
run_trend_leg --mode throttled           # compression race on emulated slow DCN (+BENCH_throttled.json)
run_trend_leg --mode whatif              # trace-driven what-if simulator: replay one recorded leg, predict the sweep; floor: prediction accuracy (+BENCH_whatif.json)
run --mode tune                          # joint (partition, credit) auto-tune incl. the sim-proposed race
run_trend_leg --mode chaos               # goodput vs fault rate incl. the bounded-staleness slow-worker leg (straggler_ratio), the scale-up churn leg: 2→4→3→5 mid-stream join/leave schedule (churn_goodput_tracking), AND the real process-death leg: supervisor SIGKILLs a live worker OS process, survivor pinned bit-identical (proc_death_goodput) (+BENCH_chaos.json)

# Real-process chaos smoke: 1 server + 2 supervised --child-worker OS
# processes, SIGKILL one mid-run; survivor must complete every round and
# the supervisor must leak zero children. Cheap (<1 min) and catches
# launcher/membership regressions the in-process legs can't.
echo "== proc_smoke ==" >&2
if ! bash scripts/proc_smoke.sh >&2; then
  echo "proc_smoke FAILED — real process-death robustness regression" >&2
  TREND_LEGS_RC=1
fi
run_trend_leg --mode hybrid              # sharded-wire hierarchical race (+BENCH_hybrid.json)
# --mode ici needs >= 4 devices and no longer re-executes itself, so this
# runbook picks the 8 virtual CPU devices for it, before the backend
# initializes (counts and bit-exactness, not a device number). On a host
# with >= 4 chips run `python bench.py --mode ici` by hand instead.
JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  run_trend_leg --mode ici               # compressed ICI tier race: staged vs ring vs native psum (+BENCH_ici.json)

# Perf-trend regression gate LAST: the legs above rewrote
# BENCH_{throttled,chaos,hybrid,serve}.json in place; compare the fresh
# headline metrics against the checked-in spread-aware floors
# (BENCH_trend.json) and FAIL the whole run on a regression. After an
# intentional trajectory change: python bench.py --mode trend --refresh
echo "== bench --mode trend ==" >&2
if [ "$TREND_LEGS_RC" -ne 0 ]; then
  echo "SKIPPING trend gate: a trend-relevant bench leg failed, its" \
       "artifact is stale — gating against it would fail OPEN" >&2
  trend_rc=1
else
  timeout 600 python bench.py --mode trend 2>&2 | tail -1 >> "$OUT"
  trend_rc=${PIPESTATUS[0]}
fi

echo "collected $(wc -l < "$OUT") results in $OUT" >&2
cat "$OUT"
if [ "$trend_rc" -ne 0 ]; then
  echo "PERF TREND REGRESSION (bench.py --mode trend exit $trend_rc)" >&2
  exit "$trend_rc"
fi
