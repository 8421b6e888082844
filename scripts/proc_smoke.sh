#!/usr/bin/env bash
# Real-process chaos smoke (ISSUE 20): 1 summation server + 2 supervised
# --child-worker OS processes; SIGKILL one mid-run and assert the
# survivor still completes every round (the membership lease evicts the
# dead id and re-targets the stalled round) AND that the supervisor
# leaks zero child processes afterwards. One command, fast enough to
# run after any launcher / server membership change.
#
# Exit codes: 0 = survivor completed + no leaked children,
# anything else = a real robustness regression.
set -u
cd "$(dirname "$0")/.."

OUT_DIR="$(mktemp -d "${TMPDIR:-/tmp}/bps_proc_smoke.XXXXXX")"
trap 'rm -rf "$OUT_DIR"' EXIT

timeout 300 env JAX_PLATFORMS=cpu PYTHONPATH="$PWD" python - "$OUT_DIR" <<'EOF'
import os
import signal
import sys
import time

from byteps_tpu.launcher import Supervisor
from byteps_tpu.server import start_server, stop_server

out_dir = sys.argv[1]
port = 24750
rounds = 8
start_server(port=port, num_workers=2, engine_threads=4,
             async_mode=False, lease_ms=800)
sup = Supervisor(base_env={
    "PYTHONPATH": os.getcwd(), "JAX_PLATFORMS": "cpu",
    "BYTEPS_CHILD_SERVERS": f"127.0.0.1:{port}",
    "BYTEPS_CHILD_ROUNDS": str(rounds),
    "BYTEPS_CHILD_ELEMS": "4096",
    "BYTEPS_CHILD_ROUND_DELAY_MS": "100",
    # Heartbeat well under lease_ms: a survivor blocked in pull on the
    # victim's stalled round makes no other server contact, and without
    # pings its OWN lease would expire too (double eviction).
    "BYTEPS_HEALTH_INTERVAL_MS": "100",
})
pids = []
try:
    for w in range(2):
        sup.spawn(w, extra_env={
            "BYTEPS_CHILD_OUT": os.path.join(out_dir, f"w{w}.json")})
        pids.append(sup.child(w).pid)
    # let the victim make real progress, then kill the PROCESS
    prog = os.path.join(out_dir, "w1.json.progress")
    deadline = time.time() + 60
    while time.time() < deadline:
        sup.poll()
        if os.path.exists(prog) and len(open(prog).read().splitlines()) > 2:
            break
        time.sleep(0.05)
    else:
        sys.exit("victim never made progress")
    sup.kill(1, signal.SIGKILL)
    if not sup.wait_all(timeout_s=120):
        sys.exit("children did not drain")
finally:
    sup.shutdown()
    stop_server()
assert sup.exit_reasons[1] == ["signal:SIGKILL"], sup.exit_reasons
assert sup.exit_reasons[0] == ["clean"], sup.exit_reasons
surv = os.path.join(out_dir, "w0.json")
assert os.path.exists(surv), "survivor wrote no result"
import json
n = len(json.load(open(surv))["rounds"])
assert n == rounds, f"survivor completed {n}/{rounds} rounds"
# zero leaked children: every spawned pid must be gone
for pid in pids:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        continue
    sys.exit(f"leaked child process pid={pid}")
print(f"proc_smoke: survivor completed {n}/{rounds} rounds after "
      "sibling SIGKILL; zero leaked children")
EOF
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "proc_smoke: FAILED (rc=$rc)" >&2
fi
exit "$rc"
