#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result, one JSON object. Everything
a cell is made of is found by name (benchmark/README.md). ``--rehearse`` is
the benchmark's own switch for a run on whatever device is here, at the tiny
sizes the data files give under ``rehearsal``: its line names the device and
carries counts and correctness only, never a device metric.
"""

import time

T_PROCESS = time.monotonic()        # set-up is timed from here

import argparse                     # noqa: E402
import importlib                    # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import sys                          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness       # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    bm = harness.load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bm["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"benchmark: no cell {args.workload!r} "
                         f"(cells: {sorted(cells)})")
    cell = cells[args.workload]
    cfg_entry = {c["name"]: c for c in bm["configs"]}[cell["config"]]
    config = harness.load_json(ROOT, cfg_entry["file"])
    traffic = harness.load_json(harness.HERE, "traffic",
                                cell["traffic"] + ".json")
    units = {m["name"]: m["unit"] for m in bm["end_to_end"]}
    run = harness.Run(args, cell, config, traffic, units, T_PROCESS)
    driver = importlib.import_module(
        f"benchmark.drivers.{traffic['driver']}")
    run.claim_device()
    observed = driver.run(run)
    line = run.result_line(observed)
    print(json.dumps({"notes": observed.get("notes", {})}), flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
