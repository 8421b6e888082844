#!/usr/bin/env python3
"""The second readings of ``dsv32-serve-docqa-reuse-sat``'s limits, and several
seeds of the cell in one process.

    python3 benchmark/controls/dsv32_limits.py --seeds 11,12 --seconds 50 \\
        --controls cache_round=float8_e4m3fn cache_round=int8_rows \\
        softmax_mscale=false group_limit=false

Each seed is one run of the cell as ``benchmark/run.py`` makes it (the same
``Run``, the same driver: weights, scheduler, traffic and window anew; only
the compiled programs are shared, so ``setup_s`` means something for the
first seed alone) and prints the same two lines. After the first seed's
comparison, the comparison is made again for each ``--controls`` entry with
that key laid over the reference's ``hp``: **the reference computed with a
cache in a narrower type, without ``m²`` in the softmax scale, or without
the router's group limit, against what the timed programs served** (the
cache one position early is in every run's notes: ``stale_row_err``) — what
a program at fault by that much would read, through the code that decides
``correct``. A control's line names the limits it breaks; one that breaks
none shows a limit that holds nothing.
"""

import time

T_PROCESS = time.monotonic()

import argparse                     # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import sys                          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness       # noqa: E402

CELL = "dsv32-serve-docqa-reuse-sat"


def _value(text: str):
    if text in ("true", "false"):
        return text == "true"
    for kind in (int, float):
        try:
            return kind(text)
        except ValueError:
            pass
    return text


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--controls", nargs="*", default=[])
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    args.trace = 0

    bm = harness.load_json(ROOT, "BENCHMARK.json")
    cell = {w["name"]: w for w in bm["workloads"]}[CELL]
    entry = {c["name"]: c for c in bm["configs"]}[cell["config"]]
    config = harness.load_json(ROOT, entry["file"])
    traffic = harness.load_json(harness.HERE, "traffic",
                                cell["traffic"] + ".json")
    units = {m["name"]: m["unit"] for m in bm["end_to_end"]}
    from benchmark.drivers import serve_dsv32 as driver

    t0 = T_PROCESS
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        args.seed = seed
        run = harness.Run(args, cell, config, traffic, units, t0)
        run.claim_device()
        st = driver.serve(run)
        observed = driver.run(run, st)
        print(json.dumps({"seed": seed, "notes": observed["notes"]}),
              flush=True)
        print(json.dumps(dict(run.result_line(observed), seed=seed)),
              flush=True)
        for text in args.controls if i == 0 else ():
            key, _, value = text.partition("=")
            chk = driver.check(run, st, over={key: _value(value)})
            print(json.dumps({"control": text, "seed": seed,
                              "not_correct_by": driver.over_limit(chk),
                              **chk}), flush=True)
        del st, observed
        t0 = time.monotonic()
    return 0


if __name__ == "__main__":
    sys.exit(main())
