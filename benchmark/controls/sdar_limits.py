#!/usr/bin/env python3
"""The second readings of ``sdar-serve-blockgen-sat``'s limits, and several
seeds of the cell in one process.

    python3 benchmark/controls/sdar_limits.py --seeds 11,12 --seconds 50 \\
        --controls causal=true stale_pass=true wrong_order=true \\
        router_dtype=bfloat16 cache_round=float8_e4m3fn

Each seed is one run of the cell as ``benchmark/run.py`` makes it (the same
``Run``, the same driver: weights, scheduler, traffic and window anew; only
the compiled programs are shared, so ``setup_s`` means something for the
first seed alone) and prints the same two lines. After the first seed's
comparison, the comparison is made again for each ``--controls`` entry with
that key laid over the reference's ``hp``: **the reference computed under a
causal mask (``causal=true``), with each block's rows as its last denoising
pass left them (``stale_pass=true``), the record read as if every block's
positions had been fixed in the reverse order (``wrong_order=true``), with a
bf16 router
(``router_dtype=bfloat16``), every product in bf16
(``compute_dtype=bfloat16``) or a cache in a narrower type
(``cache_round=float8_e4m3fn``), against what the timed programs served** —
what a program at fault by that much would read, through the code that decides
``correct``. A control's line names the limits it
breaks; one that breaks none shows a limit that holds nothing.
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

CELL = "sdar-serve-blockgen-sat"


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
    from benchmark.drivers import serve_sdar as driver

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
            # (the order of fixing shows only in a request that asked more
            # than one pass a block: both checked requests then)
            chk = driver.check(run, st, over={key: _value(value)},
                               long_only=key != "wrong_order")
            print(json.dumps({"control": text, "seed": seed,
                              "not_correct_by": driver.over_limit(chk),
                              **chk}), flush=True)
        del st, observed
        t0 = time.monotonic()
    return 0


if __name__ == "__main__":
    sys.exit(main())
