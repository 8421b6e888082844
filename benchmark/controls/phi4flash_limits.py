#!/usr/bin/env python3
"""The second readings of ``phi4flash-serve-reasoning-sat``'s limits, and
several seeds of the cell in one process.

    python3 benchmark/controls/phi4flash_limits.py --seeds 11,12 --seconds 50 \\
        --controls state_round=bfloat16 m_after_gate=true \\
        lambda_depth_shift=1 sub_norm=false window_keys=511 window_keys=513 \\
        cross_own_kv=true stale_full_kv=last_chunk rope_base=10000.0 \\
        tail_shift=1

Each seed is one run of the cell as ``benchmark/run.py`` makes it (the same
``Run``, the same driver: weights, scheduler, traffic and window anew; only
the compiled programs are shared, so ``setup_s`` means something for the
first seed alone) and prints the same two lines. After the first seed's
comparison, the comparison is made again for each ``--controls`` entry with
that key laid over the reference's ``hp``: **the reference computed with its
recurrent state kept in bf16, the GMUs' memory taken AFTER the gate,
``lambda_init`` of the layer above, the sub-norm left out, a 511- or 513-key
window, the cross layers attending over zeroed k/v of their own, layer 17's
rows of every chunk but the prompt's last made from a stale x, or a rotary
applied, against what the timed programs served and left in their slots and
pages** (``tail_shift=1``: the slot's convolution tail held to the
reference's one token early) — what a program at fault by that much would
read, through the code that decides ``correct``. A control's line names the
limits it breaks; one that breaks none shows a limit that holds nothing.
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
from benchmark.controls.falconh1_limits import _value   # noqa: E402

CELL = "phi4flash-serve-reasoning-sat"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--controls", nargs="*", default=[])
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    bm = harness.load_json(ROOT, "BENCHMARK.json")
    cell = {w["name"]: w for w in bm["workloads"]}[CELL]
    entry = {c["name"]: c for c in bm["configs"]}[cell["config"]]
    config = harness.load_json(ROOT, entry["file"])
    traffic = harness.load_json(harness.HERE, "traffic",
                                cell["traffic"] + ".json")
    units = {m["name"]: m["unit"] for m in bm["end_to_end"]}
    from benchmark.drivers import serve_phi4flash as driver

    t0 = T_PROCESS
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        args.seed = seed
        run = harness.Run(args, cell, config, traffic, units, t0)
        run.claim_device()
        st = driver.serve(run)
        observed = driver.run(run, st)
        line = dict(run.result_line(observed), seed=seed)
        print(json.dumps({"seed": seed, "notes": observed["notes"]}),
              flush=True)
        print(json.dumps(line), flush=True)
        for text in args.controls if i == 0 else ():
            key, _, value = text.partition("=")
            shift = int(value) if key == "tail_shift" else 0
            chk = driver.check(run, st, long_only=True, tail_shift=shift,
                               over=None if shift else {key: _value(value)})
            print(json.dumps({"control": text, "seed": seed,
                              "not_correct_by": driver.over_limit(chk),
                              **chk}), flush=True)
        del st, observed
        t0 = time.monotonic()
    return 0


if __name__ == "__main__":
    sys.exit(main())
