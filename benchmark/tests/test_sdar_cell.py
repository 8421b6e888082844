"""The SDAR cell rehearsed end to end on whatever device is here, at the tiny
sizes the data files give: the run is ``correct`` under the tight rehearsal
limits (f32: the served rows, logits and confidences are the reference's), and
its line carries no device metric."""

import json
import os
import subprocess
import sys

from benchmark import harness


def test_a_rehearsal_of_the_cell_is_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"), "--workload",
         "sdar-serve-blockgen-sat", "--seed", "2147483659", "--seconds", "3",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, env=env, check=True)
    notes, line = (json.loads(x) for x in out.stdout.splitlines()[-2:])
    assert line["correct"] is True, notes["notes"]["not_correct_by"]
    assert line["metrics"] == {} and line["device"]["rehearsal"] is True
    n = notes["notes"]
    assert n["long_prompt_checked"] and n["preempted"] == 0
    assert n["tokens_counted"] == n["tokens_accounted"]
    assert n["decode_steps_overlapped"] >= n["decode_steps_in_window"] - 1
    want = {"sd_passes_per_block_mean", "sd_block_gap_mean_ms",
            "sd_blocks_committed_per_s", "sd_blocks_in_use_mean",
            "serve_moe_pairs_per_program", "compiles_in_window"}
    assert want <= set(line["rehearsal"]["would_report"])
