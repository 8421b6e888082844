"""chip_smoke.py, rehearsed: the whole script end to end on CPU at
GPTConfig.tiny() sizes (every phase, every check except the platform's),
and its refusal to pass without a TPU. The real run is on the chip, through
the chip tool; this keeps the script from rotting between such runs."""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(*flags, xla_flags=""):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": xla_flags}
    return subprocess.run([sys.executable, SMOKE, *flags], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=600)


def _records(stdout):
    return [json.loads(l) for l in stdout.splitlines() if l.startswith("{")]


def test_rehearsal_runs_every_phase_and_never_says_ok():
    r = _run("--rehearse")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    recs = _records(r.stdout)
    assert not any(rec.get("ok") for rec in recs)
    assert '"ok"' not in r.stdout
    done = [rec["phase"] for rec in recs if rec.get("done")]
    assert done == ["train", "trace", "serve", "hybrid"]
    assert recs[-1]["claim"] is None and recs[-1]["rehearse"] is True
    serve = [rec for rec in recs if "token_equal_requests" in rec][0]
    # on CPU both sides run the jnp attention: the tier's bit-equality pin
    assert serve["token_equal_requests"] == 8, serve


def test_rehearsal_of_the_four_chip_phase_on_virtual_devices():
    r = _run("--rehearse", "--chips", "4",
             xla_flags="--xla_force_host_platform_device_count=4")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    recs = _records(r.stdout)
    assert '"ok"' not in r.stdout
    assert [rec["phase"] for rec in recs if rec.get("done")] == ["dp4"]
    assert recs[0]["device"]["count"] == 4


def test_without_a_tpu_it_fails_and_names_the_platform_it_found():
    r = _run()
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "jax found platform 'cpu'" in r.stderr, r.stderr[-2000:]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)     # importing runs nothing: __main__ guard
    return mod


def test_parting_finds_the_first_split_and_its_logit_gaps(smoke):
    """The serve check's comparison, on the branch a CPU run never takes:
    tokens that part are judged by the reference logits at the FIRST
    differing position only."""
    solo = np.array([5, 6, 7, 1, 2, 3], np.int32)
    assert smoke.parting(solo.copy(), solo, None) is None
    served = np.array([5, 6, 7, 4, 9, 9], np.int32)
    seen = []

    def logits(context):
        seen.append(list(context))
        lg = np.zeros(10, np.float32)
        lg[1], lg[4] = 2.0, 1.75       # solo's token best, served's close
        return lg

    got = smoke.parting(served, solo, logits)
    assert seen == [[5, 6, 7]]
    assert got == dict(position=3, served=4, solo=1,
                       logit_gaps=[0.25, 0.0])
    with pytest.raises(SystemExit):
        smoke.parting(served[:4], solo, logits)
