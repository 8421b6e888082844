"""Tracing through the env-var interface ONLY (SURVEY §5.1 — the fork's
raison d'être): BYTEPS_TRACE_ON=1 with no code changes must produce worker
stage events, server PUSH_RECV/SUM/PULL_RESP rows, and a merged aligned
timeline."""

import json
import os
import subprocess
import sys
import pytest

pytestmark = pytest.mark.slow  # subprocess/integration tier

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HELPER = os.path.join(REPO, "tests", "helpers", "hybrid_worker.py")
MNIST = os.path.join(REPO, "examples", "jax", "train_mnist_jax.py")
PORT = 19900


def test_hybrid_traces_and_merge(tmp_path):
    trace_dir = str(tmp_path)
    env_base = {
        **os.environ,
        "PYTHONPATH": REPO,
        "DMLC_NUM_WORKER": "2",
        "DMLC_NUM_SERVER": "1",
        "DMLC_PS_ROOT_URI": "127.0.0.1",
        "DMLC_PS_ROOT_PORT": str(PORT),
        "BYTEPS_PARTITION_BYTES": "65536",
        "BYTEPS_TRACE_ON": "1",
        "BYTEPS_TRACE_DIR": trace_dir,
    }
    server = subprocess.Popen(
        [sys.executable, "-m", "byteps_tpu.launcher"],
        env={**env_base, "DMLC_ROLE": "server", "JAX_PLATFORMS": "cpu"},
        cwd=REPO,
    )
    workers = []
    try:
        for wid in range(2):
            workers.append(subprocess.Popen(
                [sys.executable, HELPER],
                env={**env_base, "DMLC_ROLE": "worker",
                     "DMLC_WORKER_ID": str(wid)},
                cwd=REPO, stdout=subprocess.PIPE, text=True,
            ))
        for w in workers:
            out, _ = w.communicate(timeout=180)
            assert w.returncode == 0, out
        server.wait(timeout=30)
        assert server.returncode == 0
    finally:
        for p in workers + [server]:
            if p.poll() is None:
                p.kill()

    # worker trace: non-empty, hybrid pipeline stages present, offset probed
    wpath = os.path.join(trace_dir, "trace_rank0.json")
    assert os.path.exists(wpath), os.listdir(trace_dir)
    wdoc = json.load(open(wpath))
    wstages = {e["tid"] for e in wdoc["traceEvents"]}
    assert {"REDUCE", "PUSH", "PULL"} <= wstages, wstages
    assert "0" in wdoc["metadata"]["server_clock_offsets"]

    # server trace: the fork's server-side timestamps
    spath = os.path.join(trace_dir, "trace_server0.json")
    assert os.path.exists(spath), os.listdir(trace_dir)
    sdoc = json.load(open(spath))
    sstages = {e["tid"] for e in sdoc["traceEvents"]}
    assert {"PUSH_RECV", "SUM", "PULL_RESP"} <= sstages, sstages

    # merged, aligned timeline through the CLI
    merged = os.path.join(trace_dir, "merged.json")
    r = subprocess.run(
        [sys.executable, "-m", "byteps_tpu.common.tracing", merged,
         wpath, os.path.join(trace_dir, "trace_rank1.json"), spath],
        cwd=REPO, env={**os.environ, "PYTHONPATH": REPO},
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 0, r.stderr
    mdoc = json.load(open(merged))
    pids = {e["pid"] for e in mdoc["traceEvents"]}
    assert 0 in pids and 1 in pids and 10000 in pids, pids
    # worker and server events interleave on one clock: the server's rows
    # must fall within the workers' [first, last] window (same host here)
    wts = [e["ts"] for e in wdoc["traceEvents"]]
    sts = [e["ts"] for e in sdoc["traceEvents"]]
    assert min(wts) - 5e6 < min(sts) < max(wts) + 5e6


def test_mnist_example_fused_trace(tmp_path):
    """BYTEPS_TRACE_ON=1 on the unmodified MNIST example (fused path)
    writes a non-empty trace with per-step dispatch markers."""
    env = {
        **os.environ,
        "PYTHONPATH": REPO,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "BYTEPS_TRACE_ON": "1",
        "BYTEPS_TRACE_DIR": str(tmp_path),
    }
    r = subprocess.run(
        [sys.executable, MNIST, "--steps", "5"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    path = os.path.join(str(tmp_path), "trace_rank0.json")
    assert os.path.exists(path), os.listdir(str(tmp_path))
    doc = json.load(open(path))
    fused = [e for e in doc["traceEvents"] if e["tid"] == "FUSED_PUSHPULL"]
    assert len(fused) >= 4, doc["traceEvents"][:5]
    steps = {e["name"] for e in fused}
    assert "step2" in steps, steps
    # the raw path says what it aggregated: whole-leaf buckets (PR 49)
    for e in fused:
        assert {"total_elems", "chunks", "buckets", "bucket_bytes_max",
                "chained"} <= set(e["args"]), e
        assert e["args"]["buckets"] >= 1
        assert e["args"]["chained"] == int(e["args"]["buckets"] > 1)