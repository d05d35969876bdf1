"""TestApp harness integration tests (SURVEY.md §4: multi-instance behavior
is exercised by multiple client processes on localhost sharing one store).

These spawn real subprocesses — the completed version of the reference's
Orleans-localhost multi-silo trick (TestApp/Program.cs:37-104)."""

import json
import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TESTAPP = os.path.join(REPO_ROOT, "examples", "testapp.py")


def _run(args, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, TESTAPP, *args],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True,
        timeout=timeout,
    )


def test_single_process_smoke():
    proc = _run(["single", "--seconds", "1.5"], timeout=60)
    assert proc.returncode == 0, proc.stderr
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    # Burst capacity admits immediately; refill adds ~15 more over 1.5s.
    assert report["granted"] >= 100
    assert report["syncs"] > 0


def test_bulk_demo():
    proc = _run(["bulk", "--n", "5000", "--keys", "2000"], timeout=120)
    assert proc.returncode == 0, proc.stderr
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    # Fresh buckets/windows with limit 100 and ≤ ~3 hits per key: all grant.
    assert report["bucket_granted"] == 5000
    assert report["window_granted"] == 5000
    assert report["bucket_decisions_per_sec"] > 0


def test_cluster_demo():
    proc = _run(["cluster", "--nodes", "3", "--n", "300"], timeout=120)
    assert proc.returncode == 0, proc.stderr
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert report["granted_all_nodes_up"] == 300
    assert sum(report["key_spread"]) == 300
    after = report["after_node0_killed"]
    # Node 0's keys deny, every live node's key still grants.
    assert after["granted"] == 300 - report["key_spread"][0]
    assert after["live_node_grants"] == after["granted"]


def test_multi_process_convergence():
    proc = _run(["convergence", "--instances", "2", "--seconds", "5"],
                timeout=120)
    assert proc.returncode == 0, proc.stderr
    summary = json.loads(proc.stdout.strip().splitlines()[-1])
    assert summary["converged"], summary
    assert len(summary["per_worker"]) == 2
    # Every instance actually served traffic against the shared store.
    assert all(r["granted"] > 0 for r in summary["per_worker"])
    assert summary["steady_state_granted"] <= summary["steady_state_bound"]


def test_multi_process_convergence_device_backend():
    """The PRODUCTION topology end to end: N OS worker processes → TCP →
    a server fronting the device-resident store (kernel launches decide the
    sync traffic). Device here is jax's platform in the child env (CPU in
    CI, TPU on the chip) — same code path either way."""
    proc = _run(["convergence", "--instances", "2", "--seconds", "6",
                 "--backend", "device"], timeout=240)
    assert proc.returncode == 0, proc.stderr
    summary = json.loads(proc.stdout.strip().splitlines()[-1])
    assert summary["converged"], summary
    assert all(r["granted"] > 0 for r in summary["per_worker"])
    # The workers' shares really came from the shared device store: each
    # instance saw the other (estimate > 1 means syncs flowed both ways).
    assert any(r["instance_count_estimate"] >= 2
               for r in summary["per_worker"])
