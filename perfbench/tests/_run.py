"""Helpers of the by-hand tests: run the benchmark's command the way
the driver does, in the sandbox's rehearsal mode."""

from __future__ import annotations

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def bench(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def run_cell(workload: str, *, seed: int = 3, seconds: float = 2,
             trace: int = 0, rehearse: bool = True, fault: str = None,
             root: str = ROOT, timeout: float = 600):
    """-> (return code, parsed last stdout line or None, stdout)."""
    cmd = [sys.executable, os.path.join(root, "perfbench", "run.py"),
           "--workload", workload, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace)]
    if rehearse:
        cmd.append("--rehearse-cpu")
    if fault:
        cmd += ["--fault", fault]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(cmd, cwd=root, env=env, capture_output=True,
                          text=True, timeout=timeout)
    lines = proc.stdout.strip().splitlines()
    try:
        last = json.loads(lines[-1]) if lines else None
    except json.JSONDecodeError:
        last = None
    if not isinstance(last, dict) or "correct" not in last:
        last = None
    return proc.returncode, last, proc.stdout + proc.stderr
