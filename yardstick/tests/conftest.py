"""The yardstick's own tests: CPU only, run with
``python -m pytest yardstick/tests -q`` from the checkout's root."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cell(workload: str, seed: int, seconds: float, trace: int = 0):
    """``run.py`` in a process of its own, held to the CPU. -> (exit code,
    stdout lines, the last line parsed or None)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "yardstick", "run.py"),
         "--workload", workload, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    lines = proc.stdout.strip().splitlines()
    last = None
    if proc.returncode == 0 and lines:
        last = json.loads(lines[-1])
    return proc.returncode, lines, last
