"""Run ``bench/run.py`` as the driver would, on the CPU at a tiny size.

Each run is its own process: the harness sets process-wide JAX options
(the compilation cache among them) that must not leak into other tests.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = os.path.join(ROOT, "bench", "run.py")
KEYS = 8192


def rehearse(workload: str, *extra: str, seed: int = 5, cwd: str = ROOT,
             run: str = RUN, rehearsal: bool = True):
    """(exit code, parsed last stdout line or None, stderr)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    args = [sys.executable, run, "--workload", workload, "--seed", str(seed),
            "--seconds", "0.3", "--trace", "0", *extra]
    if rehearsal:
        args += ["--rehearsal-keys", str(KEYS)]
    p = subprocess.run(args, cwd=cwd, env=env, capture_output=True,
                       text=True, timeout=300)
    lines = p.stdout.strip().splitlines()
    result = json.loads(lines[-1]) if lines else None
    return p.returncode, result, p.stderr
