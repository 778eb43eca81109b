"""Shared benchmark helpers: timing, CSV emission, subprocess workers."""

from __future__ import annotations

import os
import subprocess
import sys
import time
from typing import Callable

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def time_fn(fn: Callable, *args, warmup: int = 1, iters: int = 3) -> float:
    """Median wall-clock seconds per call (after jit warmup)."""
    for _ in range(warmup):
        out = fn(*args)
        _block(out)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        _block(out)
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def _block(out):
    import jax

    for leaf in jax.tree.leaves(out):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()


def emit(name: str, us_per_call: float, derived: str = ""):
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def run_worker(module: str, args: list, devices: int = 8, timeout: int = 1200) -> str:
    """Run a benchmark worker in a subprocess with N host devices.

    Workers are host virtual-device runs by design: they are pinned to the
    CPU backend and never contend with the parent for an accelerator."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    proc = subprocess.run(
        [sys.executable, "-m", module, *map(str, args)],
        capture_output=True,
        text=True,
        env=env,
        timeout=timeout,
        cwd=ROOT,
    )
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr[-3000:])
        raise RuntimeError(f"worker {module} failed")
    return proc.stdout
