"""A whole run at a small size on the CPU, sound and with the timed path
broken underneath: ``correct`` has to come out false for each fault."""

import itertools
import os
import subprocess
import sys

import numpy as np
import pytest

import run
from repro.api import Counter

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2**33 + 12345


def small_spec(cell="g500s19-u7-2"):
    spec = run.load_cell(cell)
    spec["config"]["graph"]["scale"] = 10
    spec["traffic"]["batch_cap"] = 2
    spec["traffic"]["check_colorings"] = 8
    return spec


def stale(stream):
    """A step that returns its state unchanged: every batch is the first."""
    first = next(stream)
    return itertools.repeat(first)


def half_batch(stream):
    """Half the batch left out: its second half repeats the first half."""
    for est in stream:
        half = len(est) // 2
        yield np.concatenate([est[:half], est[:len(est) - half]])


def altered(stream):
    """An answer altered where it is produced, by a relative 1e-4."""
    for est in stream:
        yield est * (1 + 1e-4)


def run_with(monkeypatch, fault):
    if fault is not None:
        real = Counter.sample_stream

        def broken(self, key=None, *, batch=8):
            return fault(real(self, key, batch=batch))

        monkeypatch.setattr(Counter, "sample_stream", broken)
    return run.run_cell(small_spec(), SEED, 1.0, False, require_tpu=False)


def test_sound_run_is_correct(monkeypatch):
    res = run_with(monkeypatch, None)
    assert res["correct"], res
    assert res["failed"] == 0 and res["attempted"] > 8
    assert set(res["metrics"]) == {"colorings_per_s", "setup_s"}
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", [stale, half_batch, altered], ids=lambda f: f.__name__)
def test_fault_is_not_correct(monkeypatch, fault):
    res = run_with(monkeypatch, fault)
    assert not res["correct"], res
    assert res["failed"] > 0
    assert res["checks"]["rel_gap"]["value"] > res["checks"]["rel_gap"]["limit"]


def test_no_chip_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", "g500s19-u7-2",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == "" or not out.stdout.strip().splitlines()[-1].startswith("{")
