"""The four-chip cell on four virtual CPU devices, in a child process
(``x4_worker.py``) so that this process keeps its one device: a sound run
reads ``correct: true`` (the harness redraws the distributed backend's
colorings and the reference reproduces the program's answers), each fault
planted in the timed path or in the rule reads ``correct: false``, and the
control fails the cell's limit while the program passes it."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
FAULTS = ["wrong_coloring_rule", "stale", "half_batch", "altered", "no_exchange"]


@pytest.fixture(scope="module")
def cases():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, os.path.join(HERE, "x4_worker.py")],
                         capture_output=True, text=True, env=env, timeout=1200)
    assert out.returncode == 0, out.stderr[-4000:]
    rows = [json.loads(x) for x in out.stdout.splitlines() if x.startswith('{"case"')]
    return {r["case"]: r for r in rows}


def test_sound_run_is_correct(cases):
    res = cases["sound"]["result"]
    assert res["correct"], res
    assert res["failed"] == 0 and res["attempted"] > 4
    assert res["device"]["count"] == 4
    assert set(res["metrics"]) == {"colorings_per_s", "setup_s"}
    assert list(res)[-1] == "checks"
    # the reference ran after the program's state was dropped from every chip
    assert cases["sound"]["live_arrays"] == 0


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_is_not_correct(cases, fault):
    res = cases[fault]["result"]
    assert not res["correct"], res
    assert res["failed"] > 0
    assert res["checks"]["rel_gap"]["value"] > res["checks"]["rel_gap"]["limit"]


def test_program_bytes_grow_with_the_batch(cases):
    # the sharded batch program is lowered with the plan arrays as arguments,
    # from the closure of the Counter's own sample_fn
    one, two = cases["program_bytes"]["bytes"]
    assert 0 < one < two
    assert all(x > 0 for x in cases["program_bytes"]["warm"])


def test_control_fails_and_program_passes(cases):
    limit = cases["control"]["limit"]
    for row in cases["control"]["rows"]:
        assert row["program_gap"] <= limit, row
        assert row["bfloat16"] > limit, row
