"""``exchange_exposed_share`` on synthetic traces of several chips."""

import importlib

import pytest
from jax.profiler import ProfileData

import trace_reduce
from test_trace_reduce import plane

share = importlib.import_module("metrics.exchange_exposed_share")

HOST = plane(1, "/host:CPU", "python", [(1, 0, 10)], ["chipbench.window"])


def chip(pid, events):
    """A chip whose ops are ``(name, start_ms, duration_ms)``."""
    names = sorted({n for n, _, _ in events})
    ids = {n: i for i, n in enumerate(names, 1)}
    return plane(pid, f"/device:TPU:{pid - 2}", "XLA Ops",
                 [(ids[n], s, d) for n, s, d in events], names)


def read(*chips):
    profile = ProfileData.from_text_proto("\n".join([HOST] + list(chips)))
    return share.read({"trace": trace_reduce.reduce_trace(profile)})


def test_collective_under_compute_counts_nothing():
    assert read(chip(2, [("fusion.1", 0, 4), ("all-to-all.3", 1, 2)])) == pytest.approx(0.0)


def test_collective_alone_counts_its_length():
    # busy [0, 3) ms, of which [2, 3) is the all-to-all alone
    assert read(chip(2, [("fusion.1", 0, 2), ("all-to-all.3", 2, 1)])) == pytest.approx(100 / 3)


def test_partly_hidden_collective_counts_its_exposed_part():
    # the collective-permute-done waits [3, 5); compute covers [3, 4) of it
    events = [("fusion.1", 0, 4), ("collective-permute-done.2", 3, 2)]
    assert read(chip(2, events)) == pytest.approx(100 * 1 / 5)


def test_collective_inside_a_loop_body_counts():
    # the while op spans its body, and hides nothing
    events = [("while.4", 0, 6), ("fusion.1", 0, 2), ("all-reduce.5", 2, 1), ("fusion.2", 3, 3)]
    assert read(chip(2, events)) == pytest.approx(100 / 6)


def test_share_is_the_mean_over_chips():
    a = chip(2, [("fusion.1", 0, 2), ("all-to-all.3", 2, 2)])  # 2 of 4 ms
    b = chip(3, [("fusion.1", 0, 4), ("all-to-all.3", 1, 2)])  # 0 of 4 ms
    assert read(a, b) == pytest.approx((50 + 0) / 2)


def test_no_collective_reads_nothing():
    assert read(chip(2, [("fusion.1", 0, 4)]), chip(3, [("fusion.1", 0, 2)])) is None


def test_reduction_hands_on_one_number_per_chip():
    a = chip(2, [("fusion.1", 0, 2), ("all-to-all.3", 2, 2)])
    b = chip(3, [("fusion.1", 0, 4), ("all-reduce.4", 1, 2), ("all-reduce.5", 4, 1)])
    profile = ProfileData.from_text_proto("\n".join([HOST, a, b]))
    red = trace_reduce.reduce_trace(profile)
    assert red["collective_exposed_s"] == pytest.approx([0.002, 0.001])
    assert red["collective_ops"] == 3
    assert "ops" not in red

