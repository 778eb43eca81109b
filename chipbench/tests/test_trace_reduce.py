"""The trace reduction on a synthetic trace and on one recorded on the chip."""

import json
import os

import numpy as np
import pytest
from jax.profiler import ProfileData

import trace_reduce
import work

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def plane(pid, name, line, events, names):
    evs = "\n".join(f"events {{ metadata_id: {m} offset_ps: {int(s * 1e9)} "
                    f"duration_ps: {int(d * 1e9)} }}" for m, s, d in events)
    meta = "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                     for i, n in enumerate(names, 1))
    return (f'planes {{ id: {pid} name: "{name}" lines {{ id: 1 name: "{line}" '
            f"timestamp_ns: 0 {evs} }} {meta} }}")


def synthetic():
    """A 10 ms window; host spans; two chips, with an op nested in another
    on chip 0 and ops that stick out of the window on chip 1."""
    host = plane(1, "/host:CPU", "python",
                 [(1, 0, 10), (2, 1, 3), (3, 6, 1)],
                 ["chipbench.window", "chipbench.stream_next", "chipbench.aggregate"])
    chip0 = plane(2, "/device:TPU:0", "XLA Ops", [(1, 2, 3), (2, 3, 1), (1, 8, 1)],
                  ["fusion.1", "scatter.2"])
    chip1 = plane(3, "/device:TPU:1", "XLA Ops", [(1, -1, 2), (2, 9, 3)],
                  ["fusion.1", "all-to-all.3"])
    return ProfileData.from_text_proto("\n".join([host, chip0, chip1]))


def test_union_of_overlapping_intervals():
    assert trace_reduce._union([(3, 5), (0, 1), (4, 6), (1, 2)]) == [(0, 2), (3, 6)]


def test_synthetic_trace():
    red = trace_reduce.reduce_trace(synthetic())
    assert red["window_s"] == pytest.approx(0.010)
    assert red["chips"] == 2
    # chip 0: [2, 5) and [8, 9) ms; chip 1: clipped to [0, 1) and [9, 10)
    assert red["busy_s"] == pytest.approx([0.004, 0.002])
    ops = dict(red["device_ops"])
    # self times: fusion.1 on chip 0 is 3 ms less the 1 ms nested in it
    assert ops["fusion.1"] == pytest.approx((0.002 + 0.001 + 0.001) / 2)
    assert ops["scatter.2"] == pytest.approx(0.001 / 2)
    assert ops["all-to-all.3"] == pytest.approx(0.001 / 2)
    gaps = red["idle_gaps"]
    assert gaps[0] == ["-", pytest.approx(0.008)]  # chip 1 [1, 9), middle 5 ms
    assert ["chipbench.stream_next", pytest.approx(0.002)] in gaps  # chip 0 [0, 2)
    assert ["chipbench.aggregate", pytest.approx(0.003)] in gaps  # chip 0 [5, 8)
    assert ["-", pytest.approx(0.001)] in gaps  # chip 0 [9, 10)


def recorded():
    path = os.path.join(DATA, "recorded.json")
    if not os.path.exists(path):
        pytest.skip("no recorded chip trace")
    with open(path) as f:
        meta = json.load(f)
    return meta, ProfileData.from_file(os.path.join(DATA, meta["trace"]))


def test_recorded_idle_share_is_the_union_of_op_intervals():
    meta, profile = recorded()
    red = trace_reduce.reduce_trace(profile)
    lo, hi = [(s, e) for n, s, e in trace_reduce.host_spans(profile)
              if n == trace_reduce.WINDOW_SPAN][-1]
    for (name, ops), busy in zip(sorted(trace_reduce.device_ops(profile).items()),
                                 red["busy_s"]):
        # a microsecond timeline of the window, every op marked on it
        line = np.zeros(int((hi - lo) / 1e3) + 1, bool)
        for _, s, e in ops:
            a, b = max(s, lo), min(e, hi)
            if b > a:
                line[int((a - lo) / 1e3):int(np.ceil((b - lo) / 1e3))] = True
        assert busy == pytest.approx(line.sum() / 1e6, rel=0.01, abs=1e-4)
    idle = 100 * (1 - sum(red["busy_s"]) / len(red["busy_s"]) / red["window_s"])
    assert idle == pytest.approx(meta["device_idle_share"], rel=1e-6)


def test_recorded_roofline_is_at_most_100():
    meta, profile = recorded()
    red = trace_reduce.reduce_trace(profile)
    least, _ = work.least_seconds(*work.coloring_work(
        meta["template_edges"], meta["k"], meta["n"], meta["directed_edges"]),
        meta["peak"], red["chips"])
    busy = sum(red["busy_s"]) / len(red["busy_s"])
    share = 100 * least / (busy / meta["colorings"])
    assert 0 < share <= 100
    assert share == pytest.approx(meta["coloring_roofline"], rel=1e-6)
