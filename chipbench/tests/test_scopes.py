"""Device seconds by program scope and idle gaps by program span, on a
synthetic trace and on a scoped trace recorded on the chip."""

import json
import os

import pytest
from jax.profiler import ProfileData

import scopes

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1e9  # picoseconds


def events(evs):
    return " ".join(f"events {{ metadata_id: {m} offset_ps: {int(s * MS)} "
                    f"duration_ps: {int((e - s) * MS)} }}" for m, s, e in evs)


def line(lid, name, evs):
    return f'lines {{ id: {lid} name: "{name}" timestamp_ns: 0 {events(evs)} }}'


def host_plane(spans):
    meta = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                    for i, (n, _, _) in enumerate(spans, 1))
    evs = [(i, s, e) for i, (_, s, e) in enumerate(spans, 1)]
    return f'planes {{ id: 1 name: "/host:CPU" {line(1, "python", evs)} {meta} }}'


def device_plane(ops, modules):
    """``ops``: (name, stats, start_ms, end_ms); ``modules``: (display name,
    start_ms, end_ms).  Stat 1 is ``tf_op``, 2 ``program_id``; stat 3 names a
    string that a ``ref_value`` of ``tf_op`` points at."""
    meta, op_evs, mod_evs = [], [], []
    for i, (name, stats, s, e) in enumerate(ops, 1):
        meta.append(f'event_metadata {{ key: {i} value {{ id: {i} name: "{name}" {stats} }} }}')
        op_evs.append((i, s, e))
    for j, (name, s, e) in enumerate(modules, len(ops) + 1):
        meta.append(f'event_metadata {{ key: {j} value {{ id: {j} display_name: "{name}" }} }}')
        mod_evs.append((j, s, e))
    stat_meta = " ".join(
        f'stat_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for i, n in [(1, "tf_op"), (2, "program_id"),
                     (3, "jit(count_batch)/vmap(node5)/combine/dot_general:")])
    return (f'planes {{ id: 2 name: "/device:TPU:0" {line(1, "XLA Modules", mod_evs)} '
            f'{line(2, "XLA Ops", op_evs)} {" ".join(meta)} {stat_meta} }}')


def tf_op(path, program):
    return f'stats {{ metadata_id: 1 str_value: "{path}" }} ' + program_id(program)


def program_id(program):
    return f"stats {{ metadata_id: 2 uint64_value: {program} }}"


def synthetic():
    """A 20 ms window of two batches on one chip, times in ms."""
    host = host_plane([
        ("chipbench.window", 0, 20),
        ("chipbench.stream_next", 0.4, 9), ("repro.stream.next_key", 0.5, 2),
        ("repro.sample.dispatch", 2, 3), ("repro.sample.wait", 3, 9),
        ("chipbench.stream_next", 10, 18), ("repro.sample.wait", 12, 18),
        ("chipbench.aggregate", 18, 18.3),
    ])
    count, split = 22, 11
    chip = device_plane([
        ("fusion.1", tf_op("jit(count_batch)/vmap(node3)/neighbor_sum/while/body/"
                           "closed_call/scatter-add:", count), 2.5, 6.5),
        ("fusion.2", tf_op("jit(count_batch)/vmap(node3)/neighbor_sum/while/body/gather:",
                           count), 3, 4),  # nested in fusion.1
        ("color_combine.1", tf_op("jit(count_batch)/vmap(node3)/combine/pallas_call:", count),
         6.5, 7),
        ("fusion.6", tf_op("jit(count_batch)/vmap(node3)/combine/mul:", count), 8.5, 8.8),
        ("fusion.3", tf_op("jit(count_batch)/node5/mask/mul:", count), 12.5, 13.5),
        ("fusion.4", "stats { metadata_id: 1 ref_value: 3 } " + program_id(count), 13.5, 17.5),
        ("fusion.5", tf_op("jit(_threefry_split)/add:", split), 1.2, 1.8),
        ("copy-done.1", program_id(count), 17.5, 17.7),
        ("fusion.9", "", 19.2, 21),  # no module known; sticks out of the window
    ], [("jit__threefry_split(11)", 1.2, 1.8), ("jit_count_batch(22)", 2.5, 8.8),
        ("jit_count_batch(22)", 12.5, 17.7)])
    return ProfileData.text_proto_to_serialized_xspace(host + "\n" + chip)


@pytest.mark.parametrize("path,scope", [
    ("jit(count_batch)/vmap(node3)/neighbor_sum/while/body/closed_call/scatter-add:",
     "node3/neighbor_sum"),
    ("jit(count_batch)/vmap(vmap(node12))/combine/pallas_call:", "node12/combine"),
    ("jit(count_batch)/node4/fused/pallas_call:", "node4/fused"),
    ("jit(count_batch)/vmap(node4)/exchange/all_to_all:", "node4"),
    ("jit(count_batch)/vmap(leaf)/eq:", "leaf"),
    ("jit(count_batch)/vmap(root)/reduce_sum:", "root"),
    ("jit(count_batch)/coloring/threefry2x32:", "coloring"),
    ("jit(<lambda>)/vmap()/while:", ""),
    ("jit(_threefry_split)/add:", ""),
])
def test_program_scope(path, scope):
    assert scopes.program_scope(path) == scope


def test_synthetic_trace():
    red = scopes.reduce_space(scopes.read_space(synthetic()))
    ms = pytest.approx
    assert red["window_s"] == ms(0.020)
    assert red["busy_s"] == [ms(0.0114)]
    assert red["batches"] == 2
    assert red["scopes"] == {
        "node3/neighbor_sum": ms(0.004),  # 4 ms, of which 1 ms nested
        "node5/combine": ms(0.004),  # through a ref_value
        "node5/mask": ms(0.001),
        "node3/combine": ms(0.0008),
        "-": ms(0.0008),  # clipped to the window
        "jit__threefry_split": ms(0.0006),  # no scope: its module
        "jit_count_batch": ms(0.0002),
    }
    assert red["kinds"]["neighbor_sum"] == ms(0.004)
    assert red["kinds"]["combine"] == ms(0.0048)
    assert red["idle"] == {
        "chipbench.stream_next": [1, ms(0.0037)],  # no program span over it
        "repro.sample.wait": [1, ms(0.0015)],
        "-": [1, ms(0.0015)],
        "repro.stream.next_key": [1, ms(0.0012)],
        "repro.sample.dispatch": [1, ms(0.0007)],
    }


def recorded():
    path = os.path.join(DATA, "recorded-scoped.json")
    if not os.path.exists(path):
        pytest.skip("no recorded scoped chip trace")
    with open(path) as f:
        meta = json.load(f)
    return meta, os.path.join(DATA, meta["trace"])


def test_recorded_scopes_cover_the_busy_time():
    meta, path = recorded()
    red = scopes.reduce_file(path)
    busy = sum(red["busy_s"]) / len(red["busy_s"])
    assert busy == pytest.approx(meta["busy_s"], rel=1e-6)
    assert sum(red["scopes"].values()) == pytest.approx(busy, rel=1e-6)
    for label, sec in meta["scopes"].items():
        assert red["scopes"][label] == pytest.approx(sec, rel=1e-6), label
    for label, (gaps, sec) in meta["idle"].items():
        assert red["idle"][label] == [gaps, pytest.approx(sec, rel=1e-6)], label
    # the counting program's work is all under its scopes
    unscoped = sum(s for label, s in red["scopes"].items() if not label.startswith("node")
                   and label not in scopes.TOP_SCOPES and label.startswith("jit_count"))
    assert unscoped < 0.01 * busy


def test_main_prints_the_tables_of_a_kept_trace(capsys):
    meta, path = recorded()
    assert scopes.main([path]) == 0
    out = capsys.readouterr().out.splitlines()
    assert len(out) == len(meta["scopes"]) + len(meta["idle"])
    top = max(meta["scopes"], key=meta["scopes"].get)
    assert out[0].startswith(f"[scopes] {top} ")
    assert any(line.startswith("[idle] repro.sample.wait: ") for line in out)


def test_print_obs(capsys):
    scopes.print_obs({"counters": {"neighbor_sum.columns_true": 70},
                      "spans": [("plan.slab_layout", None, 0, 2_500_000_000, {}),
                                ("sample.wait", None, 10, 20, {}),
                                ("plan.slab_layout", None, 0, 500_000_000, {})]})
    assert capsys.readouterr().out.splitlines() == [
        '[obs] counters {"neighbor_sum.columns_true": 70}',
        "[obs] span plan.slab_layout: 2 in 3.000000s",
        "[obs] span sample.wait: 1 in 0.000000s",
    ]
