"""The program's own measurement: host spans and counters (``repro.obs``)
and the device scopes that name the table program's work in a trace.

The spans are read on the profiler's clock by the chip benchmark
(``chipbench/scopes.py``); here they are checked as records, and the scopes
as the op names of a program lowered on the CPU.
"""

import math
import os
import re
import sys
import threading

import jax
import numpy as np
import pytest

from repro import obs
from repro.api import Counter
from repro.core import erdos_renyi, rmat
from repro.core.count_engine import build_counting_plan, count_fn
from repro.core.graphs import edge_list
from repro.core.templates import path_tree, spider_tree
from repro.kernels import ops


@pytest.fixture
def recording():
    obs.enable()
    yield
    obs.disable()


def test_off_records_nothing_and_returns_the_shared_no_op():
    obs.disable()
    a, b = obs.span("a", batch=3), obs.span("b")
    assert a is b
    with a:
        obs.count("c", 5)
    obs.enable()
    obs.disable()  # a fresh recording, then off again
    with obs.span("d"):
        obs.count("c")
    assert obs.snapshot() == {"spans": [], "counters": {}}


def test_nested_spans_record_their_parents_per_thread(recording):
    ready, go = threading.Event(), threading.Event()

    def other():
        with obs.span("t.outer"):
            ready.set()
            go.wait(10)
            with obs.span("t.inner"):
                obs.count("n", 2)

    th = threading.Thread(target=other)
    th.start()
    assert ready.wait(10)
    with obs.span("outer", batch=4):
        go.set()  # the other thread nests inside its own span meanwhile
        with obs.span("inner"):
            obs.count("n", 3)
        th.join(10)
    assert not th.is_alive()
    snap = obs.snapshot()
    spans = {name: rest for name, *rest in snap["spans"]}
    assert spans["inner"][0] == "outer" and spans["outer"][0] is None
    assert spans["t.inner"][0] == "t.outer" and spans["t.outer"][0] is None
    assert spans["outer"][3] == {"batch": 4}
    assert spans["outer"][1] <= spans["inner"][1] <= spans["inner"][2] <= spans["outer"][2]
    assert snap["counters"] == {"n": 5}


def test_threads_lose_no_count_or_span(recording):
    """More threads than cores, switching every microsecond: the counter
    and the ring keep every update."""
    threads, per = min(4 * (os.cpu_count() or 1), 64), 2000

    def work(t):
        for i in range(per):
            obs.count("n")
            if i % 10 == 0:
                with obs.span("s", t=t):
                    obs.count("m")

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        pool = [threading.Thread(target=work, args=(t,)) for t in range(threads)]
        for th in pool:
            th.start()
        for th in pool:
            th.join(60)
    finally:
        sys.setswitchinterval(was)
    assert not any(th.is_alive() for th in pool)
    snap = obs.snapshot()
    assert snap["counters"] == {"n": threads * per, "m": threads * per // 10}
    assert len(snap["spans"]) == threads * per // 10
    assert all(parent is None for _, parent, _, _, _ in snap["spans"])


def _internal(program):
    return [i for i, nd in enumerate(program.nodes) if nd.kind == "combine"]


@pytest.mark.parametrize("fuse", [False, True], ids=["two_step", "fused"])
def test_lowered_count_fn_names_every_node(fuse):
    g = erdos_renyi(200, 600, seed=1)
    plan = build_counting_plan(g, spider_tree([2, 1, 1]), fuse=fuse)
    lowered = count_fn(plan, batch=2).lower(jax.random.key(0))
    assert lowered.as_text().startswith("module @jit_count_batch")
    names = set(re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True)))
    # under vmap the outermost scope is written vmap(<scope>)
    scopes = {m.group(1, 2) for n in names
              if (m := re.match(r"jit\(count_batch\)/vmap\((node\d+)\)/(\w+)", n))}
    wanted = ["fused"] if fuse else ["neighbor_sum", "combine", "mask"]
    for i in _internal(plan.chain):
        for op in wanted:
            assert (f"node{i}", op) in scopes, (i, op, sorted(scopes))
    tops = {n.split("/")[1] for n in names if n.startswith("jit(count_batch)/")}
    assert {"coloring", "vmap(leaf)", "vmap(root)"} <= tops


@pytest.mark.parametrize("lane", [1, 128])
def test_column_counters_equal_the_plan_widths(recording, lane):
    g = erdos_renyi(150, 400, seed=3)
    tree = spider_tree([3, 2, 1])
    plan = build_counting_plan(g, tree, lane=lane)
    nodes = plan.chain.nodes
    rights = [nodes[i].right for i in _internal(plan.chain)]
    true = sum(math.comb(plan.k, nodes[r].size) for r in rights)
    snap = obs.snapshot()
    pieces = plan.spmm_plan.piece_cols
    assert snap["counters"] == {
        "neighbor_sum.columns_true": true,
        "neighbor_sum.columns_stored": sum(plan.widths[r] for r in rights),
        "neighbor_sum.pieces": sum(len(p) for p in pieces),
        "neighbor_sum.edge_slots": sum(p.size for p in pieces),
    }
    # k = 7: every table is at most C(7, 3) = 35 columns, one lane block
    assert snap["counters"]["neighbor_sum.columns_stored"] == (
        true if lane == 1 else 128 * len(rights)
    )
    assert [s[0] for s in snap["spans"]] == [
        "plan.from_edges", "plan.slab_layout", "plan.piece_layout", "plan.node_tables"
    ]


def test_plan_build_counts_the_piece_layout(recording):
    """The piece layout's padding is counted where the plan is built: every
    directed edge takes one slot, and a vertex one piece per 128 neighbors."""
    g = rmat(400, 3000, skew=8, seed=2)
    rows, cols = edge_list(g)
    ops.build_spmm_plan(rows, cols, g.n, kind="edges", tile_size=128)
    counters = obs.snapshot()["counters"]
    degree = np.bincount(rows, minlength=g.n)
    assert counters["neighbor_sum.pieces"] == int(np.sum(-(-degree // 128)))
    # each piece pads to the next power of two: under twice its edges
    assert len(rows) <= counters["neighbor_sum.edge_slots"] < 2 * len(rows)
    assert [s[0] for s in obs.snapshot()["spans"]][-2:] == ["plan.slab_layout", "plan.piece_layout"]


def test_sample_stream_spans_the_host_turn(recording):
    g = erdos_renyi(120, 300, seed=4)
    counter = Counter.from_graph(g, path_tree(3), backend="single")
    stream = counter.sample_stream(jax.random.key(1), batch=2)
    for _ in range(3):
        est = next(stream)
    assert est.shape == (2,) and np.all(np.isfinite(est))
    steps = [(name, parent, attrs) for name, parent, _, _, attrs in obs.snapshot()["spans"]
             if not name.startswith("plan.")]
    assert steps == [
        ("stream.next_key", None, {}),
        ("sample.dispatch", None, {"batch": 2, "first": True}),
        ("sample.wait", None, {}),
    ] + 2 * [
        ("stream.next_key", None, {}),
        ("sample.dispatch", None, {"batch": 2, "first": False}),
        ("sample.wait", None, {}),
    ]
