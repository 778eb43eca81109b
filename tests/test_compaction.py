"""Active-frontier compaction (DESIGN.md §15): parity, capacities, fallback.

The invariant under test everywhere: compaction is a pure data-layout
choice — the compact program computes **bit-identical** counts and keyed
estimator samples to the dense program whenever its capacity flags hold,
and transparently falls back to the dense program when they do not (so it
is exact even at absurd capacities).

Single-process coverage: the in-core backend across impl x fuse, the full
distributed machinery on a 1-shard mesh across all four exchange modes,
and the family (DAG) path.  Real 8-shard coverage (all modes x fuse x
pallas, compacted exchange actually crossing device boundaries) runs in
``tests/_dist_worker.py::test_compaction``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.api import Counter
from repro.core import rmat
from repro.core.graphs import RMAT_SKEW
from repro.core.brute_force import count_colorful_maps
from repro.core.count_engine import (
    build_counting_plan,
    build_multi_counting_plan,
    colorful_map_count,
    colorful_map_count_checked,
    count_fn,
    count_fn_many,
)
from repro.core.frontier import (
    CompactionSpec,
    capacity_for,
    model_density,
    probe_activity,
)
from repro.core.templates import path_tree, spider_tree, template


def _skewed_graph(n=1024, e=3000, seed=2):
    return rmat(n, e, skew=8, seed=seed)


@pytest.fixture
def force_floors(monkeypatch):
    """Drop the profitability floors so compaction engages on the small
    templates the tests can afford — exactness must hold regardless of
    whether compaction is a *win*, which is what these tests check."""
    import repro.core.frontier as frontier

    monkeypatch.setattr(frontier, "MIN_COMBINE_ELEMENTS", 1)
    monkeypatch.setattr(frontier, "MIN_TABLE_WIDTH", 1)


def _coloring(plan, g, k, seed=0):
    rng = np.random.default_rng(seed)
    col = np.zeros(plan.n_pad, np.int32)
    col[: g.n] = rng.integers(0, k, g.n)
    return jnp.asarray(col)


class TestProbe:
    def test_probe_matches_real_activity(self):
        """The boolean probe is exact: its active mask for a coloring equals
        the nonzero rows of the real DP's node tables (checked at the root:
        active root rows <=> the DP's count for that coloring is nonzero)."""
        g = _skewed_graph()
        tree = template("u7-2")
        plan = build_counting_plan(g, tree)
        masks = next(probe_activity(g, plan.chain, plan.combine, plan.k, probes=1, seed=5))
        rng = np.random.default_rng(5)  # the probe's own coloring stream
        coloring = rng.integers(0, plan.k, g.n).astype(np.int32)
        col = np.zeros(plan.n_pad, np.int32)
        col[: g.n] = coloring
        want = float(colorful_map_count(plan, jnp.asarray(col)))
        root = plan.chain.root_index
        # probe says the root has active rows iff the DP count is nonzero
        assert bool(masks[root].table.any()) == (want > 0)
        # densities shrink with sub-template depth on a skewed sparse graph
        dens = {i: m.table.mean() for i, m in masks.items()}
        sizes = {i: plan.chain.nodes[i].size for i in dens}
        deepest = max(sizes, key=sizes.get)
        shallowest = min(sizes, key=sizes.get)
        assert dens[deepest] <= dens[shallowest]

    def test_capacity_math(self):
        assert capacity_for(10, 1.5, 10_000) == 128  # padded + zero slot
        assert capacity_for(1000, 1.5, 1536) == 1536 or capacity_for(
            1000, 1.5, 1536
        ) is None  # at the limit: no win -> None
        assert capacity_for(1000, 1.5, 1537) == 1536
        assert capacity_for(0, 1.5, 1024) == 128
        assert capacity_for(50, 2.0, 64, multiple=8) is None

    def test_model_density_bounds(self):
        assert model_density(1, 7, 100.0) == 1.0
        for t in range(2, 8):
            rho = model_density(t, 7, 2.0)
            assert 0.0 <= rho <= 1.0
        # deep templates on low-degree graphs are sparse, high-degree dense
        assert model_density(7, 7, 1.5) < 0.2
        assert model_density(3, 7, 50.0) == 1.0

    def test_spec_enabled(self):
        empty = CompactionSpec(0.25, 1.5, {}, {}, {}, {})
        assert not empty.enabled
        assert CompactionSpec(0.25, 1.5, {}, {}, {1: 128}, {}).enabled


class TestSingleDeviceParity:
    @pytest.mark.parametrize("impl", ["xla", "pallas"])
    @pytest.mark.parametrize("fuse", [False, True])
    def test_compact_equals_dense_bitexact(self, impl, fuse, force_floors):
        g = _skewed_graph()
        tree = template("u7-2")
        dense = build_counting_plan(g, tree, impl=impl, fuse=fuse)
        comp = build_counting_plan(
            g, tree, impl=impl, fuse=fuse, compact=True, density_threshold=0.7
        )
        assert comp.compaction is not None and comp.compaction.enabled
        col = _coloring(dense, g, dense.k)
        want = float(colorful_map_count(dense, col))
        got, ok = colorful_map_count_checked(comp, col)
        assert bool(ok)
        assert float(got) == want  # bit-exact, not approx

    def test_right_child_indirection_engages(self, force_floors):
        """u7-2's root exchanges an internal (size-3) right child: with a
        permissive threshold its table cap must engage, driving the
        SpMM through the compact row-index indirection."""
        g = _skewed_graph()
        comp = build_counting_plan(g, template("u7-2"), compact=True, density_threshold=0.7)
        spec = comp.compaction
        rights = {
            nd.right
            for nd in comp.chain.nodes
            if not nd.is_leaf and not comp.chain.nodes[nd.right].is_leaf
        }
        assert rights & set(spec.table_caps), (rights, spec.table_caps)
        # capacities are static multiples of the pallas row tile
        for cap in list(spec.table_caps.values()) + list(
            spec.combine_caps.values()
        ):
            assert cap % 128 == 0 and cap < comp.n_pad

    def test_keyed_samples_identical(self, force_floors):
        """Same key => identical per-iteration estimator samples, compact
        vs dense (the same-key contract the estimator relies on)."""
        g = _skewed_graph()
        tree = template("u7-2")
        fd = count_fn(build_counting_plan(g, tree), batch=4)
        fc = count_fn(
            build_counting_plan(g, tree, compact=True, density_threshold=0.7),
            batch=4,
        )
        key = jax.random.key(7)
        md, ed = fd(key)
        mc, ec = fc(key)
        assert np.array_equal(np.asarray(md), np.asarray(mc))
        assert np.array_equal(np.asarray(ed), np.asarray(ec))

    def test_overflow_falls_back_to_dense(self, force_floors):
        """Absurdly small capacities overflow on every coloring; the
        wrapper must re-dispatch the dense program and still be exact."""
        g = _skewed_graph()
        tree = template("u5-2")
        dense = build_counting_plan(g, tree)
        tiny = build_counting_plan(
            g, tree, compact=True, density_threshold=1.0, capacity_factor=1e-6
        )
        assert tiny.compaction.enabled
        col = _coloring(dense, g, dense.k)
        _, ok = colorful_map_count_checked(tiny, col)
        assert not bool(ok)  # the flag actually trips
        fd = count_fn(dense, batch=3)
        ft = count_fn(tiny, batch=3)
        key = jax.random.key(1)
        md, _ = fd(key)
        mt, _ = ft(key)
        assert np.array_equal(np.asarray(md), np.asarray(mt))

    def test_colorful_map_count_stays_dense(self, force_floors):
        """The unchecked entry point keeps its dense contract even on a
        compacted plan (callers that cannot consume the flag)."""
        g = _skewed_graph()
        comp = build_counting_plan(
            g,
            template("u5-2"),
            compact=True,
            density_threshold=1.0,
            capacity_factor=1e-6,
        )
        dense = build_counting_plan(g, template("u5-2"))
        col = _coloring(dense, g, dense.k)
        assert float(colorful_map_count(comp, col)) == float(colorful_map_count(dense, col))


class TestFamilyParity:
    def test_dag_compact_parity(self, force_floors):
        g = _skewed_graph()
        family = ["u3-1", "u5-2", "u7-2"]
        dense = build_multi_counting_plan(g, family)
        comp = build_multi_counting_plan(g, family, compact=True, density_threshold=0.7)
        assert comp.compaction.enabled
        fd = count_fn_many(dense, batch=3)
        fc = count_fn_many(comp, batch=3)
        key = jax.random.key(2)
        md, _ = fd(key)
        mc, _ = fc(key)
        assert np.array_equal(np.asarray(md), np.asarray(mc))

    def test_counter_facade_family(self):
        g = _skewed_graph(512, 1500, seed=3)
        family = [path_tree(3), spider_tree([2, 1])]
        k = max(t.n for t in family)
        rng = np.random.default_rng(4)
        coloring = rng.integers(0, k, g.n).astype(np.int32)
        dense = Counter.from_graph(g, family[-1], backend="single")
        comp = Counter.from_graph(
            g,
            family[-1],
            backend="single",
            compact=True,
            density_threshold=0.9,
        )
        want = dense.count_coloring_many(family, coloring)
        got = comp.count_coloring_many(family, coloring)
        assert np.array_equal(want, got)


class TestOneShardDistributed:
    """Full distributed machinery on a 1-shard mesh in-process: compacted
    exchange + compact combine vs the dense program and the oracle."""

    @pytest.mark.parametrize("mode", ["alltoall", "pipeline", "adaptive", "ring"])
    @pytest.mark.parametrize("fuse", [False, True])
    def test_compact_parity(self, mode, fuse):
        g = _skewed_graph(512, 1500, seed=4)
        tree = spider_tree([2, 1])
        rng = np.random.default_rng(0)
        coloring = rng.integers(0, tree.n, g.n).astype(np.int32)
        want = count_colorful_maps(g, tree, coloring)
        dense = Counter.from_graph(
            g, tree, backend="distributed", num_shards=1, mode=mode, fuse=fuse
        )
        comp = Counter.from_graph(
            g,
            tree,
            backend="distributed",
            num_shards=1,
            mode=mode,
            fuse=fuse,
            compact=True,
            density_threshold=0.9,
        )
        assert comp.plan.compaction is not None
        d = dense.count_coloring(coloring)
        c = comp.count_coloring(coloring)
        assert d == c  # bit-exact between programs
        assert c == pytest.approx(want, rel=1e-6)

    def test_overflow_fallback_distributed(self):
        g = _skewed_graph(512, 1500, seed=4)
        tree = spider_tree([2, 1])
        rng = np.random.default_rng(1)
        coloring = rng.integers(0, tree.n, g.n).astype(np.int32)
        dense = Counter.from_graph(g, tree, backend="distributed", num_shards=1, mode="pipeline")
        tiny = Counter.from_graph(
            g,
            tree,
            backend="distributed",
            num_shards=1,
            mode="pipeline",
            compact=True,
            density_threshold=1.0,
            capacity_factor=1e-6,
        )
        assert tiny.plan.compaction.enabled
        assert dense.count_coloring(coloring) == tiny.count_coloring(coloring)

    def test_keyed_estimate_samples_identical(self):
        g = _skewed_graph(512, 1500, seed=4)
        tree = path_tree(4)
        dense = Counter.from_graph(g, tree, backend="distributed", num_shards=1, mode="alltoall")
        comp = Counter.from_graph(
            g,
            tree,
            backend="distributed",
            num_shards=1,
            mode="alltoall",
            compact=True,
            density_threshold=0.9,
        )
        key = jax.random.key(6)
        rd = dense.estimate(n_iter=6, key=key, batch=3)
        rc = comp.estimate(n_iter=6, key=key, batch=3)
        assert np.array_equal(rd.samples, rc.samples)


class TestPlanOpts:
    def test_api_accepts_compaction_opts(self):
        g = _skewed_graph(256, 800, seed=5)
        c = Counter.from_graph(
            g,
            path_tree(3),
            backend="single",
            compact=True,
            density_threshold=0.5,
            capacity_factor=2.0,
            probes=1,
        )
        plan = c.plan
        assert plan.compaction is not None
        assert plan.compaction.threshold == 0.5
        assert plan.compaction.capacity_factor == 2.0
        assert plan.compaction.probes == 1

    def test_unknown_opt_still_rejected(self):
        g = _skewed_graph(256, 800, seed=5)
        with pytest.raises(TypeError):
            Counter.from_graph(g, path_tree(3), compacct=True)


class TestPropertyParity:
    """Hypothesis sweep: compaction on vs off agrees bit-for-bit on counts
    and keyed samples for arbitrary skewed graphs, templates, thresholds,
    and capacity factors — including factors small enough to overflow."""

    def test_compact_parity_property(self, force_floors):
        pytest.importorskip(
            "hypothesis",
            reason="property tests need hypothesis (see requirements-dev.txt)",
        )
        from hypothesis import HealthCheck, given, settings
        from hypothesis import strategies as st

        @given(
            st.integers(100, 500),
            st.sampled_from(sorted(RMAT_SKEW)),
            st.sampled_from(["p4", "sp21", "u5-2"]),
            st.floats(0.05, 2.0),
            st.integers(0, 10_000),
        )
        @settings(
            max_examples=8,
            deadline=None,
            suppress_health_check=[
                HealthCheck.too_slow,
                HealthCheck.data_too_large,
            ],
        )
        def check(n, skew, tname, cf, seed):
            g = rmat(n, 3 * n, skew=skew, seed=seed)
            tree = {
                "p4": path_tree(4),
                "sp21": spider_tree([2, 1]),
                "u5-2": template("u5-2"),
            }[tname]
            dense = build_counting_plan(g, tree)
            comp = build_counting_plan(
                g,
                tree,
                compact=True,
                density_threshold=1.0,
                capacity_factor=cf,
                probes=1,
            )
            fd = count_fn(dense, batch=2)
            fc = count_fn(comp, batch=2)
            key = jax.random.key(seed)
            md, ed = fd(key)
            mc, ec = fc(key)
            assert np.array_equal(np.asarray(md), np.asarray(mc))
            assert np.array_equal(np.asarray(ed), np.asarray(ec))

        check()
