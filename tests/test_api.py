"""The unified ``Counter`` facade: backend parity, estimator agreement with
the brute-force oracle, config resolution, graph I/O round trips.

Backend parity is the core invariant of the API layer: for a FIXED
coloring, ``backend="single"`` and ``backend="distributed"`` must produce
the identical colorful map count (both compute the same deterministic
integer).  These tests run in the main (single-device) process with a
1-shard mesh — the full shard_map/exchange machinery still executes; the
multi-shard variants run in tests/_dist_worker.py.
"""

import numpy as np
import pytest

import jax

from repro.api import CountRequest, CountResult, Counter, run
from repro.configs import COUNTING_CONFIGS
from repro.core import erdos_renyi, load_edge_file, load_npz, save_npz
from repro.core.brute_force import count_colorful_maps, count_copies
from repro.core.distributed import build_distributed_plan, shard_coloring
from repro.core.templates import path_tree, spider_tree, star_tree


class TestBackendParity:
    @pytest.mark.parametrize(
        "tree_fn", [lambda: path_tree(4), lambda: star_tree(4),
                    lambda: spider_tree([2, 1])]
    )
    def test_fixed_coloring_parity(self, tree_fn):
        tree = tree_fn()
        g = erdos_renyi(57, 4.0, seed=3)  # 57 not divisible: ragged shard
        rng = np.random.default_rng(0)
        coloring = rng.integers(0, tree.n, g.n).astype(np.int32)
        want = count_colorful_maps(g, tree, coloring)

        single = Counter.from_graph(g, tree, backend="single")
        dist = Counter.from_graph(g, tree, backend="distributed", num_shards=1, mode="alltoall")
        got_s = single.count_coloring(coloring)
        got_d = dist.count_coloring(coloring)
        assert got_s == pytest.approx(want)
        assert got_d == pytest.approx(want)
        assert got_s == pytest.approx(got_d)

    def test_estimate_matches_oracle_both_backends(self):
        tree = path_tree(3)
        g = erdos_renyi(40, 4.0, seed=5)
        truth = count_copies(g, tree)
        for backend, opts in (
            ("single", {}),
            ("distributed", {"num_shards": 1, "mode": "pipeline"}),
        ):
            c = Counter.from_graph(g, tree, backend=backend, **opts)
            res = c.estimate(n_iter=200, key=jax.random.key(0), batch=32)
            assert isinstance(res, CountResult)
            assert res.backend == backend
            assert res.niter == 200 and len(res.samples) == 200
            assert res.mean == pytest.approx(truth, rel=0.2), (backend, res)

    def test_count_one_and_stream(self):
        tree = path_tree(3)
        g = erdos_renyi(30, 4.0, seed=1)
        c = Counter.from_graph(g, tree, backend="single")
        est = c.count_one(jax.random.key(0))
        assert np.isfinite(est) and est >= 0
        stream = c.sample_stream(jax.random.key(1), batch=4)
        a, b = next(stream), next(stream)
        assert a.shape == (4,) and b.shape == (4,)
        # key-split stream: consecutive batches are distinct draws
        assert not np.array_equal(a, b)
        # reproducible from the same key
        a2 = next(c.sample_stream(jax.random.key(1), batch=4))
        np.testing.assert_array_equal(a, a2)


class TestRequests:
    def test_config_resolves_to_request(self):
        ccfg = COUNTING_CONFIGS["bench-small"]
        g = erdos_renyi(60, 4.0, seed=2)
        req = ccfg.to_request(g, backend="single", n_iter=8)
        assert isinstance(req, CountRequest)
        assert req.template == ccfg.template
        # distributed-only opts ride along and are dropped by the facade
        res = run(req, key=jax.random.key(0))
        assert res.backend == "single" and res.niter == 8

    def test_unknown_plan_opt_raises(self):
        g = erdos_renyi(20, 3.0, seed=0)
        with pytest.raises(TypeError, match="unknown plan_opts"):
            Counter.from_graph(g, path_tree(3), typo_opt=1)

    def test_iter_axis_must_be_a_mesh_axis(self):
        g = erdos_renyi(20, 3.0, seed=0)
        c = Counter.from_graph(
            g, path_tree(3), backend="distributed", num_shards=1,
            iter_axis="model",  # auto-built mesh only has the data axis
        )
        with pytest.raises(ValueError, match="iter_axis"):
            _ = c.plan
        base = Counter.from_graph(g, path_tree(3), backend="distributed", num_shards=1)
        with pytest.raises(ValueError, match="iter_axis"):
            base.with_options(iter_axis="model")
        with pytest.raises(TypeError, match="only swaps"):
            base.with_options(num_shards=2)

    def test_with_options_distributed_knobs(self):
        """The distributed with_options allow-list covers the shared kernel
        knobs (impl/fuse) and the §3.3 tile size; unknown keys are rejected
        with a message naming the backend; bucket_tile rebuilds the plan."""
        g = erdos_renyi(60, 4.0, seed=8)
        tree = path_tree(3)
        rng = np.random.default_rng(2)
        coloring = rng.integers(0, tree.n, g.n).astype(np.int32)
        want = count_colorful_maps(g, tree, coloring)
        base = Counter.from_graph(g, tree, backend="distributed", num_shards=1, mode="pipeline")
        # exchange/kernel knobs share the built plan
        fused = base.with_options(mode="ring", fuse=True, impl="xla")
        assert fused.plan is base.plan
        assert fused.count_coloring(coloring) == pytest.approx(want)
        # bucket_tile changes the tiled layout itself -> plan rebuilds
        retiled = base.with_options(bucket_tile=64)
        assert retiled.plan is not base.plan
        assert retiled.plan.bucket_tile == 64
        assert retiled.count_coloring(coloring) == pytest.approx(want)
        # unknown keys: rejected, message names the backend
        with pytest.raises(TypeError, match="distributed"):
            base.with_options(spmm_kind="edges")
        single = Counter.from_graph(g, tree, backend="single")
        with pytest.raises(ValueError, match="single"):
            single.with_options(mode="ring")

    def test_estimate_requires_budget_or_eps(self):
        g = erdos_renyi(20, 3.0, seed=0)
        c = Counter.from_graph(g, path_tree(3), backend="single")
        with pytest.raises(ValueError, match="n_iter or eps"):
            c.estimate()
        # eps derives the worst-case bound; k=3 keeps it small enough to run
        res = c.estimate(eps=2.0, delta=0.5, key=jax.random.key(0))
        assert res.niter >= 1 and res.eps == 2.0


class TestGraphIO:
    def test_npz_roundtrip(self, tmp_path):
        g = erdos_renyi(50, 5.0, seed=4, name="roundtrip")
        path = str(tmp_path / "g.npz")
        save_npz(g, path)
        g2 = load_npz(path)
        assert g2.n == g.n and g2.name == g.name
        np.testing.assert_array_equal(g2.indptr, g.indptr)
        np.testing.assert_array_equal(g2.indices, g.indices)

    def test_load_edge_file(self, tmp_path):
        path = tmp_path / "edges.txt"
        path.write_text(
            "# comment line\n"
            "% another comment\n"
            "0 1\n"
            "1 2 0.5\n"  # extra columns ignored
            "\n"
            "2 0\n"
            "2 0\n"  # duplicate removed
            "3 3\n"  # self loop removed
        )
        g = load_edge_file(str(path))
        assert g.n == 4 and g.num_edges == 3
        assert set(map(int, g.neighbors(2))) == {0, 1}

    def test_load_edge_file_one_indexed(self, tmp_path):
        path = tmp_path / "edges1.txt"
        path.write_text("1 2\n2 3\n")
        g = load_edge_file(str(path), zero_indexed=False)
        assert g.n == 3 and g.num_edges == 2

    def test_loaded_graph_counts(self, tmp_path):
        # the API accepts real (file-loaded) datasets end to end
        g = erdos_renyi(40, 4.0, seed=6)
        path = str(tmp_path / "g.npz")
        save_npz(g, path)
        g2 = load_npz(path)
        tree = path_tree(3)
        c = Counter.from_graph(g2, tree, backend="single")
        rng = np.random.default_rng(1)
        coloring = rng.integers(0, tree.n, g2.n).astype(np.int32)
        assert c.count_coloring(coloring) == pytest.approx(count_colorful_maps(g, tree, coloring))


class TestShardColoring:
    @pytest.mark.parametrize("n,shards", [(97, 4), (96, 4), (5, 2), (64, 8)])
    def test_vectorized_matches_reference(self, n, shards):
        g = erdos_renyi(n, 3.0, seed=0)
        plan = build_distributed_plan(g, path_tree(3), shards)
        rng = np.random.default_rng(7)
        coloring = rng.integers(0, 3, n).astype(np.int32)
        got = shard_coloring(plan, coloring)
        # reference: the original per-shard python loop
        want = np.zeros((plan.num_shards, plan.n_loc_pad), np.int32)
        for p in range(plan.num_shards):
            lo = p * plan.shard_size
            hi = min((p + 1) * plan.shard_size, plan.n)
            want[p, : hi - lo] = coloring[lo:hi]
        np.testing.assert_array_equal(got, want)


class TestPlacement:
    def test_distributed_plan_arrays_are_placed_once(self):
        from jax.sharding import NamedSharding, PartitionSpec

        g = erdos_renyi(57, 4.0, seed=3)
        c = Counter.from_graph(g, path_tree(3), backend="distributed", num_shards=1)
        for arr in c.plan.device_arrays:
            assert isinstance(arr.sharding, NamedSharding)
            assert arr.sharding.spec == PartitionSpec("data")
        # a mode switch reuses the placed plan instead of copying it
        assert c.with_options(mode="ring").plan is c.plan


class TestCompileCache:
    @pytest.mark.parametrize("env", [None, "elsewhere/jax-cache"])
    def test_cache_dir(self, monkeypatch, env):
        import pathlib

        from repro.launch.compile_cache import REPO_CACHE_DIR, use_compile_cache

        repo = pathlib.Path(__file__).resolve().parents[1]
        assert REPO_CACHE_DIR == repo / ".jax_cache"
        if env is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        else:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
        was = jax.config.jax_compilation_cache_dir
        was_min = jax.config.jax_persistent_cache_min_compile_time_secs
        try:
            got = use_compile_cache()
            now = jax.config.jax_compilation_cache_dir
            # every program is cached, however quick its compile
            assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        finally:
            jax.config.update("jax_compilation_cache_dir", was)
            jax.config.update("jax_persistent_cache_min_compile_time_secs", was_min)
        if env is None:
            assert got == now == str(REPO_CACHE_DIR)
        else:
            # JAX reads the variable itself: nothing is set in code
            assert got == env and now == was
