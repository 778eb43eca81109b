"""Narrow-wire exchange + measured adaptive routing (DESIGN.md §18).

The invariant under test everywhere: the wire dtype is a pure transport
choice — int16/int8 slabs (dense, and compacted with bit-packed activity
bitmaps) produce **bit-identical** counts to the float32 wire, with
saturation escalating through the wider-wire ladder transparently.  The
single-process coverage here runs the full distributed machinery on a
1-shard mesh; real 8-shard coverage (slabs actually crossing device
boundaries, the calibration probe timing a real ppermute) runs in
``tests/_dist_worker.py::test_compressed_exchange``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.api import Counter
from repro.comm.adaptive import V5E_ICI, assumed_model, calibrate, choose_mode_full
from repro.comm.compress import (
    WIRE_DTYPES,
    WIRE_ESCALATION,
    mask_column_count,
    mask_columns,
    mask_from_columns,
    narrow_cast,
    widen,
    wire_itemsize,
)
from repro.compat import make_mesh
from repro.core import erdos_renyi, rmat
from repro.core.brute_force import count_colorful_maps
from repro.core.frontier import node_exchange_bytes, sampled_density
from repro.core.templates import path_tree, spider_tree
from repro.testing import faults

WIRES = ["int16", "int8"]


def _skewed_graph(n=512, e=1500, seed=4):
    return rmat(n, e, skew=8, seed=seed)


class TestWireHelpers:
    @pytest.mark.parametrize("wire", WIRES)
    def test_narrow_cast_exact_below_max(self, wire):
        maxv = WIRE_DTYPES[wire][2]
        x = jnp.asarray([[0.0, 1.0, float(maxv)], [2.0, 3.0, 5.0]])
        flags = []
        y = narrow_cast(x, wire, flags)
        assert y.dtype == WIRE_DTYPES[wire][0]
        assert bool(flags[0])  # within range: flag holds
        np.testing.assert_array_equal(np.asarray(widen(y)), np.asarray(x))

    @pytest.mark.parametrize("wire", WIRES)
    def test_narrow_cast_flags_saturation(self, wire):
        maxv = WIRE_DTYPES[wire][2]
        flags = []
        narrow_cast(jnp.asarray([[float(maxv + 1)]]), wire, flags)
        assert not bool(flags[0])

    def test_float32_wire_is_identity(self):
        x = jnp.asarray([[1.5, -2.0]])
        flags = []
        assert narrow_cast(x, "float32", flags) is x
        assert flags == []  # no flag: the wide wire cannot saturate
        assert widen(x) is x

    def test_escalation_ladder_terminates(self):
        wire = "int8"
        seen = {wire}
        while wire in WIRE_ESCALATION:
            wire = WIRE_ESCALATION[wire]
            assert wire not in seen, "escalation must not cycle"
            seen.add(wire)
        assert wire == "float32"

    @pytest.mark.parametrize("wire", WIRES)
    @pytest.mark.parametrize("r_len", [1, 7, 8, 17, 64, 100])
    def test_mask_columns_roundtrip(self, wire, r_len):
        rng = np.random.default_rng(r_len)
        mask = jnp.asarray(rng.integers(0, 2, (3, r_len)).astype(bool))
        cap = 4
        cols = mask_columns(mask, cap, wire)
        assert cols.dtype == WIRE_DTYPES[wire][0]
        assert cols.shape == (3, cap, mask_column_count(r_len, cap, wire))
        back = mask_from_columns(cols, r_len, wire)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(mask))

    def test_mask_column_count_math(self):
        # 100 rows -> 13 int8 words -> ceil(13/4) = 4 payload columns
        assert mask_column_count(100, 4, "int8") == 4
        # int16 words halve the column count's word term
        assert mask_column_count(100, 4, "int16") == 2
        assert mask_column_count(8, 8, "int8") == 1

    def test_wire_itemsize(self):
        assert wire_itemsize("float32") == 4
        assert wire_itemsize("int16") == 2
        assert wire_itemsize("int8") == 1


class TestBytesModel:
    def test_narrow_wire_halves_dense_bytes(self):
        from repro.core.distributed import build_distributed_plan

        g = _skewed_graph()
        plan = build_distributed_plan(g, spider_tree([2, 1]), 8)
        i = plan.program.root_index
        d32, _ = node_exchange_bytes(plan, i, "ring")
        d16, _ = node_exchange_bytes(plan, i, "ring", wire_dtype="int16")
        d8, _ = node_exchange_bytes(plan, i, "ring", wire_dtype="int8")
        assert d16 * 2 == d32  # the acceptance ratio: exactly 0.5x
        assert d8 * 4 == d32

    def test_compact_bytes_include_mask_columns(self):
        from repro.core.distributed import build_distributed_plan

        g = _skewed_graph()
        plan = build_distributed_plan(
            g, spider_tree([2, 1]), 8, compact=True, density_threshold=0.9
        )
        spec = plan.compaction
        assert spec is not None and spec.shard_caps
        i = next(
            i for i, nd in enumerate(plan.program.nodes)
            if not nd.is_leaf and nd.right in spec.shard_caps
        )
        dense, compact = node_exchange_bytes(plan, i, "ring", wire_dtype="int16")
        assert 0 < compact < dense
        b = plan.widths[plan.program.nodes[i].right]
        cap = spec.shard_caps[plan.program.nodes[i].right]
        ncols = mask_column_count(plan.n_loc_pad, cap, "int16")
        assert compact == (plan.num_shards - 1) * cap * (b + ncols) * 2


class TestRouter:
    def test_latency_bound_picks_alltoall(self):
        mode, diag = choose_mode_full(1024, 1024, 0.0, 8)
        assert mode == "alltoall"
        assert diag["predicted_s"] == min(diag["costs_s"].values())

    def test_compute_bound_picks_overlap(self):
        mode, _ = choose_mode_full(1e6, 1e6, 1e15, 8)
        assert mode in ("pipeline", "ring")

    def test_cheap_ring_bytes_pick_ring(self):
        mode, _ = choose_mode_full(1e9, 1e3, 0.0, 8)
        assert mode == "ring"

    def test_calibrate_single_device_returns_base(self):
        mesh = make_mesh((1,), ("data",))
        assert calibrate(mesh, "data") is V5E_ICI

    @pytest.mark.parametrize("kind", ["TPU v5 lite", "cpu"])
    def test_assumed_model_by_device_kind(self, kind):
        assert assumed_model(kind) is V5E_ICI

    def test_unknown_device_kind_raises(self):
        with pytest.raises(ValueError, match="no assumed link model"):
            assumed_model("TPU v9 imaginary")


class TestSampledDensity:
    def test_probe_density_in_range_and_sparser_when_deep(self):
        from repro.core.count_engine import build_counting_plan

        g = _skewed_graph(1024, 3000, seed=2)
        plan = build_counting_plan(g, spider_tree([2, 1]))
        dens = sampled_density(
            g.n,
            2.0 * g.num_edges / g.n,
            plan.chain,
            plan.combine,
            plan.k,
            sample_vertices=256,
            probes=1,
        )
        assert dens and all(0.0 <= d <= 1.0 for d in dens.values())
        # the probe is exact where the Markov model saturates: deep nodes
        # on a skewed sparse graph come back measurably below 1.0
        sizes = {i: plan.chain.nodes[i].size for i in dens}
        deepest = max(sizes, key=sizes.get)
        assert dens[deepest] < 1.0


class TestOneShardParity:
    """Full distributed machinery on a 1-shard mesh: narrow slabs vs the
    float32 wire and the oracle, with counts large enough that int8 (and
    on the denser graph int16) genuinely saturates and the wider-wire
    redispatch carries the batch."""

    @pytest.mark.parametrize("mode", ["alltoall", "pipeline", "adaptive", "ring"])
    @pytest.mark.parametrize("wire", WIRES)
    def test_wire_parity(self, mode, wire):
        g = _skewed_graph()
        tree = spider_tree([2, 1])
        rng = np.random.default_rng(0)
        coloring = rng.integers(0, tree.n, g.n).astype(np.int32)
        want = count_colorful_maps(g, tree, coloring)
        wide = Counter.from_graph(g, tree, backend="distributed", num_shards=1, mode=mode)
        narrow = Counter.from_graph(
            g,
            tree,
            backend="distributed",
            num_shards=1,
            mode=mode,
            wire_dtype=wire,
        )
        d = wide.count_coloring(coloring)
        c = narrow.count_coloring(coloring)
        assert d == c  # bit-exact between wires
        assert c == pytest.approx(want, rel=1e-6)

    @pytest.mark.parametrize("wire", WIRES)
    def test_compact_narrow_parity(self, wire):
        g = _skewed_graph()
        tree = spider_tree([2, 1])
        rng = np.random.default_rng(1)
        coloring = rng.integers(0, tree.n, g.n).astype(np.int32)
        wide = Counter.from_graph(g, tree, backend="distributed", num_shards=1, mode="pipeline")
        narrow = Counter.from_graph(
            g,
            tree,
            backend="distributed",
            num_shards=1,
            mode="pipeline",
            wire_dtype=wire,
            compact=True,
            density_threshold=0.9,
        )
        assert narrow.plan.compaction is not None
        assert wide.count_coloring(coloring) == narrow.count_coloring(coloring)

    def test_dense_graph_saturates_and_escalates(self):
        # avg degree 20: DP table entries far exceed 127, so the int8 wire
        # saturates for real (no fault injection) and the ladder redispatch
        # must deliver the wide answer bit for bit
        g = erdos_renyi(128, 20.0, seed=3)
        tree = path_tree(4)
        rng = np.random.default_rng(2)
        coloring = rng.integers(0, tree.n, g.n).astype(np.int32)
        wide = Counter.from_graph(g, tree, backend="distributed", num_shards=1, mode="alltoall")
        n8 = Counter.from_graph(
            g,
            tree,
            backend="distributed",
            num_shards=1,
            mode="alltoall",
            wire_dtype="int8",
        )
        assert wide.count_coloring(coloring) == n8.count_coloring(coloring)

    def test_keyed_estimate_samples_identical(self):
        g = _skewed_graph()
        tree = path_tree(4)
        wide = Counter.from_graph(g, tree, backend="distributed", num_shards=1, mode="ring")
        narrow = Counter.from_graph(
            g,
            tree,
            backend="distributed",
            num_shards=1,
            mode="ring",
            wire_dtype="int16",
        )
        key = jax.random.key(6)
        rd = wide.estimate(n_iter=6, key=key, batch=3)
        rc = narrow.estimate(n_iter=6, key=key, batch=3)
        assert np.array_equal(rd.samples, rc.samples)

    def test_forced_saturation_storm(self):
        """The ``compression.saturate`` site forces the redispatch even when
        no slab saturated; two consecutive storms walk int8 -> int16 ->
        float32 and the counts never change."""
        g = _skewed_graph()
        tree = spider_tree([2, 1])
        rng = np.random.default_rng(5)
        coloring = rng.integers(0, tree.n, g.n).astype(np.int32)
        wide = Counter.from_graph(g, tree, backend="distributed", num_shards=1, mode="pipeline")
        n8 = Counter.from_graph(
            g,
            tree,
            backend="distributed",
            num_shards=1,
            mode="pipeline",
            wire_dtype="int8",
        )
        want = wide.count_coloring(coloring)
        with faults.active(faults.inject("compression.saturate", at=(0, 1))) as fp:
            got = n8.count_coloring(coloring)
        assert got == want
        fired = [s for s, _ in fp.fired]
        assert fired.count("compression.saturate") == 2


class TestPlanOpts:
    def test_api_accepts_wire_opts(self):
        g = _skewed_graph(256, 800, seed=5)
        c = Counter.from_graph(
            g,
            path_tree(3),
            backend="distributed",
            num_shards=1,
            wire_dtype="int16",
            adaptive="measured",
        )
        assert c.plan_opts["wire_dtype"] == "int16"
        assert c.plan_opts["adaptive"] == "measured"

    def test_with_options_swaps_wire(self):
        g = _skewed_graph(256, 800, seed=5)
        c = Counter.from_graph(
            g,
            path_tree(3),
            backend="distributed",
            num_shards=1,
            mode="pipeline",
        )
        rng = np.random.default_rng(3)
        coloring = rng.integers(0, 3, g.n).astype(np.int32)
        want = c.count_coloring(coloring)
        c16 = c.with_options(wire_dtype="int16")
        assert c16._plan is c._plan  # the built plan is shared
        assert c16.count_coloring(coloring) == want

    def test_invalid_wire_dtype_rejected(self):
        from repro.core.distributed import make_count_fn

        g = _skewed_graph(256, 800, seed=5)
        c = Counter.from_graph(g, path_tree(3), backend="distributed", num_shards=1)
        mesh = make_mesh((1,), ("data",))
        with pytest.raises(ValueError, match="wire_dtype"):
            make_count_fn(c.plan, mesh, wire_dtype="int4")
        with pytest.raises(ValueError, match="adaptive"):
            make_count_fn(c.plan, mesh, adaptive="oracle")
