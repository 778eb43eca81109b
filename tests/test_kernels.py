"""Pallas kernel validation: interpret-mode kernels vs pure-jnp oracles.

Each kernel sweeps shapes (and dtypes where meaningful) and asserts
allclose against ref.py.
"""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import erdos_renyi, rmat
from repro.core.graphs import edge_list, from_edges
from repro.kernels import ops, ref
from repro.kernels.color_combine import color_combine_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.fused_count import fused_count_pallas
from repro.kernels.spmm_edgetile import spmm_block_pallas, spmm_edge_tile_pallas


def _random_table(rng, n_pad, width, n_valid, dtype=np.float32):
    t = rng.random((n_pad, width)).astype(dtype)
    t[n_valid:] = 0.0
    return jnp.asarray(t)


class TestSpmmKernels:
    @pytest.mark.parametrize(
        "n,deg,width,tile",
        [(100, 5.0, 128, 128), (300, 8.0, 256, 64), (64, 3.0, 384, 32)],
    )
    def test_edge_tile_kernel_matches_ref(self, n, deg, width, tile):
        g = erdos_renyi(n, deg, seed=n)
        plan = ops.build_spmm_plan(*edge_list(g), g.n, kind="edges", tile_size=tile)
        rng = np.random.default_rng(0)
        table = _random_table(rng, plan.n_pad, width, g.n)
        got = spmm_edge_tile_pallas(
            plan.slab_dst,
            plan.slab_cols,
            table,
            slabs_per_block=plan.slabs_per_block,
            interpret=True,
        )
        want = ref.spmm_segment_ref(plan.rows, plan.cols, table, plan.n_pad - 1)[: plan.n_pad]
        np.testing.assert_allclose(got[: g.n], want[: g.n], rtol=1e-6)
        # zero-degree and pad rows come out exactly zero (pad slabs no-op)
        np.testing.assert_array_equal(np.asarray(got[g.n :]), 0.0)

    def test_slab_layout_skewed_graph(self):
        # a supernode row owns many slabs; every slab is still tile_size slots
        g = rmat(200, 3000, skew=8, seed=3)
        plan = ops.build_spmm_plan(*edge_list(g), g.n, kind="edges", tile_size=64)
        assert plan.slab_dst.shape == (
            (plan.n_pad // plan.row_tile) * plan.slabs_per_block,
            64,
        )
        rng = np.random.default_rng(4)
        table = _random_table(rng, plan.n_pad, 128, g.n)
        got = spmm_edge_tile_pallas(
            plan.slab_dst,
            plan.slab_cols,
            table,
            slabs_per_block=plan.slabs_per_block,
            interpret=True,
        )
        want = ref.spmm_segment_ref(plan.rows, plan.cols, table, plan.n_pad - 1)
        np.testing.assert_allclose(got[: g.n], want[: g.n], rtol=1e-5)

    def test_auto_plan_kind_adapts_to_density(self):
        # dense small graph: occupied patches are heavy -> block-dense plan
        dense = rmat(512, 30_000, skew=3, seed=1)
        p_dense = ops.build_spmm_plan(*edge_list(dense), dense.n, kind="auto")
        assert p_dense.kind == "blocks"
        assert p_dense.patch_density >= ops.AUTO_DENSITY_THRESHOLD
        # large sparse graph: patches nearly empty -> edge-tiled plan
        sparse = erdos_renyi(5000, 3.0, seed=2)
        p_sparse = ops.build_spmm_plan(*edge_list(sparse), sparse.n, kind="auto")
        assert p_sparse.kind == "edges"
        assert p_sparse.patch_density < ops.AUTO_DENSITY_THRESHOLD
        # both dispatch paths agree with the oracle
        rng = np.random.default_rng(5)
        table = _random_table(rng, p_dense.n_pad, 128, dense.n)
        got = ops.spmm(p_dense, table, impl="xla")
        eplan = ops.build_spmm_plan(*edge_list(dense), dense.n, kind="edges")
        want = ops.spmm(eplan, table, impl="xla")
        np.testing.assert_allclose(got[: dense.n], want[: dense.n], rtol=1e-5)

    @pytest.mark.parametrize("n,deg,width", [(200, 6.0, 128), (500, 10.0, 256)])
    def test_block_kernel_matches_ref(self, n, deg, width):
        g = rmat(n, int(n * deg / 2), skew=3, seed=n)
        rows, cols = edge_list(g)
        plan = ops.build_spmm_plan(rows, cols, g.n, kind="blocks")
        rng = np.random.default_rng(1)
        table = _random_table(rng, plan.n_pad, width, g.n)
        got = spmm_block_pallas(
            plan.block_rows,
            plan.block_cols,
            plan.patches,
            table,
            num_row_blocks=plan.n_pad // plan.block_size,
            interpret=True,
        )[: plan.n_pad]
        got = jnp.where(plan.written_mask[:, None], got, 0)
        eplan = ops.build_spmm_plan(rows, cols, g.n, kind="edges")
        want = ref.spmm_segment_ref(eplan.rows, eplan.cols, table, plan.n_pad - 1)[: plan.n_pad]
        np.testing.assert_allclose(got[: g.n], want[: g.n], rtol=1e-5)

    def test_xla_block_path_matches_edges_path(self):
        g = erdos_renyi(150, 7.0, seed=5)
        rows, cols = edge_list(g)
        bplan = ops.build_spmm_plan(rows, cols, g.n, kind="blocks")
        eplan = ops.build_spmm_plan(rows, cols, g.n, kind="edges")
        rng = np.random.default_rng(2)
        table = _random_table(rng, bplan.n_pad, 128, g.n)
        a = ops.spmm(bplan, table, impl="xla")
        b = ops.spmm(eplan, table, impl="xla")
        np.testing.assert_allclose(a[: g.n], b[: g.n], rtol=1e-6)

    @pytest.mark.parametrize("chunk_elements", [128 * 7, 128 * 64])
    def test_chunked_gather_scatter_matches_segment_sum(self, monkeypatch, chunk_elements):
        # chunks of 7 and 64 edges: several scan steps, with a padded tail
        monkeypatch.setattr(ops, "XLA_GATHER_ELEMENTS", chunk_elements)
        g = rmat(300, 2000, skew=8, seed=6)
        plan = ops.build_spmm_plan(*edge_list(g), g.n, kind="edges")
        table = _random_table(np.random.default_rng(6), plan.n_pad, 128, g.n)
        want = jax.ops.segment_sum(table[plan.cols], plan.rows, num_segments=plan.n_pad)
        got = ops.gather_scatter_add(table, plan.cols, plan.rows, plan.n_pad)
        np.testing.assert_allclose(got, want, rtol=1e-6)


def _undirected(g):
    rows, cols = edge_list(g)
    return np.stack([rows[rows < cols], cols[rows < cols]], 1)


def _star_with(n: int, hubs):
    """Vertex ``i`` of ``hubs`` joined to ``hubs[i]`` leaves of its own; the
    other vertices of ``n`` are isolated."""
    edges, leaf = [], len(hubs)
    for hub, degree in enumerate(hubs):
        edges += [(hub, leaf + j) for j in range(degree)]
        leaf += degree
    assert leaf <= n
    return from_edges(n, np.asarray(edges), "star")


class TestPieceSum:
    """The XLA neighbor sum over the piece layout: neighbor lists cut into
    ``tile_size`` pieces, summed by gathers and reductions, the piece sums
    placed by a scatter over pieces."""

    GRAPHS = {
        "rmat_skew8": lambda: rmat(300, 3000, skew=8, seed=6),
        # a hub of 400 neighbors spans four pieces, three of them full
        "star_hub": lambda: from_edges(
            401, np.concatenate([np.stack([np.zeros(400, int), np.arange(1, 401)], 1),
                                 _undirected(rmat(401, 300, skew=3, seed=1))]), "hub"),
        # degrees exactly tile_size and tile_size + 1
        "degree_tile_and_tile_plus_1": lambda: _star_with(300, [128, 129]),
        # 199 of the 300 vertices have no edge
        "isolated": lambda: _star_with(300, [3, 1, 40, 2, 50]),
    }

    @pytest.mark.parametrize(
        "case",
        ["rmat_skew8", "star_hub", "degree_tile_and_tile_plus_1", "isolated", "vmap",
         "compact", "chunked"],
    )
    def test_matches_segment_sum(self, monkeypatch, case):
        g = self.GRAPHS.get(case, self.GRAPHS["rmat_skew8"])()
        if case == "chunked":  # 448 / width pieces a chunk: the wide groups loop, with tails
            monkeypatch.setattr(ops, "XLA_GATHER_ELEMENTS", 128 * 7 * 64)
        plan = ops.build_spmm_plan(*edge_list(g), g.n, kind="edges")
        assert plan.tile_size == 128
        rng = np.random.default_rng(7)
        # small integers: every order of the same terms sums to the same float
        tables = rng.integers(0, 10, (3, plan.n_pad, 128)).astype(np.float32)
        tables[:, g.n :] = 0.0
        want = np.stack([
            np.asarray(ref.spmm_segment_ref(plan.rows, plan.cols, t, plan.n_pad - 1))[: g.n]
            for t in tables
        ])
        spmm = jax.jit(lambda p, t: ops.spmm(p, t, impl="xla"))
        if case == "vmap":
            got = jax.jit(jax.vmap(lambda p, t: ops.spmm(p, t, impl="xla"), (None, 0)))(
                plan, jnp.asarray(tables))
        elif case == "compact":
            # a frontier of every third row: the compact table holds those
            # rows, then one zero slot, and the indirection maps the rest there
            active = np.arange(0, g.n, 3)
            tables[:, np.setdiff1d(np.arange(plan.n_pad), active)] = 0.0
            want = np.stack([
                np.asarray(ref.spmm_segment_ref(plan.rows, plan.cols, t, plan.n_pad - 1))[: g.n]
                for t in tables
            ])
            inv = np.full(plan.n_pad, len(active), np.int32)
            inv[active] = np.arange(len(active))
            compact = np.concatenate([tables[:, active], np.zeros((3, 1, 128), np.float32)], 1)
            got = np.stack([
                np.asarray(ops.spmm_compact(plan, jnp.asarray(c), jnp.asarray(inv), impl="xla"))
                for c in compact
            ])
        else:
            got = np.stack([np.asarray(spmm(plan, jnp.asarray(t))) for t in tables])
        np.testing.assert_array_equal(np.asarray(got)[:, : g.n], want)

    def test_layout_cuts_lists_at_tile_size(self):
        g = _star_with(700, [128, 129, 400, 5])
        rows, cols = edge_list(g)
        piece_cols, piece_rows = ops.build_piece_layout(rows, cols, 768, 128, sentinel_col=700)
        widths = [c.shape[1] for c in piece_cols]
        assert widths == sorted(widths) and all(w & (w - 1) == 0 and w <= 128 for w in widths)
        owner = np.concatenate(piece_rows)
        # pieces per vertex: ceil(degree / 128); leaves one each
        assert np.bincount(owner, minlength=768)[:4].tolist() == [1, 2, 4, 1]
        assert all(np.all(np.diff(r) >= 0) for r in piece_rows)
        # every edge sits in exactly one slot, beside the pads
        slots = np.concatenate([c.ravel() for c in piece_cols])
        assert sorted(slots[slots != 700].tolist()) == sorted(cols.tolist())

    @staticmethod
    def _scatter_rows(fn, *args):
        """Update rows (elements over the table width) of every scatter in
        ``fn``'s lowered HLO, loop bodies included."""
        txt = jax.jit(fn).lower(*args).as_text(dialect="hlo")
        shapes = dict(re.findall(r"(\S+) = \w+\[([\d,]*)\]", txt))
        out = []
        for upd in re.findall(r" scatter\([^,()]+, [^,()]+, ([^,()]+)\)", txt):
            out.append(math.prod(int(d) for d in shapes[upd.strip()].split(",")) // 128)
        return out

    @pytest.mark.parametrize("chunk_elements", [None, 128 * 64])
    def test_no_scatter_over_edges(self, monkeypatch, chunk_elements):
        """The in-core XLA neighbor sum scatters only piece sums: no scatter
        in its program updates as many rows as the graph has edges."""
        g = rmat(300, 3000, skew=8, seed=6)
        plan = ops.build_spmm_plan(*edge_list(g), g.n, kind="edges")
        edges = len(edge_list(g)[0])
        table = jnp.zeros((plan.n_pad, 128), jnp.float32)
        # the detector sees the edge scatter of the distributed path's sum
        over_edges = self._scatter_rows(
            lambda p, t: ops.gather_scatter_add(t, p.cols, p.rows, p.n_pad), plan, table)
        assert max(over_edges) >= edges
        if chunk_elements:
            monkeypatch.setattr(ops, "XLA_GATHER_ELEMENTS", chunk_elements)
        pieces = self._scatter_rows(lambda p, t: ops.spmm(p, t, impl="xla"), plan, table)
        assert pieces and max(pieces) <= max(len(r) for r in plan.piece_rows) < edges / 4


class TestRouting:
    """``impl='auto'`` picks per op from the kernel's VMEM need."""

    @pytest.mark.parametrize(
        "tpu,need,want",
        [(True, 0, "pallas"), (True, ops.VMEM_LIMIT_BYTES + 1, "xla"),
         (False, 0, "xla"), (False, ops.VMEM_LIMIT_BYTES + 1, "xla")],
    )
    def test_auto_resolves_from_vmem_need(self, monkeypatch, tpu, need, want):
        monkeypatch.setattr(ops, "on_tpu", lambda: tpu)
        assert ops.resolve_impl("auto", need) == want
        assert ops.resolve_impl("xla", need) == "xla"

    def test_explicit_pallas_over_limit_raises(self):
        assert ops.resolve_impl("pallas", ops.VMEM_LIMIT_BYTES) == "pallas"
        with pytest.raises(ValueError, match="impl='pallas'.*VMEM"):
            ops.resolve_impl("pallas", ops.VMEM_LIMIT_BYTES + 1)

    @pytest.mark.parametrize("fuse", [False, True])
    def test_node_kernels_follow_table_size(self, monkeypatch, fuse):
        from repro.core.count_engine import build_counting_plan, node_kernels
        from repro.core.templates import template

        monkeypatch.setattr(ops, "on_tpu", lambda: True)
        g = rmat(2000, 6000, skew=3, seed=7)
        plan = build_counting_plan(g, template("u5-2"), impl="auto", fuse=fuse)
        small = node_kernels(plan)
        assert small and all("xla" not in c for c in small.values())
        # a limit below the edge/fused kernels' resident table, above the
        # row-tiled combine's step: only the table-resident ops move
        limit = ops.edge_tile_vmem_bytes(plan.n_pad, 128) // 2
        monkeypatch.setattr(ops, "VMEM_LIMIT_BYTES", limit)
        big = node_kernels(plan)
        assert big.keys() == small.keys()
        for choice in big.values():
            assert choice == ("fused=xla" if fuse else "spmm=xla combine=pallas")


class TestColorCombine:
    @pytest.mark.parametrize("k,t1,t2", [(5, 2, 2), (7, 3, 2), (10, 3, 3), (12, 4, 3)])
    def test_matches_ref(self, k, t1, t2):
        tables = ops.build_combine_tables(k, t1, t2)
        n_pad = 256
        a_pad = ops.pad_to(math.comb(k, t1), 128)
        b_pad = ops.pad_to(math.comb(k, t2), 128)
        rng = np.random.default_rng(k)
        left = jnp.asarray(rng.random((n_pad, a_pad)).astype(np.float32))
        m = jnp.asarray(rng.random((n_pad, b_pad)).astype(np.float32))
        got = color_combine_pallas(
            left, m, tables.idx1_t, tables.idx2_t, num_splits=tables.j, interpret=True
        )
        want = ref.color_combine_ref(left, m, tables.idx1, tables.idx2)
        np.testing.assert_allclose(got[:, : tables.s], want, rtol=1e-5)

    def test_xla_chunked_matches_einsum(self):
        # force the chunked path by a tiny chunk threshold
        tables = ops.build_combine_tables(9, 4, 3)
        n_pad = 128
        rng = np.random.default_rng(3)
        left = jnp.asarray(rng.random((n_pad, ops.pad_to(math.comb(9, 4), 128))).astype(np.float32))
        m = jnp.asarray(rng.random((n_pad, ops.pad_to(math.comb(9, 3), 128))).astype(np.float32))
        want = ref.color_combine_ref(left, m, tables.idx1, tables.idx2)

        def chunked(jc=5):
            s, j = tables.idx1.shape
            acc = jnp.zeros((n_pad, s), jnp.float32)
            for j0 in range(0, j, jc):
                i1 = tables.idx1[:, j0 : j0 + jc]
                i2 = tables.idx2[:, j0 : j0 + jc]
                acc = acc + jnp.einsum("vsj,vsj->vs", left[:, i1], m[:, i2])
            return acc

        np.testing.assert_allclose(chunked(), want, rtol=1e-5)


def _iter_eqns(jaxpr):
    """All equations of a jaxpr, recursing into sub-jaxprs (scan/cond/...)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    def subs(v):
        if isinstance(v, Jaxpr):
            return [v]
        if isinstance(v, ClosedJaxpr):
            return [v.jaxpr]
        if isinstance(v, (tuple, list)):
            return [s for item in v for s in subs(item)]
        return []

    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in subs(val):
                yield from _iter_eqns(sub)


class TestFusedCount:
    """Fused SpMM->combine vs the unfused oracle, k in {3, 5, 7, 10}."""

    CASES = [(3, 1, 1), (5, 2, 2), (7, 3, 2), (10, 4, 3)]

    def _setup(self, k, t1, t2, n=150, deg=6.0, lane=128):
        g = erdos_renyi(n, deg, seed=k)
        plan = ops.build_spmm_plan(*edge_list(g), g.n, kind="edges")
        tables = ops.build_combine_tables(k, t1, t2, lane=lane)
        rng = np.random.default_rng(k)
        a_pad = ops.pad_to(math.comb(k, t1), lane)
        b_pad = ops.pad_to(math.comb(k, t2), lane)
        left = _random_table(rng, plan.n_pad, a_pad, g.n)
        right = _random_table(rng, plan.n_pad, b_pad, g.n)
        return g, plan, tables, left, right

    @pytest.mark.parametrize("k,t1,t2", CASES)
    def test_pallas_matches_ref(self, k, t1, t2):
        g, plan, tbl, left, right = self._setup(k, t1, t2)
        want = ref.fused_count_ref(plan.rows, plan.cols, left, right, tbl.idx1, tbl.idx2)
        got = fused_count_pallas(
            plan.slab_dst,
            plan.slab_cols,
            left,
            right,
            tbl.idx1_t,
            tbl.idx2_t,
            num_splits=tbl.j,
            slabs_per_block=plan.slabs_per_block,
            interpret=True,
        )
        np.testing.assert_allclose(got[: g.n, : tbl.s], want[: g.n], rtol=1e-5)

    @pytest.mark.parametrize("k,t1,t2", CASES)
    def test_xla_matches_ref(self, k, t1, t2):
        g, plan, tbl, left, right = self._setup(k, t1, t2, lane=1)
        want = ref.fused_count_ref(plan.rows, plan.cols, left, right, tbl.idx1, tbl.idx2)
        got = ops.fused_count(plan, left, right, tbl, impl="xla")
        np.testing.assert_allclose(got[: g.n, : tbl.s], want[: g.n], rtol=1e-5)

    def test_block_plan_falls_back(self):
        # a block-dense plan has no edge slabs; the wrapper must still give
        # the fused result via the two-step path
        k, t1, t2 = 5, 2, 2
        g = erdos_renyi(100, 6.0, seed=11)
        eplan = ops.build_spmm_plan(*edge_list(g), g.n, kind="edges")
        bplan = ops.build_spmm_plan(*edge_list(g), g.n, kind="blocks")
        tbl = ops.build_combine_tables(k, t1, t2)
        rng = np.random.default_rng(6)
        left = _random_table(rng, eplan.n_pad, 128, g.n)
        right = _random_table(rng, eplan.n_pad, 128, g.n)
        want = ops.fused_count(eplan, left, right, tbl, impl="xla")
        got = ops.fused_count(bplan, left, right, tbl, impl="xla")
        np.testing.assert_allclose(got[: g.n, : tbl.s], want[: g.n, : tbl.s], rtol=1e-5)

    def test_never_materializes_m(self):
        """The fused jaxpr has no [n_pad, B] intermediate; the unfused one
        does (which also proves the detector works)."""
        k, t1, t2 = 7, 2, 2  # C(7,2)=21 != C(7,4)=35: B and S shapes distinct
        g, plan, tbl, left, right = self._setup(k, t1, t2, n=300, deg=5.0, lane=1)
        b = right.shape[1]
        forbidden = (plan.n_pad, b)
        # test validity: neither the output nor the per-block edge-slab
        # gather may coincidentally have the forbidden shape
        assert tbl.s != b
        assert plan.slabs_per_block * plan.tile_size != plan.n_pad

        def shapes_of(fn):
            jaxpr = jax.make_jaxpr(fn)(left, right)
            return [tuple(v.aval.shape) for e in _iter_eqns(jaxpr.jaxpr) for v in e.outvars]

        fused = lambda l, r: ops.fused_count(plan, l, r, tbl, impl="xla")
        mask = (jnp.arange(plan.n_pad) < plan.n).astype(jnp.float32)[:, None]
        unfused = lambda l, r: ops.color_combine(
            l, ops.spmm(plan, r, impl="xla") * mask, tbl, impl="xla"
        )
        assert forbidden in shapes_of(unfused)  # detector sanity
        assert forbidden not in shapes_of(fused)

        # the Pallas kernel only ever allocates M as a [row_tile, B] VMEM
        # scratch: at the HBM level (top-level jaxpr; the interpret-mode
        # kernel internals emulate VMEM with host arrays and are not HBM
        # traffic) its only output is the [n_pad, S] table
        fused_p = lambda l, r: fused_count_pallas(
            plan.slab_dst,
            plan.slab_cols,
            l,
            r,
            tbl.idx1_t,
            tbl.idx2_t,
            num_splits=tbl.j,
            slabs_per_block=plan.slabs_per_block,
            interpret=True,
        )
        top = jax.make_jaxpr(fused_p)(left, right).jaxpr
        top_shapes = [tuple(v.aval.shape) for e in top.eqns for v in e.outvars]
        assert forbidden not in top_shapes
        assert (plan.n_pad, tbl.s_pad) in top_shapes  # the fused output


class TestFlashAttention:
    @pytest.mark.parametrize(
        "b,hq,hkv,l,d", [(1, 4, 4, 256, 64), (2, 8, 2, 128, 64), (1, 6, 2, 384, 128)]
    )
    def test_causal_matches_ref(self, b, hq, hkv, l, d):
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal((b, hq, l, d)).astype(np.float32))
        k = jnp.asarray(rng.standard_normal((b, hkv, l, d)).astype(np.float32))
        v = jnp.asarray(rng.standard_normal((b, hkv, l, d)).astype(np.float32))
        got = flash_attention_pallas(q, k, v, causal=True, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("window", [64, 128, 200])
    def test_sliding_window(self, window):
        rng = np.random.default_rng(1)
        b, h, l, d = 1, 2, 256, 64
        q = jnp.asarray(rng.standard_normal((b, h, l, d)).astype(np.float32))
        k = jnp.asarray(rng.standard_normal((b, h, l, d)).astype(np.float32))
        v = jnp.asarray(rng.standard_normal((b, h, l, d)).astype(np.float32))
        got = flash_attention_pallas(q, k, v, causal=True, window=window, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def test_bf16(self):
        rng = np.random.default_rng(2)
        b, h, l, d = 1, 2, 128, 64
        q = jnp.asarray(rng.standard_normal((b, h, l, d)), dtype=jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((b, h, l, d)), dtype=jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((b, h, l, d)), dtype=jnp.bfloat16)
        got = flash_attention_pallas(q, k, v, causal=True, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(
            got.astype(np.float32), want.astype(np.float32), rtol=5e-2, atol=5e-2
        )
