"""Single-device color-coding DP engine.

Pipeline per coloring iteration (Algorithm 1 of the paper):

1. sample a random coloring ``col(v) in {0..k-1}``;
2. leaf tables = one-hot of the coloring, ``[n_pad, k_pad]``;
3. for each internal partition node (topological order):
   ``M = spmm(A, C_right)`` (neighbor sum) then
   ``C_node = color_combine(C_left, M)`` (split-table contraction),
   with pad rows/cols re-masked — or, with ``fuse=True``, one
   ``ops.fused_count`` call that contracts each ``row_tile``-row block of
   ``M`` as soon as it is produced and never materializes the full
   ``[n_pad, B]`` neighbor sum (the paper's fine-grained pipeline, §3.2,
   at kernel granularity; see DESIGN.md §11);
4. colorful map count = ``sum_{v, S} C_root[v, S]`` (one column per color
   set of the template's size; the single full-set column when t == k).

Column padding is impl-dependent (``lane``): the Pallas kernels need
128-lane-aligned tables, while the XLA paths run at true table widths —
on CPU/GPU that alone removes the 12.8x waste of padding the k-wide leaf
tables to 128 columns.

Batched colorings: the outer color-coding loop is embarrassingly parallel,
so ``count_fn(plan, batch=B)`` evaluates B independent colorings per jit
call (vmap over the DP), amortizing dispatch and plan overheads across the
batch — the single-device mirror of the paper's multi-node outer loop.

Multi-template counting: :func:`build_multi_counting_plan` compiles a whole
template family into one deduplicated :class:`TemplateDag` (DESIGN.md §14)
and :func:`colorful_map_count_many` runs it as ONE table program per
coloring — every canonically-unique subtree table is computed once and
every template root reads its own entry, so counting N related templates
costs the unique-table work, not N independent chains.

The DP uses ``d = 1`` in the recurrence and divides the final count by
``|Aut(T)|`` once — equivalent to the paper's per-step over-counting factor
(see DESIGN.md §1) and exactly testable against the brute-force oracle.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels import ops
from repro.testing import faults
from .frontier import (
    DEFAULT_CAPACITY_FACTOR,
    DEFAULT_DENSITY_THRESHOLD,
    CompactionSpec,
    make_frontier_fn,
    single_device_compaction,
)
from .colorsets import excluded_color_mask
from .graphs import Graph, edge_list
from .table_program import (
    BagFns,
    leaf_table,
    local_node_fn,
    build_node_tables,
    root_count,
    run_table_program,
)
from .templates import (
    PartitionChain,
    Template,
    TemplateDag,
    Tree,
    automorphism_count,
    compile_templates,
    partition_tree,
    program_has_bags,
    template_program,
)

__all__ = [
    "CountingPlan",
    "MultiCountingPlan",
    "build_counting_plan",
    "build_edge_plan",
    "build_multi_counting_plan",
    "colorful_map_count",
    "colorful_map_count_checked",
    "colorful_map_count_many",
    "colorful_map_count_many_checked",
    "count_fn",
    "count_fn_many",
    "plan_sample_fn",
    "multi_sample_fn",
    "copy_scale",
    "node_kernels",
]


def copy_scale(k: int, t: int, aut: int) -> float:
    """Per-iteration estimator scale for a size-``t`` template counted with
    ``k`` colors: ``k^t (k-t)! / k! / |Aut|`` — the inverse probability that
    the t image vertices of a copy draw pairwise-distinct colors, divided by
    the rooted-map over-count.  Reduces to the paper's ``k^k / k! / |Aut|``
    when ``t == k``."""
    return (k ** t) * math.factorial(k - t) / math.factorial(k) / aut


@dataclasses.dataclass(frozen=True)
class CountingPlan:
    """Static data for jit: graph plan + per-node combine tables."""

    tree: Tree
    chain: PartitionChain
    k: int  # color budget (== tree.n unless n_colors widened it)
    n: int
    n_pad: int
    aut: int
    spmm_plan: ops.SpmmPlan
    combine: Dict[int, ops.CombineTables]  # internal node index -> tables
    widths: Dict[int, int]  # node index -> padded table width
    impl: str = "auto"
    #: route each internal node through the fused SpMM->combine path
    fuse: bool = False
    #: column padding multiple the tables were built with (128 = pallas)
    lane: int = 128
    #: active-frontier compaction spec (None = dense; DESIGN.md §15)
    compaction: Optional[CompactionSpec] = None
    #: dense host adjacency ``[n_pad, n]`` for pinned bag leaves (treewidth-2
    #: templates only; None for pure-tree programs — DESIGN.md §19)
    pin_adj: Optional[jax.Array] = None

    @property
    def scale(self) -> float:
        """Maps the colorful map count to the copy estimate."""
        return copy_scale(self.k, self.tree.n, self.aut)


@dataclasses.dataclass(frozen=True)
class MultiCountingPlan:
    """Static data for one-pass family counting: shared graph plan + the
    deduplicated template DAG's combine tables."""

    templates: Tuple[Tree, ...]
    dag: TemplateDag
    k: int  # shared color budget (max template size unless widened)
    n: int
    n_pad: int
    auts: Tuple[int, ...]
    spmm_plan: ops.SpmmPlan
    combine: Dict[int, ops.CombineTables]
    widths: Dict[int, int]
    impl: str = "auto"
    fuse: bool = False
    lane: int = 128
    compaction: Optional[CompactionSpec] = None
    pin_adj: Optional[jax.Array] = None

    @property
    def num_templates(self) -> int:
        return len(self.templates)

    @property
    def scales(self) -> Tuple[float, ...]:
        """Per-template copy-estimate scales (all against the shared k)."""
        return tuple(copy_scale(self.k, t.n, a) for t, a in zip(self.templates, self.auts))


def build_edge_plan(
    g: Graph, *, spmm_kind: str = "edges", tile_size: int = 128, block_size: int = 128
) -> ops.SpmmPlan:
    """The graph's device-resident neighbor-sum layout.  Plans built with
    ``spmm_plan=`` share one (every template on a resident graph)."""
    rows, cols = edge_list(g)
    return ops.build_spmm_plan(
        rows, cols, g.n, kind=spmm_kind, tile_size=tile_size, block_size=block_size
    )


def _resolve_lane(lane, impl):
    if lane is None:
        # Pallas kernels need 128-lane tables; XLA runs at true widths.
        lane = 128 if ops.resolve_impl(impl) == "pallas" else 1
    return lane


def _build_pin_adj(g: Graph, n_pad: int) -> jax.Array:
    """Dense ``[n_pad, n]`` float32 host adjacency for pinned bag leaves.

    Pad rows stay zero, so a pinned leaf's pad rows are zero without extra
    masking (the §15/§18 pad-row invariant holds for bag tables too)."""
    rows, cols = edge_list(g)
    a = np.zeros((n_pad, g.n), np.float32)
    a[np.asarray(rows), np.asarray(cols)] = 1.0
    return jnp.asarray(a)


def _maybe_compaction(
    g,
    program,
    combine,
    k,
    spmm_plan,
    compact,
    density_threshold,
    capacity_factor,
    probes,
):
    if not compact:
        return None
    if program_has_bags(program):
        # §15's boolean activity probe models tree combines only; bag-table
        # programs run dense (DESIGN.md §19 documents the bypass)
        return None
    return single_device_compaction(
        g, program, combine, k,
        n_pad=spmm_plan.n_pad,
        threshold=density_threshold,
        capacity_factor=capacity_factor,
        probes=probes,
        # the SpMM indirection needs edge slabs; a blocks plan has none
        has_edge_slabs=spmm_plan.slab_dst is not None,
    )


def build_counting_plan(
    g: Graph,
    tree: Tree,
    *,
    root: int = 0,
    spmm_kind: str = "edges",
    impl: str = "auto",
    fuse: bool = False,
    tile_size: int = 128,
    block_size: int = 128,
    lane: Optional[int] = None,
    n_colors: Optional[int] = None,
    compact: bool = False,
    density_threshold: float = DEFAULT_DENSITY_THRESHOLD,
    capacity_factor: float = DEFAULT_CAPACITY_FACTOR,
    probes: int = 2,
    spmm_plan: Optional[ops.SpmmPlan] = None,
) -> CountingPlan:
    """``n_colors`` widens the color budget past the template size (used to
    compare single-template runs against a family counted with shared k).

    ``compact=True`` probes per-node table densities at build time and
    compacts every node below ``density_threshold`` (DESIGN.md §15):
    combines contract only active rows, the SpMM/fused kernels read sparse
    right tables through the compact row-index indirection, and the
    capacity headroom is ``capacity_factor`` (overflow falls back to the
    dense program, bit-exactly).

    ``tree`` may be a :class:`Tree` or a :class:`Template`: tree-shaped
    templates take the classic :func:`partition_tree` path bit-identically,
    non-trees compile to an apex-pinned bag program (DESIGN.md §19)."""
    if isinstance(tree, Template) and tree.is_tree:
        tree = tree.as_tree()
    chain = template_program(tree, root=root)
    has_bags = program_has_bags(chain)
    k = n_colors if n_colors is not None else tree.n
    if k < tree.n:
        raise ValueError(f"n_colors={k} is smaller than the template ({tree.n})")
    plan = spmm_plan or build_edge_plan(
        g, spmm_kind=spmm_kind, tile_size=tile_size, block_size=block_size
    )
    lane = _resolve_lane(lane, impl)
    combine, widths = build_node_tables(chain, k, lane=lane, x_dim=g.n if has_bags else None)
    compaction = _maybe_compaction(
        g,
        chain,
        combine,
        k,
        plan,
        compact,
        density_threshold,
        capacity_factor,
        probes,
    )
    return CountingPlan(
        tree=tree,
        chain=chain,
        k=k,
        n=g.n,
        n_pad=plan.n_pad,
        aut=automorphism_count(tree),
        spmm_plan=plan,
        combine=combine,
        widths=widths,
        impl=impl,
        fuse=fuse,
        lane=lane,
        compaction=compaction,
        pin_adj=_build_pin_adj(g, plan.n_pad) if has_bags else None,
    )


def build_multi_counting_plan(
    g: Graph,
    templates: Sequence,
    *,
    roots: Optional[Sequence[int]] = None,
    spmm_kind: str = "edges",
    impl: str = "auto",
    fuse: bool = False,
    tile_size: int = 128,
    block_size: int = 128,
    lane: Optional[int] = None,
    n_colors: Optional[int] = None,
    compact: bool = False,
    density_threshold: float = DEFAULT_DENSITY_THRESHOLD,
    capacity_factor: float = DEFAULT_CAPACITY_FACTOR,
    probes: int = 2,
    spmm_plan: Optional[ops.SpmmPlan] = None,
) -> MultiCountingPlan:
    """One plan for a whole template family: compile the set into a shared
    :class:`TemplateDag` and build each unique node's combine tables once."""
    dag = compile_templates(templates, n_colors=n_colors, roots=roots)
    has_bags = program_has_bags(dag)
    plan = spmm_plan or build_edge_plan(
        g, spmm_kind=spmm_kind, tile_size=tile_size, block_size=block_size
    )
    lane = _resolve_lane(lane, impl)
    combine, widths = build_node_tables(dag, dag.k, lane=lane, x_dim=g.n if has_bags else None)
    compaction = _maybe_compaction(
        g,
        dag,
        combine,
        dag.k,
        plan,
        compact,
        density_threshold,
        capacity_factor,
        probes,
    )
    return MultiCountingPlan(
        templates=dag.templates,
        dag=dag,
        k=dag.k,
        n=g.n,
        n_pad=plan.n_pad,
        auts=tuple(automorphism_count(t) for t in dag.templates),
        spmm_plan=plan,
        combine=combine,
        widths=widths,
        impl=impl,
        fuse=fuse,
        lane=lane,
        compaction=compaction,
        pin_adj=_build_pin_adj(g, plan.n_pad) if has_bags else None,
    )


def node_kernels(plan) -> Dict[int, str]:
    """Per internal node of the dense program, the implementation each of
    its ops runs on this backend — the choice ``ops`` makes from the
    node's table shapes (:func:`repro.kernels.ops.resolve_impl`)."""
    program = plan.chain if isinstance(plan, CountingPlan) else plan.dag
    sp = plan.spmm_plan
    out: Dict[int, str] = {}
    for i, nd in enumerate(program.nodes):
        if nd.kind not in ("combine", "bag_combine"):
            continue
        tbl = plan.combine[i]
        a, b = plan.widths[nd.left], plan.widths[nd.right]
        if nd.kind == "combine" and plan.fuse and sp.slab_dst is not None:
            out[i] = "fused=" + ops.fused_impl(a, b, tbl, plan.impl, sp.n_pad, sp.row_tile)
            continue
        x = plan.n if nd.kind == "bag_combine" else 1  # combine runs per x block
        out[i] = (f"spmm={ops.spmm_impl(sp, b, plan.impl)} "
                  f"combine={ops.combine_impl(a // x, b // x, tbl, plan.impl)}")
    return out


def _program_counts(plan, program, coloring: jax.Array, *, checked=False):
    """Run ``program`` on one coloring; per-root colorful map counts.

    ``checked=True`` engages the plan's compaction spec and additionally
    returns the AND of every no-overflow flag — ``False`` means at least
    one static capacity overflowed and the counts must be recomputed on the
    dense program (the caller's responsibility; see :func:`count_fn`).
    """
    n_pad = plan.n_pad
    row_mask = (jnp.arange(n_pad) < plan.n).astype(jnp.float32)[:, None]
    k_pad = ops.pad_to(plan.k, plan.lane)
    leaf = leaf_table(coloring, k_pad, row_mask)
    bag = _bag_fns(plan, program, coloring, leaf) if program_has_bags(program) else None
    spec = plan.compaction if checked else None
    if spec is not None and spec.enabled:
        flags: list = []
        frontier_fn = make_frontier_fn(spec.table_caps, plan.n, flags)
        node_fn = local_node_fn(
            plan.spmm_plan,
            row_mask,
            impl=plan.impl,
            fuse=plan.fuse,
            compaction=spec,
            sentinel_row=plan.n,
            flags=flags,
        )
        roots = run_table_program(
            program,
            plan.combine,
            leaf,
            row_mask,
            node_fn,
            root_fn=root_count,
            frontier_fn=frontier_fn,
        )
        ok = jnp.bool_(True)
        for f in flags:
            ok = jnp.logical_and(ok, f)
        return roots, ok
    node_fn = local_node_fn(plan.spmm_plan, row_mask, impl=plan.impl, fuse=plan.fuse)
    if bag is not None:
        node_fn = _bag_node_fn(plan, program, row_mask, node_fn)
    roots = run_table_program(
        program, plan.combine, leaf, row_mask, node_fn, root_fn=root_count, bag=bag
    )
    return (roots, jnp.bool_(True)) if checked else roots


def _bag_node_fn(plan, program, row_mask, base_fn):
    """Wrap the in-core neighbor-sum strategy for ``bag_combine`` nodes.

    A bag table ``[rows, x * W]`` is, row-major, ``x`` contiguous blocks of
    width ``W`` per vertex row — so the whole-graph SpMM applies unchanged
    (it is width-agnostic), and the color convolution runs on the exact
    ``[rows * x, W]`` reshape.  Fusion is bypassed per bag node (the fused
    kernel contracts over vertex rows and cannot align the ``(v, x)`` pair
    axis); tree nodes of a mixed program keep their fused path.
    """
    x_dim = plan.n

    def node_fn(i, tbl, c_left, c_right, f_left, f_right):
        if program.nodes[i].kind != "bag_combine":
            return base_fn(i, tbl, c_left, c_right, f_left, f_right)
        m = ops.spmm(plan.spmm_plan, c_right, impl=plan.impl) * row_mask
        rows = c_left.shape[0]
        lhs = c_left.reshape(rows * x_dim, -1)
        rhs = m.reshape(rows * x_dim, -1)
        out = ops.color_combine(lhs, rhs, tbl, impl=plan.impl)
        return out.reshape(rows, x_dim * tbl.s_pad)

    return node_fn


def _bag_fns(plan, program, coloring: jax.Array, leaf: jax.Array) -> BagFns:
    """In-core strategy for the bag-only node kinds (DESIGN.md §19)."""
    n_pad, x_dim = plan.n_pad, plan.n
    k_pad = leaf.shape[1]
    pin_adj = plan.pin_adj  # [n_pad, n]; pad rows zero
    coloring_x = coloring[: plan.n]  # the x axis is the real host vertices

    def leaf_fn(i, nd):
        if nd.pin:
            t = leaf[:, None, :] * pin_adj[:, :, None]
        else:
            t = jnp.broadcast_to(leaf[:, None, :], (n_pad, x_dim, k_pad))
        return t.reshape(n_pad, x_dim * k_pad)

    def collapse_fn(i, child):
        w = child.shape[1] // x_dim
        r = child.reshape(n_pad, x_dim, w).sum(axis=0)  # pad v-rows are zero
        t = program.nodes[i].size
        filt = excluded_color_mask(plan.k, t)  # [k, C(k, t)]
        filt_pad = np.zeros((plan.k, w), np.float32)
        filt_pad[:, : filt.shape[1]] = filt
        # keep only the color sets that exclude the apex color col(x)
        return r * jnp.asarray(filt_pad)[coloring_x]

    def join_fn(i, tbl, left, right):
        return ops.color_combine(left, right, tbl, impl=plan.impl)

    return BagFns(leaf_fn, collapse_fn, join_fn)


def colorful_map_count(plan: CountingPlan, coloring: jax.Array) -> jax.Array:
    """Number of colorful rooted embedding maps for one coloring.

    ``coloring``: int32 [n_pad] (entries past plan.n ignored).
    Differentiable-free pure function of the coloring; jit with
    ``jax.jit(functools.partial(colorful_map_count, plan))`` or use
    :func:`count_fn`.  The DP itself is the shared table program
    (:mod:`repro.core.table_program`) with the ``local`` (whole-graph SpMM)
    neighbor-sum strategy.  Always executes the dense program — the
    compact path (which needs its overflow flag consumed) is
    :func:`colorful_map_count_checked`.
    """
    return _program_counts(plan, plan.chain, coloring)[0]


def colorful_map_count_checked(
    plan: CountingPlan, coloring: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Compact-path count plus its no-overflow flag ``(maps, ok)``.

    When ``ok`` is False some static capacity overflowed and ``maps`` is
    not trustworthy — recompute with :func:`colorful_map_count` (dense);
    when True the value is bit-identical to the dense program's.
    """
    roots, ok = _program_counts(plan, plan.chain, coloring, checked=True)
    return roots[0], ok


def colorful_map_count_many(plan: MultiCountingPlan, coloring: jax.Array) -> jax.Array:
    """Per-template colorful map counts ``[num_templates]`` for ONE coloring.

    One pass over the deduplicated DAG: shared subtree tables are computed
    once; each template root reduces to its own count.  Dense program (see
    :func:`colorful_map_count`); the compact path is
    :func:`colorful_map_count_many_checked`.
    """
    return jnp.stack(_program_counts(plan, plan.dag, coloring))


def colorful_map_count_many_checked(
    plan: MultiCountingPlan, coloring: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Family analogue of :func:`colorful_map_count_checked`."""
    roots, ok = _program_counts(plan, plan.dag, coloring, checked=True)
    return jnp.stack(roots), ok


def _checked_fallback(compact_fn, make_dense):
    """Host-side overflow fallback around a jitted compact counter.

    The compact program is speculative: it returns its no-overflow flag
    alongside the counts, and on the rare batch where a static capacity
    overflowed the whole batch is re-dispatched on the lazily-built dense
    twin — bit-identical results either way, since the compact path equals
    the dense path exactly whenever its flag holds.
    """
    state: Dict[str, object] = {}

    def f(key: jax.Array):
        maps, est, ok = compact_fn(key)
        # the fault site forces an overflow storm so tests drive the dense
        # twin (and its interaction with resume) without a lucky coloring
        forced = faults.fire("compaction.overflow") is not None
        if not forced and bool(np.all(np.asarray(ok))):
            return maps, est
        fd = state.get("dense")
        if fd is None:
            fd = state["dense"] = make_dense()
        return fd(key)

    return f


def _jit_counter(plan, f):
    """``jax.jit(f)`` over ``key`` with the plan's edge layout passed as an
    argument (never a closed-over constant, see :class:`ops.SpmmPlan`).
    The returned callable also exposes ``lower(key)`` for memory analysis.
    The jitted step is named ``count_batch``: its module is
    ``jit_count_batch`` in the compiler's output and the device trace."""

    def count_batch(sp, key: jax.Array):
        return f(dataclasses.replace(plan, spmm_plan=sp), key)

    jf = jax.jit(count_batch)

    def call(key: jax.Array):
        return jf(plan.spmm_plan, key)

    call.lower = lambda key: jf.lower(plan.spmm_plan, key)
    return call


def _draw_colorings(key: jax.Array, shape, k: int) -> jax.Array:
    """A step's colorings, ``randint(key, shape, 0, k)``, under the device
    scope ``coloring``."""
    with jax.named_scope("coloring"):
        return jax.random.randint(key, shape, 0, k, dtype=jnp.int32)


def count_fn(plan: CountingPlan, batch: Optional[int] = None):
    """Jitted per-iteration counter.

    ``batch=None``: returns ``f(key) -> (maps, estimate)`` scalars for one
    coloring (the original contract).  ``batch=B``: returns
    ``f(key) -> (maps[B], estimates[B])`` evaluating B independent colorings
    in one jit call — the colorings are embarrassingly parallel, so vmapping
    the DP amortizes dispatch and SpMM-plan constant overheads across the
    batch.

    A compacted plan (``plan.compaction``) runs the active-frontier program
    and transparently re-dispatches the dense twin on capacity overflow
    (DESIGN.md §15) — the returned callable keeps the exact same contract.
    """
    compact = plan.compaction is not None and plan.compaction.enabled
    count1 = colorful_map_count_checked if compact else (
        lambda p, c: (colorful_map_count(p, c), None)
    )

    if batch is None:

        def f(p, key: jax.Array):
            coloring = _draw_colorings(key, (p.n_pad,), p.k)
            maps, ok = count1(p, coloring)
            return (maps, maps * p.scale) if ok is None else (maps, maps * p.scale, ok)

    else:

        def f(p, key: jax.Array):
            colorings = _draw_colorings(key, (batch, p.n_pad), p.k)
            maps, ok = jax.vmap(lambda c: count1(p, c))(colorings)
            return (maps, maps * p.scale) if not compact else (maps, maps * p.scale, ok)

    if not compact:
        return _jit_counter(plan, f)
    dense_plan = dataclasses.replace(plan, compaction=None)
    return _checked_fallback(_jit_counter(plan, f), lambda: count_fn(dense_plan, batch))


def count_fn_many(plan: MultiCountingPlan, batch: Optional[int] = None):
    """Jitted family counter: ``f(key) -> (maps, estimates)`` with shapes
    ``[R]`` (``batch=None``) or ``[B, R]`` — the same key-derived colorings
    as :func:`count_fn` with ``n_colors=plan.k``, so a family run and a
    per-template run from the same key see identical colorings.  Compacted
    plans fall back to the dense twin on overflow, like :func:`count_fn`."""
    scales = np.asarray(plan.scales, np.float32)
    compact = plan.compaction is not None and plan.compaction.enabled
    count1 = colorful_map_count_many_checked if compact else (
        lambda p, c: (colorful_map_count_many(p, c), None)
    )

    if batch is None:

        def f(p, key: jax.Array):
            coloring = _draw_colorings(key, (p.n_pad,), p.k)
            maps, ok = count1(p, coloring)
            return (maps, maps * scales) if ok is None else (maps, maps * scales, ok)

    else:

        def f(p, key: jax.Array):
            colorings = _draw_colorings(key, (batch, p.n_pad), p.k)
            maps, ok = jax.vmap(lambda c: count1(p, c))(colorings)
            return (maps, maps * scales[None, :]) if not compact else (
                maps, maps * scales[None, :], ok
            )

    if not compact:
        return _jit_counter(plan, f)
    dense_plan = dataclasses.replace(plan, compaction=None)
    return _checked_fallback(
        _jit_counter(plan, f), lambda: count_fn_many(dense_plan, batch)
    )


def _cached_sampler(make_fn):
    cache: Dict[int, object] = {}

    def sample(key: jax.Array, batch: int) -> np.ndarray:
        f = cache.get(batch)
        first = f is None
        if first:
            f = cache[batch] = make_fn(batch)
        with obs.span("sample.dispatch", batch=batch, first=first):
            _, est = f(key)
        with obs.span("sample.wait"):  # the device's finish and the copy to the host
            return np.asarray(est, np.float64)

    return sample


def plan_sample_fn(plan: CountingPlan):
    """Adapt a single-device plan to the backend ``sample_fn`` protocol.

    The protocol (shared with the distributed backend and consumed by
    :func:`repro.core.estimator.estimate_counts`) is
    ``sample_fn(key, batch) -> float64 [batch]`` copy estimates for ``batch``
    independent colorings derived from ``key``.  Compiled ``count_fn``
    closures are cached per batch size so repeated calls reuse the jit cache.
    """
    sample = _cached_sampler(lambda b: count_fn(plan, batch=b))

    def sample1(key: jax.Array, batch: int) -> np.ndarray:
        return sample(key, batch).reshape(-1)

    return sample1


def multi_sample_fn(plan: MultiCountingPlan):
    """The family variant of the protocol: ``sample_fn(key, batch) ->
    float64 [batch, num_templates]`` per-coloring copy estimates, consumed
    by :func:`repro.core.estimator.estimate_counts_many`."""
    sample = _cached_sampler(lambda b: count_fn_many(plan, batch=b))

    def sample_many(key: jax.Array, batch: int) -> np.ndarray:
        return sample(key, batch).reshape(batch, plan.num_templates)

    return sample_many
