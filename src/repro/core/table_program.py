"""One table program: THE partition-DP executor, shared by backends.

The color-coding DP is one *table program*: walk the partition nodes in
topological order, keep a table ``C_node [rows, width]`` per live node, and
at each internal node contract the left child against the neighbor sum of
the right child.  Until this module existed that recursion was written
twice — once in ``count_engine`` (in-core) and once inside ``distributed``
(shard_map) — and the two copies had already drifted (fusion, true-width
tables, and batched colorings only worked in-core).

Now the recursion lives here, once, over a *program* — either a single
template's :class:`~repro.core.templates.PartitionChain` or a whole family
compiled into a :class:`~repro.core.templates.TemplateDag` (deduplicated by
rooted-canonical subtree signature, so canonically-identical subtrees across
templates are computed once and read many times).  The backends differ only
in their **neighbor-sum strategy** — the ``node_fn`` callback that produces
one internal node's (unmasked) output table:

``local`` (:func:`local_node_fn`)
    ``M = spmm(A, C_right)`` over the whole in-core graph, or the fused
    SpMM->combine kernel that never materializes ``M``.

``exchange`` (built inside :mod:`repro.core.distributed`)
    ``M`` assembled from remote shards via one of the four exchange modes
    (``alltoall``/``pipeline``/``adaptive``/``ring``), consumed through the
    §3.3 tiled bucket layout — the same edge-tile/fused kernels, per chunk.

The executor owns everything the strategies must agree on: leaf
construction, pad-row/pad-column re-masking after every combine, table
lifetime (reference-counted: a table is freed the moment its last reader —
parent node or root delivery — has consumed it, the paper's sub-template
table lifetime management generalized to shared tables), and the root
reduction.  A strategy cannot forget to mask or leak a table; the backends
cannot drift.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, List, Mapping, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import ops
from .frontier import (
    CompactionSpec,
    Frontier,
    compact_combine,
    inverse_map,
)

__all__ = [
    "build_node_tables",
    "leaf_table",
    "run_table_program",
    "root_count",
    "local_node_fn",
    "BagFns",
]

#: strategy signature: (node_index, combine_tables, c_left, c_right,
#: f_left, f_right) -> unmasked output table [rows, >= s_pad] for that
#: internal node.  ``f_left``/``f_right`` are the children's
#: :class:`~repro.core.frontier.Frontier` records (None when dense).
NodeFn = Callable[
    [
        int,
        ops.CombineTables,
        jax.Array,
        jax.Array,
        Optional[Frontier],
        Optional[Frontier],
    ],
    jax.Array,
]

#: frontier hook: (node_index, masked table) -> Frontier or None; computed
#: once per produced table, shared by every consumer (see core.frontier)
FrontierFn = Callable[[int, jax.Array], Optional[Frontier]]


class BagFns(NamedTuple):
    """Backend strategy for the three bag-only node kinds (DESIGN.md §19).

    ``bag_combine`` nodes flow through the ordinary ``node_fn`` — the
    backend's neighbor-sum strategy reshapes ``[rows, x*W]`` tables to
    ``[rows*x, W]`` around its color convolution — so only the kinds with
    no tree analogue need callbacks here:

    * ``leaf_fn(i, nd)`` — build the bag leaf table ``[rows, x * k_pad]``
      (``pin=True`` multiplies the one-hot by the apex adjacency).
    * ``collapse_fn(i, child)`` — sum the finished forest-tree table over
      its vertex rows and apply the apex-color filter; returns ``[x, W]``.
    * ``join_fn(i, tbl, left, right)`` — disjoint color-set convolution of
      two collapsed ``[x, W]`` tables on aligned rows.
    """

    leaf_fn: Callable[[int, object], jax.Array]
    collapse_fn: Callable[[int, jax.Array], jax.Array]
    join_fn: Callable[[int, ops.CombineTables, jax.Array, jax.Array], jax.Array]


def build_node_tables(
    program, k: int, *, lane: int = 128, x_dim: Optional[int] = None
) -> Tuple[Dict[int, ops.CombineTables], Dict[int, int]]:
    """Per-node split tables + padded widths for one table program.

    ``program`` is a :class:`PartitionChain` or :class:`TemplateDag` (any
    object with ``.nodes`` of partition nodes).  ``lane`` is the
    column-padding multiple (128 for the Pallas kernels, 1 for true-width
    XLA tables).  Shared by both plan builders.

    ``x_dim`` (the host vertex count) is required when the program carries
    bag nodes: their stored tables are ``[rows, x_dim * W]`` row-major over
    the pinned-apex axis, so the recorded width is the *stored* column
    count — ``x_dim`` per-x blocks of the lane-padded block width ``W``.
    Collapsed/joined tables live on the ``x`` axis itself (one block wide).

    While :mod:`repro.obs` records, counts the columns of the tree tables
    that the program's neighbor sums read: ``neighbor_sum.columns_true``
    (``C(k, t)`` per right child) and ``neighbor_sum.columns_stored`` (its
    padded width).
    """
    with obs.span("plan.node_tables"):
        combine, widths = _node_tables(program, k, lane, x_dim)
    for nd in program.nodes:
        if nd.kind == "combine":
            obs.count("neighbor_sum.columns_true", math.comb(k, program.nodes[nd.right].size))
            obs.count("neighbor_sum.columns_stored", widths[nd.right])
    return combine, widths


def _node_tables(program, k, lane, x_dim):
    combine: Dict[int, ops.CombineTables] = {}
    widths: Dict[int, int] = {}
    for i, nd in enumerate(program.nodes):
        kind = nd.kind
        if kind in ("bag_leaf", "bag_combine", "bag_collapse", "bag_join"):
            if x_dim is None:
                raise ValueError("bag-node programs need x_dim (host vertex count)")
        if kind == "leaf":
            widths[i] = ops.pad_to(k, lane)
        elif kind == "bag_leaf":
            widths[i] = ops.pad_to(k, lane) * x_dim
        elif kind == "bag_collapse":
            # per-x block of the child, on the x axis: one block wide
            widths[i] = widths[nd.left] // x_dim
        else:  # "combine" / "bag_combine" / "bag_join": a color convolution
            t1 = program.nodes[nd.left].size
            t2 = program.nodes[nd.right].size
            tables = ops.build_combine_tables(k, t1, t2, lane=lane)
            combine[i] = tables
            widths[i] = tables.s_pad * (x_dim if kind == "bag_combine" else 1)
    return combine, widths


def leaf_table(coloring: jax.Array, k_pad: int, row_mask: jax.Array) -> jax.Array:
    """Leaf tables: one-hot of the coloring, pad rows zeroed."""
    with jax.named_scope("leaf"):
        return jax.nn.one_hot(coloring, k_pad, dtype=jnp.float32) * row_mask


def run_table_program(
    program,
    combine: Mapping[int, ops.CombineTables],
    leaf: jax.Array,
    row_mask: jax.Array,
    node_fn: NodeFn,
    root_fn: Optional[Callable[[jax.Array], jax.Array]] = None,
    frontier_fn: Optional[FrontierFn] = None,
    bag: Optional[BagFns] = None,
) -> tuple:
    """Execute a table program; returns one value per ``program.roots`` entry.

    ``program`` is a :class:`PartitionChain` (one root) or a
    :class:`TemplateDag` (one root per compiled template).  This is the only
    copy of the node recursion in the codebase.  Every leaf shares the
    single ``leaf`` table; each internal node's output from ``node_fn`` is
    re-masked (pad rows via ``row_mask``, pad columns past the node's true
    width) before anyone reads it.

    Table lifetime is reference-counted from ``program.table_reads()``:
    each child read and each root delivery decrements the count, and the
    table is dropped at zero — for a chain this is exactly the
    free-both-children-at-the-parent order; for a DAG a shared subtree
    table stays live only until its last reader (keeping XLA liveness
    tight while still computing every unique table once).

    ``root_fn`` (e.g. :func:`root_count`) reduces each root table to its
    delivered value as soon as the root node is built, so wide root tables
    of sub-``k``-sized templates never outlive their reduction; without it
    the masked root tables themselves are returned.

    ``frontier_fn`` threads active-row frontiers through the program
    (DESIGN.md §15): each produced table's frontier is computed once, lives
    exactly as long as the table, and reaches every consumer via the
    ``f_left``/``f_right`` arguments of ``node_fn`` — a DAG table read by
    several parents never recomputes its activity.

    ``bag`` supplies the backend strategy for the treewidth-2 node kinds
    (:class:`BagFns`); required iff the program carries bag nodes.  A
    ``bag_combine`` is the same neighbor-sum contraction as ``combine`` and
    flows through ``node_fn`` (whose strategy handles the ``x`` axis), but
    its column mask repeats per ``x`` block.  Collapse/join outputs live on
    the ``x`` axis — every row is a real host vertex — so the vertex-row
    ``row_mask`` does not apply to them.
    """
    reads = list(program.table_reads())
    want: Dict[int, int] = {}
    for r in program.roots:
        want[r] = want.get(r, 0) + 1
    tables: Dict[int, jax.Array] = {}
    frontiers: Dict[int, Frontier] = {}
    delivered: Dict[int, jax.Array] = {}
    for i, nd in enumerate(program.nodes):
        kind = nd.kind
        if kind.startswith("bag_") and bag is None:
            raise ValueError("program has bag nodes but no BagFns strategy")
        with jax.named_scope(f"node{i}"):
            if kind == "leaf":
                out = leaf  # leaves are dense: every vertex has a color
            elif kind == "bag_leaf":
                out = bag.leaf_fn(i, nd)
            elif kind == "bag_collapse":
                # strategy output is final (pad columns of the child are already
                # zero and survive the sum as zero); rows are the x axis
                out = bag.collapse_fn(i, tables[nd.left])
            elif kind == "bag_join":
                tbl = combine[i]
                raw = bag.join_fn(i, tbl, tables[nd.left], tables[nd.right])
                with jax.named_scope("mask"):
                    col_mask = (jnp.arange(raw.shape[1]) < tbl.s).astype(jnp.float32)[None, :]
                    out = raw * col_mask
            else:  # "combine" / "bag_combine": the neighbor-sum contraction
                tbl = combine[i]
                raw = node_fn(
                    i,
                    tbl,
                    tables[nd.left],
                    tables[nd.right],
                    frontiers.get(nd.left),
                    frontiers.get(nd.right),
                )
                with jax.named_scope("mask"):
                    if kind == "bag_combine":
                        # one true-width block per x: mask repeats every s_pad cols
                        col_mask = (jnp.arange(raw.shape[1]) % tbl.s_pad < tbl.s).astype(
                            jnp.float32
                        )[None, :]
                    else:
                        col_mask = (jnp.arange(raw.shape[1]) < tbl.s).astype(jnp.float32)[None, :]
                    out = raw * row_mask * col_mask
        # the children just had one read each consumed; free at zero
        # (left may equal right for symmetric splits — counted twice)
        for c in nd.children[::-1]:
            reads[c] -= 1
            if reads[c] == 0:
                tables.pop(c, None)
                frontiers.pop(c, None)
        if i in want:
            with jax.named_scope("root"):
                delivered[i] = root_fn(out) if root_fn is not None else out
            reads[i] -= want[i]
        if reads[i] > 0:
            tables[i] = out
            if frontier_fn is not None and kind == "combine":
                fr = frontier_fn(i, out)
                if fr is not None:
                    frontiers[i] = fr
    return tuple(delivered[r] for r in program.roots)


def root_count(root: jax.Array) -> jax.Array:
    """Colorful map count from a root table: ``sum_{v, S} C_root[v, S]``.

    For a full-``k`` template the root table has the single full-color-set
    column; for a sub-``k`` template (family counting) every color set of
    the template's size contributes one column, and each colorful embedding
    lands in exactly one of them.  Pad rows/columns are already masked to
    zero by the executor, so the plain sum is exact either way.
    """
    acc_dtype = jnp.float64 if root.dtype == jnp.float64 else jnp.float32
    return jnp.sum(root, dtype=acc_dtype)


def local_node_fn(
    spmm_plan: ops.SpmmPlan,
    row_mask: jax.Array,
    *,
    impl: str = "auto",
    fuse: bool = False,
    compaction: Optional[CompactionSpec] = None,
    sentinel_row: Optional[int] = None,
    flags: Optional[List[jax.Array]] = None,
) -> NodeFn:
    """The in-core neighbor-sum strategy: SpMM over the whole graph.

    With ``fuse=True`` each node is one ``ops.fused_count`` call that
    contracts every ``row_tile``-row block of ``M`` as soon as it is
    produced and never materializes the full ``[n_pad, B]`` neighbor sum
    (the paper's fine-grained pipeline, §3.2, at kernel granularity).

    With ``compaction`` (DESIGN.md §15): a right child carrying a frontier
    feeds the SpMM/fused kernels in compact ``[cap, B]`` form through the
    row-index indirection (``ops.spmm_compact`` / ``fused_count_compact``),
    and nodes with a ``combine_caps`` entry contract only the rows where
    both the left table and the neighbor sum are active
    (:func:`~repro.core.frontier.compact_combine`), appending their
    no-overflow flags to ``flags``.  A compacted node takes the two-step
    path even under ``fuse`` — skipping inactive rows beats skipping the
    ``M`` materialization once the table is sparse.
    """

    def compact_right(c_right, f_right):
        """(compact table, inverse map) when the indirection applies."""
        if f_right is None or f_right.idx is None or spmm_plan.slab_dst is None:
            return None, None
        table_c = jnp.take(c_right, f_right.idx, axis=0)
        inv = inverse_map(f_right.idx, c_right.shape[0], f_right.cap - 1)
        return table_c, inv

    def neighbor_sum(c_right, f_right):
        with jax.named_scope("neighbor_sum"):
            right_c, inv = compact_right(c_right, f_right)
            if right_c is not None:
                return ops.spmm_compact(spmm_plan, right_c, inv, impl=impl)
            return ops.spmm(spmm_plan, c_right, impl=impl)

    def node_fn(i, tbl, c_left, c_right, f_left, f_right):
        cap = compaction.combine_caps.get(i) if compaction is not None else None
        if cap is not None:
            m = neighbor_sum(c_right, f_right)
            with jax.named_scope("combine"):
                return compact_combine(
                    c_left,
                    m,
                    tbl,
                    cap,
                    sentinel_row,
                    impl,
                    flags,
                    left_mask=f_left.mask if f_left is not None else None,
                )
        if fuse:
            with jax.named_scope("fused"):
                right_c, inv = compact_right(c_right, f_right)
                if right_c is not None:
                    return ops.fused_count_compact(
                        spmm_plan, c_left, right_c, inv, tbl, impl=impl
                    )
                return ops.fused_count(spmm_plan, c_left, c_right, tbl, impl=impl)
        m = neighbor_sum(c_right, f_right)
        with jax.named_scope("neighbor_sum"):
            # mask pad rows of the neighbor sum before the combine
            m = m * row_mask
        with jax.named_scope("combine"):
            return ops.color_combine(c_left, m, tbl, impl=impl)

    return node_fn
