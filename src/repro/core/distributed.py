"""Distributed color-coding under ``shard_map`` — the paper's Algorithms 2/3.

The graph is vertex-partitioned in contiguous blocks over the ``data`` mesh
axis (combine with :func:`repro.core.graphs.relabel_random` for the paper's
random partition).  Count tables are row-sharded alongside.  The DP itself
is the shared table program (:mod:`repro.core.table_program`); this module
contributes the *exchange* neighbor-sum strategy: for each internal
partition node the neighbor sum needs remote rows of the child table, and
four exchange modes provide them:

``alltoall``  (paper: Naive)
    Compact per-pair request lists exchanged with one fused
    ``lax.all_to_all``; all P received chunks are materialized before any
    compute (peak memory O(P * R * B) — Eq. 7's pathology).  Because the
    whole buffer exists anyway, the consume is one call of the SAME
    edge-tile / fused SpMM->combine kernels as the in-core engine
    (``ops.spmm_slabs`` / ``ops.fused_count_slabs``) over the concatenated
    ``[P * r_pad, B]`` buffer — ``impl="pallas"`` and ``fuse=True`` route
    through ``kernels/spmm_edgetile.py`` / ``kernels/fused_count.py``.

``pipeline``  (paper: Pipeline, Algorithm 3)
    The same compact requests, but sent with W = ceil((P-1)/g) grouped
    ``ppermute`` steps; each step's transfer overlaps the previous chunk's
    consume (peak memory O(g * R * B) — Eq. 12).

``adaptive``  (paper: Adaptive)
    Per-sub-template trace-time choice between the two via the Hockney
    model + computation intensity (comm.adaptive; the paper's |T_i|
    switch).

``ring``  (beyond paper)
    Shift-by-one relay of whole table shards in a ``fori_loop``
    (O(1) program size in P).  Trades the compact request lists for relayed
    full shards; this is what lets the engine shard over hundreds of
    devices where the unrolled direct-send schedule would explode compile
    time.  See DESIGN.md §4.

**Tiled buckets (§3.3).**  The per-(shard, shard) edge buckets are stored
as fixed-size ``bucket_tile``-edge tiles with CSR-style offsets
(``tile_off[p, q]``), so plan memory is O(E + tiles) — independent of the
largest bucket — and every incremental consume task is one uniform tile:
a gather of ``bucket_tile`` chunk rows plus one bounded scatter-add,
regardless of degree skew.  (The seed layout padded every bucket to the
global max, [P, P, max_e]: memory and per-chunk work scaled with skew.)
With ``fuse=True`` the incremental modes exploit the combine's linearity
in ``M`` to accumulate each tile's contribution **directly into the output
table** — the full ``[n_loc_pad, B]`` neighbor sum never exists, the
paper's fine-grained pipeline (§3.2) stretched across exchange chunks.

**Compacted exchange (§15).**  With ``compact=True`` the plan probes each
node table's active-row density at build time (``core.frontier``) and,
for sufficiently sparse exchanged tables, ships only active rows:
capacity-padded ``[rc, B+1]`` per-peer slabs (rows + a bitcast slot
column) on alltoall/pipeline and ``[cap, B+1]`` compacted whole-shard
relays on ring.  The receiver scatters into the zero-initialized dense
buffer, so the tiled consume below is byte-for-byte the dense code, and a
psum'd overflow flag re-dispatches the dense twin when a static capacity
is exceeded — bit-exact either way.

**Narrow wire (§18).**  Counts are nonnegative integers held in float32,
so with ``wire_dtype="int16"``/``"int8"`` every exchange payload ships at
integer width — 2x/4x less wire than float32 — with a per-slab saturation
flag riding the same speculate-check-redispatch contract: on overflow the
batch re-runs one rung up the int8 -> int16 -> float32 -> dense ladder,
bit-exact always.  Compacted slabs replace their float32 slot column with
bit-packed activity-bitmap columns of the wire dtype (``comm.compress``);
the receiver re-derives the slot indices deterministically.  This
composes multiplicatively with compaction.

Iteration parallelism: the outer color-coding loop is embarrassingly
parallel, so independent colorings shard over a second mesh axis
(``iter_axis``), mirroring the paper's multi-node outer loop.

Family counting: :func:`build_distributed_plan` accepts a sequence of
templates and compiles them into one shared
:class:`~repro.core.templates.TemplateDag` (DESIGN.md §14) — the count
function then returns per-template count vectors from ONE table-program
pass per coloring, with cross-template subtree tables exchanged and
computed once.

Coloring sampling runs **on-device** when the key-based contract is used
(``make_count_fn(..., keyed=True)`` / :func:`keyed_sample_fn`): each shard
folds its data-axis index into the iteration key and draws only its own
rows, giving the distributed backend the same ``f(key)`` interface as the
single-device engine (see DESIGN.md §12).  Host-side colorings via
:func:`shard_coloring` remain supported for fixed-coloring parity tests.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.comm import (
    WIRE_DTYPES,
    WIRE_ESCALATION,
    HockneyModel,
    assumed_model,
    calibrate,
    choose_mode_full,
    grouped_exchange,
    mask_columns,
    mask_from_columns,
    narrow_cast,
    ring_allgather_overlap,
    widen,
)
from repro.compat import pvary_like
from repro.kernels import ops
from repro.testing import faults
from .count_engine import copy_scale
from .frontier import (
    DEFAULT_CAPACITY_FACTOR,
    DEFAULT_DENSITY_THRESHOLD,
    CompactionSpec,
    abstract_compaction,
    chunk_slots,
    compact_combine,
    decode_slots,
    distributed_compaction,
    encode_slots,
    make_frontier_fn,
    node_exchange_bytes,
)
from .colorsets import excluded_color_mask
from .graphs import Graph
from .table_program import (
    BagFns,
    build_node_tables,
    leaf_table,
    root_count,
    run_table_program,
)
from .templates import (
    Template,
    TemplateDag,
    Tree,
    automorphism_count,
    bag_program,
    compile_templates,
    partition_tree,
    program_has_bags,
)

__all__ = [
    "DistributedPlan",
    "build_distributed_plan",
    "place_plan",
    "make_count_fn",
    "keyed_sample_fn",
    "plan_route_report",
    "shard_coloring",
    "global_coloring",
]


@dataclasses.dataclass(frozen=True)
class DistributedPlan:
    #: the template family (a 1-tuple for single-template plans)
    templates: Tuple[Tree, ...]
    #: the table program: a PartitionChain (single template, the original
    #: contract) or a TemplateDag (family counting, DESIGN.md §14)
    program: object
    k: int
    n: int
    num_shards: int
    shard_size: int  # vertices per shard (last shard may be ragged)
    n_loc_pad: int  # padded local rows; row `shard_size` is the zero sentinel
    r_pad: int  # padded request-list length (slot r_pad-1 always a zero row)
    bucket_tile: int  # §3.3 task size: edges per bucket tile
    num_tiles: int  # T: per-shard tile-array height (uniform across shards)
    slabs_per_block: int  # alltoall slab layout (uniform across shards)
    auts: Tuple[int, ...]  # per-template |Aut|
    combine: Dict[int, ops.CombineTables]
    widths: Dict[int, int]
    # host-global arrays; sharded over dim 0 by the data axis.
    # build_distributed_plan leaves them on the host (numpy); :func:`place_plan` puts shard p's
    # slice on device p.  The bucket arrays are O(E + tiles): tiles are
    # addressed via CSR offsets, never padded to the largest bucket.
    tile_dst: jax.Array  # [P, T, tile] int32 local dst row (pad: shard_size)
    tile_src_local: jax.Array  # [P, T, tile] int32 src-shard-local row (ring)
    tile_src_compact: jax.Array  # [P, T, tile] int32 request slot (pipeline)
    tile_off: jax.Array  # [P, P+1] int32 CSR tile offsets by src shard
    send_idx: jax.Array  # [P, P, r_pad] int32: rows this shard sends to q
    a2a_slab_dst: jax.Array  # [P, NRB*spb, tile] int32 block-local dst (-1 pad)
    a2a_slab_cols: jax.Array  # [P, NRB*spb, tile] int32 col into [P*r_pad]
    bucket_counts: np.ndarray  # [P, P] true bucket sizes (diagnostics)
    #: active-frontier compaction spec (None = dense; DESIGN.md §15)
    compaction: Optional[CompactionSpec] = None
    #: sharded pinned-apex adjacency [P, n_loc_pad, n] (bag programs only;
    #: DESIGN.md §19) — row v_loc, column x is A[global(v), x]
    pin_adj: Optional[jax.Array] = None

    @property
    def tree(self) -> Tree:
        return self.templates[0]

    @property
    def aut(self) -> int:
        return self.auts[0]

    @property
    def num_templates(self) -> int:
        return len(self.templates)

    @property
    def is_multi(self) -> bool:
        """Family plans return per-template count vectors; single-template
        plans keep the original scalar-per-iteration contract."""
        return isinstance(self.program, TemplateDag)

    @property
    def scale(self) -> float:
        return copy_scale(self.k, self.templates[0].n, self.auts[0])

    @property
    def scales(self) -> Tuple[float, ...]:
        return tuple(copy_scale(self.k, t.n, a) for t, a in zip(self.templates, self.auts))

    @property
    def device_arrays(self) -> Tuple[jax.Array, ...]:
        """The per-shard plan arrays, in ``make_count_fn`` argument order."""
        base = (
            self.tile_dst,
            self.tile_src_local,
            self.tile_src_compact,
            self.tile_off,
            self.send_idx,
            self.a2a_slab_dst,
            self.a2a_slab_cols,
        )
        if self.pin_adj is not None:
            base = base + (self.pin_adj,)
        return base


_ARRAY_FIELDS = (
    "tile_dst", "tile_src_local", "tile_src_compact", "tile_off", "send_idx",
    "a2a_slab_dst", "a2a_slab_cols", "pin_adj",
)


def place_plan(
    plan: DistributedPlan, mesh: jax.sharding.Mesh, data_axis: str = "data"
) -> DistributedPlan:
    """The plan with every per-shard array laid out over ``data_axis`` of
    ``mesh``: shard p's slice lives on the devices of data index p (and is
    replicated over any other mesh axis).  Arrays already placed so are
    left where they are."""
    sharding = NamedSharding(mesh, P(data_axis))
    placed = {
        name: jax.device_put(getattr(plan, name), sharding)
        for name in _ARRAY_FIELDS
        if getattr(plan, name) is not None
    }
    return dataclasses.replace(plan, **placed)


def _resolve_program(tree, root: int, n_colors: Optional[int]):
    """One template -> its PartitionChain; a family -> the shared DAG.

    Returns ``(program, templates, k)``; ``n_colors`` widens the color
    budget past the (largest) template size.
    """
    if isinstance(tree, Template) and tree.is_tree:
        tree = tree.as_tree()
    if isinstance(tree, Tree):
        k = n_colors if n_colors is not None else tree.n
        if k < tree.n:
            raise ValueError(f"n_colors={k} is smaller than the template ({tree.n})")
        return partition_tree(tree, root=root), (tree,), k
    if isinstance(tree, Template):
        prog = bag_program(tree, n_colors=n_colors)
        return prog, (tree,), prog.k
    dag = compile_templates(tree, n_colors=n_colors)
    return dag, dag.templates, dag.k


def build_distributed_plan(
    g: Graph,
    tree,
    num_shards: int,
    *,
    root: int = 0,
    bucket_tile: int = 128,
    n_colors: Optional[int] = None,
    compact: bool = False,
    density_threshold: float = DEFAULT_DENSITY_THRESHOLD,
    capacity_factor: float = DEFAULT_CAPACITY_FACTOR,
    probes: int = 2,
) -> DistributedPlan:
    """``tree`` is a single :class:`Tree` (original contract) or a sequence
    of trees / template names — a family compiled into one shared
    :class:`TemplateDag` counted in a single pass per coloring.

    ``compact=True`` probes per-node table densities at build time
    (DESIGN.md §15) and, for every exchanged table below
    ``density_threshold``, ships only its active rows: capacity-padded
    per-peer slabs plus an index column on alltoall/pipeline, compacted
    whole-shard relays on ring — shrinking the wire volume of all four
    modes by the measured sparsity, with a bit-exact dense fallback on
    capacity overflow."""
    from .graphs import edge_list

    Pn = num_shards
    program, templates, k = _resolve_program(tree, root, n_colors)
    shard_size = (g.n + Pn - 1) // Pn
    n_loc_pad = ops.pad_to(shard_size + 1, 128)
    sentinel = shard_size

    rows, cols = edge_list(g)
    p_of = (rows // shard_size).astype(np.int64)
    q_of = (cols // shard_size).astype(np.int64)
    counts = np.zeros((Pn, Pn), np.int64)
    np.add.at(counts, (p_of, q_of), 1)

    # --- compact request lists + per-edge request slots -------------------
    # bucket (p, q): the distinct src-local rows device p requests from
    # device q (paper's C_{q,p}); slot_of[e] is edge e's index into them.
    key = p_of * Pn + q_of
    order = np.argsort(key, kind="stable")  # rows sorted -> dst-sorted buckets
    bkt_start = np.zeros(Pn * Pn + 1, np.int64)
    np.cumsum(np.bincount(key, minlength=Pn * Pn), out=bkt_start[1:])
    slot_of = np.zeros(len(rows), np.int64)
    uniq_lists = {}
    r_len = 0
    for pp in range(Pn):
        for qq in range(Pn):
            sel = order[bkt_start[pp * Pn + qq] : bkt_start[pp * Pn + qq + 1]]
            uniq, inv = np.unique(cols[sel] - qq * shard_size, return_inverse=True)
            uniq_lists[(pp, qq)] = uniq
            slot_of[sel] = inv
            r_len = max(r_len, len(uniq))
    # strict +1: slot r_pad-1 is a pad slot in EVERY chunk, so it always
    # carries the zero sentinel row — the tile/slab pad sentinel points there
    r_pad = ops.pad_to(r_len + 1, 128)
    send_idx = np.full((Pn, Pn, r_pad), sentinel, np.int32)
    for (pp, qq), u in uniq_lists.items():
        # device q sends rows u to device p: stored at send_idx[q, p]
        send_idx[qq, pp, : len(u)] = u

    # --- §3.3 tiled buckets: fixed-size tiles + CSR offsets ---------------
    tiles_per_dev = (-(-counts // bucket_tile)).sum(axis=1)
    num_tiles = max(1, int(tiles_per_dev.max(initial=0)))
    tile_dst = np.full((Pn, num_tiles, bucket_tile), sentinel, np.int32)
    tile_src_local = np.full((Pn, num_tiles, bucket_tile), sentinel, np.int32)
    tile_src_compact = np.full((Pn, num_tiles, bucket_tile), r_pad - 1, np.int32)
    tile_off = np.zeros((Pn, Pn + 1), np.int32)
    # --- alltoall slab layout over the concatenated exchange buffer -------
    dev_slice = np.searchsorted(p_of, np.arange(Pn + 1))
    dst_local_all = (rows - p_of * shard_size).astype(np.int64)
    concat_col_all = q_of * r_pad + slot_of
    spb = 1
    nrb_loc = n_loc_pad // 128
    for pp in range(Pn):
        sl = slice(dev_slice[pp], dev_slice[pp + 1])
        blk_counts = np.bincount(dst_local_all[sl] // 128, minlength=nrb_loc)
        spb = max(spb, int(-(-blk_counts.max(initial=0) // bucket_tile)))
    a2a_slab_dst = np.empty((Pn, nrb_loc * spb, bucket_tile), np.int32)
    a2a_slab_cols = np.empty((Pn, nrb_loc * spb, bucket_tile), np.int32)
    for pp in range(Pn):
        sl = slice(dev_slice[pp], dev_slice[pp + 1])
        # tiled buckets: stable sort by src shard keeps dst order per bucket
        sub = np.argsort(q_of[sl], kind="stable")
        td, (tsl, tsc), toff = ops.build_bucket_tiles(
            q_of[sl][sub],
            dst_local_all[sl][sub],
            ((cols[sl] - q_of[sl] * shard_size)[sub], slot_of[sl][sub]),
            Pn,
            bucket_tile,
            dst_sentinel=sentinel,
            src_sentinels=(sentinel, r_pad - 1),
            num_tiles=num_tiles,
        )
        tile_dst[pp], tile_src_local[pp], tile_src_compact[pp] = td, tsl, tsc
        tile_off[pp] = toff
        # alltoall slabs: this shard's edges (already dst-sorted), columns
        # pointing into the [P * r_pad] concatenated compact buffer
        sd, sc, _ = ops.build_slab_layout(
            dst_local_all[sl],
            concat_col_all[sl],
            n_loc_pad,
            bucket_tile,
            128,
            sentinel_col=r_pad - 1,
            slabs_per_block=spb,
        )
        a2a_slab_dst[pp], a2a_slab_cols[pp] = sd, sc

    has_bags = program_has_bags(program)
    combine, widths = build_node_tables(program, k, lane=128, x_dim=g.n if has_bags else None)

    pin_adj = None
    if has_bags:
        # sharded dense apex adjacency [P, n_loc_pad, n]: for the local row
        # holding global vertex v, column x is A[v, x] (pad rows all-zero)
        pa = np.zeros((Pn, n_loc_pad, g.n), np.float32)
        pa[p_of, rows - p_of * shard_size, cols] = 1.0
        pin_adj = pa

    compaction = None
    if compact and not has_bags:
        compaction = distributed_compaction(
            g,
            program,
            combine,
            k,
            num_shards=Pn,
            shard_size=shard_size,
            n_loc_pad=n_loc_pad,
            r_pad=r_pad,
            send_idx=send_idx,
            threshold=density_threshold,
            capacity_factor=capacity_factor,
            probes=probes,
        )

    return DistributedPlan(
        templates=templates,
        program=program,
        k=k,
        n=g.n,
        num_shards=Pn,
        shard_size=shard_size,
        n_loc_pad=n_loc_pad,
        r_pad=r_pad,
        bucket_tile=bucket_tile,
        num_tiles=num_tiles,
        slabs_per_block=spb,
        auts=tuple(automorphism_count(t) for t in templates),
        combine=combine,
        widths=widths,
        tile_dst=tile_dst,
        tile_src_local=tile_src_local,
        tile_src_compact=tile_src_compact,
        tile_off=tile_off,
        send_idx=send_idx,
        a2a_slab_dst=a2a_slab_dst,
        a2a_slab_cols=a2a_slab_cols,
        bucket_counts=counts,
        compaction=compaction,
        pin_adj=pin_adj,
    )


def abstract_plan(
    num_vertices: int,
    num_edges: int,
    tree,
    num_shards: int,
    *,
    root: int = 0,
    skew_headroom: float = 3.0,
    compact_requests: bool = True,  # False (ring): request arrays minimal
    bucket_tile: int = 128,
    n_colors: Optional[int] = None,
    compact: bool = False,
    density_threshold: float = DEFAULT_DENSITY_THRESHOLD,
    capacity_factor: float = DEFAULT_CAPACITY_FACTOR,
) -> DistributedPlan:
    """Shape-only plan for dry-run lowering at paper-scale graph sizes.

    Tile/request sizes follow the paper's Eq. 5 expectation
    E[bucket] = |E_directed| / P^2 with a skew headroom factor; with tiled
    buckets the headroom costs O(E) extra tile slots, not O(P^2 * max_e).
    Array fields are ShapeDtypeStructs — nothing is allocated.  Arrays the
    requested mode never touches are kept minimal so the dry-run memory
    analysis reflects what the program actually ships.  ``tree`` may be a
    family (sequence of trees/names) — the lowered program is then the
    shared-DAG multi-template counter.

    ``compact=True`` sizes frontier-compaction capacities from the exact
    boolean-DP probe run on a small sampled same-degree subgraph
    (:func:`repro.core.frontier.sampled_density` — the paper-scale graph
    itself is never materialized), so dry-run cells lower and report the
    compacted exchange at paper scale with densities that track a real
    plan's measurements.
    """
    Pn = num_shards
    program, templates, k = _resolve_program(tree, root, n_colors)
    shard_size = (num_vertices + Pn - 1) // Pn
    n_loc_pad = ops.pad_to(shard_size + 1, 128)
    e_dev = 2.0 * num_edges / Pn
    avg_bucket = e_dev / Pn
    r_pad = ops.pad_to(min(int(avg_bucket * skew_headroom) + 128, shard_size + 1), 128)
    num_tiles = Pn * (int(avg_bucket * skew_headroom / bucket_tile) + 1)
    nrb_loc = n_loc_pad // 128
    spb = int(e_dev * skew_headroom / (nrb_loc * bucket_tile)) + 1

    has_bags = program_has_bags(program)
    combine, widths = build_node_tables(
        program, k, lane=128, x_dim=num_vertices if has_bags else None
    )
    compaction = None
    if compact and not has_bags:
        # densities from the exact boolean-DP probe on a sampled subgraph
        # (frontier.sampled_density) — the Markov bound saturated on dense
        # paper graphs, so dry-run capacities never engaged
        compaction = abstract_compaction(
            num_vertices,
            2.0 * num_edges / max(num_vertices, 1),
            program,
            k,
            r_pad=r_pad,
            n_loc_pad=n_loc_pad,
            threshold=density_threshold,
            capacity_factor=capacity_factor,
            combine=combine,
        )

    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    if compact_requests:
        tsl = s(Pn, 1, bucket_tile)  # ring-only array
        tsc = s(Pn, num_tiles, bucket_tile)
        sidx = s(Pn, Pn, r_pad)
        sd = sc = s(Pn, nrb_loc * spb, bucket_tile)
    else:
        tsl = s(Pn, num_tiles, bucket_tile)
        tsc = s(Pn, 1, bucket_tile)
        r_pad = 128
        sidx = s(Pn, Pn, r_pad)
        spb = 1
        sd = sc = s(Pn, 1, bucket_tile)
    return DistributedPlan(
        templates=templates,
        program=program,
        k=k,
        n=num_vertices,
        num_shards=Pn,
        shard_size=shard_size,
        n_loc_pad=n_loc_pad,
        r_pad=r_pad,
        bucket_tile=bucket_tile,
        num_tiles=num_tiles,
        slabs_per_block=spb,
        auts=tuple(automorphism_count(t) for t in templates),
        combine=combine,
        widths=widths,
        tile_dst=s(Pn, num_tiles, bucket_tile),
        tile_src_local=tsl,
        tile_src_compact=tsc,
        tile_off=s(Pn, Pn + 1),
        send_idx=sidx,
        a2a_slab_dst=sd,
        a2a_slab_cols=sc,
        bucket_counts=np.zeros((Pn, Pn), np.int64),
        compaction=compaction,
        pin_adj=(
            jax.ShapeDtypeStruct((Pn, n_loc_pad, num_vertices), jnp.float32)
            if has_bags
            else None
        ),
    )


def shard_coloring(plan: DistributedPlan, coloring: np.ndarray) -> np.ndarray:
    """Global coloring [n] -> sharded layout [P, n_loc_pad].

    One pad+reshape: the global array is zero-padded to ``P * shard_size``
    (covering the ragged last shard), viewed as ``[P, shard_size]``, and
    dropped into the first ``shard_size`` columns of the padded layout.
    Kept exported for tests and host-side callers that bring their own
    colorings; the keyed path (``make_count_fn(..., keyed=True)``) samples
    directly on-device and never builds this layout.
    """
    Pn, ss = plan.num_shards, plan.shard_size
    coloring = np.asarray(coloring, np.int32).reshape(-1)[: plan.n]
    out = np.zeros((Pn, plan.n_loc_pad), np.int32)
    padded = np.zeros(Pn * ss, np.int32)
    padded[: plan.n] = coloring
    out[:, :ss] = padded.reshape(Pn, ss)
    return out


def global_coloring(key: jax.Array, n: int, k: int) -> jax.Array:
    """The keyed backend's coloring for one iteration: int32 ``[n]``.

    Deliberately a function of ``(key, n, k)`` only — no shard count, no
    padding — so the coloring stream is identical on every mesh shape.
    ``sharded_fn_keyed`` slices this per shard on-device; tests and the
    elasticity contract (resume the same run on a different shard count)
    reconstruct it on the host to assert parity.
    """
    return jax.random.randint(key, (n,), 0, k, dtype=jnp.int32)


def _node_flops(plan: DistributedPlan, node_index: int) -> float:
    """Per-device compute consuming node ``node_index``'s exchange."""
    nd = plan.program.nodes[node_index]
    tbl = plan.combine[node_index]
    b_width = plan.widths[nd.right]
    edges_dev = float(plan.bucket_counts.sum()) / plan.num_shards
    if edges_dev <= 0:  # abstract plan: estimate from the tile capacity
        edges_dev = float(plan.num_tiles * plan.bucket_tile)
    spmm_flops = 2.0 * edges_dev * b_width
    x = plan.n if nd.kind == "bag_combine" else 1
    combine_flops = 2.0 * plan.n_loc_pad * x * tbl.s * tbl.j
    return spmm_flops + combine_flops


def _node_mode(
    plan: DistributedPlan,
    node_index: int,
    mode: str,
    hockney: HockneyModel,
    group_factor: int,
    wire_dtype: str = "float32",
) -> str:
    if mode != "adaptive":
        return mode
    # compacted+compressed byte counts: the slabs the wire actually ships
    _, a2a_bytes = node_exchange_bytes(plan, node_index, "alltoall", wire_dtype)
    _, ring_bytes = node_exchange_bytes(plan, node_index, "ring", wire_dtype)
    picked, _ = choose_mode_full(
        a2a_bytes,
        ring_bytes,
        _node_flops(plan, node_index),
        plan.num_shards,
        hockney,
        group_factor,
    )
    return picked


def plan_route_report(
    plan: DistributedPlan,
    *,
    mode: str = "adaptive",
    group_factor: int = 1,
    wire_dtype: str = "float32",
    adaptive: str = "model",
    hockney: Optional[HockneyModel] = None,
    mesh: Optional[jax.sharding.Mesh] = None,
    data_axis: str = "data",
) -> dict:
    """Per-node routing decisions + predicted costs for plan reports.

    With ``adaptive="measured"`` and a mesh, the Hockney constants come
    from the one-shot calibration probe (``comm.adaptive.calibrate``);
    otherwise the assumed ``hockney`` model is used (by default the
    :func:`~repro.comm.adaptive.assumed_model` of the mesh's device kind,
    or of the default device without a mesh).  Per internal node
    the report carries the compacted+compressed byte counts of both wire
    layouts, the consuming flops, the modeled cost of each schedule, and
    the mode the router picks — the launcher plan report and the dry-run
    cells surface this verbatim.
    """
    if hockney is None:
        dev = mesh.devices.flat[0] if mesh is not None else jax.devices()[0]
        hockney = assumed_model(dev.device_kind)
    model = hockney
    calibrated = False
    if adaptive == "measured" and mesh is not None:
        model = calibrate(mesh, data_axis, base=hockney)
        calibrated = model is not hockney
    per_node = {}
    for i, nd in enumerate(plan.program.nodes):
        if nd.kind not in ("combine", "bag_combine"):
            continue
        _, a2a_bytes = node_exchange_bytes(plan, i, "alltoall", wire_dtype)
        _, ring_bytes = node_exchange_bytes(plan, i, "ring", wire_dtype)
        flops = _node_flops(plan, i)
        picked, diag = choose_mode_full(
            a2a_bytes, ring_bytes, flops, plan.num_shards, model, group_factor
        )
        chosen = picked if mode == "adaptive" else mode
        per_node[i] = {
            "mode": chosen,
            "a2a_bytes": int(a2a_bytes),
            "ring_bytes": int(ring_bytes),
            "flops": float(flops),
            "costs_s": diag["costs_s"],
            "predicted_s": diag["costs_s"].get(chosen, diag["predicted_s"]),
        }
    return {
        "wire_dtype": wire_dtype,
        "adaptive": adaptive,
        "calibrated": calibrated,
        "model": {
            "alpha": model.alpha,
            "beta": model.beta,
            "flops_per_s": model.flops_per_s,
        },
        "per_node": per_node,
    }


def make_count_fn(
    plan: DistributedPlan,
    mesh: jax.sharding.Mesh,
    *,
    mode: str = "adaptive",
    data_axis: str = "data",
    iter_axis: Optional[str] = None,
    group_factor: int = 1,
    impl: str = "xla",
    fuse: bool = False,
    hockney: Optional[HockneyModel] = None,
    wire_dtype: str = "float32",
    adaptive: str = "model",
    return_raw: bool = False,
    keyed: bool = False,
):
    """Build the jitted distributed count function.

    Default contract: ``f(colorings) -> counts`` where ``colorings`` is int32
    ``[I, P, n_loc_pad]`` (I = number of parallel coloring iterations,
    sharded over ``iter_axis`` when given) and ``counts`` is float32 [I]
    (colorful map counts; multiply by ``plan.scale`` for copy estimates).
    Family plans (``plan.is_multi``, built from a template sequence) return
    ``[I, R]`` per-template counts instead — ONE table-program pass per
    coloring, shared subtree tables computed once; multiply by
    ``plan.scales`` for per-template copy estimates.

    ``impl``/``fuse`` carry the same semantics as the in-core engine:
    ``impl`` routes the SpMM/combine kernels (``"pallas"`` engages the
    edge-tile and fused kernels on the alltoall consume and the Pallas
    combine everywhere), and ``fuse=True`` never materializes the full
    per-node neighbor sum ``M`` — via ``ops.fused_count_slabs`` on the
    materialized alltoall buffer, and via per-tile accumulation directly
    into the output table on the incremental (pipeline/ring) modes.

    ``keyed=True``: the same key-based contract as the single-device engine —
    ``f(keys) -> counts`` where ``keys`` is a jax PRNG key array ``[I]`` (or
    raw uint32 key data ``[I, 2]``).  Colorings are sampled **on-device**
    inside the shard_map: each shard folds its ``data``-axis index into the
    iteration key and draws its own ``[n_loc_pad]`` slice with
    ``jax.random.randint`` — per-vertex colors stay iid uniform over ``k``
    while no ``[n]`` host array, numpy loop, or host->device coloring
    transfer exists at all.

    ``return_raw=True`` (dry-run): returns ``(jitted_fn, structs, in_shard)``
    where the fn takes all plan arrays as explicit arguments so the plan may
    hold ShapeDtypeStructs (see :func:`abstract_plan`); ``iter_axis`` may be
    a tuple of mesh axes.

    A compacted plan (``plan.compaction``, DESIGN.md §15) ships every
    sufficiently sparse exchanged table as active rows only — per-peer
    ``[rc, B+1]`` slabs (rows + a bitcast slot column) on alltoall and
    pipeline, ``[cap, B+1]`` whole-shard relays on ring — and restricts the
    final combine to active rows.  The compact program is speculative: it
    also returns per-iteration overflow counts, and the returned callable
    transparently re-dispatches a dense twin when any static capacity
    overflowed (bit-exact either way).  With ``return_raw=True`` the raw
    ``(counts, overflow)`` function is returned instead (dry-run measures
    the compact program itself).

    ``wire_dtype`` (``"float32"`` | ``"int16"`` | ``"int8"``, DESIGN.md
    §18) narrows every exchange payload: counts are nonnegative integers,
    so in-range slabs round-trip through the integer wire bit-exactly,
    guarded by per-slab saturation flags riding the same
    speculate-check-redispatch contract as compaction.  On saturation the
    batch re-runs one rung up the escalation ladder
    (int8 -> int16 -> float32 -> dense twin).  Compacted slabs swap the
    float32 slot column for bit-packed activity-bitmap columns of the
    wire dtype; the receiver re-derives slot indices deterministically.

    ``adaptive="measured"`` replaces the assumed Hockney constants with a
    one-shot calibration probe on this mesh (``comm.adaptive.calibrate``,
    cached per device kind and axis size) before the per-node routing
    decision; ``"model"`` keeps the assumed ``hockney`` constants (by
    default those of the mesh's device kind,
    :func:`~repro.comm.adaptive.assumed_model`).
    """
    assert not (keyed and return_raw), "keyed and return_raw are exclusive"
    if wire_dtype not in WIRE_DTYPES:
        raise ValueError(f"wire_dtype={wire_dtype!r}; expected one of {sorted(WIRE_DTYPES)}")
    if adaptive not in ("model", "measured"):
        raise ValueError(f"adaptive={adaptive!r}; expected 'model' or 'measured'")
    Pn = plan.num_shards
    n_loc_pad = plan.n_loc_pad
    r_pad = plan.r_pad
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    assert axis_sizes[data_axis] == Pn, (axis_sizes, Pn)
    wire_narrow = wire_dtype != "float32"
    if hockney is None:
        hockney = assumed_model(mesh.devices.flat[0].device_kind)

    if mode == "adaptive" and adaptive == "measured":
        hockney = calibrate(mesh, data_axis, base=hockney)
    node_modes = {
        i: _node_mode(plan, i, mode, hockney, group_factor, wire_dtype)
        for i, nd in enumerate(plan.program.nodes)
        if nd.kind in ("combine", "bag_combine")
    }

    spec = plan.compaction
    compact_on = spec is not None and spec.enabled
    # either narrowing makes the program speculative: it returns overflow
    # counts and the caller re-dispatches a wider twin on any saturation
    speculative = compact_on or wire_narrow
    # Which tables carry a frontier, and in which form, follows each
    # parent's resolved exchange mode: ring relays need the index form
    # (whole-shard compaction), alltoall/pipeline and the compact combine
    # only the activity mask.  Leaves are dense by construction.
    fr_caps: Dict[int, int] = {}
    mask_only = set()
    if compact_on:
        for i, nd in enumerate(plan.program.nodes):
            if nd.is_leaf:
                continue
            if node_modes[i] == "ring":
                if nd.right in spec.shard_caps:
                    fr_caps[nd.right] = spec.shard_caps[nd.right]
            elif nd.right in spec.exchange_caps:
                mask_only.add(nd.right)
            if i in spec.combine_caps and not fuse:
                mask_only.add(nd.left)
        keep = lambda j: not plan.program.nodes[j].is_leaf
        fr_caps = {j: c for j, c in fr_caps.items() if keep(j)}
        mask_only = frozenset(j for j in mask_only if keep(j) and j not in fr_caps)

    has_bags = program_has_bags(plan.program)

    def local_count(
        coloring,
        tile_dst,
        tile_src_loc,
        tile_src_cmp,
        tile_off,
        s_idx,
        slab_dst,
        slab_cols,
        pin_adj=None,
    ):
        """One coloring iteration on this device's shard; returns partial sum.

        The DP loop is the shared executor; only the neighbor-sum strategy
        below (exchange + tiled-bucket consume) is distributed-specific.
        """
        row_mask = (jnp.arange(n_loc_pad) < plan.shard_size).astype(jnp.float32)[:, None]
        leaf = leaf_table(coloring, ops.pad_to(plan.k, 128), row_mask)
        flags: list = []
        frontier_fn = (
            make_frontier_fn(fr_caps, plan.shard_size, flags, mask_only=mask_only)
            if compact_on else None
        )

        bag = None
        if has_bags:
            # treewidth-2 strategy (DESIGN.md §19), distributed form: bag
            # tables keep the [v_loc, x * W] sharded layout through every
            # exchange mode unchanged (the wire is width-agnostic); the
            # collapse reduces the local vertex rows and psums the [x, W]
            # result, so collapsed/joined tables are replicated — every
            # shard holds the full x axis.
            x_dim = plan.n
            k_pad = ops.pad_to(plan.k, 128)

            def bag_leaf_fn(i, nd):
                if nd.pin:
                    t = leaf[:, None, :] * pin_adj[:, :, None]
                else:
                    t = jnp.broadcast_to(leaf[:, None, :], (n_loc_pad, x_dim, k_pad))
                return t.reshape(n_loc_pad, x_dim * k_pad)

            def bag_collapse_fn(i, child):
                w = child.shape[1] // x_dim
                r = child.reshape(n_loc_pad, x_dim, w).sum(axis=0)
                r = jax.lax.psum(r, data_axis)  # [x, w], replicated
                t = plan.program.nodes[i].size
                filt = excluded_color_mask(plan.k, t)
                filt_pad = np.zeros((plan.k, w), np.float32)
                filt_pad[:, : filt.shape[1]] = filt
                # the apex filter needs the GLOBAL coloring: reassemble it
                # from the shards' true rows (ragged tail sliced off)
                col_glob = jax.lax.all_gather(
                    coloring[: plan.shard_size], data_axis, tiled=True
                )[: plan.n]
                return r * jnp.asarray(filt_pad)[col_glob]

            def bag_join_fn(i, tbl, left, right):
                # both inputs are replicated [x, w] tables; the disjoint
                # color convolution is pure local compute on aligned rows
                return ops.color_combine(left, right, tbl, impl=impl)

            bag = BagFns(bag_leaf_fn, bag_collapse_fn, bag_join_fn)

        def consume_into_m(tile_src):
            """Accumulate a chunk's bucket into the neighbor sum M.

            One uniform §3.3 task per tile: gather ``bucket_tile`` chunk
            rows, one bounded scatter-add — per-chunk work scales with the
            bucket's edge count, never with the globally largest bucket.
            """

            def consume(acc, chunk, src):
                acc = pvary_like(acc, chunk)

                def tile_task(t, a):
                    d = jax.lax.dynamic_index_in_dim(tile_dst, t, 0, keepdims=False)
                    s = jax.lax.dynamic_index_in_dim(tile_src, t, 0, keepdims=False)
                    return a.at[d].add(jnp.take(chunk, s, axis=0))

                return jax.lax.fori_loop(tile_off[src], tile_off[src + 1], tile_task, acc)

            return consume

        def consume_into_out(tile_src, c_left, tbl):
            """Fused incremental consume: the combine is linear in M, so each
            tile's contribution lands directly in the output table — the
            full [n_loc_pad, B] neighbor sum never exists (§3.2 across
            exchange chunks)."""

            def consume(acc, chunk, src):
                acc = pvary_like(acc, chunk)

                def tile_task(t, a):
                    d = jax.lax.dynamic_index_in_dim(tile_dst, t, 0, keepdims=False)
                    s = jax.lax.dynamic_index_in_dim(tile_src, t, 0, keepdims=False)
                    g1 = jnp.take(c_left, d, axis=0)  # [tile, A]
                    g2 = jnp.take(chunk, s, axis=0)  # [tile, B]
                    contrib = jnp.einsum("esj,esj->es", g1[:, tbl.idx1], g2[:, tbl.idx2])
                    contrib = jnp.pad(contrib, ((0, 0), (0, tbl.s_pad - tbl.s)))
                    return a.at[d].add(contrib)

                return jax.lax.fori_loop(tile_off[src], tile_off[src + 1], tile_task, acc)

            return consume

        def node_fn(i, tbl, c_left, c_right, f_left, f_right):
            nm = node_modes[i]
            bw = c_right.shape[1]
            nd_i = plan.program.nodes[i]
            # bag combines exchange/consume exactly like tree combines (the
            # wire is width-agnostic over the [v_loc, x * W] layout) but the
            # contraction must pair per-x blocks, which the fused kernels
            # cannot address — force the two-step path for these nodes only
            is_bag = nd_i.kind == "bag_combine"
            node_fuse = fuse and not is_bag
            rc = spec.exchange_caps.get(nd_i.right) if compact_on else None
            ring_cap = spec.shard_caps.get(nd_i.right) if compact_on else None
            ccap = spec.combine_caps.get(i) if compact_on and not fuse else None

            def final_combine(m):
                if ccap is not None:
                    return compact_combine(
                        c_left,
                        m,
                        tbl,
                        ccap,
                        plan.shard_size,
                        impl,
                        flags,
                        left_mask=f_left.mask if f_left is not None else None,
                    )
                if is_bag:
                    m = m * row_mask
                    rows = c_left.shape[0]
                    lhs = c_left.reshape(rows * plan.n, -1)
                    rhs = m.reshape(rows * plan.n, -1)
                    out = ops.color_combine(lhs, rhs, tbl, impl=impl)
                    return out.reshape(rows, plan.n * tbl.s_pad)
                return ops.color_combine(c_left, m * row_mask, tbl, impl=impl)

            def compact_chunks():
                """Compacted per-peer slabs: the active rows of each request
                chunk plus a slot carrier — a bitcast float32 slot column on
                the wide wire ([P, rc, B+1]), or bit-packed activity-bitmap
                columns of the wire dtype on a narrow one (the receiver
                re-derives the identical slots from the mask with the same
                deterministic capacity-padded nonzero the sender ran)."""
                act_chunks = jnp.take(f_right.mask, s_idx)  # [P, r_pad]
                counts = jnp.sum(act_chunks.astype(jnp.int32), axis=1)
                flags.append(jnp.max(counts) <= rc - 1)
                slots = chunk_slots(act_chunks, rc, r_pad - 1)  # [P, rc]
                rows = jnp.take(
                    c_right,
                    jnp.take_along_axis(s_idx, slots, axis=1).reshape(-1),
                    axis=0,
                ).reshape(Pn, rc, bw)
                if wire_narrow:
                    return jnp.concatenate(
                        [
                            narrow_cast(rows, wire_dtype, flags),
                            mask_columns(act_chunks, rc, wire_dtype),
                        ],
                        axis=-1,
                    )
                return jnp.concatenate([rows, encode_slots(slots)[..., None]], axis=-1)

            if nm == "alltoall":
                # Naive mode: the whole exchange buffer is materialized
                # anyway, so consume it with the in-core engine's kernels
                # over the [P * r_pad, B] concatenation (slab columns were
                # built against exactly this layout).
                if rc is not None and f_right is not None:
                    # compacted alltoall: ship [P, rc, B+extra], scatter the
                    # received rows back into the (zero-initialized) dense
                    # buffer — inactive slots stay exactly zero, which is
                    # what the dense exchange would have delivered there
                    payload = compact_chunks()
                    received = jax.lax.all_to_all(payload, data_axis, split_axis=0, concat_axis=0)
                    r_rows = widen(received[..., :bw]).reshape(Pn * rc, bw)
                    if wire_narrow:
                        masks = mask_from_columns(
                            received[..., bw:], r_pad, wire_dtype
                        )  # [P, r_pad] — the senders' chunk activity
                        r_slots = chunk_slots(masks, rc, r_pad - 1)
                    else:
                        r_slots = decode_slots(received[..., bw])  # [P, rc]
                    flat = r_slots + (jnp.arange(Pn, dtype=jnp.int32) * r_pad)[:, None]
                    remote = (
                        jnp.zeros((Pn * r_pad, bw), jnp.float32)
                        .at[flat.reshape(-1)]
                        .add(r_rows)
                    )
                else:
                    chunks = jnp.take(c_right, s_idx, axis=0)  # [P, r_pad, B]
                    received = jax.lax.all_to_all(
                        narrow_cast(chunks, wire_dtype, flags),
                        data_axis,
                        split_axis=0,
                        concat_axis=0,
                    )
                    # the slab kernels widen narrow tables at entry, so the
                    # received buffer feeds them without a separate copy
                    remote = received.reshape(Pn * r_pad, bw)
                if node_fuse:
                    return ops.fused_count_slabs(
                        slab_dst,
                        slab_cols,
                        c_left,
                        remote,
                        tbl,
                        slabs_per_block=plan.slabs_per_block,
                        impl=impl,
                    )
                m = ops.spmm_slabs(
                    slab_dst,
                    slab_cols,
                    remote,
                    out_rows=n_loc_pad,
                    slabs_per_block=plan.slabs_per_block,
                    impl=impl,
                )
                return final_combine(m)
            # incremental modes: per-chunk tiled-bucket consume
            if node_fuse:
                init = jnp.zeros((n_loc_pad, tbl.s_pad), jnp.float32)
            else:
                init = jnp.zeros((n_loc_pad, bw), c_right.dtype)
            if nm == "ring":
                src_arr = tile_src_loc  # chunks are whole remote shards
                consume_dense = (
                    consume_into_out(src_arr, c_left, tbl) if node_fuse
                    else consume_into_m(src_arr)
                )

                def consume(acc, chunk, src):
                    # relayed chunks arrive at wire width; the tiled
                    # consume runs on the (exactly) widened rows
                    return consume_dense(acc, widen(chunk), src)

                if ring_cap is not None and f_right is not None:
                    # compacted relay: the ring carries [cap, B+extra]
                    # active rows + their row ids (slot column on the wide
                    # wire, packed activity bitmap on a narrow one); each
                    # hop reconstructs the dense shard before the
                    # (unchanged) tiled consume
                    rows = jnp.take(c_right, f_right.idx, axis=0)
                    if wire_narrow:
                        payload = jnp.concatenate(
                            [
                                narrow_cast(rows, wire_dtype, flags),
                                mask_columns(
                                    f_right.mask, ring_cap, wire_dtype
                                ),
                            ],
                            axis=1,
                        )
                    else:
                        payload = jnp.concatenate(
                            [rows, encode_slots(f_right.idx)[:, None]], axis=1
                        )

                    def consume_compact(acc, chunk, src):
                        if wire_narrow:
                            mask = mask_from_columns(chunk[:, bw:], n_loc_pad, wire_dtype)
                            idx = jnp.nonzero(
                                mask, size=ring_cap,
                                fill_value=plan.shard_size,
                            )[0].astype(jnp.int32)
                        else:
                            idx = decode_slots(chunk[:, bw])
                        dense = (
                            jnp.zeros((n_loc_pad, bw), jnp.float32)
                            .at[idx]
                            .add(widen(chunk[:, :bw]))
                        )
                        return consume_dense(acc, dense, src)

                    out = ring_allgather_overlap(payload, data_axis, consume_compact, init)
                else:
                    out = ring_allgather_overlap(
                        narrow_cast(c_right, wire_dtype, flags),
                        data_axis,
                        consume,
                        init,
                    )
            else:  # pipeline
                src_arr = tile_src_cmp  # chunks are compact request lists
                consume_dense = (
                    consume_into_out(src_arr, c_left, tbl) if node_fuse
                    else consume_into_m(src_arr)
                )

                def consume(acc, chunk, src):
                    return consume_dense(acc, widen(chunk), src)

                if rc is not None and f_right is not None:
                    payload = compact_chunks()

                    def consume_compact(acc, chunk, src):
                        if wire_narrow:
                            mask = mask_from_columns(chunk[:, bw:], r_pad, wire_dtype)
                            slots = jnp.nonzero(
                                mask, size=rc, fill_value=r_pad - 1
                            )[0].astype(jnp.int32)
                        else:
                            slots = decode_slots(chunk[:, bw])
                        dense = (
                            jnp.zeros((r_pad, bw), jnp.float32)
                            .at[slots]
                            .add(widen(chunk[:, :bw]))
                        )
                        return consume_dense(acc, dense, src)

                    out = grouped_exchange(
                        payload,
                        data_axis,
                        consume_compact,
                        init,
                        group_factor=group_factor,
                    )
                else:
                    chunks = jnp.take(c_right, s_idx, axis=0)  # [P, r_pad, B]
                    out = grouped_exchange(
                        narrow_cast(chunks, wire_dtype, flags),
                        data_axis,
                        consume,
                        init,
                        group_factor=group_factor,
                    )
            if node_fuse:
                return out
            return final_combine(out)

        roots = run_table_program(
            plan.program,
            plan.combine,
            leaf,
            row_mask,
            node_fn,
            root_fn=root_count,
            frontier_fn=frontier_fn,
            bag=bag,
        )
        ok = jnp.bool_(True)
        for fl in flags:
            ok = jnp.logical_and(ok, fl)
        # [R] per-template counts plus this coloring's no-overflow flag
        return jnp.stack(roots), ok

    # bag roots (collapse/join) are psum'd inside local_count, so their
    # per-shard partials are already the replicated global count — summing
    # them again across shards would multiply by P.  Static 0/1 weights pick
    # the right reduction per root without any per-root control flow.
    w_root = np.array(
        [
            0.0
            if plan.program.nodes[r].kind in ("bag_collapse", "bag_join")
            else 1.0
            for r in plan.program.roots
        ],
        np.float32,
    )
    mixed_roots = bool((w_root == 0.0).any())

    def _reduce(partials, oks):
        if mixed_roots:
            w = jnp.asarray(w_root)
            counts = jax.lax.psum(partials * w, data_axis) + partials * (1.0 - w)
        else:
            counts = jax.lax.psum(partials, data_axis)  # [I_loc, R]
        if not speculative:
            return counts
        # per-iteration overflow/saturation counts, replicated across shards
        bad = jax.lax.psum(
            jnp.logical_not(oks).astype(jnp.int32), data_axis
        )
        return counts, bad

    def sharded_fn(colorings, *arrs):
        # local shapes: colorings [I_loc, 1, n_loc_pad]; plan arrays [1, ...]
        colorings = colorings[:, 0]
        local = tuple(a[0] for a in arrs)
        partials, oks = jax.vmap(lambda col: local_count(col, *local))(colorings)
        return _reduce(partials, oks)

    def sharded_fn_keyed(key_data, *arrs):
        # local shapes: key_data [I_loc, 2] uint32; plan arrays [1, ...]
        local = tuple(a[0] for a in arrs)
        p = jax.lax.axis_index(data_axis)

        def one(kd):
            # every shard draws the same GLOBAL coloring and slices its own
            # rows, so the coloring stream depends only on (key, n, k) —
            # never on the shard count.  That is what lets a checkpointed
            # run resume on a different shard count (ROADMAP elasticity)
            # and keeps service coloring streams portable across meshes.
            # Rows past the shard's true size (local pad, and global pad on
            # the ragged last shard) take a clipped color; they are either
            # masked (row >= shard_size) or edgeless, contributing zero to
            # every internal-node table, exactly like the zero color
            # shard_coloring pads with.
            col_glob = global_coloring(
                jax.random.wrap_key_data(kd), plan.n, plan.k
            )
            idx = p * plan.shard_size + jnp.arange(n_loc_pad)
            col = jnp.take(col_glob, jnp.minimum(idx, plan.n - 1))
            return local_count(col, *local)

        partials, oks = jax.vmap(one)(key_data)  # [I_loc, R]
        return _reduce(partials, oks)

    iter_spec = P(iter_axis) if iter_axis else P()
    out_spec = (iter_spec, iter_spec) if speculative else iter_spec
    lead_spec = (
        P(iter_axis) if keyed
        else (P(iter_axis, data_axis) if iter_axis else P(None, data_axis))
    )
    in_specs = (lead_spec,) + (P(data_axis),) * len(plan.device_arrays)
    # check_vma=False: the tiled-bucket consume iterates a traced CSR tile
    # range (a `while` under jit), which the replication checker cannot
    # type; outputs are psum-reduced, hence replicated by construction.
    mapped = shard_map(
        sharded_fn_keyed if keyed else sharded_fn,
        mesh=mesh, in_specs=in_specs, out_specs=out_spec, check_vma=False,
    )

    if return_raw:
        iter_size = 1
        for ax in (iter_axis if isinstance(iter_axis, tuple) else (iter_axis,)):
            if ax:
                iter_size *= dict(zip(mesh.axis_names, mesh.devices.shape))[ax]
        as_struct = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
        structs = (
            jax.ShapeDtypeStruct((iter_size, Pn, n_loc_pad), jnp.int32),
        ) + tuple(as_struct(a) for a in plan.device_arrays)
        in_shard = tuple(NamedSharding(mesh, s) for s in in_specs)
        fn = jax.jit(mapped, in_shardings=in_shard)
        return fn, structs, in_shard

    # the plan arrays are arguments, sharded as the shard_map reads them: a
    # closed-over array would be embedded in the program as a constant
    arrays = place_plan(plan, mesh, data_axis).device_arrays
    shard_args = jax.jit(
        lambda data, *arrs: _unpack(mapped(data, *arrs)),
        in_shardings=tuple(NamedSharding(mesh, s) for s in in_specs),
    )

    def _unpack(out):
        if speculative:
            counts, bad = out
            return (counts if plan.is_multi else counts[:, 0]), bad
        return out if plan.is_multi else out[:, 0]

    def fj(data):
        return shard_args(data, *arrays)

    if speculative:
        # speculative dispatch: the narrow/compact program reports
        # per-iteration overflow counts; any overflow re-runs the batch one
        # rung up the escalation ladder — a narrow wire widens first
        # (int8 -> int16 -> float32, keeping the same compaction), then the
        # float32 compact program falls back to its dense twin.  Each twin
        # wraps itself the same way, so the ladder always terminates at the
        # dense float32 program (bit-exact — narrow == wide when flags hold).
        twin_state: Dict[str, object] = {}

        def run(data):
            res, bad = fj(data)
            # fault sites: force the saturation/overflow storm onto the twin
            forced = wire_narrow and (
                faults.fire("compression.saturate") is not None
            )
            forced = forced or (compact_on and faults.fire("compaction.overflow") is not None)
            if not forced and int(np.asarray(bad).sum()) == 0:
                return res
            ft = twin_state.get("fn")
            if ft is None:
                if wire_narrow:
                    twin_plan = plan
                    twin_wire = WIRE_ESCALATION[wire_dtype]
                else:
                    twin_plan = dataclasses.replace(plan, compaction=None)
                    twin_wire = "float32"
                ft = twin_state["fn"] = make_count_fn(
                    twin_plan, mesh,
                    mode=mode, data_axis=data_axis, iter_axis=iter_axis,
                    group_factor=group_factor, impl=impl, fuse=fuse,
                    hockney=hockney, wire_dtype=twin_wire, adaptive=adaptive,
                    keyed=keyed,
                )
            return ft(data)

    else:
        run = fj

    if not keyed:
        return run

    def f_keyed(keys):
        keys = jnp.asarray(keys)
        if jnp.issubdtype(keys.dtype, jax.dtypes.prng_key):
            keys = jax.random.key_data(keys)
        return run(keys.astype(jnp.uint32))

    return f_keyed


def keyed_sample_fn(plan: DistributedPlan, mesh: jax.sharding.Mesh, **kw):
    """Adapt a distributed plan to the backend ``sample_fn`` protocol.

    Returns ``sample_fn(key, batch) -> float64 [batch]`` copy estimates —
    the same contract :func:`repro.core.count_engine.plan_sample_fn` gives
    the single-device engine, so :func:`repro.core.estimator.estimate_counts`
    (and anything else speaking the protocol) runs unmodified on top of the
    shard_map backend.  A family plan returns ``[batch, R]`` per-template
    estimates instead (the :func:`~repro.core.count_engine.multi_sample_fn`
    contract, consumed by ``estimate_counts_many``).  ``kw`` is forwarded to
    :func:`make_count_fn` (mode/group_factor/impl/fuse/axes/...).  Each call
    evaluates ``batch`` coloring iterations in one jitted dispatch; jit
    caches per distinct batch size.  When colorings shard over ``iter_axis``
    the key count is rounded up to a multiple of the axis size (shard_map
    divisibility) and the surplus estimates are discarded.
    """
    f = make_count_fn(plan, mesh, keyed=True, **kw)
    iter_axis = kw.get("iter_axis")
    isz = 1
    if iter_axis:
        isz = dict(zip(mesh.axis_names, mesh.devices.shape))[iter_axis]
    scales = np.asarray(plan.scales, np.float64)

    def sample(key: jax.Array, batch: int) -> np.ndarray:
        b = -(-batch // isz) * isz
        counts = np.asarray(f(jax.random.split(key, b)), np.float64)
        if plan.is_multi:
            return counts[:batch] * scales[None, :]
        return counts.reshape(-1)[:batch] * plan.scale

    return sample
