"""Jit'd dispatch wrappers for the Pallas kernels, with XLA fallbacks.

Every op has three implementations selected by ``impl``:

* ``"xla"`` — pure-jnp path (gather/scatter/einsum); the default off-TPU
  and where a table is too large for a kernel's VMEM.
* ``"pallas"`` — the Pallas kernel, compiled on TPU, ``interpret=True``
  elsewhere (so CPU tests execute the actual kernel body).
* ``"auto"`` — per call: pallas on TPU backends when the kernel's VMEM
  need (computed from the operand shapes) fits ``VMEM_LIMIT_BYTES``, xla
  otherwise.  An explicit ``"pallas"`` that cannot fit raises.

Sparse ops consume a prebuilt :class:`SpmmPlan` (host-side preprocessing of
the graph into padded edge lists / block patches) so that jitted code sees
only static shapes.

Padding conventions (hardware-true even in interpret mode):
  * vertex dimension padded to a multiple of 128, ``n_pad > n`` strictly, so
    row ``n`` is a writable zero sentinel;
  * count-table column dimension padded to a multiple of 128; engine
    re-masks pad rows/cols after each combine (kernels may write garbage
    there).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.compat import pvary_like

from . import ref
from .color_combine import color_combine_pallas, combine_vmem_bytes
from .flash_attention import flash_attention_pallas
from .fused_count import fused_count_pallas, fused_count_xla, fused_vmem_bytes
from .spmm_edgetile import (
    VMEM_LIMIT_BYTES,
    block_vmem_bytes,
    edge_tile_vmem_bytes,
    spmm_block_pallas,
    spmm_edge_tile_pallas,
)

__all__ = [
    "on_tpu",
    "resolve_impl",
    "spmm_impl",
    "combine_impl",
    "fused_impl",
    "pad_to",
    "SpmmPlan",
    "build_spmm_plan",
    "build_slab_layout",
    "build_bucket_tiles",
    "expected_patch_density",
    "gather_scatter_add",
    "spmm",
    "spmm_compact",
    "spmm_slabs",
    "CombineTables",
    "build_combine_tables",
    "color_combine",
    "fused_count",
    "fused_count_compact",
    "fused_count_slabs",
    "flash_attention",
]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_impl(impl: str, vmem_bytes: int = 0) -> str:
    """The implementation one op call runs, from ``impl`` and the VMEM its
    Pallas kernel would hold for these operand shapes."""
    if vmem_bytes > VMEM_LIMIT_BYTES:
        if impl == "pallas":
            raise ValueError(
                f"impl='pallas': this kernel call needs {vmem_bytes / 2**20:.1f} MiB "
                f"of VMEM, over the {VMEM_LIMIT_BYTES >> 20} MiB limit; "
                f"use impl='auto' or impl='xla'"
            )
        if impl == "auto":
            return "xla"
    if impl == "auto":
        return "pallas" if on_tpu() else "xla"
    return impl


def spmm_impl(plan: "SpmmPlan", width: int, impl: str, table_rows: Optional[int] = None) -> str:
    """Implementation of one neighbor sum over a ``[table_rows, width]``
    source table (``table_rows`` defaults to the plan's ``n_pad``)."""
    if plan.kind == "blocks":
        need = block_vmem_bytes(plan.block_size, width)
    else:
        need = edge_tile_vmem_bytes(table_rows or plan.n_pad, width, plan.row_tile)
    return resolve_impl(impl, need)


def combine_impl(a: int, b: int, tables: "CombineTables", impl: str) -> str:
    """Implementation of one combine of ``[n, a]`` by ``[n, b]`` tables."""
    return resolve_impl(impl, combine_vmem_bytes(a, b, tables.idx1_t.shape[0]))


def fused_impl(
    a: int, b: int, tables: "CombineTables", impl: str, table_rows: int, row_tile: int = 128
) -> str:
    """Implementation of one fused count against a ``[table_rows, b]``
    right table."""
    need = fused_vmem_bytes(
        table_rows, a, b, tables.idx1_t.shape[1], tables.idx1_t.shape[0], row_tile
    )
    return resolve_impl(impl, need)


def pad_to(x: int, multiple: int) -> int:
    return ((x + multiple - 1) // multiple) * multiple


# ---------------------------------------------------------------------------
# SpMM (neighbor sum)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SpmmPlan:
    """Static preprocessing of a graph for the neighbor-sum op.

    ``kind``: 'edges' (XLA piece sums / Pallas edge-tiled gather) or 'blocks'
    (block-dense Pallas); ``"auto"`` at build time picks one from measured
    patch density.  All index arrays are np/jnp int32, padded; the sentinel
    row is ``n`` (< n_pad).

    The 'edges' plan carries three layouts of the same edge list:

    * flat ``rows``/``cols`` [E_pad] — the oracles' form;
    * pieces ``piece_cols``/``piece_rows`` — the XLA neighbor sum's
      (:func:`build_piece_layout`, :func:`piece_sum`);
    * slab ``slab_dst``/``slab_cols`` [NRB * slabs_per_block, tile_size] —
      the paper's bounded neighbor-list tasks (§3.3): slabs of exactly
      ``tile_size`` edges grouped under the ``row_tile``-row output block
      of their destinations, consumed by ``spmm_edge_tile_pallas`` and the
      fused SpMM->combine kernels.  ``slab_dst`` holds block-local dst rows
      (-1 for pad slots), ``slab_cols`` global src rows (sentinel for pads).
    """

    kind: str
    n: int
    n_pad: int
    rows: Optional[jax.Array] = None  # [E_pad]
    cols: Optional[jax.Array] = None  # [E_pad]
    block_rows: Optional[jax.Array] = None  # [NB]
    block_cols: Optional[jax.Array] = None  # [NB]
    patches: Optional[jax.Array] = None  # [NB, VB, KB]
    block_size: int = 128
    #: rows the kernel actually writes (zero-degree rows are never visited
    #: by the block kernel, so its output there must be masked off)
    written_mask: Optional[jax.Array] = None  # bool [n_pad]
    # --- edge-slab layout (kind == 'edges') ---
    slab_dst: Optional[jax.Array] = None  # [NRB * spb, tile_size]
    slab_cols: Optional[jax.Array] = None  # [NRB * spb, tile_size]
    slabs_per_block: int = 0
    # --- piece layout (kind == 'edges'); the bucket shapes are static ---
    piece_cols: Tuple[jax.Array, ...] = ()  # per group [pieces, 2^j] source rows
    piece_rows: Tuple[jax.Array, ...] = ()  # per group [pieces] destination rows
    tile_size: int = 128
    row_tile: int = 128
    #: measured edges per occupied 128x128 patch (set by kind='auto')
    patch_density: Optional[float] = None

    @property
    def layout_bytes(self) -> Dict[str, int]:
        """Device bytes of each index layout this plan holds."""
        out = {}
        for name in ("rows", "cols", "slab_dst", "slab_cols", "patches"):
            a = getattr(self, name)
            if a is not None:
                out[name] = int(a.size) * a.dtype.itemsize
        for name in ("piece_cols", "piece_rows"):
            if getattr(self, name):
                out[name] = sum(int(a.size) * a.dtype.itemsize for a in getattr(self, name))
        return out


# a pytree, so jitted counters take the (device-resident) layout as an
# argument: a closed-over array would be embedded in the program as a
# constant, which at deployment size is gigabytes of HLO
jax.tree_util.register_dataclass(
    SpmmPlan,
    data_fields=[
        "rows", "cols", "block_rows", "block_cols", "patches",
        "written_mask", "slab_dst", "slab_cols", "piece_cols", "piece_rows",
    ],
    meta_fields=[
        "kind", "n", "n_pad", "block_size", "slabs_per_block", "tile_size",
        "row_tile", "patch_density",
    ],
)


#: 'auto' picks the block-dense plan once occupied 128x128 patches average
#: this many edges: at that density one patch matmul (128 rows x B lanes per
#: nnz) costs about the same MXU time as the edge-slab scatter matmuls for
#: the same edges, and the dense-patch storage (64 KB) stops dominating the
#: slab metadata (8 B/edge).
AUTO_DENSITY_THRESHOLD = 64.0


def build_slab_layout(
    rows: np.ndarray,
    cols: np.ndarray,
    n_pad: int,
    tile_size: int,
    row_tile: int,
    *,
    sentinel_col: int,
    slabs_per_block: Optional[int] = None,
) -> Tuple[np.ndarray, np.ndarray, int]:
    """Cut a (dst-sorted) edge list into uniform tile_size-edge slabs
    grouped by ``row_tile``-row destination block (the paper's §3.3
    bounded-task layout).

    ``rows`` are destination rows in ``[0, n_pad)``; ``cols`` may index any
    source table (the graph's own vertex table, or a concatenated exchange
    buffer in the distributed engine) — pad slots carry ``dst = -1`` and
    ``sentinel_col`` (which must name an all-zero source row).
    ``slabs_per_block`` forces a larger uniform slab count per block (so
    layouts built per shard can share one shape across shards).
    """
    nrb = n_pad // row_tile
    blk = rows // row_tile
    counts = np.bincount(blk, minlength=nrb)
    spb = max(1, int(-(-counts.max(initial=0) // tile_size)))
    if slabs_per_block is not None:
        assert slabs_per_block >= spb, (slabs_per_block, spb)
        spb = slabs_per_block
    slab_dst = np.full((nrb, spb * tile_size), -1, np.int32)
    slab_cols = np.full((nrb, spb * tile_size), sentinel_col, np.int32)
    starts = np.zeros(nrb, np.int64)
    np.cumsum(counts[:-1], out=starts[1:])
    pos = np.arange(len(rows)) - starts[blk]  # rows sorted => in-block rank
    slab_dst[blk, pos] = (rows % row_tile).astype(np.int32)
    slab_cols[blk, pos] = cols.astype(np.int32)
    return (
        slab_dst.reshape(nrb * spb, tile_size),
        slab_cols.reshape(nrb * spb, tile_size),
        spb,
    )


def build_bucket_tiles(
    bucket: np.ndarray,
    dst: np.ndarray,
    srcs: Tuple[np.ndarray, ...],
    num_buckets: int,
    tile_size: int,
    *,
    dst_sentinel: int,
    src_sentinels: Tuple[int, ...],
    num_tiles: Optional[int] = None,
) -> Tuple[np.ndarray, Tuple[np.ndarray, ...], np.ndarray]:
    """Cut a bucketed edge list into fixed-size tiles with CSR offsets.

    This is the §3.3 neighbor-list partitioning applied to the distributed
    engine's (src-shard) buckets: every bucket ``q`` becomes
    ``ceil(count_q / tile_size)`` tiles of exactly ``tile_size`` slots, laid
    out back to back, so storage is ``O(edges + num_buckets * tile_size)``
    — independent of the largest bucket — and every consume task is one
    uniform tile.  ``bucket`` must be nondecreasing (edges pre-sorted by
    bucket).  ``srcs`` is a tuple of parallel per-edge source-index arrays
    (the distributed plan carries both a shard-local and a compact-slot
    view of the same edges); each gets its own sentinel for pad slots.

    Returns ``(tile_dst [T, tile], tuple of tile_src [T, tile],
    tile_off [num_buckets + 1])``; ``num_tiles`` pads T to a caller-chosen
    value (uniform shape across shards).
    """
    counts = np.bincount(bucket, minlength=num_buckets)
    tiles_per = -(-counts // tile_size)  # ceil; empty buckets take 0 tiles
    tile_off = np.zeros(num_buckets + 1, np.int32)
    np.cumsum(tiles_per, out=tile_off[1:])
    t_need = int(tile_off[-1])
    t = t_need if num_tiles is None else num_tiles
    assert t >= t_need, (t, t_need)
    tile_dst = np.full((t, tile_size), dst_sentinel, np.int32)
    tile_srcs = tuple(np.full((t, tile_size), s, np.int32) for s in src_sentinels)
    starts = np.zeros(num_buckets, np.int64)
    np.cumsum(counts[:-1], out=starts[1:])
    # in-bucket rank -> (tile, slot); buckets own disjoint tile ranges
    rank = np.arange(len(bucket)) - starts[bucket]
    tidx = tile_off[bucket] + rank // tile_size
    slot = rank % tile_size
    tile_dst[tidx, slot] = dst.astype(np.int32)
    for out, src in zip(tile_srcs, srcs):
        out[tidx, slot] = src.astype(np.int32)
    return tile_dst, tile_srcs, tile_off


def build_piece_layout(
    rows: np.ndarray, cols: np.ndarray, n_pad: int, tile_size: int, *, sentinel_col: int
) -> Tuple[Tuple[np.ndarray, ...], Tuple[np.ndarray, ...]]:
    """The neighbor-sum layout of a (dst-sorted) edge list that needs no
    scatter over edges: the paper's §3.3 neighbor-list partitioning.

    Each vertex's neighbor list is cut into consecutive pieces of at most
    ``tile_size`` edges, and the pieces are grouped by length padded to the
    next power of two.  Returns, per group, shortest first, the pieces'
    source rows ``[pieces, 2^j]`` (pad slots ``sentinel_col``, which must
    name an all-zero source row) and their destination rows ``[pieces]``,
    nondecreasing.  A vertex of degree ``d`` owns ``ceil(d / tile_size)``
    pieces: one, unless it has more than ``tile_size`` neighbors.
    """
    deg = np.bincount(rows, minlength=n_pad).astype(np.int64)
    start = np.zeros(n_pad, np.int64)
    np.cumsum(deg[:-1], out=start[1:])
    count = -(-deg // tile_size)
    owner = np.repeat(np.arange(n_pad), count)  # each piece's vertex, in order
    first = np.repeat(np.cumsum(count) - count, count)
    rank = np.arange(len(owner)) - first  # the piece's place in its vertex's list
    begin = start[owner] + rank * tile_size
    length = np.minimum(deg[owner] - rank * tile_size, tile_size)
    width = np.left_shift(1, np.frexp(length - 1)[1])  # next power of two
    padded = np.append(cols, sentinel_col).astype(np.int32)  # pad slots read the end
    piece_cols, piece_rows = [], []
    for w in np.unique(width):
        sel = np.flatnonzero(width == w)
        slot = np.arange(w)
        piece_cols.append(
            padded[np.where(slot < length[sel, None], begin[sel, None] + slot, len(cols))]
        )
        piece_rows.append(owner[sel].astype(np.int32))
    return tuple(piece_cols), tuple(piece_rows)


def _build_slabs(
    rows: np.ndarray,
    cols: np.ndarray,
    n: int,
    n_pad: int,
    tile_size: int,
    row_tile: int,
) -> Tuple[np.ndarray, np.ndarray, int]:
    return build_slab_layout(rows, cols, n_pad, tile_size, row_tile, sentinel_col=n)


def build_spmm_plan(
    rows: np.ndarray,
    cols: np.ndarray,
    n: int,
    *,
    kind: str = "edges",
    block_size: int = 128,
    tile_size: int = 128,
    row_tile: int = 128,
) -> SpmmPlan:
    """Build a plan from a directed edge list (rows sorted nondecreasing).

    ``tile_size`` is the paper's neighbor-list task size ``s`` — every slab
    of ``tile_size`` edge slots is one uniform unit of work regardless of
    degree skew.  ``kind="auto"`` measures the graph's density over occupied
    128x128 adjacency patches and picks 'blocks' (dense-patch MXU SpMM) for
    dense graphs, 'edges' (edge-tiled gather) for sparse ones — the
    GraphBLAS-style storage/format adaptivity.
    """
    n_pad = pad_to(n + 1, 128)
    sentinel = n
    e = len(rows)
    density = None
    if kind == "auto":
        if e:
            occupied = len(
                np.unique(
                    (rows // block_size).astype(np.int64) * (n_pad // block_size)
                    + cols // block_size
                )
            )
            density = e / occupied
        else:
            density = 0.0
        kind = "blocks" if density >= AUTO_DENSITY_THRESHOLD else "edges"
    if kind == "edges":
        e_pad = max(pad_to(e, tile_size), tile_size)
        r = np.full(e_pad, sentinel, np.int32)
        c = np.full(e_pad, sentinel, np.int32)
        r[:e] = rows
        c[:e] = cols
        written = np.zeros(n_pad, bool)
        written[r] = True
        with obs.span("plan.slab_layout"):
            slab_dst, slab_cols, spb = _build_slabs(
                np.asarray(rows), np.asarray(cols), n, n_pad, tile_size, row_tile
            )
        with obs.span("plan.piece_layout"):
            piece_cols, piece_rows = build_piece_layout(
                np.asarray(rows), np.asarray(cols), n_pad, tile_size, sentinel_col=sentinel
            )
        obs.count("neighbor_sum.pieces", sum(len(p) for p in piece_cols))
        obs.count("neighbor_sum.edge_slots", sum(p.size for p in piece_cols))
        return SpmmPlan(
            "edges",
            n,
            n_pad,
            rows=jnp.asarray(r),
            cols=jnp.asarray(c),
            written_mask=jnp.asarray(written),
            slab_dst=jnp.asarray(slab_dst),
            slab_cols=jnp.asarray(slab_cols),
            slabs_per_block=spb,
            piece_cols=tuple(jnp.asarray(p) for p in piece_cols),
            piece_rows=tuple(jnp.asarray(p) for p in piece_rows),
            tile_size=tile_size,
            row_tile=row_tile,
            patch_density=density,
        )
    if kind == "blocks":
        vb = kb = block_size
        br = rows // vb
        bc = cols // kb
        key = br.astype(np.int64) * (n_pad // kb + 1) + bc
        uniq, inv = np.unique(key, return_inverse=True)
        nb = len(uniq)
        patches = np.zeros((nb, vb, kb), np.float32)
        patches[inv, rows % vb, cols % kb] += 1.0
        block_rows = (uniq // (n_pad // kb + 1)).astype(np.int32)
        block_cols = (uniq % (n_pad // kb + 1)).astype(np.int32)
        # append one sentinel (all-zero) patch so NB >= 1 and the final
        # output block flushes; sentinel row block = n_pad // vb.
        block_rows = np.concatenate([block_rows, [n_pad // vb]]).astype(np.int32)
        block_cols = np.concatenate([block_cols, [0]]).astype(np.int32)
        patches = np.concatenate([patches, np.zeros((1, vb, kb), np.float32)], 0)
        written = np.zeros(n_pad, bool)
        for rb in block_rows[:-1]:
            written[rb * vb : (rb + 1) * vb] = True
        return SpmmPlan(
            "blocks",
            n,
            n_pad,
            block_rows=jnp.asarray(block_rows),
            block_cols=jnp.asarray(block_cols),
            patches=jnp.asarray(patches),
            block_size=block_size,
            written_mask=jnp.asarray(written),
            patch_density=density,
        )
    raise ValueError(f"unknown spmm plan kind {kind!r}")


def expected_patch_density(n: int, e_directed: int, block: int = 128) -> float:
    """Model of the ``kind="auto"`` patch-density signal for shape-only
    plans (dry-run cells, where no edges exist to measure): expected edges
    per occupied ``block x block`` adjacency patch under uniform placement,
    ``E[occupied] = patches * (1 - exp(-e / patches))``.  Real plans carry
    the measured value in :attr:`SpmmPlan.patch_density` instead."""
    import math

    nb = max(1, pad_to(n + 1, block) // block)
    patches = float(nb) * float(nb)
    occupied = patches * (1.0 - math.exp(-float(e_directed) / patches))
    return float(e_directed) / max(occupied, 1.0)


#: bound, in elements, on the gathered ``[edges, B]`` intermediate of the
#: XLA neighbor sums; longer edge lists and piece groups are summed in
#: chunks.  Unchunked, XLA materializes every gathered row (15 GB for a
#: 2^20-vertex Graph500 graph at B = 128).
XLA_GATHER_ELEMENTS = 1 << 26


def gather_scatter_add(
    table: jax.Array,
    cols: jax.Array,
    rows: jax.Array,
    num_rows: int,
    *,
    indices_are_sorted: bool = False,
) -> jax.Array:
    """``out[rows[e]] += table[cols[e]]`` over ``num_rows`` output rows;
    ``rows`` out of range are dropped.  The XLA neighbor sum, with the
    gathered intermediate bounded by ``XLA_GATHER_ELEMENTS``."""
    e, b = cols.shape[0], table.shape[1]
    chunk = max(1, XLA_GATHER_ELEMENTS // b)
    if e <= chunk:
        return jax.ops.segment_sum(
            jnp.take(table, cols, axis=0),
            rows,
            num_segments=num_rows,
            indices_are_sorted=indices_are_sorted,
        )
    steps = -(-e // chunk)
    pad = steps * chunk - e
    cols = jnp.pad(cols, (0, pad)).reshape(steps, chunk)
    rows = jnp.pad(rows, (0, pad), constant_values=num_rows).reshape(steps, chunk)

    def step(acc, rc):
        r, c = rc
        acc = acc.at[r].add(
            jnp.take(table, c, axis=0), mode="drop", indices_are_sorted=indices_are_sorted
        )
        return acc, None

    acc0 = pvary_like(jnp.zeros((num_rows, b), table.dtype), table)
    return jax.lax.scan(step, acc0, (rows, cols))[0]


#: ``table[idx]`` for ``[pieces, width, 1]`` row indices, and the scatter-add
#: of ``[pieces, B]`` rows into a table by ``[pieces, 1]`` row indices
_ROW_GATHER = jax.lax.GatherDimensionNumbers(
    offset_dims=(2,), collapsed_slice_dims=(0,), start_index_map=(0,)
)
_ROW_SCATTER = jax.lax.ScatterDimensionNumbers(
    update_window_dims=(1,), inserted_window_dims=(0,), scatter_dims_to_operand_dims=(0,)
)


def piece_sum(
    table: jax.Array,
    piece_cols: Tuple[jax.Array, ...],
    piece_rows: Tuple[jax.Array, ...],
    num_rows: int,
) -> jax.Array:
    """The neighbor sum over a piece layout (:func:`build_piece_layout`):
    each piece is summed by a gather and a reduction over its slots, and
    the ``[pieces, B]`` sums are added into ``[num_rows, B]`` by a scatter
    over pieces, not edges.  The gathered ``[pieces, 2^j, B]``
    intermediate is bounded by ``XLA_GATHER_ELEMENTS``: longer groups are
    summed in chunks.  ``piece_cols`` index ``table``, whose rows named by
    pads must be zero."""
    b = table.shape[1]
    in_bounds = jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS

    def add(out, cols, rows):
        sums = jax.lax.gather(
            table, cols[:, :, None], _ROW_GATHER, (1, b), mode=in_bounds
        ).sum(axis=1)
        # rows are sorted but not declared so: on a TPU v5e the sorted
        # scatter passes over the whole [num_rows, B] table on every call,
        # 6 ms at 524,416 x 896, where one chunk's pieces take far less
        return jax.lax.scatter_add(out, rows[:, None], sums, _ROW_SCATTER, mode=in_bounds)

    out = pvary_like(jnp.zeros((num_rows, b), table.dtype), table)
    for cols, rows in zip(piece_cols, piece_rows):
        pieces, width = cols.shape
        chunk = max(1, XLA_GATHER_ELEMENTS // (width * b))
        steps = pieces // chunk if pieces > chunk else 0
        if steps:

            def step(i, out, cols=cols, rows=rows, chunk=chunk):
                c = jax.lax.dynamic_slice_in_dim(cols, i * chunk, chunk)
                return add(out, c, jax.lax.dynamic_slice_in_dim(rows, i * chunk, chunk))

            out = jax.lax.fori_loop(0, steps, step, out)
        if steps * chunk < pieces:
            out = add(out, cols[steps * chunk :], rows[steps * chunk :])
    return out


def spmm(plan: SpmmPlan, table: jax.Array, impl: str = "auto") -> jax.Array:
    """Neighbor sum ``M[v] = sum_{(v,u) in E} table[u]``.

    ``table``: [n_pad, B_pad]; returns [n_pad, B_pad].  Rows >= plan.n of the
    input must be zero; output rows >= plan.n are unspecified (engine masks).
    """
    n_pad, b = table.shape
    assert n_pad == plan.n_pad, (n_pad, plan.n_pad)
    impl = spmm_impl(plan, b, impl)
    if plan.kind == "edges":
        if impl == "xla":
            return piece_sum(table, plan.piece_cols, plan.piece_rows, plan.n_pad)
        # edge-tiled kernel writes every output block (pad slabs contribute
        # zeros), so zero-degree rows come out correctly zeroed
        return spmm_edge_tile_pallas(
            plan.slab_dst,
            plan.slab_cols,
            table,
            slabs_per_block=plan.slabs_per_block,
            row_tile=plan.row_tile,
            interpret=not on_tpu(),
        )
    # blocks
    if impl == "xla":
        # dense-block einsum fallback (oracle for the block kernel)
        kb = plan.block_size
        gathered = table.reshape(n_pad // kb, kb, b)[plan.block_cols]  # [NB,KB,B]
        prod = jnp.einsum("nvk,nkb->nvb", plan.patches, gathered)
        out = jnp.zeros((n_pad // kb + 1, kb, b), table.dtype)
        out = out.at[plan.block_rows].add(prod)
        return out[: n_pad // kb].reshape(n_pad, b)
    nb_rows = plan.n_pad // plan.block_size
    out = spmm_block_pallas(
        plan.block_rows,
        plan.block_cols,
        plan.patches,
        table,
        num_row_blocks=nb_rows,
        interpret=not on_tpu(),
    )[: plan.n_pad]
    return jnp.where(plan.written_mask[:, None], out, 0)


def spmm_compact(
    plan: SpmmPlan,
    table_c: jax.Array,  # [cap, B] compact source (active rows gathered)
    inv: jax.Array,  # [n_pad] int32 row -> compact slot (inactive -> zero slot)
    impl: str = "auto",
) -> jax.Array:
    """Neighbor sum driven through a row-index indirection: the same edge
    program as :func:`spmm`, but every source lookup goes ``row -> inv ->
    compact slot``, so the gathered table is the ``[cap, B]`` active-row
    form — inactive rows are never touched, and the Pallas kernel's
    VMEM-resident table shrinks from ``n_pad`` to ``cap`` rows.  Exact:
    rows outside the compact form are all-zero by construction, which is
    precisely what the dense gather would have contributed.

    Requires an edge plan (``kind == 'edges'``).  Returns ``[n_pad, B]``;
    output rows >= plan.n are unspecified (engine masks).
    """
    assert plan.kind == "edges", "spmm_compact needs the edge-slab layout"
    impl = spmm_impl(plan, table_c.shape[1], impl, table_rows=table_c.shape[0])
    if impl == "xla":
        cols = tuple(jnp.take(inv, c) for c in plan.piece_cols)
        return piece_sum(table_c, cols, plan.piece_rows, plan.n_pad)
    return spmm_edge_tile_pallas(
        plan.slab_dst,
        jnp.take(inv, plan.slab_cols),
        table_c,
        slabs_per_block=plan.slabs_per_block,
        row_tile=plan.row_tile,
        out_rows=plan.n_pad,
        interpret=not on_tpu(),
    )


def spmm_slabs(
    slab_dst: jax.Array,  # [NRB * spb, tile] int32 block-local dst (-1 pad)
    slab_cols: jax.Array,  # [NRB * spb, tile] int32 rows of `table`
    table: jax.Array,  # [C, B] source table; sentinel cols must be zero rows
    *,
    out_rows: int,
    slabs_per_block: int,
    row_tile: int = 128,
    impl: str = "auto",
) -> jax.Array:
    """Neighbor sum over an explicit slab layout — the rectangular form of
    :func:`spmm` where the source table need not be the output table.

    The distributed engine routes its all-to-all consume through here: the
    slab columns index a ``[P * r_pad, B]`` concatenation of the received
    exchange chunks, while the output is this shard's ``[out_rows, B]``
    neighbor sum — the same edge-tile kernel as the single-device engine,
    one uniform ``tile``-edge task per grid step.  Returns [out_rows, B].

    ``table`` may arrive at narrow wire width (int16/int8 — the compressed
    exchange, DESIGN.md §18); it is widened to float32 here, once, so both
    kernel paths keep their float32 contract.
    """
    if table.dtype != jnp.float32:
        table = table.astype(jnp.float32)
    impl = resolve_impl(impl, edge_tile_vmem_bytes(table.shape[0], table.shape[1], row_tile))
    num_slabs, tile = slab_dst.shape
    nrb = out_rows // row_tile
    assert num_slabs == nrb * slabs_per_block, (num_slabs, nrb, slabs_per_block)
    if impl == "xla":
        blk = (jnp.arange(num_slabs, dtype=jnp.int32) // slabs_per_block) * row_tile
        dst_g = jnp.where(slab_dst < 0, out_rows, slab_dst + blk[:, None])
        return gather_scatter_add(table, slab_cols.reshape(-1), dst_g.reshape(-1), out_rows)
    return spmm_edge_tile_pallas(
        slab_dst,
        slab_cols,
        table,
        slabs_per_block=slabs_per_block,
        row_tile=row_tile,
        out_rows=out_rows,
        interpret=not on_tpu(),
    )


# ---------------------------------------------------------------------------
# Color-set combine
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CombineTables:
    """Padded split tables for one partition node."""

    idx1: jax.Array  # [S, J] int32 (xla layout)
    idx2: jax.Array
    idx1_t: jax.Array  # [J_pad, S_pad] int32 (pallas layout)
    idx2_t: jax.Array
    s: int  # true output width C(k, t)
    j: int  # true split count C(t, t1)
    s_pad: int


def build_combine_tables(
    k: int, t1: int, t2: int, *, lane: int = 128, sublane: int = 8
) -> CombineTables:
    """``lane``/``sublane`` set the column/row padding multiples.

    The Pallas kernels need the TPU-native 128/8; the XLA paths work at any
    width, and ``lane=1`` (true table widths) saves the 12.8x column-padding
    waste of small tables (e.g. the k-wide leaf tables) on CPU/GPU.
    """
    from repro.core.colorsets import split_tables

    idx1, idx2 = split_tables(k, t1, t2)
    s, j = idx1.shape
    s_pad = pad_to(s, lane)
    j_pad = pad_to(j, sublane)
    idx1_t = np.zeros((j_pad, s_pad), np.int32)
    idx2_t = np.zeros((j_pad, s_pad), np.int32)
    idx1_t[:j, :s] = idx1.T
    idx2_t[:j, :s] = idx2.T
    return CombineTables(
        idx1=jnp.asarray(idx1),
        idx2=jnp.asarray(idx2),
        idx1_t=jnp.asarray(idx1_t),
        idx2_t=jnp.asarray(idx2_t),
        s=s,
        j=j,
        s_pad=s_pad,
    )


def color_combine(
    left: jax.Array,  # [n_pad, A_pad]
    m: jax.Array,  # [n_pad, B_pad]
    tables: CombineTables,
    impl: str = "auto",
    xla_chunk: int = 8,
) -> jax.Array:
    """``out[v, s] = sum_j left[v, idx1[s,j]] * m[v, idx2[s,j]]``.

    Returns [n_pad, S_pad]; pad rows/cols are unspecified (engine masks).
    """
    impl = combine_impl(left.shape[1], m.shape[1], tables, impl)
    if impl == "xla":
        n = left.shape[0]
        s, j = tables.idx1.shape
        # bound the [n, S, j_chunk] gather intermediate to ~2^27 elements
        # (the paper's bounded-intermediate principle, §3.2.1 / Eq. 7)
        budget = 1 << 27
        if n * s * j <= budget:
            out = ref.color_combine_ref(left, m, tables.idx1, tables.idx2)
        else:
            xla_chunk = max(1, min(xla_chunk, budget // max(n * s, 1)))

            # j-chunked accumulation to bound the [n, S, j] intermediate
            def body(jc, acc):
                i1 = jax.lax.dynamic_slice(tables.idx1, (0, jc), (s, xla_chunk))
                i2 = jax.lax.dynamic_slice(tables.idx2, (0, jc), (s, xla_chunk))
                return acc + jnp.einsum("vsj,vsj->vs", left[:, i1], m[:, i2])

            # iterate full chunks; handle the ragged tail separately
            acc = pvary_like(jnp.zeros((n, s), left.dtype), left)
            full = (j // xla_chunk) * xla_chunk
            acc = jax.lax.fori_loop(
                0,
                full // xla_chunk,
                lambda c, a: body(c * xla_chunk, a),
                acc,
            )
            if full < j:
                i1 = tables.idx1[:, full:]
                i2 = tables.idx2[:, full:]
                acc = acc + jnp.einsum("vsj,vsj->vs", left[:, i1], m[:, i2])
            out = acc
        s_out = tables.s_pad
        if out.shape[1] < s_out:
            out = jnp.pad(out, ((0, 0), (0, s_out - out.shape[1])))
        return out
    n = left.shape[0]
    rows = pad_to(n, 128)  # the kernel's row tile
    if rows != n:
        left = jnp.pad(left, ((0, rows - n), (0, 0)))
        m = jnp.pad(m, ((0, rows - n), (0, 0)))
    return color_combine_pallas(
        left,
        m,
        tables.idx1_t,
        tables.idx2_t,
        num_splits=tables.j,
        interpret=not on_tpu(),
    )[:n]


# ---------------------------------------------------------------------------
# Fused SpMM -> combine (fine-grained pipeline, §3.2)
# ---------------------------------------------------------------------------


def fused_count(
    plan: SpmmPlan,
    left: jax.Array,  # [n_pad, A_pad]
    right: jax.Array,  # [n_pad, B_pad]; rows >= plan.n must be zero
    tables: CombineTables,
    impl: str = "auto",
) -> jax.Array:
    """``out[v, s] = sum_j left[v, idx1[s,j]] * (A @ right)[v, idx2[s,j]]``
    without materializing the full neighbor-sum table ``M = A @ right``.

    Requires the edge-slab layout (``plan.kind == 'edges'``); a block plan
    falls back to the two-step spmm + combine path.  Returns
    ``[n_pad, S_pad]``; pad rows/cols are unspecified (engine masks).
    """
    if plan.slab_dst is None:
        m = spmm(plan, right, impl=impl)
        mask = (jnp.arange(plan.n_pad) < plan.n).astype(m.dtype)[:, None]
        return color_combine(left, m * mask, tables, impl=impl)
    impl = fused_impl(left.shape[1], right.shape[1], tables, impl, plan.n_pad, plan.row_tile)
    if impl == "xla":
        out = fused_count_xla(
            plan.slab_dst,
            plan.slab_cols,
            left,
            right,
            tables.idx1,
            tables.idx2,
            row_tile=plan.row_tile,
        )
        if out.shape[1] < tables.s_pad:
            out = jnp.pad(out, ((0, 0), (0, tables.s_pad - out.shape[1])))
        return out
    return fused_count_pallas(
        plan.slab_dst,
        plan.slab_cols,
        left,
        right,
        tables.idx1_t,
        tables.idx2_t,
        num_splits=tables.j,
        slabs_per_block=plan.slabs_per_block,
        row_tile=plan.row_tile,
        interpret=not on_tpu(),
    )


def fused_count_compact(
    plan: SpmmPlan,
    left: jax.Array,  # [n_pad, A_pad]
    right_c: jax.Array,  # [cap, B] compact right table (active rows)
    inv: jax.Array,  # [n_pad] int32 row -> compact slot (inactive -> zero slot)
    tables: CombineTables,
    impl: str = "auto",
) -> jax.Array:
    """:func:`fused_count` with the right operand in compact active-row
    form, routed through the same row-index indirection as
    :func:`spmm_compact` — the fused kernel's resident source table shrinks
    to ``cap`` rows and ``M`` still never materializes.  Requires the
    edge-slab layout.  Returns ``[n_pad, S_pad]`` (engine masks pads)."""
    assert plan.slab_dst is not None, "fused_count_compact needs edge slabs"
    impl = fused_impl(
        left.shape[1], right_c.shape[1], tables, impl, right_c.shape[0], plan.row_tile
    )
    cols_c = jnp.take(inv, plan.slab_cols)
    if impl == "xla":
        out = fused_count_xla(
            plan.slab_dst,
            cols_c,
            left,
            right_c,
            tables.idx1,
            tables.idx2,
            row_tile=plan.row_tile,
        )
        if out.shape[1] < tables.s_pad:
            out = jnp.pad(out, ((0, 0), (0, tables.s_pad - out.shape[1])))
        return out
    return fused_count_pallas(
        plan.slab_dst,
        cols_c,
        left,
        right_c,
        tables.idx1_t,
        tables.idx2_t,
        num_splits=tables.j,
        slabs_per_block=plan.slabs_per_block,
        row_tile=plan.row_tile,
        interpret=not on_tpu(),
    )


def fused_count_slabs(
    slab_dst: jax.Array,  # [NRB * spb, tile] int32 block-local dst (-1 pad)
    slab_cols: jax.Array,  # [NRB * spb, tile] int32 rows of `right`
    left: jax.Array,  # [out_rows, A]
    right: jax.Array,  # [C, B] source table; sentinel cols must be zero rows
    tables: CombineTables,
    *,
    slabs_per_block: int,
    row_tile: int = 128,
    impl: str = "auto",
) -> jax.Array:
    """Rectangular form of :func:`fused_count` over an explicit slab layout.

    ``right`` may be any source table (the distributed engine passes the
    concatenated all-to-all exchange buffer); the ``[out_rows, B]`` neighbor
    sum is never materialized — each ``row_tile`` block of it lives only as
    the kernel scratch (or one ``lax.map`` step on XLA) before being
    contracted against the resident ``left`` block.  Returns
    ``[out_rows, S_pad]``; pad rows/cols unspecified (engine masks).

    ``right`` may arrive at narrow wire width (the compressed exchange,
    DESIGN.md §18); it is widened to float32 here, once, before dispatch.
    """
    if right.dtype != jnp.float32:
        right = right.astype(jnp.float32)
    impl = fused_impl(left.shape[1], right.shape[1], tables, impl, right.shape[0], row_tile)
    if impl == "xla":
        out = fused_count_xla(
            slab_dst,
            slab_cols,
            left,
            right,
            tables.idx1,
            tables.idx2,
            row_tile=row_tile,
        )
        if out.shape[1] < tables.s_pad:
            out = jnp.pad(out, ((0, 0), (0, tables.s_pad - out.shape[1])))
        return out
    return fused_count_pallas(
        slab_dst,
        slab_cols,
        left,
        right,
        tables.idx1_t,
        tables.idx2_t,
        num_splits=tables.j,
        slabs_per_block=slabs_per_block,
        row_tile=row_tile,
        interpret=not on_tpu(),
    )


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    impl: str = "auto",
    block_q: int = 128,
    block_k: int = 128,
) -> jax.Array:
    impl = resolve_impl(impl)
    if impl == "xla":
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    return flash_attention_pallas(
        q,
        k,
        v,
        causal=causal,
        window=window,
        block_q=block_q,
        block_k=block_k,
        interpret=not on_tpu(),
    )
