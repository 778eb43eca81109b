"""Pallas TPU kernels for the neighbor sum ``M = A @ C`` (SpMM).

This is the second hotspot of the color-coding DP: for every directed edge
``(v, u)``, ``M[v, :] += C[u, :]``.  Two TPU-native realizations, both
embodying the paper's *neighbor-list partitioning* (§3.3) — bounded,
uniform-size tasks independent of degree skew:

``spmm_edge_tile_pallas``
    Edge-tiled SpMM.  The directed edge list is partitioned into *slabs* of
    exactly ``tile_size`` edges (the paper's bounded task size ``s``),
    grouped under the 128-row output block their destinations fall in; the
    grid is ``(row_blocks, slabs_per_block)`` with the slab axis innermost
    so output-block revisits are consecutive and a ``j == 0`` first-visit
    check re-zeroes the resident accumulator.  Each grid step brings the
    slab's destination and source indices into SMEM and adds source row
    ``C[col]`` into output row ``dst`` one edge at a time (dynamic
    one-row loads and stores, which Mosaic lowers; a vector gather by an
    index vector it does not).  A max-degree "supernode" row simply owns
    many slabs.  Padded slab slots carry ``dst = -1`` and the zero sentinel
    source row, so they add zeros.  The whole count table is held resident
    in VMEM (constant index_map); ``edge_tile_vmem_bytes`` says how much,
    and ``ops`` routes tables that do not fit to the XLA path.

``spmm_block_pallas``
    Block-dense SpMM.  The adjacency is tiled into dense 128x128 0/1
    patches over (dst-block, src-block); only nonzero patches are stored
    (coordinates ``block_rows``/``block_cols``, sorted by dst block).  Each
    grid step issues one MXU matmul ``patch @ C[src_block]`` and accumulates
    into the resident output block.  Wins over the edge-tiled kernel when
    occupied patches are dense enough that the 64 KB/patch storage and the
    full 128x128 matmul beat per-edge slab metadata (``build_spmm_plan``'s
    ``"auto"`` kind measures exactly this).

Preprocessing helpers that build the slab/patch arrays live in ``ops.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "VMEM_LIMIT_BYTES",
    "block_vmem_bytes",
    "edge_tile_vmem_bytes",
    "spmm_block_pallas",
    "spmm_edge_tile_pallas",
]

#: scoped VMEM the table-resident kernels ask Mosaic for.  A v5e TensorCore
#: has 128 MiB of VMEM; the compiler's default scoped limit is 16 MiB.
VMEM_LIMIT_BYTES = 64 << 20


# ---------------------------------------------------------------------------
# Block-dense SpMM (MXU path)
# ---------------------------------------------------------------------------


def block_vmem_bytes(block_size: int, width: int) -> int:
    """VMEM of one block-kernel step: the double-buffered 0/1 patch, source
    block and output block (float32)."""
    return 2 * 4 * block_size * (block_size + 2 * width)


def _block_kernel(block_rows_ref, block_cols_ref, patch_ref, table_ref, out_ref):
    nb = pl.program_id(0)
    row = block_rows_ref[nb]
    prev = block_rows_ref[jnp.maximum(nb - 1, 0)]
    first = jnp.logical_or(nb == 0, row != prev)

    @pl.when(first)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    patch = patch_ref[0]  # [VB, KB]
    ctab = table_ref[...]  # [KB, B]
    # HIGHEST: full float32 passes on the MXU, so integer counts stay exact
    out_ref[...] += jnp.dot(
        patch,
        ctab.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("num_row_blocks", "interpret"))
def spmm_block_pallas(
    block_rows: jax.Array,  # [NB] int32, sorted; sentinel = num_row_blocks
    block_cols: jax.Array,  # [NB] int32; sentinel patches point at block 0
    patches: jax.Array,  # [NB, VB, KB] f32 0/1 (sentinel patches all-zero)
    table: jax.Array,  # [n_pad, B]  (n_pad % KB == 0, B % 128 == 0)
    *,
    num_row_blocks: int,  # output row blocks EXCLUDING the sentinel block
    interpret: bool = False,
) -> jax.Array:
    nb, vb, kb = patches.shape
    n_pad, b = table.shape
    assert n_pad % kb == 0 and b % 128 == 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, vb, kb), lambda i, rows, cols: (i, 0, 0)),
            pl.BlockSpec((kb, b), lambda i, rows, cols: (cols[i], 0)),
        ],
        out_specs=pl.BlockSpec((vb, b), lambda i, rows, cols: (rows[i], 0)),
    )
    out = pl.pallas_call(
        _block_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(((num_row_blocks + 1) * vb, b), table.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="spmm_block",
    )(block_rows, block_cols, patches, table)
    return out


# ---------------------------------------------------------------------------
# Edge-tiled SpMM (general-sparsity path, tile_size edges per step)
# ---------------------------------------------------------------------------


def slab_specs(tile: int, spb: int):
    """SMEM block specs for one slab of the ``[num_slabs, 1, tile]`` dst/col
    index arrays at grid step ``(i, j)``: slab ``i * spb + j``.  The unit
    middle axis makes the block's last two dims equal the array's, which
    the TPU tiling rule accepts for any ``tile``."""
    spec = pl.BlockSpec(
        (None, 1, tile), lambda i, j: (i * spb + j, 0, 0), memory_space=pltpu.SMEM
    )
    return [spec, spec]


def scatter_slab(dst_ref, col_ref, table_ref, acc_ref):
    """``acc[dst[e]] += table[col[e]]`` for the slab's ``tile`` edges.

    Pad slots (``dst = -1``) are clamped onto row 0 and read the all-zero
    sentinel source row, so they add exact zeros."""

    def body(e, carry):
        d = jnp.maximum(dst_ref[0, e], 0)
        c = col_ref[0, e]
        row = table_ref[pl.ds(c, 1), :].astype(acc_ref.dtype)
        acc_ref[pl.ds(d, 1), :] += row
        return carry

    jax.lax.fori_loop(0, dst_ref.shape[1], body, 0)


def edge_tile_vmem_bytes(table_rows: int, width: int, row_tile: int = 128) -> int:
    """VMEM the edge-tile kernel holds: the resident ``[table_rows, width]``
    source table and the ``[row_tile, width]`` output block, each double
    buffered by the pipeline (float32)."""
    return 2 * 4 * (table_rows + row_tile) * width


def _edge_tile_kernel(dst_ref, col_ref, table_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    scatter_slab(dst_ref, col_ref, table_ref, out_ref)


@functools.partial(
    jax.jit, static_argnames=("slabs_per_block", "row_tile", "out_rows", "interpret")
)
def spmm_edge_tile_pallas(
    slab_dst: jax.Array,  # [NRB * spb, tile_size] int32 local dst (-1 pad)
    slab_cols: jax.Array,  # [NRB * spb, tile_size] int32 src row of `table`
    table: jax.Array,  # [C, B]; sentinel source rows must be zero
    *,
    slabs_per_block: int,
    row_tile: int = 128,
    out_rows: int = None,
    interpret: bool = False,
) -> jax.Array:
    """``out_rows`` decouples the output height from the source table: the
    distributed engine scatters a ``[P * r_pad, B]`` exchange buffer into
    this shard's ``[n_loc_pad, B]`` neighbor sum; the single-device square
    case (``out_rows=None``) scatters the vertex table into itself."""
    c, b = table.shape
    if out_rows is None:
        out_rows = c
    nrb = out_rows // row_tile
    spb = slabs_per_block
    num_slabs, tile = slab_dst.shape
    assert num_slabs == nrb * spb, (num_slabs, nrb, spb)
    return pl.pallas_call(
        _edge_tile_kernel,
        grid=(nrb, spb),
        in_specs=[
            *slab_specs(tile, spb),
            pl.BlockSpec((c, b), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((row_tile, b), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((out_rows, b), table.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="spmm_edge_tile",
    )(slab_dst[:, None], slab_cols[:, None], table)
