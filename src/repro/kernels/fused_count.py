"""Fused SpMM -> color-combine kernel — the paper's fine-grained pipeline
(§3.2) realized at kernel granularity.

Computes, per sub-template split ``T_i -> (T_i', T_i'')``::

    out[v, s] = sum_j left[v, idx1[j, s]] * M[v, idx2[j, s]],
    M = A @ right   (neighbor sum)

WITHOUT ever writing the full ``[n_pad, B]`` neighbor-sum table ``M`` to
HBM.  The unfused engine materializes ``M`` between the SpMM and the
combine, so its per-node intermediate footprint is ``|C_left| + |C_right| +
|M| + |out|``; fusing drops the ``|M|`` term (``M`` exists only as one
``[row_tile, B]`` VMEM tile at a time), roughly halving the footprint for
large templates where ``B`` is the dominant table width.

Layout (shared with ``spmm_edge_tile_pallas``; built by
``ops.build_spmm_plan(kind='edges')``): the directed edge list is cut into
slabs of ``tile_size`` edges grouped under the ``row_tile``-row output block
of their destinations — the paper's bounded neighbor-list task size ``s``.

``fused_count_pallas``
    grid = (row_blocks, slabs_per_block), slab axis innermost.  Each step
    adds its slab's source rows into the resident ``[row_tile, B]``
    scratch (SMEM indices, one row per edge, as in the SpMM kernel); the
    *last* slab of a row block runs the split-table contraction (the
    combine kernel's one-hot selection matmuls) against the resident
    ``left`` block and writes the ``[row_tile, S]`` output tile.  One pass
    over the edges, zero HBM traffic for ``M``.

``fused_count_xla``
    The same schedule for non-TPU backends: ``lax.map`` (a sequential scan)
    over row blocks, each computing its ``[row_tile, B]`` neighbor-sum
    block via segment-sum and contracting it immediately.  Peak live
    intermediate is one block's worth of ``M``; the jaxpr provably contains
    no ``[n_pad, B]`` value (asserted by tests/test_kernels.py).

Oracle: ``ref.fused_count_ref`` (segment-sum then dense combine).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .color_combine import split_contract
from .spmm_edgetile import VMEM_LIMIT_BYTES, scatter_slab, slab_specs

__all__ = ["fused_count_pallas", "fused_count_xla", "fused_vmem_bytes"]


def fused_vmem_bytes(
    table_rows: int, a: int, b: int, s_pad: int, j_pad: int, row_tile: int = 128
) -> int:
    """VMEM the fused kernel holds: the double-buffered resident source
    table, ``left`` block, split tables and output tile, plus the
    ``[row_tile, B]`` neighbor-sum scratch and the combine's one-hot
    selection matrices (float32/int32)."""
    buffered = table_rows * b + row_tile * (a + s_pad) + 2 * j_pad * s_pad
    return 4 * (2 * buffered + row_tile * b + (a + b) * s_pad)


def _fused_kernel(
    dst_ref,
    col_ref,
    right_ref,
    left_ref,
    idx1_ref,
    idx2_ref,
    out_ref,
    m_ref,
    *,
    num_splits: int,
    slabs_per_block: int,
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.zeros_like(m_ref)

    scatter_slab(dst_ref, col_ref, right_ref, m_ref)

    @pl.when(j == slabs_per_block - 1)
    def _combine():
        # m_ref is the only life M ever has
        acc = split_contract(left_ref[...], m_ref[...], idx1_ref, idx2_ref, num_splits)
        out_ref[...] = acc.astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("num_splits", "slabs_per_block", "row_tile", "interpret"),
)
def fused_count_pallas(
    slab_dst: jax.Array,  # [NRB * spb, tile] int32 local dst (-1 pad)
    slab_cols: jax.Array,  # [NRB * spb, tile] int32 src row of `right`
    left: jax.Array,  # [out_rows, A] — output height follows `left`
    right: jax.Array,  # [C, B]; sentinel source rows must be zero
    idx1_t: jax.Array,  # [J_pad, S_pad] int32 transposed split table (left)
    idx2_t: jax.Array,  # [J_pad, S_pad] int32 (neighbor-sum side)
    *,
    num_splits: int,  # true J (<= J_pad)
    slabs_per_block: int,
    row_tile: int = 128,
    interpret: bool = False,
) -> jax.Array:
    c, b = right.shape
    out_rows, a = left.shape
    s_pad = idx1_t.shape[1]
    nrb = out_rows // row_tile
    spb = slabs_per_block
    num_slabs, tile = slab_dst.shape
    assert num_slabs == nrb * spb, (num_slabs, nrb, spb)
    kernel = functools.partial(_fused_kernel, num_splits=num_splits, slabs_per_block=spb)
    return pl.pallas_call(
        kernel,
        grid=(nrb, spb),
        in_specs=[
            *slab_specs(tile, spb),
            pl.BlockSpec((c, b), lambda i, j: (0, 0)),
            pl.BlockSpec((row_tile, a), lambda i, j: (i, 0)),
            pl.BlockSpec((idx1_t.shape[0], s_pad), lambda i, j: (0, 0)),
            pl.BlockSpec((idx2_t.shape[0], s_pad), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((row_tile, s_pad), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((out_rows, s_pad), left.dtype),
        scratch_shapes=[pltpu.VMEM((row_tile, b), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="fused_count",
    )(slab_dst[:, None], slab_cols[:, None], right, left, idx1_t, idx2_t)


@functools.partial(jax.jit, static_argnames=("row_tile",))
def fused_count_xla(
    slab_dst: jax.Array,  # [NRB * spb, tile] int32 local dst (-1 pad)
    slab_cols: jax.Array,  # [NRB * spb, tile] int32 global src
    left: jax.Array,  # [n_pad, A]
    right: jax.Array,  # [n_pad, B]; rows >= n must be zero
    idx1: jax.Array,  # [S, J] int32 split table (untransposed)
    idx2: jax.Array,
    *,
    row_tile: int = 128,
) -> jax.Array:
    """XLA realization of the fused schedule: sequential over row blocks.

    ``lax.map`` keeps one block in flight, so peak live intermediate is the
    ``[row_tile, B]`` neighbor-sum block — never the full ``[n_pad, B]``
    ``M``.  Under ``vmap`` (batched colorings) the map becomes a scan with a
    batched body: still one (batched) block of ``M`` alive at a time.
    """
    n_pad, a = left.shape
    nrb = n_pad // row_tile
    dst = slab_dst.reshape(nrb, -1)  # [NRB, spb * tile]
    cols = slab_cols.reshape(nrb, -1)
    left_blocks = left.reshape(nrb, row_tile, a)

    def block(xs):
        d, c, lblk = xs
        gathered = jnp.take(right, c, axis=0)  # [spb * tile, B]
        seg = jnp.where(d < 0, row_tile, d)  # pads -> discarded segment
        m_blk = jax.ops.segment_sum(gathered, seg, num_segments=row_tile + 1)[:row_tile]
        g1 = lblk[:, idx1]  # [row_tile, S, J]
        g2 = m_blk[:, idx2]
        return jnp.einsum("vsj,vsj->vs", g1, g2)

    out = jax.lax.map(block, (dst, cols, left_blocks))  # [NRB, row_tile, S]
    return out.reshape(n_pad, idx1.shape[0]).astype(left.dtype)
