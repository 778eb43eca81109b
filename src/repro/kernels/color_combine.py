"""Pallas TPU kernel for the color-set combine — the paper's compute hotspot.

Computes, per sub-template split ``T_i -> (T_i', T_i'')``::

    out[v, s] = sum_j left[v, idx1[j, s]] * m[v, idx2[j, s]]

where ``s`` ranks the output color set (|S| = t), ``j`` ranks the ordered
split ``S = S1 (+) S2`` and ``idx1/idx2`` map to ranks in the operand tables
(see ``core.colorsets.split_tables``; here they are TRANSPOSED to [J, S] so
the per-``j`` row lands on the sublane axis, letting the ``j`` loop use a
dynamic slice on the major dimension, which Mosaic supports).

TPU mapping (this is the Table-3 "computation complexity" term
``C(k,t) * C(t,t1)`` per vertex):

* grid = (n/TV, S/TS); each step holds the full operand rows for a TV-vertex
  tile in VMEM (worst case k=15: 2 x 128 x 6435 x 4B = 6.6 MB)
  and produces a (TV, TS) output tile.
* the inner ``j`` loop selects the operand columns by two one-hot
  selection matmuls on the MXU (``left @ [a == idx1[j, s]]``), built from
  the ``j``-th index row with an iota compare.  Mosaic does not lower a
  lane gather by an index vector; a 0/1 matrix product at ``HIGHEST``
  precision picks each value exactly.
* all column widths are padded to multiples of 128 by ``ops.py``; padded
  output columns are sliced off by the wrapper.

Validated against ``ref.color_combine_ref`` in interpret mode (CPU); on a
real TPU the same grid/block spec runs compiled.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .spmm_edgetile import VMEM_LIMIT_BYTES

__all__ = ["color_combine_pallas", "combine_vmem_bytes", "split_contract"]


def _select(x: jax.Array, idx_row: jax.Array) -> jax.Array:
    """``x[:, idx_row[0, s]]`` as ``x @ onehot``: exact for float32 counts."""
    onehot = (
        jax.lax.broadcasted_iota(jnp.int32, (x.shape[1], idx_row.shape[1]), 0) == idx_row
    ).astype(jnp.float32)
    return jnp.dot(
        x.astype(jnp.float32),
        onehot,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def split_contract(lv, mv, idx1_ref, idx2_ref, num_splits: int) -> jax.Array:
    """``sum_j lv[:, idx1[j]] * mv[:, idx2[j]]`` over the first
    ``num_splits`` rows of the transposed split tables; ``[TV, TS]``."""

    def body(j, acc):
        g1 = _select(lv, idx1_ref[pl.ds(j, 1), :])
        g2 = _select(mv, idx2_ref[pl.ds(j, 1), :])
        return acc + g1 * g2

    acc0 = jnp.zeros((lv.shape[0], idx1_ref.shape[1]), jnp.float32)
    return jax.lax.fori_loop(0, num_splits, body, acc0)


def combine_vmem_bytes(a: int, b: int, j_pad: int, tile_v: int = 128, tile_s: int = 128) -> int:
    """VMEM of one combine step: double-buffered ``left``/``m`` row blocks,
    split-table column blocks and output tile, plus the two one-hot
    selection matrices (float32/int32)."""
    blocks = tile_v * (a + b + tile_s) + 2 * j_pad * tile_s
    return 4 * (2 * blocks + (a + b) * tile_s)


def _combine_kernel(idx1_ref, idx2_ref, left_ref, m_ref, out_ref, *, num_splits: int):
    acc = split_contract(left_ref[...], m_ref[...], idx1_ref, idx2_ref, num_splits)
    out_ref[...] = acc.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_v", "tile_s", "num_splits", "interpret"))
def color_combine_pallas(
    left: jax.Array,  # [n, A]   (n % tile_v == 0, A % 128 == 0)
    m: jax.Array,  # [n, B]
    idx1_t: jax.Array,  # [J_pad, S] int32, transposed split table
    idx2_t: jax.Array,  # [J_pad, S]
    *,
    num_splits: int,  # true J (<= J_pad)
    tile_v: int = 128,
    tile_s: int = 128,
    interpret: bool = False,
) -> jax.Array:
    n, a = left.shape
    _, b = m.shape
    s = idx1_t.shape[1]
    assert n % tile_v == 0 and s % tile_s == 0, (n, s, tile_v, tile_s)
    grid = (n // tile_v, s // tile_s)
    kernel = functools.partial(_combine_kernel, num_splits=num_splits)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((idx1_t.shape[0], tile_s), lambda i, j: (0, j)),
            pl.BlockSpec((idx2_t.shape[0], tile_s), lambda i, j: (0, j)),
            pl.BlockSpec((tile_v, a), lambda i, j: (i, 0)),
            pl.BlockSpec((tile_v, b), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tile_v, tile_s), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, s), left.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="color_combine",
    )(idx1_t, idx2_t, left, m)
