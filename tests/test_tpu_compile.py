"""Compile the counting path's four Pallas kernels for a described TPU v5e.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: block shapes off the (8, 128) tiling, gathers Mosaic
does not lower, more VMEM than a kernel may hold.  These tests lower and
compile each kernel, plain and batched over colorings as the engine calls
it, for one chip of a ``v5e:2x2`` topology that is described, not attached.
Nothing runs.

Widths are those of the Graph500 scale-20 ``u7-2`` run: 128-lane tables
(every ``C(7, t) <= 35`` pads to one lane block) and up to 35 splits.  The
tiled kernels (combine, block SpMM) compile at that run's full vertex
count; the table-resident kernels (edge-tile SpMM, fused) at the largest
table ``ops`` routes to them, which checks that routing bound against the
compiler.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.color_combine import color_combine_pallas
from repro.kernels.fused_count import fused_count_pallas
from repro.kernels.spmm_edgetile import spmm_block_pallas, spmm_edge_tile_pallas

N_PAD = ops.pad_to(2**20 + 1, 128)  # scale-20 vertex rows (+ sentinel)
WIDTH = 128
SPLITS = 35  # C(7, 3)
J_PAD = ops.pad_to(SPLITS, 8)
TILE = 128
BATCH = 4


def _largest_rows(vmem_bytes) -> int:
    """Largest 128-multiple table height whose kernel ``ops`` would run."""
    rows = 128
    while vmem_bytes(rows + 128) <= ops.VMEM_LIMIT_BYTES:
        rows += 128
    return rows


EDGE_ROWS = _largest_rows(lambda r: ops.edge_tile_vmem_bytes(r, WIDTH))
FUSED_ROWS = _largest_rows(lambda r: ops.fused_vmem_bytes(r, WIDTH, WIDTH, WIDTH, J_PAD))


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(name, fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    # a Mosaic kernel, not the interpreter's XLA loop, named by its
    # ``pallas_call(name=...)``: the device trace names its events so
    assert re.search(
        rf"%{name}(\.\d+)? = \S+ custom-call\(.*custom_call_target=\"tpu_custom_call\"",
        compiled.as_text(),
    ), name
    return compiled


def _batched(fn, in_axes, batched: bool):
    return jax.vmap(fn, in_axes=in_axes) if batched else fn


@pytest.mark.parametrize("batched", [False, True])
def test_spmm_edge_tile_compiles(one_chip, batched):
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    spb = 2
    slabs = s(((EDGE_ROWS // 128) * spb, TILE), jnp.int32)
    lead = (BATCH,) if batched else ()
    fn = functools.partial(spmm_edge_tile_pallas, slabs_per_block=spb, interpret=False)
    _compile(
        "spmm_edge_tile",
        _batched(fn, (None, None, 0), batched),
        slabs,
        slabs,
        s(lead + (EDGE_ROWS, WIDTH), jnp.float32),
    )


@pytest.mark.parametrize("batched", [False, True])
def test_spmm_block_compiles(one_chip, batched):
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    nb = 4096  # occupied 128x128 patches (+ the sentinel)
    lead = (BATCH,) if batched else ()
    fn = functools.partial(spmm_block_pallas, num_row_blocks=N_PAD // 128, interpret=False)
    _compile(
        "spmm_block",
        _batched(fn, (None, None, None, 0), batched),
        s((nb,), jnp.int32),
        s((nb,), jnp.int32),
        s((nb, 128, 128), jnp.float32),
        s(lead + (N_PAD, WIDTH), jnp.float32),
    )


@pytest.mark.parametrize("batched", [False, True])
def test_fused_count_compiles(one_chip, batched):
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    spb = 2
    slabs = s(((FUSED_ROWS // 128) * spb, TILE), jnp.int32)
    idx = s((J_PAD, WIDTH), jnp.int32)
    table = s(((BATCH,) if batched else ()) + (FUSED_ROWS, WIDTH), jnp.float32)
    fn = functools.partial(
        fused_count_pallas, num_splits=SPLITS, slabs_per_block=spb, interpret=False
    )
    _compile(
        "fused_count",
        _batched(fn, (None, None, 0, 0, None, None), batched),
        slabs,
        slabs,
        table,
        table,
        idx,
        idx,
    )


@pytest.mark.parametrize("batched", [False, True])
def test_color_combine_compiles(one_chip, batched):
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    idx = s((J_PAD, WIDTH), jnp.int32)
    table = s(((BATCH,) if batched else ()) + (N_PAD, WIDTH), jnp.float32)
    fn = functools.partial(color_combine_pallas, num_splits=SPLITS, interpret=False)
    _compile("color_combine", _batched(fn, (0, 0, None, None), batched), table, table, idx, idx)
