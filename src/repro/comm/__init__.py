"""Adaptive-Group communication library (the paper's §3.2, generalized).

The paper decomposes a monolithic all-to-all into W ring-ordered steps of
small communication groups, overlapping each step's transfer with compute on
the previously received chunk, and switches back to the fused collective
when the workload's computation intensity is too low to hide the latency.

This package provides that pattern as reusable JAX collectives (usable under
``shard_map``), consumed by three call sites:

  * the distributed counting engine (``core.distributed``) — the faithful
    reproduction;
  * MoE token dispatch (``models.moe``) — the same exchange shape applied to
    transformers (beyond paper);
  * gradient reduction (``train``) — ring reduce-scatter, optionally
    int8-compressed (beyond paper).
"""

from .ring import ring_allgather, ring_allgather_overlap, ring_reduce_scatter  # noqa: F401
from .pipelined import grouped_exchange, fused_exchange  # noqa: F401
from .adaptive import (  # noqa: F401
    HockneyModel,
    V5E_ICI,
    V5E_DCI,
    assumed_model,
    calibrate,
    choose_mode,
    choose_mode_full,
    overlap_ratio,
    pipeline_cost,
    fused_cost,
)
from .compress import (  # noqa: F401
    WIRE_DTYPES,
    WIRE_ESCALATION,
    int8_compress,
    int8_decompress,
    compressed_ring_reduce_scatter,
    mask_column_count,
    mask_columns,
    mask_from_columns,
    narrow_cast,
    widen,
    wire_itemsize,
)
