"""Narrow wire formats for the exchange (exact) and gradients (lossy).

Two families live here:

**Exact narrow exchange** (the counting engine, DESIGN.md §18).  DP table
entries are nonnegative integer counts stored in float32, so any slab
whose maximum fits the target integer range round-trips bit-exactly
through ``int16``/``int8``.  ``narrow_cast`` ships a slab at wire width
and appends a saturation flag (``max <= dtype max``) to the caller's
speculate-check flag list — on overflow the whole batch re-runs on a
wider twin, the same contract as compaction overflow.  Compacted slabs
additionally carry their active-row bitmaps bit-packed into extra payload
*columns* of the same wire dtype (``mask_columns``/``mask_from_columns``),
replacing the float32 slot column: the receiver re-derives slot indices
from the mask with the same deterministic ``nonzero`` the sender used.

**Lossy int8 gradient compression** (the original beyond-paper ring
reduce-scatter): per-block fp32 scales, one quantization error per hop,
used by the train loop when ``grad_compression="int8"``.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp


__all__ = [
    "WIRE_DTYPES",
    "WIRE_ESCALATION",
    "wire_itemsize",
    "narrow_cast",
    "widen",
    "mask_column_count",
    "mask_columns",
    "mask_from_columns",
    "int8_compress",
    "int8_decompress",
    "compressed_ring_reduce_scatter",
]

# wire_dtype -> (jnp dtype, bytes per element, max exactly-held count)
# float32 is the wide (identity) wire; int widths hold counts exactly up
# to their max, guarded by the narrow_cast saturation flag.
WIRE_DTYPES: Dict[str, tuple] = {
    "float32": (jnp.float32, 4, None),
    "int16": (jnp.int16, 2, 32767),
    "int8": (jnp.int8, 1, 127),
}

# On saturation the batch re-dispatches one rung up this ladder (the
# float32 rung still speculates on compaction; its own twin is dense).
WIRE_ESCALATION: Dict[str, str] = {"int8": "int16", "int16": "float32"}

_WORD_BITS = {"int8": 8, "int16": 16}
_WORD_UINT = {"int8": jnp.uint8, "int16": jnp.uint16}


def wire_itemsize(wire_dtype: str) -> int:
    """Bytes per exchanged element for a wire dtype name."""
    return WIRE_DTYPES[wire_dtype][1]


def narrow_cast(
    x: jax.Array, wire_dtype: str, flags: Optional[List[jax.Array]] = None
) -> jax.Array:
    """Cast a nonnegative integer-valued float32 slab to the wire dtype.

    Appends the exactness guard ``max(x) <= dtype max`` to ``flags``;
    under that flag the cast round-trips bit-exactly (the clip makes the
    overflowing trace deterministic — its result is discarded by the
    redispatch anyway).  ``float32`` is the identity.
    """
    dt, _, maxv = WIRE_DTYPES[wire_dtype]
    if maxv is None:
        return x
    if flags is not None:
        flags.append(jnp.max(x) <= maxv)
    return jnp.clip(x, 0, maxv).astype(dt)


def widen(x: jax.Array) -> jax.Array:
    """Receiver-side inverse of ``narrow_cast`` (exact for in-range ints)."""
    return x if x.dtype == jnp.float32 else x.astype(jnp.float32)


def _pack_mask_words(mask: jax.Array, wire_dtype: str) -> jax.Array:
    """[..., r] activity mask -> bit-packed words of the wire dtype."""
    wb = _WORD_BITS[wire_dtype]
    r = mask.shape[-1]
    r_pad = -(-r // wb) * wb
    bits = jnp.asarray(mask, jnp.uint32)
    if r_pad != r:
        bits = jnp.concatenate(
            [bits, jnp.zeros(bits.shape[:-1] + (r_pad - r,), bits.dtype)], axis=-1
        )
    bits = bits.reshape(bits.shape[:-1] + (-1, wb))
    words = jnp.sum(bits << jnp.arange(wb, dtype=jnp.uint32), axis=-1)
    wdt = WIRE_DTYPES[wire_dtype][0]
    return jax.lax.bitcast_convert_type(words.astype(_WORD_UINT[wire_dtype]), wdt)


def mask_column_count(r_len: int, cap: int, wire_dtype: str) -> int:
    """How many payload columns carry a length-``r_len`` bitmap at ``cap`` rows."""
    n_words = -(-r_len // _WORD_BITS[wire_dtype])
    return -(-n_words // cap)


def mask_columns(mask: jax.Array, cap: int, wire_dtype: str) -> jax.Array:
    """Pack ``mask [..., r]`` into ``[..., cap, ncols]`` wire-dtype columns.

    The columns concatenate onto a ``[..., cap, B]`` compact slab so the
    bitmap rides the same collective as the rows it describes.
    """
    words = _pack_mask_words(mask, wire_dtype)
    n_words = words.shape[-1]
    ncols = -(-n_words // cap)
    pad = ncols * cap - n_words
    if pad:
        words = jnp.concatenate([words, jnp.zeros(words.shape[:-1] + (pad,), words.dtype)], axis=-1)
    cols = words.reshape(words.shape[:-1] + (ncols, cap))
    return jnp.swapaxes(cols, -1, -2)


def mask_from_columns(cols: jax.Array, r_len: int, wire_dtype: str) -> jax.Array:
    """Inverse of ``mask_columns``: ``[..., cap, ncols]`` -> bool ``[..., r_len]``."""
    wb = _WORD_BITS[wire_dtype]
    n_words = -(-r_len // wb)
    flat = jnp.swapaxes(cols, -1, -2).reshape(cols.shape[:-2] + (-1,))
    u = jax.lax.bitcast_convert_type(flat[..., :n_words], _WORD_UINT[wire_dtype]).astype(jnp.uint32)
    bits = (u[..., None] >> jnp.arange(wb, dtype=jnp.uint32)) & 1
    return bits.reshape(bits.shape[:-2] + (-1,))[..., :r_len] != 0


def _shift_perm(P: int, shift: int = 1):
    return [(i, (i + shift) % P) for i in range(P)]


def int8_compress(x: jax.Array, block: int = 256) -> Tuple[jax.Array, jax.Array]:
    """Flat int8 quantization with per-block scales.

    Returns (q [N], scales [N/block]) for flattened input padded to a block
    multiple by the caller.
    """
    flat = x.reshape(-1, block)
    scale = jnp.max(jnp.abs(flat), axis=1, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(flat / scale), -127, 127).astype(jnp.int8)
    return q.reshape(-1), scale[:, 0].astype(jnp.float32)


def int8_decompress(q: jax.Array, scale: jax.Array, block: int = 256) -> jax.Array:
    flat = q.reshape(-1, block).astype(jnp.float32)
    return (flat * scale[:, None]).reshape(-1)


def compressed_ring_reduce_scatter(x: jax.Array, axis_name: str, *, block: int = 256) -> jax.Array:
    """Ring reduce-scatter with int8 payloads; input [P, chunk...] per device.

    Output: this device's fully reduced chunk (fp32).  Chunk sizes must be a
    multiple of ``block`` elements.
    """
    P = jax.lax.axis_size(axis_name)
    p = jax.lax.axis_index(axis_name)
    chunk_shape = x.shape[1:]
    total = 1
    for d in chunk_shape:
        total *= d
    while total % block:  # shrink block to divide small chunks
        block //= 2
    block = max(block, 1)

    def quant(c):
        return int8_compress(c.reshape(-1), block)

    def dequant(q, s):
        return int8_decompress(q, s, block).reshape(chunk_shape)

    def body(w, carry):
        q, s = carry
        q = jax.lax.ppermute(q, axis_name, _shift_perm(P))
        s = jax.lax.ppermute(s, axis_name, _shift_perm(P))
        c = (p - w - 2) % P
        acc = dequant(q, s) + jax.lax.dynamic_index_in_dim(x, c, 0, keepdims=False)
        return quant(acc)

    q0, s0 = quant(jax.lax.dynamic_index_in_dim(x, (p - 1) % P, 0, keepdims=False))
    q, s = jax.lax.fori_loop(0, P - 1, body, (q0, s0))
    return dequant(q, s)
