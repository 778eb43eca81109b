"""Ring collectives via ``ppermute`` with structural compute/comm overlap.

These are the *relay* form of the paper's pipeline: a static shift-by-one
permutation applied W = P-1 times inside a ``fori_loop`` (HLO size is
O(1) in P), double-buffered so the next hop's ``ppermute`` is issued before
the compute on the current chunk — XLA's async collective scheduler then
overlaps the DMA with the compute, which is the paper's comm-thread /
compute-threads split realized structurally (DESIGN.md §2).

The cold-start stage (paper Fig. 3, stage 0) is the local-chunk compute
issued before the first hop.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.compat import pvary_like

__all__ = ["ring_allgather", "ring_allgather_overlap", "ring_reduce_scatter"]


def _shift_perm(P: int, shift: int = 1):
    return [(i, (i + shift) % P) for i in range(P)]


def ring_allgather(x: jax.Array, axis_name: str, *, tiled: bool = False) -> jax.Array:
    """All-gather via P-1 ring hops (reference; prefer lax.all_gather when
    no overlap is wanted — this exists to bound peak memory per step in
    callers that consume chunks immediately)."""
    P = jax.lax.axis_size(axis_name)
    p = jax.lax.axis_index(axis_name)

    def body(w, carry):
        out, buf = carry
        buf = jax.lax.ppermute(buf, axis_name, _shift_perm(P))
        src = (p - w - 1) % P
        out = jax.lax.dynamic_update_index_in_dim(out, buf, src, 0)
        return out, buf

    out0 = jnp.zeros((P,) + x.shape, x.dtype)
    out0 = jax.lax.dynamic_update_index_in_dim(out0, x, p, 0)
    out, _ = jax.lax.fori_loop(0, P - 1, body, (out0, x))
    if tiled:
        out = out.reshape((P * x.shape[0],) + x.shape[1:])
    return out


def ring_allgather_overlap(
    x: jax.Array,
    axis_name: str,
    combine: Callable[[jax.Array, jax.Array, jax.Array], jax.Array],
    init: jax.Array,
) -> jax.Array:
    """Pipelined all-gather-and-consume: never materializes all P chunks.

    ``combine(acc, chunk, src_index) -> acc`` is invoked once per shard, with
    the shard of device ``src_index`` (traced int32).  Peak live memory is
    ``|acc| + 2 * |chunk|`` (double buffer) versus ``|acc| + P * |chunk|``
    for gather-then-consume — the paper's Eq. 12 peak-memory reduction.

    The hop-w ``ppermute`` is issued *before* the chunk-w compute, so the
    transfer overlaps the combine (paper Fig. 3 pipeline; ratio rho_w of
    Eq. 14 is realized by XLA async scheduling).
    """
    P = jax.lax.axis_size(axis_name)
    p = jax.lax.axis_index(axis_name)

    def body(w, carry):
        acc, buf = carry
        nxt = jax.lax.ppermute(buf, axis_name, _shift_perm(P))  # hop w+1 in flight
        src = (p - w) % P  # buf currently holds the shard of device (p - w)
        acc = combine(acc, buf, src)  # overlaps with the permute
        return acc, nxt

    # w = 0 consumes the local shard (the paper's cold-start stage) while the
    # first hop flies; the final received chunk is consumed after the loop
    # without issuing another hop (P-1 permutes, P combines total).
    acc, buf = jax.lax.fori_loop(0, P - 1, body, (pvary_like(init, x), x))
    acc = combine(acc, buf, (p + 1) % P)
    return acc


def ring_reduce_scatter(x: jax.Array, axis_name: str, *, chunk_axis: int = 0) -> jax.Array:
    """Ring reduce-scatter: input [P, ...] per device, output chunk ``p``.

    Chunk ``c`` starts at device ``c+1`` and accumulates around the ring,
    arriving fully reduced at device ``c``.  Peak live memory is one chunk
    (plus the input), and each hop's ppermute can overlap the local add.
    """
    if chunk_axis != 0:
        x = jnp.moveaxis(x, chunk_axis, 0)
    P = jax.lax.axis_size(axis_name)
    p = jax.lax.axis_index(axis_name)

    def body(w, buf):
        buf = jax.lax.ppermute(buf, axis_name, _shift_perm(P))
        # after this hop, buf holds the partial sum of chunk (p - w - 2)
        c = (p - w - 2) % P
        return buf + jax.lax.dynamic_index_in_dim(x, c, 0, keepdims=False)

    # device p initiates chunk (p - 1): sends x[p-1] to p+1
    buf0 = jax.lax.dynamic_index_in_dim(x, (p - 1) % P, 0, keepdims=False)
    buf = jax.lax.fori_loop(0, P - 1, body, buf0)
    return buf
