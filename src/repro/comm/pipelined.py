"""Grouped direct-send exchange — the paper's Algorithm 3 / Figure 2.

The all-to-all among P processes is decoupled into W steps; at step ``w``
process ``p`` sends its chunk for destination ``r = p + w`` and receives the
chunk addressed to it from ``p - w`` (the paper's ``C_{2p-r,p}``).  Each
step is one static ``ppermute`` with shift ``w``; with group factor ``g``
(the paper's communication-group size, ``m = g + 1``), ``g`` shifts are
issued per step, so ``W = ceil((P-1)/g)`` and peak in-flight payload is
``g`` chunks.

The consume callback runs on chunks from step ``w`` while step ``w+1``'s
permutes are in flight (paper Fig. 3).  Because the shift differs per step
the schedule is unrolled (W steps of HLO) — identical to the paper, where
each step has a distinct communication group; use the relay ring
(``comm.ring``) when O(1) program size matters more than direct delivery.
"""

from __future__ import annotations

from typing import Callable

import jax


__all__ = ["grouped_exchange", "fused_exchange"]


def _shift_perm(P: int, shift: int):
    return [(i, (i + shift) % P) for i in range(P)]


def fused_exchange(
    chunks: jax.Array,
    axis_name: str,
    consume: Callable[[jax.Array, jax.Array, int], jax.Array],
    init: jax.Array,
) -> jax.Array:
    """Monolithic all-to-all then consume — the paper's Naive mode.

    ``chunks``: [P, ...] where ``chunks[q]`` is this device's payload for
    device ``q``.  ``consume(acc, chunk, src)`` folds the chunk received
    from ``src`` (static int).  All P received chunks are materialized
    before compute starts (the paper's peak-memory pathology, kept
    deliberately for the Naive baseline).
    """
    P = jax.lax.axis_size(axis_name)
    received = jax.lax.all_to_all(chunks, axis_name, split_axis=0, concat_axis=0)
    p = jax.lax.axis_index(axis_name)
    acc = init
    for q in range(P):
        # received[q] is the chunk sent by device q to this device
        acc = consume(acc, received[q], q)
    return acc


def grouped_exchange(
    chunks: jax.Array,
    axis_name: str,
    consume: Callable[[jax.Array, jax.Array, jax.Array], jax.Array],
    init: jax.Array,
    *,
    group_factor: int = 1,
    include_local: bool = True,
) -> jax.Array:
    """Pipelined Adaptive-Group exchange (paper Algorithm 3, large-|T| arm).

    ``chunks``: [P, ...]; ``chunks[q]`` is the payload for device ``q``
    (``chunks[p]`` is consumed locally at the cold-start stage when
    ``include_local``).  ``consume(acc, chunk, src_index)`` gets a traced
    src index.  Peak received-payload memory is ``group_factor`` chunks
    instead of P (Eq. 12); each group's sends overlap the previous group's
    consumes (Eq. 13/14).
    """
    P = jax.lax.axis_size(axis_name)
    p = jax.lax.axis_index(axis_name)
    g = max(1, min(group_factor, P - 1))

    acc = init
    pending = []  # list of (chunk, src) received in the in-flight group
    if include_local:
        pending.append((jax.lax.dynamic_index_in_dim(chunks, p, 0, keepdims=False), p))

    for w0 in range(1, P, g):
        shifts = [s for s in range(w0, min(w0 + g, P))]
        arrived = []
        for s in shifts:
            # send chunk for (p + s), receive the chunk addressed to us
            # from (p - s)  — one permute per group member, issued before
            # the consumes below so the transfer overlaps them.
            outgoing = jax.lax.dynamic_index_in_dim(
                chunks, (p + s) % P, 0, keepdims=False
            )
            incoming = jax.lax.ppermute(outgoing, axis_name, _shift_perm(P, s))
            arrived.append((incoming, (p - s) % P))
        for chunk, src in pending:
            acc = consume(acc, chunk, src)
        pending = arrived
    for chunk, src in pending:
        acc = consume(acc, chunk, src)
    return acc
