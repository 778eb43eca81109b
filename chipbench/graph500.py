"""The benchmark's own Graph500 Kronecker generator.

A copy of the generator of the Graph500 specification (section "Graph
Generation", the Kronecker generator of its reference code), kept here so that
no change to the program can move the yardstick.  The random bits are drawn on
the device in one jitted call from the seed; the host only drops the self loops
and duplicate edges that the device has marked.

For each of ``scale`` levels every edge picks one quadrant of the adjacency
matrix: the source bit is 1 with probability ``C + D``, the destination bit is
1 with probability ``D / (C + D)`` where the source bit is 1 and ``B / (A + B)``
where it is 0.  Vertex labels are then permuted at random.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnames=("scale", "edges", "initiator"))
def _kronecker(key, *, scale: int, edges: int, initiator: tuple):
    a, b, c, d = initiator
    n = 1 << scale
    k_bits, k_perm = jax.random.split(key)

    def level(i, ij):
        src, dst = ij
        u = jax.random.uniform(jax.random.fold_in(k_bits, i), (2, edges))
        src_bit = u[0] > a + b
        dst_bit = u[1] > jnp.where(src_bit, c / (c + d), a / (a + b))
        return src | (src_bit.astype(jnp.int32) << i), dst | (dst_bit.astype(jnp.int32) << i)

    zero = jnp.zeros(edges, jnp.int32)
    src, dst = jax.lax.fori_loop(0, scale, level, (zero, zero))
    perm = jax.random.permutation(k_perm, n).astype(jnp.int32)
    src, dst = perm[src], perm[dst]
    lo, hi = jnp.minimum(src, dst), jnp.maximum(src, dst)
    lo, hi = jax.lax.sort((lo, hi), num_keys=2)
    first = jnp.concatenate([jnp.ones(1, bool), (lo[1:] != lo[:-1]) | (hi[1:] != hi[:-1])])
    return lo, hi, first & (lo != hi)


def edges(key, scale: int, edge_factor: int, initiator):
    """``(n, edges)``: the ``n = 2**scale`` vertices and the distinct
    undirected edges ``[m, 2]`` (int64, ``lo < hi``, sorted) of a Kronecker
    graph of ``edge_factor * n`` generated edges, randomly relabelled."""
    lo, hi, keep = jax.device_get(
        _kronecker(key, scale=scale, edges=edge_factor << scale, initiator=tuple(initiator))
    )
    return 1 << scale, np.stack([lo[keep], hi[keep]], 1).astype(np.int64)
