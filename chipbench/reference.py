"""Plain color-coding reference: the colorful map count of a tree template.

Written from the algorithm (Alon, Yuster and Zwick's color coding, with the
dynamic program of Slota and Madduri's FASCIA), not from the program: it
imports nothing of ``repro`` and takes the graph as the benchmark's own edge
list and the template as the edge list in the traffic file.

For a tree rooted at ``r``, the table of a vertex ``v`` of the template holds,
for every graph vertex ``x`` and every set ``S`` of ``|sub(v)|`` colors, the
number of maps of ``v``'s subtree into the graph that send ``v`` to ``x``, keep
every edge and use exactly the colors ``S``.  It starts as the one-hot of the
coloring and takes in the template children one at a time:

    T(x, S) <- sum over S1 + S2 = S of  T(x, S1) * sum_{y ~ x} T_child(y, S2)

and the count is the sum of the root's table.  Tables are float32 on the
device, the neighbor sums run over edge chunks and the combines over row
chunks, so the reference fits beside nothing else on one chip.

``precision="high"`` forms every product as a ``Precision.HIGH`` matrix
unit forms it (three bfloat16 passes) and rounds every table that a neighbor
sum reads as such a pass rounds it; ``precision="bfloat16"`` stores every
table in bfloat16 and computes in float32.  These are the controls.
"""

from __future__ import annotations

import functools
import itertools
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: elements gathered per neighbor-sum step and per combine step; tables are
#: held at widths padded to whole 128-lane rows of the chip's vector memory
GATHER_ELEMENTS = 1 << 26
COMBINE_ELEMENTS = 1 << 27
LANES = 128
EDGE_MULTIPLE = 1 << 22
ROW_MULTIPLE = 1 << 12


def padded(width: int) -> int:
    return -(-width // LANES) * LANES


def children(edges: Sequence[Sequence[int]], k: int, root: int) -> Dict[int, List[int]]:
    adj: Dict[int, List[int]] = {v: [] for v in range(k)}
    for a, b in edges:
        adj[a].append(b)
        adj[b].append(a)
    out: Dict[int, List[int]] = {}
    stack, seen = [root], {root}
    while stack:
        v = stack.pop()
        out[v] = [u for u in adj[v] if u not in seen]
        seen.update(out[v])
        stack.extend(out[v])
    return out


def subtree_sizes(kids: Dict[int, List[int]], root: int) -> Dict[int, int]:
    size: Dict[int, int] = {}

    def visit(v):
        size[v] = 1 + sum(visit(c) for c in kids[v])
        return size[v]

    visit(root)
    return size


def automorphisms(edges: Sequence[Sequence[int]], k: int) -> int:
    """|Aut(T)| of a tree: rooted at its center, the product over vertices of
    the factorials of how often each isomorphism class recurs among the
    children; a bicentral tree doubles it when its two halves are alike."""
    adj: Dict[int, List[int]] = {v: [] for v in range(k)}
    for a, b in edges:
        adj[a].append(b)
        adj[b].append(a)
    leaves = [v for v in range(k) if len(adj[v]) <= 1]
    deg = {v: len(adj[v]) for v in range(k)}
    left, removed = k, set()
    while left > 2:
        nxt = []
        for v in leaves:
            removed.add(v)
            left -= 1
            for u in adj[v]:
                if u not in removed:
                    deg[u] -= 1
                    if deg[u] == 1:
                        nxt.append(u)
        leaves = nxt
    centers = [v for v in range(k) if v not in removed]

    def canon(v, parent) -> Tuple[str, int]:
        parts = [canon(c, v) for c in adj[v] if c != parent]
        count = 1
        for form, aut in parts:
            count *= aut
        forms = sorted(f for f, _ in parts)
        for _, group in itertools.groupby(forms):
            count *= math.factorial(len(list(group)))
        return "(" + "".join(forms) + ")", count

    if len(centers) == 1:
        return canon(centers[0], -1)[1]
    a, b = centers
    (fa, na), (fb, nb) = canon(a, b), canon(b, a)
    return na * nb * (2 if fa == fb else 1)


def copy_scale(k: int, aut: int) -> float:
    """Colorful maps of a k-vertex template under k colors -> copies."""
    return k**k / math.factorial(k) / aut


def _sets(k: int, t: int) -> Dict[Tuple[int, ...], int]:
    return {s: i for i, s in enumerate(itertools.combinations(range(k), t))}


@functools.lru_cache(maxsize=None)
def split_index(k: int, t1: int, t2: int) -> Tuple[np.ndarray, np.ndarray]:
    """For every color set ``S`` of size ``t1 + t2`` (lexicographic order),
    the indices of its ``C(t1 + t2, t1)`` splits into a ``t1``-set and the
    rest: int32 ``[C(k, t1 + t2), C(t1 + t2, t1)]`` each."""
    one, two = _sets(k, t1), _sets(k, t2)
    left, right = [], []
    for s in itertools.combinations(range(k), t1 + t2):
        ls, rs = [], []
        for s1 in itertools.combinations(s, t1):
            ls.append(one[s1])
            rs.append(two[tuple(c for c in s if c not in s1)])
        left.append(ls)
        right.append(rs)
    return np.asarray(left, np.int32), np.asarray(right, np.int32)


def _bf16(x: jax.Array) -> jax.Array:
    """``x`` rounded to bfloat16 and kept in float32 (``reduce_precision``,
    which no compiler pass elides, unlike a pair of casts)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _round_high(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    hi = _bf16(x)
    return hi, _bf16(x - hi)


def _product(a: jax.Array, b: jax.Array, precision: str) -> jax.Array:
    if precision != "high":
        return a * b
    (ah, al), (bh, bl) = _round_high(a), _round_high(b)
    return ah * bh + ah * bl + al * bh


@functools.partial(jax.jit, static_argnames=("precision",))
def _neighbor_sum(table, src, dst, precision):
    """``out[x] = sum of table[y] over the edges (x, y)``, in steps of
    ``GATHER_ELEMENTS`` gathered values."""
    if precision == "high":
        hi, lo = _round_high(table)
        table = hi + lo
    chunk = min(EDGE_MULTIPLE, 1 << max(12, (GATHER_ELEMENTS // table.shape[1]).bit_length() - 1))

    def step(acc, sd):
        s, d = sd
        return acc.at[d].add(table[s], mode="drop", indices_are_sorted=True), None

    acc = jnp.zeros_like(table)
    return jax.lax.scan(step, acc, (src.reshape(-1, chunk), dst.reshape(-1, chunk)))[0]


@functools.partial(jax.jit, static_argnames=("width", "rows", "precision"))
def _combine(left, right, i_left, i_right, width, rows, precision):
    """``out[x, S] = sum over the splits j of S of left[x, i_left[S, j]] *
    right[x, i_right[S, j]]``, in blocks of ``rows`` graph vertices held
    column-major, so that each split gathers whole rows."""
    n = left.shape[0]

    def block(lr):
        a, b = lr
        return _product(a.T[i_left], b.T[i_right], precision).sum(1)  # [S, rows]

    out = jax.lax.map(
        block, (left.reshape(n // rows, rows, -1), right.reshape(n // rows, rows, -1))
    )  # [n // rows, S, rows]
    out = out.transpose(0, 2, 1).reshape(n, -1)
    return jnp.pad(out, ((0, 0), (0, width - out.shape[1])))


@functools.partial(jax.jit, static_argnames=("width",))
def _leaf(coloring, width):
    ids = jnp.arange(width, dtype=jnp.int32)
    return (coloring[:, None] == ids[None, :]).astype(jnp.float32)


class Reference:
    """The reference on one graph: ``count(coloring)`` is the colorful map
    count of the template, in float32 tables and a float64 final sum."""

    def __init__(self, n: int, edges: np.ndarray, template_edges, k: int,
                 precision: str = "highest"):
        self.n, self.k, self.precision = n, k, precision
        self.rows = -(-n // ROW_MULTIPLE) * ROW_MULTIPLE
        e = np.asarray(edges, np.int64)
        src = np.concatenate([e[:, 1], e[:, 0]])
        dst = np.concatenate([e[:, 0], e[:, 1]])
        order = np.argsort(dst, kind="stable")
        src, dst = src[order], dst[order]
        pad = -len(src) % EDGE_MULTIPLE
        self.src = jnp.asarray(np.concatenate([src, np.zeros(pad, np.int64)]).astype(np.int32))
        self.dst = jnp.asarray(
            np.concatenate([dst, np.full(pad, self.rows, np.int64)]).astype(np.int32))
        self.root, self.kids = cheapest_root(template_edges, k)
        self.size = subtree_sizes(self.kids, self.root)

    def _store(self, table):
        return _bf16(table) if self.precision == "bfloat16" else table

    def _table(self, v, leaf):
        table, t1 = leaf, 1
        for c in self.kids[v]:
            nsum = self._store(
                _neighbor_sum(self._table(c, leaf), self.src, self.dst, self.precision))
            t2 = self.size[c]
            il, ir = split_index(self.k, t1, t2)
            rows = _rows_per_step(self.rows, il.size)
            table = self._store(_combine(table, nsum, jnp.asarray(il), jnp.asarray(ir),
                                         padded(len(il)), rows, self.precision))
            t1 += t2
        return table

    def count(self, coloring) -> float:
        col = jnp.full(self.rows, -1, jnp.int32).at[: self.n].set(
            jnp.asarray(coloring, jnp.int32)[: self.n]
        )
        leaf = _leaf(col, padded(self.k))
        root = self._table(self.root, leaf)
        return float(np.asarray(root, np.float64).sum())


def _rows_per_step(rows: int, elements_per_row: int) -> int:
    step = ROW_MULTIPLE
    while step > LANES and step * elements_per_row > COMBINE_ELEMENTS:
        step //= 2
    return step


def combine_work(kids: Dict[int, List[int]], size: Dict[int, int], k: int) -> int:
    """Multiply-adds per graph vertex of the combines, children in the given
    order."""
    total = 0
    for v, cs in kids.items():
        t1 = 1
        for c in cs:
            t2 = size[c]
            total += math.comb(k, t1 + t2) * math.comb(t1 + t2, t1)
            t1 += t2
    return total


def cheapest_root(template_edges, k: int):
    """The root whose decomposition takes the fewest multiply-adds."""
    best = None
    for r in range(k):
        kids = children(template_edges, k, r)
        work = combine_work(kids, subtree_sizes(kids, r), k)
        if best is None or work < best[0]:
            best = (work, r, kids)
    return best[1], best[2]
