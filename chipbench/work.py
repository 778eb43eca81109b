"""Useful operations and least bytes of one coloring, from sizes alone.

The work is that of the color-coding dynamic program on the template, whatever
implements it.  A decomposition roots the tree at one vertex and takes in each
vertex's children one at a time; each child is one neighbor sum and one
combine.  For one coloring on a graph of ``n`` vertices and ``e`` directed
edges, under ``k`` colors:

* operations: per combine, two per disjoint split (a multiply and an add),
  ``2 n C(k, t1 + t2) C(t1 + t2, t1)``; per neighbor sum, one add per directed
  edge and column of the child table, ``e C(k, t2)``;
* bytes: per child, the edge indices once (``8 e``, two int32 per edge), the
  child's table once at its true width, the partial table it joins once and
  the joined table written once, all float32; a single-vertex table is read as
  the coloring itself (one int32 per vertex), and the root's last table is
  reduced as it is made and never written.

Operations and bytes are each taken at the least over every root and every
order of children, so the least time they give bounds any decomposition.
"""

from __future__ import annotations

import itertools
import math
from typing import Dict, List, Sequence

from reference import children, subtree_sizes


def _vertex_work(kids: List[int], size: Dict[int, int], k: int, n: int, e: int,
                 is_root: bool):
    """(least ops, least bytes) of one template vertex's joins over every
    order of its children."""
    best_ops = best_bytes = None
    for order in itertools.permutations(kids):
        ops = bts = 0
        t1 = 1
        for i, c in enumerate(order):
            t2, t = size[c], t1 + size[c]
            ops += 2 * n * math.comb(k, t) * math.comb(t, t1) + e * math.comb(k, t2)
            right = 1 if t2 == 1 else math.comb(k, t2)
            left = 1 if t1 == 1 else math.comb(k, t1)
            out = 0 if (is_root and i == len(order) - 1) else math.comb(k, t)
            bts += 8 * e + 4 * n * (right + left + out)
            t1 = t
        best_ops = ops if best_ops is None else min(best_ops, ops)
        best_bytes = bts if best_bytes is None else min(best_bytes, bts)
    return best_ops or 0, best_bytes or 0


def coloring_work(template_edges: Sequence[Sequence[int]], k: int, n: int, e: int):
    """``(ops, bytes)``: the least operations and the least bytes of one
    coloring over every decomposition."""
    least_ops = least_bytes = None
    for r in range(k):
        kids = children(template_edges, k, r)
        size = subtree_sizes(kids, r)
        ops = bts = 0
        for v, cs in kids.items():
            o, b = _vertex_work(cs, size, k, n, e, v == r)
            ops += o
            bts += b
        least_ops = ops if least_ops is None else min(least_ops, ops)
        least_bytes = bts if least_bytes is None else min(least_bytes, bts)
    return least_ops, least_bytes


def least_seconds(ops: float, nbytes: float, peak: Dict[str, float], chips: int):
    """The least time of the work on ``chips`` chips and which bound sets it."""
    t_ops = ops / (chips * peak["flops_per_s"])
    t_bytes = nbytes / (chips * peak["hbm_bytes_per_s"])
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")
