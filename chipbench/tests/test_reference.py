"""The reference against a brute-force count, and its helpers."""

import itertools
import json
import math
import os

import numpy as np
import pytest

import reference
import work

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def brute_force(n, edges, tmpl, k, coloring):
    """Colorful maps of the template into the graph, one by one."""
    adj = {v: set() for v in range(n)}
    for a, b in edges:
        adj[a].add(b)
        adj[b].add(a)
    count = 0
    for image in itertools.permutations(range(n), k):
        if len({coloring[v] for v in image}) < k:
            continue
        if all(image[b] in adj[image[a]] for a, b in tmpl):
            count += 1
    return count


def small_graph(n, m, seed):
    rng = np.random.default_rng(seed)
    e = rng.integers(0, n, (m, 2))
    e = e[e[:, 0] != e[:, 1]]
    return np.unique(np.sort(e, axis=1), axis=0)


@pytest.mark.parametrize("tmpl,k", [
    ([[0, 1], [1, 2]], 3),
    ([[0, 1], [1, 2], [2, 3], [1, 4]], 5),
    ([[0, 4], [4, 6], [4, 5], [0, 3], [0, 2], [0, 1]], 7),
])
def test_reference_equals_brute_force(tmpl, k):
    n = 9
    edges = small_graph(n, 24, seed=k)
    rng = np.random.default_rng(k)
    for _ in range(3):
        coloring = rng.integers(0, k, n)
        want = brute_force(n, edges, tmpl, k, coloring)
        for precision in ("highest", "high"):
            got = reference.Reference(n, edges, tmpl, k, precision).count(coloring)
            assert got == want


def brute_automorphisms(tmpl, k):
    es = {frozenset(e) for e in map(tuple, tmpl)}
    return sum(all(frozenset((p[a], p[b])) in es for a, b in tmpl)
               for p in itertools.permutations(range(k)))


@pytest.mark.parametrize("name", ["estimate-u7-2", "path5", "star4", "bicentral"])
def test_automorphisms(name):
    tmpl, k = {
        "path5": ([[0, 1], [1, 2], [2, 3], [3, 4]], 5),
        "star4": ([[0, 1], [0, 2], [0, 3], [0, 4]], 5),
        "bicentral": ([[0, 1], [0, 2], [0, 3], [1, 4], [1, 5]], 6),
    }.get(name, (None, None))
    if tmpl is None:
        m = mix(name)
        tmpl, k = m["template_edges"], m["template_size"]
    assert reference.automorphisms(tmpl, k) == brute_automorphisms(tmpl, k)


def test_u12_2_has_no_automorphism():
    m = mix("estimate-u12-2")
    assert reference.automorphisms(m["template_edges"], m["template_size"]) == 1


def test_work_of_one_edge():
    # root, one leaf child: one combine 2 n C(2,2) C(2,1), one neighbor sum
    # e C(2,1); bytes: the edges, the leaf as a coloring, the root's leaf as
    # a coloring, no root output
    n, e = 10, 30
    ops, nbytes = work.coloring_work([[0, 1]], 2, n, e)
    assert ops == 2 * n * 1 * 2 + e * 2
    assert nbytes == 8 * e + 4 * n * 2


def test_work_is_least_over_decompositions():
    m = mix("estimate-u12-2")
    tmpl, k = m["template_edges"], m["template_size"]
    n, e = 1000, 30000
    ops, nbytes = work.coloring_work(tmpl, k, n, e)
    root, kids = reference.cheapest_root(tmpl, k)
    size = reference.subtree_sizes(kids, root)
    assert ops <= 2 * n * reference.combine_work(kids, size, k) + e * sum(
        math.comb(k, size[c]) for c in size if c != root)
    assert 0 < nbytes
