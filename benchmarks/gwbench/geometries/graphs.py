"""A collection of small molecule-like graphs at a dataset's published
statistics (node counts, edges per graph): adjacency matrices as
relations, degree-normalized marginals, as the paper's graph protocol
(arXiv 2205.13573, section 6.2) represents them. A pure function of a
``numpy.random.Generator``.
"""
from statistics import NormalDist

import numpy as np


def graph_sizes(spec: dict):
    """Node counts of the collection: the quantiles of a normal
    N(mean, std) at (i + 0.5) / count, rounded and clipped to [min, max].
    The same multiset for every seed, so the seed changes which graphs
    and pairs are drawn, never the amount of work."""
    dist = NormalDist(spec["nodes_mean"], spec["nodes_std"])
    lo, hi = spec["nodes_min"], spec["nodes_max"]
    count = spec["count"]
    return [int(min(hi, max(lo, round(dist.inv_cdf((i + 0.5) / count)))))
            for i in range(count)]


def molecule_graph(n: int, rng, edges_per_node: float):
    """Adjacency of a connected sparse graph with round(n * edges_per_node)
    edges (at least a tree): a random recursive tree, closed into rings by
    the extra edges, as molecule graphs are."""
    A = np.zeros((n, n), np.float32)
    for v in range(1, n):
        u = rng.integers(0, v)
        A[u, v] = A[v, u] = 1.0
    extra = max(0, int(round(n * edges_per_node)) - (n - 1))
    while extra:
        u, v = rng.choice(n, 2, replace=False)
        if A[u, v] == 0.0:
            A[u, v] = A[v, u] = 1.0
            extra -= 1
    return A


def collection(spec: dict, rng):
    """The collection as (adjacency, degree-normalized marginal) pairs,
    with graph sizes in a seeded order."""
    sizes = graph_sizes(spec)
    out = []
    for i in rng.permutation(len(sizes)):
        A = molecule_graph(sizes[i], rng, spec["edges_mean"]
                           / spec["nodes_mean"])
        d = A.sum(1)
        out.append((A, (d / d.sum()).astype(np.float32)))
    return out
