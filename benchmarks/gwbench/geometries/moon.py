"""Moon point clouds (paper arXiv 2205.13573, section 6.1 and appendix C):
two ``make_moons`` clouds with Gaussian noise, Euclidean distance matrices
as relations, marginals N(n * mean, n * std) over point indices.

Copied from the program's ``benchmarks/datasets.py`` so that no change to
the program can change the benchmark's inputs. A pure function of a
``numpy.random.Generator``.
"""
import numpy as np


def gaussian_weights(n: int, mean_frac: float, std_frac: float):
    """Marginal N(n * mean_frac, n * std_frac) over point indices (Moon:
    N(n/3, n/20) and N(n/2, n/20))."""
    idx = np.arange(n)
    w = np.exp(-0.5 * ((idx - mean_frac * n) / (std_frac * n)) ** 2) + 1e-9
    return (w / w.sum()).astype(np.float32)


def pairwise_distances(x):
    """Euclidean distances of 2-D points, float64 then rounded to float32:
    the same bits as ``sqrt(((x[:, None] - x[None, :]) ** 2).sum(-1))``
    without its (n, n, 2) intermediate and its reduction over the short
    last axis, which were most of a run's set-up at n = 2000."""
    d = np.subtract.outer(x[:, 0], x[:, 0])
    d *= d
    dy = np.subtract.outer(x[:, 1], x[:, 1])
    dy *= dy
    d += dy
    return np.sqrt(d, out=d).astype(np.float32)


def moons_points(n: int, rng, noise: float):
    """Two interleaving half circles (sklearn ``make_moons`` equivalent)."""
    n1 = n // 2
    n2 = n - n1
    t1 = np.pi * rng.random(n1)
    t2 = np.pi * rng.random(n2)
    outer = np.stack([np.cos(t1), np.sin(t1)], 1)
    inner = np.stack([1 - np.cos(t2), 0.5 - np.sin(t2)], 1)
    pts = np.concatenate([outer, inner], 0)
    return pts + noise * rng.standard_normal(pts.shape)


def pair(spec: dict, n: int, rng):
    """One alignment problem: ((Cx, a), (Cy, b)) with n points a side."""
    x = moons_points(n, rng, spec["noise"])
    y = moons_points(n, rng, spec["noise"])
    (ma, sa), (mb, sb) = spec["marginals"]
    return ((pairwise_distances(x), gaussian_weights(n, ma, sa)),
            (pairwise_distances(y), gaussian_weights(n, mb, sb)))
