"""Moon point clouds given as points, not as relation matrices: the
geometry is then the squared Euclidean cost of the points, which
``Geometry.from_points`` keeps implicit (its exact rank-(d + 2) factors
are what ``lowrank_gw`` runs on). Same clouds and marginals as ``moon``
(arXiv 2205.13573, section 6.1). With a ``features`` block each point
also carries node features, as in the paper's fused GW experiment
(appendix C.2, Fig. 6): N(mean, variance * I_dim), one mean per side.

A pure function of a ``numpy.random.Generator``.
"""
import numpy as np

from harness import plugin

_moon = plugin("geometries", "moon")


def _side(spec: dict, n: int, rng, marginal, mean: float) -> dict:
    side = {"points": _moon.moons_points(n, rng,
                                         spec["noise"]).astype(np.float32),
            "weights": _moon.gaussian_weights(n, *marginal)}
    if "features" in spec:
        f = spec["features"]
        side["features"] = (mean + np.sqrt(f["variance"])
                            * rng.standard_normal((n, f["dim"]))
                            ).astype(np.float32)
    return side


def pair(spec: dict, n: int, rng):
    """One alignment problem: two sides of n points each, as dicts."""
    means = spec.get("features", {}).get("means", (0.0, 0.0))
    return tuple(_side(spec, n, rng, marginal, mean)
                 for marginal, mean in zip(spec["marginals"], means))
