"""A pool of ``size`` independent problems, each a fresh pair of
geometries of ``n`` points drawn by the geometry's ``pair``."""


def build(geometry, spec: dict, pool: dict, rng):
    geoms, pairs = [], []
    for i in range(pool["size"]):
        geoms += geometry.pair(spec, pool["n"], rng)
        pairs.append((2 * i, 2 * i + 1))
    return geoms, pairs
