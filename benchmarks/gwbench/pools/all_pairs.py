"""Every unordered pair of a collection drawn by the geometry's
``collection``: the all-pairs distance-matrix job."""


def build(geometry, spec: dict, pool: dict, rng):
    geoms = geometry.collection(spec, rng)
    pairs = [(i, j) for i in range(len(geoms))
             for j in range(i + 1, len(geoms))]
    return geoms, pairs
