"""Minimal functional module system: params as pytrees + logical-axis metadata.

A ``Builder`` interprets parameter declarations in one of three modes:
  · ``init``  — materialize arrays (CPU smoke tests, real training)
  · ``shape`` — ShapeDtypeStructs only (no allocation, 90B-safe)
  · ``axes``  — logical sharding axes tuples (fed to distrib.sharding rules)

Module code declares each parameter exactly once; all three interpretations
stay structurally identical by construction.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class Builder:
    def __init__(self, mode: str, key: Optional[jax.Array] = None,
                 dtype=jnp.float32):
        assert mode in ("init", "shape", "axes")
        self.mode = mode
        self.key = key
        self.dtype = dtype
        self._counter = 0

    def _next_key(self):
        self._counter += 1
        return jax.random.fold_in(self.key, self._counter)

    def param(self, shape: Tuple[int, ...], axes: Tuple[Optional[str], ...],
              init: str = "normal", scale: Optional[float] = None,
              dtype=None):
        assert len(shape) == len(axes), (shape, axes)
        dtype = dtype or self.dtype
        if self.mode == "axes":
            return axes
        if self.mode == "shape":
            return jax.ShapeDtypeStruct(shape, dtype)
        if init == "zeros":
            return jnp.zeros(shape, dtype)
        if init == "ones":
            return jnp.ones(shape, dtype)
        if scale is None:
            # fan-in scaled normal
            fan_in = shape[0] if len(shape) >= 2 else max(shape[0], 1)
            scale = 1.0 / np.sqrt(fan_in)
        return (scale * jax.random.normal(self._next_key(), shape)).astype(dtype)

    def vmapped(self, fn, n: int):
        """Build ``n`` stacked copies of a param subtree (scan-over-layers).

        Leaves get a leading dim of size ``n``; axes get a leading ``layer``
        (i.e. unsharded stacking) entry.
        """
        if self.mode == "axes":
            sub = fn(self)
            return jax.tree.map(lambda a: (None,) + tuple(a), sub,
                                is_leaf=lambda x: isinstance(x, tuple))
        if self.mode == "shape":
            sub = fn(self)
            return jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), sub)
        keys = jax.random.split(self._next_key(), n)

        def one(k):
            b = Builder("init", k, self.dtype)
            return fn(b)

        return jax.vmap(one)(keys)


def make(init_fn, cfg, mode: str, key=None, dtype=jnp.float32):
    b = Builder(mode, key=key, dtype=dtype)
    return init_fn(b, cfg)
