"""Importance-sampling probabilities and samplers (paper §3.1, eq. 5 / 9).

The balanced probability p_ij ∝ sqrt(a_i b_j) is a *product measure*:
p_ij = (sqrt(a_i)/Z_a)(sqrt(b_j)/Z_b). We exploit this twice:
  · COO path — sample rows and cols independently per draw (exact i.i.d.
    draws from p with O(m+n) setup instead of O(mn));
  · grid path — sample a row set and a col set once and take the cross
    product (TPU-native; see core/grid_gw.py and DESIGN.md §4).

``shrink`` linearly interpolates toward the uniform distribution, which
enforces regularity condition (H.4): p_ij ≥ c3/n².
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class FactorizedProbs(NamedTuple):
    pa: jnp.ndarray   # (m,) row factor, sums to 1
    pb: jnp.ndarray   # (n,) col factor, sums to 1

    def pair_prob(self, rows, cols):
        return self.pa[rows] * self.pb[cols]


def balanced_probs(a, b, shrink: float = 0.0) -> FactorizedProbs:
    """Eq. (5): p_ij = sqrt(a_i b_j) / Σ sqrt(a_i b_j), factorized."""
    pa = jnp.sqrt(a)
    pa = pa / pa.sum()
    pb = jnp.sqrt(b)
    pb = pb / pb.sum()
    if shrink > 0.0:
        pa = (1 - shrink) * pa + shrink / a.shape[0]
        pb = (1 - shrink) * pb + shrink / b.shape[0]
    return FactorizedProbs(pa, pb)


def sample_pairs(key, probs: FactorizedProbs, s: int):
    """s i.i.d. pairs from the product measure (paper Alg. 2 step 3)."""
    kr, kc = jax.random.split(key)
    rows = jax.random.choice(kr, probs.pa.shape[0], (s,), p=probs.pa)
    cols = jax.random.choice(kc, probs.pb.shape[0], (s,), p=probs.pb)
    return rows, cols


def sample_grid(key, probs: FactorizedProbs, s_r: int, s_c: int):
    """Row set R (s_r i.i.d.) and col set C (s_c i.i.d.) for the grid path."""
    kr, kc = jax.random.split(key)
    R = jax.random.choice(kr, probs.pa.shape[0], (s_r,), p=probs.pa)
    C = jax.random.choice(kc, probs.pb.shape[0], (s_c,), p=probs.pb)
    return R, C


def unbalanced_probs(a, b, logK, lam: float, eps: float, shrink: float = 0.0):
    """Eq. (9): p_ij ∝ (a_i b_j)^{λ/(2λ+ε)} K_ij^{ε/(2λ+ε)}  (dense m×n).

    Takes log K for numerical robustness (the kernel at T⁰ underflows fp32
    for small ε); the normalization is computed with max-subtraction.
    """
    e1 = lam / (2 * lam + eps)
    e2 = eps / (2 * lam + eps)
    logab = jnp.log(jnp.maximum(a[:, None] * b[None, :], 1e-38))
    logP = e1 * logab + e2 * logK
    logP = logP - jnp.max(logP)
    P = jnp.exp(logP)
    P = P / P.sum()
    if shrink > 0.0:
        P = (1 - shrink) * P + shrink / (P.shape[0] * P.shape[1])
    return P


def sample_pairs_2d(key, P, s: int):
    """s i.i.d. index pairs from a dense 2-D probability matrix.

    The draws of ``jax.random.choice(key, m * n, (s,), p=P.reshape(-1))``
    (inverse transform of the same uniforms over the row-major CDF), with
    the CDF summed per row and then over the row totals: for a TPU v5e one
    cumulative sum over all 1024² cells compiles in ~25 s, these in ~1 s.
    """
    m, n = P.shape
    row_cdf = jnp.cumsum(P, axis=1)
    row_start = jnp.cumsum(row_cdf[:, -1]) - row_cdf[:, -1]
    cdf = (row_cdf + row_start[:, None]).reshape(-1)
    r = cdf[-1] * (1 - jax.random.uniform(key, (s,), dtype=cdf.dtype))
    flat = jnp.searchsorted(cdf, r).astype(jnp.int32)
    return flat // n, flat % n


def poisson_mask(key, probs_flat, s: int):
    """Poisson subsampling (appendix B): keep element ij w.p. min(1, s p_ij).

    Returned mask has E[nnz] ≤ s; used in tests to check expectation-
    equivalence with the fixed-size i.i.d. scheme.
    """
    p_star = jnp.minimum(1.0, s * probs_flat)
    return jax.random.uniform(key, probs_flat.shape) < p_star, p_star
