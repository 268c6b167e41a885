"""The ``spar_gw`` family: what the check reads from a served answer, the
plain reference it is compared with, and the numbers compared.

The reference is Algorithm 2 of arXiv 2205.13573 (SPAR-GW), written from
the paper and importing nothing of the program: proximal gradient (PGA)
outer steps whose Sinkhorn projections run in the log domain, on a COO
support of s sampled pairs. The support is the served one, after
``support_outside_band`` has checked every index against the draw that
the request's key dictates.

``dtype`` is the precision the reference computes in: float32 (matrix
products at HIGHEST) for the reference, bfloat16 for the control that
must fail the comparison.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import logsumexp

_TINY = 1e-38

# number compared -> (per-request measure, how requests are aggregated)
NUMBERS = {
    "support_outside_band": ("band", "sum"),
    "value_gap": ("value", "max"),
    "value_gap_median": ("value", "median"),
    "coupling_gap_max": ("coupling", "max"),
    "coupling_gap_median": ("coupling", "median"),
}


def _hp(dtype):
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


def served(result) -> dict:
    """What the program said about one request, on the host."""
    rows, cols, vals = (np.asarray(x) for x in result.output.coupling)
    return {"value": float(result.value), "rows": rows, "cols": cols,
            "vals": vals}


def reference(traffic, checked, solver: dict, dtype) -> list:
    """The reference's answers, in ``served``'s form, to the checked
    requests [(request number, served answer)], on the served supports."""
    out = []
    for i, ans in checked:
        Cx, a, Cy, b = traffic.problem_data(i)
        v, T = spar_gw(Cx, a, Cy, b, ans["rows"], ans["cols"], solver,
                       dtype=dtype)
        out.append(dict(ans, value=float(v), vals=np.asarray(T, np.float64)))
    return out


def measures(traffic, i: int, ans: dict, ref: dict, solver: dict) -> dict:
    """Per request: relative value gap, L1 gap of the coupling on the
    support, and the count of support indices outside the key's draw."""
    _, a, _, b = traffic.problem_data(i)
    key = jax.random.PRNGKey(traffic.key_seed(i))
    s = solver["s_per_n"] * max(len(a), len(b))
    return {"value": abs(ans["value"] - ref["value"]) / abs(ref["value"]),
            "coupling": float(np.abs(ans["vals"] - ref["vals"]).sum()),
            "band": support_outside_band(key, a, b, ans["rows"], ans["cols"],
                                         s)}


# ---------------------------------------------------------------------------
# support check: every served index against the key's own draw
# ---------------------------------------------------------------------------

def support_outside_band(key, a, b, rows, cols, s: int,
                         band: float = 1e-4) -> int:
    """Count the served support indices that no correct draw can give.

    The sampler draws s i.i.d. pairs from p_ij = sqrt(a_i b_j) / Z,
    factorized: with (k_r, k_c) = split(key) and u = uniform(k, (s,)), the
    row of draw k is the first i whose cumulative probability reaches
    r_k = total * (1 - u_k) (inverse transform; ``jax.random.choice``).
    The cumulative sums here are float64 on the host; an index counts as
    consistent when r_k lies in its cell widened by ``band`` on each side,
    which covers float32 rounding of the program's own sums and nothing
    more: a support drawn from other marginals, another key or another
    law fails on almost every entry.
    """
    def bad(k, w, idx):
        u = np.asarray(jax.random.uniform(k, (s,), dtype=jnp.float32),
                       np.float64)
        p = np.sqrt(np.asarray(w, np.float64))
        cdf = np.cumsum(p / p.sum())
        r = cdf[-1] * (1.0 - u)
        idx = np.asarray(idx, np.int64)
        ok = (idx >= 0) & (idx < len(w))
        j = np.clip(idx, 0, len(w) - 1)
        lo = np.where(j > 0, cdf[np.maximum(j - 1, 0)], 0.0)
        hi = cdf[j]
        ok &= (r > lo - band) & (r <= hi + band)
        return int(np.sum(~ok))

    kr, kc = jax.random.split(key)
    return bad(kr, a, rows) + bad(kc, b, cols)


# ---------------------------------------------------------------------------
# SPAR-GW (Algorithm 2), log-domain proximal PGA on a COO support
# ---------------------------------------------------------------------------

def _loss_rows(Cx, Cy, rows, cols, rk, ck):
    """Rows of the support's loss matrix: L[k, l] = (Cx[r_k, r_l] -
    Cy[c_k, c_l])**2 for k in the block (rk, ck)."""
    d = Cx[rk][:, rows] - Cy[ck][:, cols]
    return d * d


# the largest support loss matrix contracted in one product: at HIGHEST
# precision the product holds split copies of it, about twice its size
# again (s = 32000: 12.4 GB in all against a v5e's 15.75), so past this
# the contraction runs block by block over its rows
WHOLE_LOSS_BYTES = 2**31


@partial(jax.jit, static_argnames=("m", "n", "outer", "inner", "block",
                                   "blocked", "dtype"))
def _spar(Cx, a, Cy, b, rows, cols, epsilon, *, m: int, n: int, outer: int,
          inner: int, block: int, blocked: bool, dtype):
    s = rows.shape[0]
    Cx, Cy, a, b = (x.astype(dtype) for x in (Cx, Cy, a, b))
    # the support's loss matrix, built in row blocks so the gathers stay
    # small; constant over the outer iterations
    nb = -(-s // block)
    pad = nb * block - s
    rk = jnp.pad(rows, (0, pad)).reshape(nb, block)
    ck = jnp.pad(cols, (0, pad)).reshape(nb, block)
    L = jax.lax.map(lambda rc: _loss_rows(Cx, Cy, rows, cols, *rc),
                    (rk, ck))

    if blocked:
        def contract(t):
            return jax.lax.map(
                lambda Lb: jnp.dot(Lb, t, precision=_hp(dtype),
                                   preferred_element_type=dtype),
                L).reshape(nb * block)[:s]
    else:
        L = L.reshape(nb * block, s)[:s]

        def contract(t):
            return jnp.dot(L, t, precision=_hp(dtype),
                           preferred_element_type=dtype)

    pa = jnp.sqrt(a) / jnp.sum(jnp.sqrt(a))
    pb = jnp.sqrt(b) / jnp.sum(jnp.sqrt(b))
    logw = -jnp.log(s * pa[rows] * pb[cols])
    la, lb = jnp.log(a), jnp.log(b)
    cell = rows * n + cols
    row_used = jnp.zeros((m,), bool).at[rows].set(True)
    col_used = jnp.zeros((n,), bool).at[cols].set(True)

    def sinkhorn(logK):
        # log-sum-exp of duplicate pairs into one (m, n) log-kernel;
        # cells off the support are -inf
        cmax = jax.ops.segment_max(logK, cell, num_segments=m * n)
        cmax = jnp.where(jnp.isfinite(cmax), cmax, 0.0).astype(dtype)
        csum = jax.ops.segment_sum(jnp.exp(logK - cmax[cell]), cell,
                                   num_segments=m * n)
        G = jnp.where(csum > 0, jnp.log(jnp.where(csum > 0, csum, 1)) + cmax,
                      -jnp.inf).reshape(m, n).astype(dtype)

        def body(_, fg):
            f, g = fg
            f = jnp.where(row_used, la - logsumexp(G + g[None, :], axis=1), 0)
            g = jnp.where(col_used, lb - logsumexp(G + f[:, None], axis=0), 0)
            return f.astype(dtype), g.astype(dtype)

        f, g = jax.lax.fori_loop(0, inner, body,
                                 (jnp.zeros((m,), dtype),
                                  jnp.zeros((n,), dtype)))
        return jnp.exp(logK + f[rows] + g[cols])

    def pga(_, T):
        logK = (-contract(T) / epsilon + logw
                + jnp.log(jnp.maximum(T, _TINY))).astype(dtype)
        return sinkhorn(logK)

    T = jax.lax.fori_loop(0, outer, pga, (a[rows] * b[cols]).astype(dtype))
    value = jnp.sum(T.astype(jnp.float32) * contract(T).astype(jnp.float32))
    return value, T.astype(jnp.float32)


def spar_gw(Cx, a, Cy, b, rows, cols, solver: dict, dtype=jnp.float32,
            block: int = 1024):
    """(value, coupling values on the support) of Algorithm 2 with the
    configuration's parameters, on the given support."""
    s = len(rows)
    return _spar(jnp.asarray(Cx), jnp.asarray(a), jnp.asarray(Cy),
                 jnp.asarray(b), jnp.asarray(rows, jnp.int32),
                 jnp.asarray(cols, jnp.int32), solver["epsilon"],
                 m=len(a), n=len(b), outer=solver["outer_iters"],
                 inner=solver["inner_iters"], block=block,
                 blocked=4 * s * s > WHOLE_LOSS_BYTES, dtype=dtype)
