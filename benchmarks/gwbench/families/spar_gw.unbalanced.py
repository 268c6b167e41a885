"""The ``spar_gw`` family on unbalanced problems (a config whose
``problem`` block sets ``lam``): what the check reads from a served
answer, the plain reference it is compared with, and the numbers compared.

The reference is Algorithm 3 of arXiv 2205.13573 (SPAR-UGW), written from
the paper and importing nothing of the program:

* the rank-one start T0 = a b^T / sqrt(m(a) m(b)) and its cost
  C0 = L (x) T0 + E(T0), as a dense product;
* the sampling law of eq. 9, P_ij ~ (a_i b_j)^(lam / (2 lam + eps))
  K0_ij^(eps / (2 lam + eps)) with log K0 = -C0 / (eps m(T0)) + log T0
  (E(T0) is one number for every cell, so it leaves P as it is), and the
  importance weights w = 1 / (s P) on the support;
* R outer steps from T0 on the support: m_T = m(T), eps_bar = eps m_T,
  lam_bar = lam m_T; log K = -(L T + E(T)) / eps_bar + log T + log w;
  H log-domain Sinkhorn trips with exponent rho = lam_bar / (lam_bar +
  eps_bar) on the (m, n) cell grid; then T = sqrt(m_T / m(T_new)) T_new;
* the value <T, L T> + lam KL(mu x mu || a x a) + lam KL(nu x nu || b x b).

E(T) = lam [sum_i mu_i log(mu_i / a_i) + sum_j nu_j log(nu_j / b_j)] is
the scalar marginal penalty of the step's kernel, over the rows and
columns that carry mass. Departures from the paper: the Sinkhorn runs in
the log domain (the plain-domain kernel underflows float32 at eps = 1e-2),
rows and columns without support keep a zero potential, and duplicate
pairs of the support merge into one cell by log-sum-exp.

It runs on the served support, after ``support_outside_band`` has checked
every served pair against the draw that the request's key dictates, and
it weighs that support by its own eq.-9 law on the unpadded problem.
``dtype`` is the precision the reference computes in: float32 (matrix
products at HIGHEST) for the reference, bfloat16 for the control that
must fail the comparison. Only the ``l2`` loss, the configs' own.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import logsumexp

from harness import plugin

_balanced = plugin("families", "spar_gw")
_TINY = _balanced._TINY

# the server pads a bucket's weights at 1e-30 (the program's
# serve/batching.py PAD_WEIGHT), its relations with zeros; the check
# rebuilds the law on that grid, so a pair's flat index reads as the
# server drew it
PAD_WEIGHT = 1e-30

# number compared -> (per-request measure, how requests are aggregated);
# ``support_drift`` is read by calibration only: the largest distance, in
# cumulative probability, from a served pair's cell to the point the
# key's uniform asks for, which sets the band
NUMBERS = {
    "support_outside_band": ("band", "sum"),
    "support_drift": ("drift", "max"),
    "value_gap": ("value", "max"),
    "coupling_gap_max": ("coupling", "max"),
    "coupling_gap_median": ("coupling", "median"),
    "mass_gap": ("mass", "max"),
}

# The band of the support check, in cumulative probability. The program
# draws by inverse transform over the float32 CDF of its 2**20-cell law
# (bucket 1024), summed along each row of 1024 cells and then over the
# 1024 row totals, ~10 levels each in XLA: its drift from the float64
# sum is bounded by ~21 * 2**-24 = 1.3e-6 of the total. On a TPU v5e
# the largest distance of a served pair's cell from its draw's point
# was 3.5e-7 over 6 calibration windows (312 solves, ~5.0 million
# draws), and the whole CDF of four pairs lay within 2.5e-7 of the
# float64 one (a single 2**20-cell sum: 3.9e-7 and 3.5e-7; PERF.md
# §2.2). The band, 2e-6, lies above all of them. A cell of the law
# holds ~1e-6 of the mass at n = 1000, so a draw from another key or
# another law passes with a chance of a few in a million per pair.
BAND = 2e-6


def served(result) -> dict:
    """What the program said about one request, on the host, with the
    grid its support was drawn on."""
    rows, cols, vals = (np.asarray(x) for x in result.output.coupling)
    grid = result.shape if result.fell_back else result.padded_shape
    return {"value": float(result.value), "rows": rows, "cols": cols,
            "vals": vals, "grid": tuple(int(g) for g in grid)}


def reference(traffic, checked, solver: dict, dtype) -> list:
    """The reference's answers, in ``served``'s form, to the checked
    requests [(request number, served answer)], on the served supports."""
    lam = traffic.problem_terms["lam"]
    out = []
    for i, ans in checked:
        Cx, a, Cy, b = traffic.problem_data(i)
        v, T = spar_ugw(Cx, a, Cy, b, ans["rows"], ans["cols"], lam, solver,
                        dtype=dtype)
        out.append(dict(ans, value=float(v), vals=np.asarray(T, np.float64)))
    return out


def measures(traffic, i: int, ans: dict, ref: dict, solver: dict) -> dict:
    """Per request: relative value gap, L1 gap of the coupling on the
    support, relative gap of its total mass, and the served support's
    pairs outside the key's draw (with their largest drift)."""
    _, a, _, b = traffic.problem_data(i)
    key = jax.random.PRNGKey(traffic.key_seed(i))
    s = solver["s_per_n"] * max(len(a), len(b))
    cdf = law_cdf(traffic, i, ans["grid"], solver)
    bad, drift = support_outside_band(key, cdf, ans["grid"][1], ans["rows"],
                                      ans["cols"], s)
    ref_mass = float(np.sum(ref["vals"]))
    return {"value": abs(ans["value"] - ref["value"]) / abs(ref["value"]),
            "coupling": float(np.abs(ans["vals"] - ref["vals"]).sum()),
            "mass": abs(float(np.sum(ans["vals"], dtype=np.float64))
                        - ref_mass) / ref_mass,
            "band": bad, "drift": drift}


# ---------------------------------------------------------------------------
# support check: every served pair against the key's own 2-D draw
# ---------------------------------------------------------------------------

# the float64 CDFs of the laws of one traffic, by (pair, grid): the law
# depends on the pair alone, and a traffic cycles through few pairs
_CDFS = {"traffic": None, "cdf": {}}


def law_cdf(traffic, i: int, grid: tuple, solver: dict):
    """The float64 cumulative eq.-9 law of request i's pair, row-major
    over the (mb, nb) grid the server drew on (pad cells at their own
    tiny mass)."""
    if _CDFS["traffic"] is not traffic:
        _CDFS.update(traffic=traffic, cdf={})
    k = (traffic.pair_of(i), grid)
    if k not in _CDFS["cdf"]:
        Cx, a, Cy, b = traffic.problem_data(i)
        P = eq9_law_f64(*_pad(Cx, a, grid[0]), *_pad(Cy, b, grid[1]),
                        traffic.problem_terms["lam"], solver["epsilon"],
                        solver.get("shrink", 0.0))
        _CDFS["cdf"][k] = np.cumsum(P.ravel())
    return _CDFS["cdf"][k]


def _pad(C, w, nb: int):
    C = np.asarray(C, np.float64)
    w = np.asarray(w, np.float64)
    pad = nb - len(w)
    return (np.pad(C, ((0, pad), (0, pad))),
            np.pad(w, (0, pad), constant_values=np.float32(PAD_WEIGHT)))


def eq9_law_f64(Cx, a, Cy, b, lam: float, eps: float, shrink: float = 0.0):
    """Eq. 9 in float64 on the host: the (m, n) law of the 2-D draw."""
    sigma = np.sqrt(a.sum() * b.sum())
    T0 = np.outer(a, b) / sigma
    C0 = ((Cx * Cx) @ T0.sum(1))[:, None] + ((Cy * Cy) @ T0.sum(0))[None, :] \
        - 2.0 * Cx @ T0 @ Cy.T
    logK0 = -C0 / (eps * T0.sum()) + np.log(T0)
    e1, e2 = lam / (2 * lam + eps), eps / (2 * lam + eps)
    logP = e1 * np.log(np.outer(a, b)) + e2 * logK0
    P = np.exp(logP - logP.max())
    P /= P.sum()
    if shrink > 0.0:
        P = (1 - shrink) * P + shrink / P.size
    return P


def support_outside_band(key, cdf, nb: int, rows, cols, s: int,
                         band: float = BAND):
    """(count, drift): the served pairs that no correct draw can give, and
    the largest distance from a served pair's cell to its draw's point.

    The sampler draws s i.i.d. flat indices f = i * nb + j from the law
    over the grid (``jax.random.choice`` with p): with u = uniform(key,
    (s,)), draw k is the first cell whose cumulative probability reaches
    r_k = total * (1 - u_k). The cumulative sum here is float64; a pair
    counts as consistent when r_k lies in its cell widened by ``band`` on
    each side (``BAND`` says why that wide and no wider).
    """
    u = np.asarray(jax.random.uniform(key, (s,), dtype=jnp.float32),
                   np.float64)
    r = cdf[-1] * (1.0 - u)
    rows = np.asarray(rows, np.int64)
    cols = np.asarray(cols, np.int64)
    flat = rows * nb + cols
    ok = (rows >= 0) & (cols >= 0) & (cols < nb) & (flat < len(cdf))
    f = np.clip(flat, 0, len(cdf) - 1)
    lo = np.where(f > 0, cdf[np.maximum(f - 1, 0)], 0.0)
    hi = cdf[f]
    miss = np.maximum(np.maximum(lo - r, r - hi), 0.0)
    ok &= (r > lo - band) & (r <= hi + band)
    return int(np.sum(~ok)), float(np.max(miss, initial=0.0))


# ---------------------------------------------------------------------------
# SPAR-UGW (Algorithm 3), log-domain proximal PGA on a COO support
# ---------------------------------------------------------------------------

def _kl_sum(p, q):
    """sum_i p_i log(p_i / q_i) over the entries that carry mass."""
    live = p > 0
    return jnp.sum(jnp.where(live, p * (jnp.log(jnp.where(live, p, 1))
                                        - jnp.log(q)), 0))


def _quadratic_kl(p, q):
    """KL(p x p || q x q) = 2 m(p) sum p log(p / q) - m(p)^2 + m(q)^2."""
    mp, mq = jnp.sum(p), jnp.sum(q)
    return 2.0 * mp * _kl_sum(p, q) - mp * mp + mq * mq


@partial(jax.jit, static_argnames=("m", "n", "outer", "inner", "block",
                                   "blocked", "dtype"))
def _spar_ugw(Cx, a, Cy, b, rows, cols, lam, epsilon, *, m: int, n: int,
              outer: int, inner: int, block: int, blocked: bool, dtype):
    s = rows.shape[0]
    hp = _balanced._hp(dtype)
    Cx, Cy, a, b = (x.astype(dtype) for x in (Cx, Cy, a, b))

    # steps 2-3: the rank-one start and its cost, dense (less E(T0))
    sigma = jnp.sqrt(jnp.sum(a) * jnp.sum(b))
    T0 = (a[:, None] * b[None, :] / sigma).astype(dtype)
    C0 = (jnp.dot(Cx * Cx, jnp.sum(T0, 1), precision=hp)[:, None]
          + jnp.dot(Cy * Cy, jnp.sum(T0, 0), precision=hp)[None, :]
          - 2.0 * jnp.dot(jnp.dot(Cx, T0, precision=hp), Cy.T, precision=hp))
    logK0 = -C0 / (epsilon * jnp.sum(T0)) + jnp.log(T0)

    # steps 4-5: the law of eq. 9 and the importance weights on the support
    e1, e2 = lam / (2 * lam + epsilon), epsilon / (2 * lam + epsilon)
    logP = e1 * jnp.log(a[:, None] * b[None, :]) + e2 * logK0
    logP = logP - logsumexp(logP)
    logw = (-jnp.log(jnp.asarray(s, dtype)) - logP[rows, cols]).astype(dtype)

    # the support's loss matrix, built in row blocks; constant over steps
    nb = -(-s // block)
    pad = nb * block - s
    rk = jnp.pad(rows, (0, pad)).reshape(nb, block)
    ck = jnp.pad(cols, (0, pad)).reshape(nb, block)
    L = jax.lax.map(lambda rc: _balanced._loss_rows(Cx, Cy, rows, cols, *rc),
                    (rk, ck))
    if blocked:
        def contract(t):
            return jax.lax.map(
                lambda Lb: jnp.dot(Lb, t, precision=hp,
                                   preferred_element_type=dtype),
                L).reshape(nb * block)[:s]
    else:
        L = L.reshape(nb * block, s)[:s]

        def contract(t):
            return jnp.dot(L, t, precision=hp, preferred_element_type=dtype)

    la, lb = jnp.log(a), jnp.log(b)
    cell = rows * n + cols
    row_used = jnp.zeros((m,), bool).at[rows].set(True)
    col_used = jnp.zeros((n,), bool).at[cols].set(True)

    def marginals(T):
        return (jax.ops.segment_sum(T, rows, num_segments=m),
                jax.ops.segment_sum(T, cols, num_segments=n))

    def sinkhorn(logK, rho):
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
            f = jnp.where(row_used,
                          rho * (la - logsumexp(G + g[None, :], axis=1)), 0)
            g = jnp.where(col_used,
                          rho * (lb - logsumexp(G + f[:, None], axis=0)), 0)
            return f.astype(dtype), g.astype(dtype)

        f, g = jax.lax.fori_loop(0, inner, body,
                                 (jnp.zeros((m,), dtype),
                                  jnp.zeros((n,), dtype)))
        return jnp.exp(logK + f[rows] + g[cols])

    def pga(_, T):
        mT = jnp.sum(T)
        eps_bar, lam_bar = epsilon * mT, lam * mT
        mu, nu = marginals(T)
        penalty = lam * (_kl_sum(mu, a) + _kl_sum(nu, b))      # E(T)
        logK = (-(contract(T) + penalty) / eps_bar
                + jnp.log(jnp.maximum(T, _TINY)) + logw).astype(dtype)
        T_new = sinkhorn(logK, lam_bar / (lam_bar + eps_bar))
        return (jnp.sqrt(mT / jnp.sum(T_new)) * T_new).astype(dtype)

    T = jax.lax.fori_loop(0, outer, pga,
                          (a[rows] * b[cols] / sigma).astype(dtype))
    T = T.astype(jnp.float32)
    mu, nu = marginals(T)
    a32, b32 = a.astype(jnp.float32), b.astype(jnp.float32)
    value = (jnp.sum(T * contract(T.astype(dtype)).astype(jnp.float32))
             + lam * _quadratic_kl(mu, a32) + lam * _quadratic_kl(nu, b32))
    return value, T


def spar_ugw(Cx, a, Cy, b, rows, cols, lam: float, solver: dict,
             dtype=jnp.float32, block: int = 1024):
    """(value, coupling values on the support) of Algorithm 3 with the
    configuration's parameters, on the given support."""
    if solver.get("loss", "l2") != "l2":
        raise ValueError(f"the reference knows the l2 loss only, not "
                         f"{solver['loss']!r}")
    s = len(rows)
    return _spar_ugw(jnp.asarray(Cx), jnp.asarray(a), jnp.asarray(Cy),
                     jnp.asarray(b), jnp.asarray(rows, jnp.int32),
                     jnp.asarray(cols, jnp.int32), lam, solver["epsilon"],
                     m=len(a), n=len(b), outer=solver["outer_iters"],
                     inner=solver["inner_iters"], block=block,
                     blocked=4 * s * s > _balanced.WHOLE_LOSS_BYTES,
                     dtype=dtype)
