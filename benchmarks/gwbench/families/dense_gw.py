"""The ``dense_gw`` family: what the check reads from a served answer, the
plain reference it is compared with, and the numbers compared.

The reference is Algorithm 1 of arXiv 2205.13573 (proximal PGA-GW with
log-domain Sinkhorn projections), written from the paper and importing
nothing of the program, with the tensor-matrix product written out as the
four-index sum, on the unpadded problem (padding to a common shape is
masked out exactly).

``dtype`` is the precision the reference computes in: float32 for the
reference, bfloat16 for the control that must fail the comparison.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import logsumexp

_TINY = 1e-38

# number compared -> (per-request measure, how requests are aggregated)
NUMBERS = {
    "value_gap_median": ("value", "median"),
    "value_gap_max": ("value", "max"),
    "coupling_gap_median": ("coupling", "median"),
    "coupling_gap_p90": ("coupling", "p90"),
    "coupling_gap_max": ("coupling", "max"),
    "marginal_gap": ("marginal", "max"),
}


def served(result) -> dict:
    """What the program said about one request, on the host."""
    return {"value": float(result.value),
            "coupling": np.asarray(result.coupling_dense())}


def reference(traffic, checked, solver: dict, dtype) -> list:
    """The reference's answers, in ``served``'s form, to the checked
    requests [(request number, served answer)], solved in one batch."""
    probs = [traffic.problem_data(i) for i, _ in checked]
    size = max(max(len(p[1]), len(p[3])) for p in probs)
    vals, Ts = dense_gw_batch(probs, solver, size, dtype=dtype)
    return [{"value": float(v), "coupling": T[:len(p[1]), :len(p[3])]}
            for p, v, T in zip(probs, np.asarray(vals, np.float64),
                               np.asarray(Ts, np.float64))]


def measures(traffic, i: int, ans: dict, ref: dict, solver: dict) -> dict:
    """Per request: relative value gap, L1 gap of the coupling, and the L1
    gap of the served coupling's column sums to b. The last Sinkhorn
    half-step matches the column marginal, so every sound coupling meets
    b up to rounding, whichever local solution its trajectory reached."""
    b = traffic.problem_data(i)[3]
    return {"value": abs(ans["value"] - ref["value"]) / abs(ref["value"]),
            "coupling": float(np.abs(ans["coupling"] - ref["coupling"]).sum()),
            "marginal": float(np.abs(ans["coupling"].sum(0) - b).sum())}


# ---------------------------------------------------------------------------
# dense GW (Algorithm 1), log-domain proximal PGA, masked to the real sizes
# ---------------------------------------------------------------------------

def _dense(Cx, a, Cy, b, ma, mb, epsilon, *, outer: int, inner: int, dtype):
    Cx, Cy, a, b = (x.astype(dtype) for x in (Cx, Cy, a, b))
    mask = ma[:, None] & mb[None, :]
    la = jnp.log(jnp.where(ma, a, 1))
    lb = jnp.log(jnp.where(mb, b, 1))

    def cost(T):
        # C_ij = sum_kl (Cx_ik - Cy_jl)**2 T_kl, the four-index sum
        d = Cx[:, None, :, None] - Cy[None, :, None, :]
        return jnp.sum(d * d * T[None, None], axis=(2, 3), dtype=dtype)

    def sinkhorn(logK):
        logK = jnp.where(mask, logK, -jnp.inf)

        def body(_, fg):
            f, g = fg
            f = jnp.where(ma, la - logsumexp(logK + g[None, :], axis=1), 0)
            g = jnp.where(mb, lb - logsumexp(logK + f[:, None], axis=0), 0)
            return f.astype(dtype), g.astype(dtype)

        f, g = jax.lax.fori_loop(0, inner, body,
                                 (jnp.zeros(a.shape, dtype),
                                  jnp.zeros(b.shape, dtype)))
        return jnp.where(mask, jnp.exp(logK + f[:, None] + g[None, :]), 0)

    def pga(_, T):
        logK = -cost(T) / epsilon + jnp.log(jnp.maximum(T, _TINY))
        return sinkhorn(logK.astype(dtype)).astype(dtype)

    T0 = jnp.where(mask, a[:, None] * b[None, :], 0).astype(dtype)
    T = jax.lax.fori_loop(0, outer, pga, T0)
    value = jnp.sum(T.astype(jnp.float32) * cost(T).astype(jnp.float32))
    return value, T.astype(jnp.float32)


@partial(jax.jit, static_argnames=("outer", "inner", "dtype"))
def _dense_batch(Cx, a, Cy, b, ma, mb, epsilon, *, outer: int, inner: int,
                 dtype):
    one = partial(_dense, outer=outer, inner=inner, dtype=dtype)
    return jax.vmap(one, in_axes=(0,) * 6 + (None,))(Cx, a, Cy, b, ma, mb,
                                                     epsilon)


def dense_gw_batch(problems, solver: dict, size: int, dtype=jnp.float32):
    """(values, couplings) of Algorithm 1 for a list of (Cx, a, Cy, b)
    problems, each zero-padded to ``size`` and masked back to its own
    shape; couplings come back padded to (size, size)."""
    def pad(x, n):
        x = np.asarray(x, np.float32)
        return np.pad(x, [(0, n - d) for d in x.shape])

    def mask(k):
        return np.arange(size) < k

    args = [np.stack(z) for z in zip(*(
        (pad(Cx, size), pad(a, size), pad(Cy, size), pad(b, size),
         mask(len(a)), mask(len(b))) for Cx, a, Cy, b in problems))]
    return _dense_batch(*map(jnp.asarray, args), solver["epsilon"],
                        outer=solver["outer_iters"],
                        inner=solver["inner_iters"], dtype=dtype)
