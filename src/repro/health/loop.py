"""The health-instrumented outer-loop driver shared by every solver.

Extends the tolerance-aware ``lax.while_loop`` driver (api/driver.py is
now a thin wrapper over this module) with:

* **detection** — after every step the new iterate is checked for
  non-finite leaves *and* mass collapse (total ℓ1 below ``mass_floor``,
  the silent failure mode of underflowed plain-domain kernels at tiny ε)
  *and* mass explosion (ℓ1 above ``mass_ceil`` — an overflow in progress
  that log-domain inner solves would otherwise carry, finite, to the
  final iterate); an unhealthy iterate is never kept — the lane holds
  its last healthy state;
* **ε-rescue** — an unhealthy step consumes one of ``max_rescues``
  restarts: the lane resumes from its last healthy iterate and the step
  escalation ``scale`` doubles (``rescue_factor ** n_rescues``), which
  solvers map onto their own stability knob (ε-doubling for entropic
  kernels, step-size halving for mirror descent). Rescues draw no new
  randomness, so a recovered solve is bitwise reproducible. When rescue
  is exhausted the lane dies with status DIVERGED at the iteration of
  first failure;
* **status** — the loop returns a :class:`~repro.health.status.
  SolveStatus` computed per lane: DIVERGED > STALLED (tolerance met but
  marginal error above ``stall_err`` — a non-coupling fixed point) >
  MAXITER > CONVERGED;
* **fault injection** — an optional :class:`~repro.health.faults.
  FaultSpec` poisons the iterate at configured iterations, making all of
  the above testable (site="cost" poisons the step *input*, so the fault
  transits the cost evaluation and inner Sinkhorn).

Everything is masked per lane with the same ``where(done, old, new)``
trick as before, so the loop keeps its ``jit``/``vmap`` contract: one
poisoned lane in a stacked solve neither corrupts nor delays its peers.
With ``max_rescues=0``, no fault, and a healthy trajectory the numerics
are bitwise-identical to the pre-health driver (the guards only ever
*read* the iterate).
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.health.status import (
    CONVERGED,
    DIVERGED,
    MAXITER,
    STALLED,
    SolveStatus,
)
from repro.obs.trace import ConvergenceTrace, empty_trace

_TINY = 1e-30

# iterates with total ℓ1 mass below this are "collapsed": every entry of
# a coupling underflowed to zero (e.g. K = exp(-C/ε) at tiny ε in the
# plain domain) — finite, but as fatal as a NaN
DEFAULT_MASS_FLOOR = 1e-20

# ...and above this they are "exploded": a coupling's mass is bounded by
# its marginals (O(1)), so an iterate at 1e20 is an overflow in progress
# that hasn't hit inf yet (log-domain inner solves can renormalize every
# *subsequent* step while the scaled iterate itself survives to the end)
DEFAULT_MASS_CEIL = 1e20

# a tolerance-met lane whose final marginal ℓ1 violation exceeds this is
# STALLED, not CONVERGED: the historical dense-PGA mixing fixed points
# left 0.3–1.0 of violation, healthy converged solves reach ≲1e-2
DEFAULT_STALL_ERR = 0.25


class LoopResult(NamedTuple):
    """What the driver hands back to a solver."""
    iterate: Any        # last healthy iterate (pytree)
    errors: Any         # (max_iters,) per-iteration diagnostic, NaN-padded
    n_iters: Any        # iterations consumed (including rescue attempts)
    converged: Any      # tolerance met (bool; False under tol=0)
    status: SolveStatus
    trace: Optional[ConvergenceTrace] = None   # per-iteration buffers
                                               # (None unless trace=True)


def _tree_l1(tree):
    return jax.tree.reduce(
        lambda acc, leaf: acc + jnp.sum(jnp.abs(leaf)), tree, jnp.float32(0))


def tree_finite(tree):
    """Scalar bool: every leaf of ``tree`` is everywhere finite."""
    return jax.tree.reduce(
        lambda acc, leaf: acc & jnp.all(jnp.isfinite(leaf)), tree,
        jnp.bool_(True))


def health_loop(step_fn: Callable, err_fn: Callable, T0, max_iters: int,
                tol: float, *, scaled_step: bool = False,
                max_rescues: int = 0, rescue_factor: float = 2.0,
                mass_floor: float = DEFAULT_MASS_FLOOR,
                mass_ceil: float = DEFAULT_MASS_CEIL,
                stall_err: float = DEFAULT_STALL_ERR,
                fault: Optional[Any] = None,
                trace: bool = False,
                obj_fn: Optional[Callable] = None) -> LoopResult:
    """Iterate ``T <- step_fn(T[, scale])`` with health instrumentation.

    step_fn     — one outer solver step; with ``scaled_step`` it receives
                  ``(T, scale)`` where ``scale = rescue_factor**n_rescues``
                  is the rescue escalation (1.0 until a rescue fires)
    err_fn      — per-iteration diagnostic (marginal ℓ1 violation)
    tol         — stop when the relative ℓ1 change of the iterate (summed
                  over pytree leaves) is <= tol; 0 compiles the predicate
                  out (fixed budget, ``converged`` stays False)
    max_rescues — divergence restarts before a lane dies DIVERGED
    fault       — optional FaultSpec (see health/faults.py)
    trace       — static: carry :class:`~repro.obs.trace.ConvergenceTrace`
                  buffers through the loop and return them on the result;
                  when False (default) the loop body is the exact pre-obs
                  computation and ``result.trace`` is None (zero leaves)
    obj_fn      — optional per-iteration objective ``obj_fn(T_new) ->
                  scalar``, recorded in the trace; only evaluated when
                  ``trace=True`` (otherwise ignored)

    All keyword arguments except ``fault.at_iter`` are static.
    """
    errs0 = jnp.full((max_iters,), jnp.nan, jnp.float32)
    if max_iters <= 0:
        return LoopResult(T0, errs0, jnp.int32(0), jnp.bool_(False),
                          SolveStatus.healthy(MAXITER),
                          empty_trace(0) if trace else None)

    def cond(state):
        # indexed (not star-unpacked): the trace buffers, when carried,
        # ride at the end of the state tuple
        i, conv, dead = state[0], state[6], state[7]
        return (i < max_iters) & jnp.logical_not(conv | dead)

    def body(state):
        if trace:
            i, T, errs, last_err, fail_iter, n_rescues, conv, dead, tr = state
        else:
            i, T, errs, last_err, fail_iter, n_rescues, conv, dead = state
        done = conv | dead
        T_in = fault.apply(T, i) if fault is not None and \
            fault.site == "cost" else T
        with jax.named_scope("gw.pga_step"):
            if scaled_step:
                scale = jnp.float32(rescue_factor) ** n_rescues
                T_new = step_fn(T_in, scale)
            else:
                T_new = step_fn(T_in)
        if fault is not None and fault.site == "iterate":
            T_new = fault.apply(T_new, i)
        l1 = _tree_l1(T_new)
        healthy = tree_finite(T_new) & (l1 > mass_floor) & (l1 < mass_ceil)
        bad = jnp.logical_not(healthy) & jnp.logical_not(done)
        # an unhealthy step consumes a rescue (restart from the current,
        # still-healthy T with escalated scale) or kills the lane
        can_rescue = n_rescues < max_rescues
        fail_iter = jnp.where(bad & (fail_iter < 0), i, fail_iter)
        rescued_now = bad & can_rescue
        n_rescues_in = n_rescues          # pre-update: the scale in effect
        n_rescues = jnp.where(rescued_now, n_rescues + 1, n_rescues)
        dead = dead | (bad & jnp.logical_not(can_rescue))
        # only healthy, not-yet-done lanes advance their iterate/diagnostics
        adv = healthy & jnp.logical_not(done)
        err = err_fn(T_new).astype(jnp.float32)
        errs = jnp.where(adv, errs.at[i].set(err), errs)
        last_err = jnp.where(adv, err, last_err)
        T_out = jax.tree.map(lambda new, old: jnp.where(adv, new, old),
                             T_new, T)
        i_out = jnp.where(done, i, i + 1)   # rescues consume budget too
        delta = None
        if trace or tol > 0:
            num = _tree_l1(jax.tree.map(lambda new, old: new - old, T_new, T))
            delta = num / jnp.maximum(_tree_l1(T), _TINY)
        if tol > 0:                  # tol is static: predicate compiled out
            conv = conv | (adv & (delta <= tol))
        if trace:
            notdone = jnp.logical_not(done)

            def _wr(buf, val, mask):
                return jnp.where(mask,
                                 buf.at[i].set(val.astype(jnp.float32)), buf)

            # err/objective/delta describe an *accepted* step (mask adv);
            # mass/scale/rescued describe the attempt itself (mask ~done),
            # so rescue iterations keep their forensic record: the
            # exploded mass, the scale that failed, the rescue event
            obj = (obj_fn(T_new).astype(jnp.float32)
                   if obj_fn is not None else None)
            scale_now = jnp.float32(rescue_factor) ** n_rescues_in
            tr = ConvergenceTrace(
                err=_wr(tr.err, err, adv),
                objective=(_wr(tr.objective, obj, adv)
                           if obj is not None else tr.objective),
                delta=_wr(tr.delta, delta, adv),
                mass=_wr(tr.mass, l1, notdone),
                scale=_wr(tr.scale, scale_now, notdone),
                rescued=_wr(tr.rescued,
                            jnp.where(rescued_now, jnp.float32(1),
                                      jnp.float32(0)), notdone),
            )
            return (i_out, T_out, errs, last_err, fail_iter, n_rescues,
                    conv, dead, tr)
        return i_out, T_out, errs, last_err, fail_iter, n_rescues, conv, dead

    state0 = (jnp.int32(0), T0, errs0, jnp.float32(jnp.nan), jnp.int32(-1),
              jnp.int32(0), jnp.bool_(False), jnp.bool_(False))
    if trace:
        state0 = state0 + (empty_trace(max_iters),)
    final = lax.while_loop(cond, body, state0)
    (n_iters, T, errors, last_err, fail_iter, n_rescues, conv,
     dead) = final[:8]
    tr_out = final[8] if trace else None

    stalled = conv & (last_err > stall_err)
    code = jnp.where(dead, DIVERGED,
                     jnp.where(stalled, STALLED,
                               jnp.where(conv, CONVERGED,
                                         MAXITER))).astype(jnp.int32)
    status = SolveStatus(code=code, fail_iter=fail_iter, last_err=last_err,
                         n_rescues=n_rescues)
    return LoopResult(T, errors, n_iters, conv, status, tr_out)
