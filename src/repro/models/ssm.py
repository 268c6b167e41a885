"""State-space / recurrent blocks: Mamba2 (SSD), mLSTM and sLSTM (xLSTM).

Training/prefill uses chunkwise-parallel forms (MXU-friendly); decode uses
O(1)-state recurrent steps — these blocks are why the ssm/hybrid archs
support the ``long_500k`` cell.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig
from repro.models.module import Builder

# ---------------------------------------------------------------------------
# Mamba2 — state-space dual (SSD), chunked. Single B/C group, no short conv
# (simplification documented in DESIGN.md).
# ---------------------------------------------------------------------------


# Sharding option: the fused in_proj output dim
# (2*d_in + 2N + H) is generally NOT divisible by the TP degree (zamba2:
# 14563 % 16 != 0) -> the divisibility guard replicates the whole 208MB
# parameter and its gradient all-reduces dominate. split_proj=True factors
# it into a TP-shardable (d, 2*d_in) matmul + a small replicated remainder.
_MAMBA_OPTS = {"split_proj": False}


def set_mamba_options(**kw):
    _MAMBA_OPTS.update(kw)


def mamba2_params(b: Builder, cfg: ArchConfig):
    d = cfg.d_model
    d_in = cfg.ssm_expand * d
    H = cfg.ssm_heads
    N = cfg.ssm_state
    p = {
        "a_log": b.param((H,), (None,), init="zeros"),
        "skip_d": b.param((H,), (None,), init="ones"),
        "dt_bias": b.param((H,), (None,), init="zeros"),
        "norm": b.param((d_in,), ("mlp",), init="ones"),
        "out_proj": b.param((d_in, d), ("mlp", "embed")),
    }
    if _MAMBA_OPTS["split_proj"]:
        p["in_zx"] = b.param((d, 2 * d_in), ("embed", "mlp"))
        p["in_bcdt"] = b.param((d, 2 * N + H), ("embed", None))
    else:
        p["in_proj"] = b.param((d, 2 * d_in + 2 * N + H), ("embed", "mlp"))
    return p


def _ssd_chunked(xh, dt, a_log, Bm, Cm, chunk: int):
    """SSD over chunks. xh: (B,L,H,P), dt: (B,L,H), Bm/Cm: (B,L,N).

    Returns y: (B,L,H,P) and final state (B,H,N,P).
    """
    Bsz, L, H, P = xh.shape
    N = Bm.shape[-1]
    c = L // chunk
    A = -jnp.exp(a_log.astype(jnp.float32))                  # (H,) negative
    dA = dt * A                                              # (B,L,H)
    xk = (xh * dt[..., None]).reshape(Bsz, c, chunk, H, P)
    dAk = dA.reshape(Bsz, c, chunk, H)
    Bk = Bm.reshape(Bsz, c, chunk, N)
    Ck = Cm.reshape(Bsz, c, chunk, N)

    cs = jnp.cumsum(dAk, axis=2)                             # (B,c,k,H)
    # intra-chunk: M[s,t] = C_s·B_t · exp(cs_s - cs_t) for t <= s
    decay = jnp.exp(cs[:, :, :, None, :] - cs[:, :, None, :, :])  # (B,c,k,k,H)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    G = jnp.einsum("bcsn,bctn->bcst", Ck, Bk)                # (B,c,k,k)
    M = jnp.where(tri[None, None, :, :, None], G[..., None] * decay, 0.0)
    y_intra = jnp.einsum("bcsth,bcthp->bcshp", M, xk)

    # per-chunk input state: S_c = Σ_t exp(cs_last - cs_t) B_t ⊗ x_t
    last = cs[:, :, -1:, :]                                  # (B,c,1,H)
    w = jnp.exp(last - cs)                                   # (B,c,k,H)
    S_c = jnp.einsum("bctn,bcth,bcthp->bchnp", Bk, w, xk)    # (B,c,H,N,P)
    total = jnp.exp(last[:, :, 0, :])                        # (B,c,H)

    def scan_fn(state, inp):
        S_chunk, tot = inp                                   # (B,H,N,P), (B,H)
        out_state = state
        state = state * tot[:, :, None, None] + S_chunk
        return state, out_state

    state0 = jnp.zeros((Bsz, H, N, P), jnp.float32)
    final_state, prev_states = lax.scan(
        scan_fn, state0,
        (jnp.moveaxis(S_c, 1, 0).astype(jnp.float32),
         jnp.moveaxis(total, 1, 0).astype(jnp.float32)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)            # (B,c,H,N,P)

    y_inter = jnp.einsum("bcsn,bchnp,bcsh->bcshp", Ck, prev_states,
                         jnp.exp(cs))
    y = (y_intra + y_inter).reshape(Bsz, L, H, P)
    return y, final_state


def mamba2_block(p, cfg: ArchConfig, x, state=None):
    """x: (B,S,D). state: (B,H,N,P) for decode (S==1) else None.

    Returns (out, new_state).
    """
    B, S, D = x.shape
    d_in = cfg.ssm_expand * D
    H, N = cfg.ssm_heads, cfg.ssm_state
    P = d_in // H
    if "in_zx" in p:
        proj_zx = x @ p["in_zx"]
        proj_r = x @ p["in_bcdt"]
        z, xi = proj_zx[..., :d_in], proj_zx[..., d_in:]
        Bm, Cm, dt = jnp.split(proj_r, [N, 2 * N], axis=-1)
    else:
        proj = x @ p["in_proj"]
        z, xi, Bm, Cm, dt = jnp.split(
            proj, [d_in, 2 * d_in, 2 * d_in + N, 2 * d_in + 2 * N], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B,S,H)
    xh = xi.reshape(B, S, H, P)

    if state is None:
        y, new_state = _ssd_chunked(xh, dt, p["a_log"], Bm, Cm,
                                    min(cfg.ssm_chunk, S))
        new_state = new_state.astype(xh.dtype)
    else:
        # single-step recurrence
        A = -jnp.exp(p["a_log"].astype(jnp.float32))
        dA = jnp.exp(dt[:, 0] * A)                           # (B,H)
        dBx = jnp.einsum("bn,bh,bhp->bhnp", Bm[:, 0], dt[:, 0], xh[:, 0])
        new_state = (state * dA[:, :, None, None] + dBx).astype(state.dtype)
        y = jnp.einsum("bn,bhnp->bhp", Cm[:, 0], new_state)[:, None]
    y = y + xh * p["skip_d"][None, None, :, None]
    y = y.reshape(B, S, d_in) * jax.nn.silu(z)
    y32 = y.astype(jnp.float32)
    y = (y32 * lax.rsqrt(jnp.mean(y32**2, -1, keepdims=True) + 1e-6)
         ).astype(x.dtype) * p["norm"]
    return y @ p["out_proj"], new_state


def mamba2_state_spec(cfg: ArchConfig, batch: int, dtype):
    d_in = cfg.ssm_expand * cfg.d_model
    P = d_in // cfg.ssm_heads
    return jax.ShapeDtypeStruct((batch, cfg.ssm_heads, cfg.ssm_state, P),
                                dtype)


# ---------------------------------------------------------------------------
# mLSTM — matrix-memory LSTM (xLSTM), stabilized parallel + recurrent forms.
# ---------------------------------------------------------------------------


def mlstm_params(b: Builder, cfg: ArchConfig):
    d = cfg.d_model
    pd = int(cfg.lstm_proj_factor * d)
    return {
        "w_up": b.param((d, 2 * pd), ("embed", "mlp")),
        "wq": b.param((pd, pd), ("mlp", "heads")),
        "wk": b.param((pd, pd), ("mlp", "heads")),
        "wv": b.param((pd, pd), ("mlp", "heads")),
        "w_if": b.param((pd, 2 * cfg.n_heads), ("mlp", None)),
        "norm": b.param((pd,), ("mlp",), init="ones"),
        "w_down": b.param((pd, d), ("mlp", "embed")),
    }


def mlstm_block(p, cfg: ArchConfig, x, state=None):
    """x: (B,S,D). state = (C: (B,H,P,P'), n: (B,H,P), m: (B,H)) for decode."""
    B, S, D = x.shape
    pd = int(cfg.lstm_proj_factor * D)
    H = cfg.n_heads
    P = pd // H
    up = x @ p["w_up"]
    xi, z = up[..., :pd], up[..., pd:]
    q = (xi @ p["wq"]).reshape(B, S, H, P)
    k = (xi @ p["wk"]).reshape(B, S, H, P) / jnp.sqrt(P)
    v = (xi @ p["wv"]).reshape(B, S, H, P)
    gates = (xi @ p["w_if"]).astype(jnp.float32)             # (B,S,2H)
    i_raw, f_raw = gates[..., :H], gates[..., H:]
    log_f = jax.nn.log_sigmoid(f_raw)                        # (B,S,H)

    if state is None:
        F = jnp.cumsum(log_f, axis=1)                        # (B,S,H)
        Dmat = F[:, :, None, :] - F[:, None, :, :] + i_raw[:, None, :, :]
        tri = jnp.tril(jnp.ones((S, S), bool))
        Dmat = jnp.where(tri[None, :, :, None], Dmat, -jnp.inf)
        m = jnp.max(Dmat, axis=2, keepdims=True)             # (B,S,1,H)
        m = jnp.maximum(m, -1e30)
        W = jnp.exp(Dmat - m)                                # (B,S,T,H)
        scores = jnp.einsum("bshp,bthp->bsth", q, k) * W
        denom = jnp.maximum(jnp.abs(jnp.sum(scores, axis=2)),
                            jnp.exp(-m[:, :, 0, :]))         # (B,S,H)
        h = jnp.einsum("bsth,bthp->bshp", scores, v) / denom[..., None]
        # final recurrent state for handoff to decode
        mT = F[:, -1:, :] - F + i_raw                        # (B,S,H) decay-to-end
        m_last = jnp.maximum(jnp.max(mT, axis=1), -1e30)     # (B,H)
        wT = jnp.exp(mT - m_last[:, None, :])
        C_last = jnp.einsum("bsh,bshp,bshq->bhpq", wT, v, k).astype(v.dtype)
        n_last = jnp.einsum("bsh,bshp->bhp", wT, k).astype(v.dtype)
        new_state = (C_last, n_last, m_last.astype(jnp.float32))
    else:
        C, n, m_prev = state
        i_t, lf_t = i_raw[:, 0], log_f[:, 0]                 # (B,H)
        m_new = jnp.maximum(lf_t + m_prev, i_t)
        f_s = jnp.exp(lf_t + m_prev - m_new)[:, :, None]
        i_s = jnp.exp(i_t - m_new)[:, :, None]
        C = (C * f_s[..., None] + i_s[..., None] * jnp.einsum(
            "bhp,bhq->bhpq", v[:, 0], k[:, 0])).astype(C.dtype)
        n = (n * f_s + i_s * k[:, 0]).astype(n.dtype)
        num = jnp.einsum("bhpq,bhq->bhp", C, q[:, 0])
        den = jnp.maximum(jnp.abs(jnp.sum(n * q[:, 0], -1)),
                          jnp.exp(-m_new))[..., None]
        h = (num / den)[:, None]                             # (B,1,H,P)
        new_state = (C, n, m_new)

    h = h.reshape(B, S, pd).astype(x.dtype)
    h32 = h.astype(jnp.float32)
    h = (h32 * lax.rsqrt(jnp.mean(h32**2, -1, keepdims=True) + 1e-6)
         ).astype(x.dtype) * p["norm"]
    out = (h * jax.nn.silu(z)) @ p["w_down"]
    return out, new_state


def mlstm_state_spec(cfg: ArchConfig, batch: int, dtype):
    pd = int(cfg.lstm_proj_factor * cfg.d_model)
    H = cfg.n_heads
    P = pd // H
    return (jax.ShapeDtypeStruct((batch, H, P, P), dtype),
            jax.ShapeDtypeStruct((batch, H, P), dtype),
            jax.ShapeDtypeStruct((batch, H), jnp.float32))


# ---------------------------------------------------------------------------
# sLSTM — scalar-memory LSTM with exponential gating (recurrent only).
# ---------------------------------------------------------------------------


def slstm_params(b: Builder, cfg: ArchConfig):
    d = cfg.d_model
    pd = int(cfg.lstm_proj_factor * d)
    H = cfg.n_heads
    hd = pd // H
    return {
        "w_up": b.param((d, 2 * pd), ("embed", "mlp")),
        "w_in": b.param((pd, 4 * pd), ("mlp", None)),       # z,i,f,o pre-acts
        "r": b.param((4, H, hd, hd), (None, "heads", None, None),
                     scale=0.5 / hd**0.5),                  # recurrent, per head
        "norm": b.param((pd,), ("mlp",), init="ones"),
        "w_down": b.param((pd, d), ("mlp", "embed")),
    }


def _slstm_step(p, cfg, pre, carry):
    """One recurrence step. pre: (B, 4*pd) input pre-activations."""
    c, n, m, h = carry                                       # each (B, pd)/(B,pd)
    B = pre.shape[0]
    pd = c.shape[-1]
    H = cfg.n_heads
    hd = pd // H
    hh = h.reshape(B, H, hd)
    rec = jnp.einsum("bhd,ghde->bghe", hh, p["r"]).reshape(B, 4, pd)
    z_r, i_r, f_r, o_r = [jnp.squeeze(t, 1) for t in jnp.split(
        pre.reshape(B, 4, pd) + rec, 4, axis=1)]
    z = jnp.tanh(z_r)
    o = jax.nn.sigmoid(o_r)
    lf = jax.nn.log_sigmoid(f_r.astype(jnp.float32))
    m_new = jnp.maximum(lf + m, i_r.astype(jnp.float32))
    i_s = jnp.exp(i_r - m_new)
    f_s = jnp.exp(lf + m - m_new)
    c = f_s * c + i_s * z
    n = f_s * n + i_s
    h = o * (c / jnp.maximum(n, 1.0))
    return (c, n, m_new, h)


def slstm_block(p, cfg: ArchConfig, x, state=None):
    """x: (B,S,D). state = (c,n,m,h) each (B,pd) for decode."""
    B, S, D = x.shape
    pd = int(cfg.lstm_proj_factor * D)
    up = x @ p["w_up"]
    xi, z_gate = up[..., :pd], up[..., pd:]
    pre = xi @ p["w_in"]                                     # (B,S,4pd)

    if state is None:
        carry0 = (jnp.zeros((B, pd), jnp.float32), jnp.zeros((B, pd), jnp.float32),
                  jnp.full((B, pd), -1e30, jnp.float32), jnp.zeros((B, pd), jnp.float32))

        def step(carry, pre_t):
            new = _slstm_step(p, cfg, pre_t, carry)
            return new, new[3]

        new_state, hs = lax.scan(step, carry0, jnp.moveaxis(pre, 1, 0))
        h = jnp.moveaxis(hs, 0, 1)                           # (B,S,pd)
    else:
        new_state = _slstm_step(p, cfg, pre[:, 0], state)
        h = new_state[3][:, None]
    h = h.astype(x.dtype)
    h32 = h.astype(jnp.float32)
    h = (h32 * lax.rsqrt(jnp.mean(h32**2, -1, keepdims=True) + 1e-6)
         ).astype(x.dtype) * p["norm"]
    out = (h * jax.nn.silu(z_gate)) @ p["w_down"]
    return out, new_state


def slstm_state_spec(cfg: ArchConfig, batch: int, dtype):
    pd = int(cfg.lstm_proj_factor * cfg.d_model)
    f32 = jnp.float32
    return tuple(jax.ShapeDtypeStruct((batch, pd), f32) for _ in range(4))
