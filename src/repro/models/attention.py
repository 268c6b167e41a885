"""Attention variants: GQA self-attention, MLA (latent), cross-attention.

All projections are stored flat ``(d_model, n*head_dim)`` so tensor-parallel
sharding of the output dim never hits head-count divisibility limits (see
distrib/sharding.py). KV caches are functional inputs/outputs.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.layers import apply_rope
from repro.models.module import Builder

NEG_INF = -1e30

# Blockwise-attention KV chunk size (``set_flash_chunk`` resets it).
_FLASH_CHUNK = {"size": 512}


def set_flash_chunk(size: int):
    _FLASH_CHUNK["size"] = size


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

def gqa_params(b: Builder, cfg: ArchConfig):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    return {
        "wq": b.param((d, cfg.n_heads * hd), ("embed", "heads")),
        "wk": b.param((d, cfg.n_kv_heads * hd), ("embed", "kv")),
        "wv": b.param((d, cfg.n_kv_heads * hd), ("embed", "kv")),
        "wo": b.param((cfg.n_heads * hd, d), ("heads", "embed")),
    }


def blockwise_gqa(q, k, v, chunk: int = 0):
    """Causal online-softmax attention, scanned over KV chunks — never
    materializes the (S, T) score matrix. Pure XLA (compiles on any backend);
    the Pallas flash kernel (kernels/flash_attention) is the TPU analogue
    and is validated against the same math.

    q: (B,S,K,G,hd), k/v: (B,S,K,hd). Self-attention, positions = arange(S).
    """
    B, S, K, G, hd = q.shape
    chunk = min(chunk or _FLASH_CHUNK["size"], S)
    while S % chunk != 0:
        chunk //= 2
    c = S // chunk
    scale = 1.0 / jnp.sqrt(hd)
    kc = jnp.moveaxis(k.reshape(B, c, chunk, K, hd), 1, 0)   # (c,B,chunk,K,hd)
    vc = jnp.moveaxis(v.reshape(B, c, chunk, K, hd), 1, 0)
    q_pos = jnp.arange(S)

    @jax.checkpoint
    def body(carry, inp):
        # rematerialized in backward: per-chunk probabilities are never
        # stored across the scan (flash-attention backward semantics)
        m, l, acc = carry                                    # (B,K,G,S), ..., (B,S,K,G,hd)
        idx, k_blk, v_blk = inp
        s = jnp.einsum("bskgd,btkd->bkgst", q, k_blk).astype(jnp.float32)
        s = s * scale
        k_pos = idx * chunk + jnp.arange(chunk)
        mask = k_pos[None, :] <= q_pos[:, None]              # (S, chunk)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgst,btkd->bskgd", p.astype(v_blk.dtype), v_blk)
        acc = acc * jnp.moveaxis(corr, 3, 1)[..., None].astype(acc.dtype) + pv
        return (m_new, l, acc), None

    m0 = jnp.full((B, K, G, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, K, G, S), jnp.float32)
    acc0 = jnp.zeros((B, S, K, G, hd), v.dtype)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0),
                                  (jnp.arange(c), kc, vc))
    out = acc / jnp.maximum(jnp.moveaxis(l, 3, 1), 1e-30)[..., None].astype(acc.dtype)
    return out


def _gqa_scores_combine(q, k, v, mask):
    """q: (B,S,K,G,hd), k/v: (B,T,K,hd), mask: (S,T) or (B,S,T) bool."""
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    if mask.ndim == 2:
        mask_b = mask[None, None, None]
    else:
        mask_b = mask[:, None, None]
    scores = jnp.where(mask_b, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgst,btkd->bskgd", probs, v)


def gqa_attention(p, cfg: ArchConfig, x, positions, cache=None,
                  cache_index=None, use_flash: bool = False):
    """Self-attention. Train/prefill: cache=None or returned fresh.
    Decode: cache=(k,v) of shape (B, S_max, K, hd), cache_index scalar.

    Returns (out, new_cache).
    """
    B, S, _ = x.shape
    K, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    H = cfg.n_heads
    G = H // K
    q = (x @ p["wq"]).reshape(B, S, K, G, hd)
    k = (x @ p["wk"]).reshape(B, S, K, hd)
    v = (x @ p["wv"]).reshape(B, S, K, hd)
    q = apply_rope(q.reshape(B, S, H, hd), positions, cfg.rope_theta)
    q = q.reshape(B, S, K, G, hd)
    k = apply_rope(k, positions, cfg.rope_theta)

    if cache is None:
        # causal full attention
        if use_flash and S > 1:
            out = blockwise_gqa(q, k, v)
        else:
            mask = jnp.tril(jnp.ones((S, S), bool))
            out = _gqa_scores_combine(q, k, v, mask)
        new_cache = (k, v)
    else:
        ck, cv = cache
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), cache_index, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), cache_index, axis=1)
        T = ck.shape[1]
        valid = jnp.arange(T)[None, :] <= positions[:, -1:]   # absolute positions
        mask = jnp.broadcast_to(valid[:, None, :], (B, S, T))
        out = _gqa_scores_combine(q, ck, cv, mask)
        new_cache = (ck, cv)
    out = out.reshape(B, S, H * hd)
    return out @ p["wo"], new_cache


def gqa_cache_spec(cfg: ArchConfig, batch: int, seq: int, dtype):
    shape = (batch, seq, cfg.n_kv_heads, cfg.resolved_head_dim)
    return (jax.ShapeDtypeStruct(shape, dtype),
            jax.ShapeDtypeStruct(shape, dtype))


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (MiniCPM3 / DeepSeek-V2 style)
# ---------------------------------------------------------------------------

def mla_params(b: Builder, cfg: ArchConfig):
    d = cfg.d_model
    H = cfg.n_heads
    qr, kr = cfg.mla_q_rank, cfg.mla_kv_rank
    nd, rd, vd = cfg.mla_nope_dim, cfg.mla_rope_dim, cfg.mla_v_dim
    return {
        "wq_a": b.param((d, qr), ("embed", "lora")),
        "q_norm": b.param((qr,), ("lora",), init="ones"),
        "wq_b": b.param((qr, H * (nd + rd)), ("lora", "heads")),
        "wkv_a": b.param((d, kr + rd), ("embed", "lora")),
        "kv_norm": b.param((kr,), ("lora",), init="ones"),
        "wkv_b": b.param((kr, H * (nd + vd)), ("lora", "heads")),
        "wo": b.param((H * vd, d), ("heads", "embed")),
    }


def _mla_qkv(p, cfg: ArchConfig, x, positions):
    B, S, _ = x.shape
    H = cfg.n_heads
    nd, rd, vd = cfg.mla_nope_dim, cfg.mla_rope_dim, cfg.mla_v_dim
    kr = cfg.mla_kv_rank
    qa = x @ p["wq_a"]
    qa = qa * jax.lax.rsqrt(jnp.mean(qa.astype(jnp.float32) ** 2, -1,
                                     keepdims=True) + 1e-6).astype(qa.dtype) \
        * p["q_norm"]
    q = (qa @ p["wq_b"]).reshape(B, S, H, nd + rd)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    ckv = x @ p["wkv_a"]
    c_kv, k_rope = ckv[..., :kr], ckv[..., kr:]
    c_kv = c_kv * jax.lax.rsqrt(
        jnp.mean(c_kv.astype(jnp.float32) ** 2, -1, keepdims=True) + 1e-6
    ).astype(c_kv.dtype) * p["kv_norm"]
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)   # (B,S,rd) shared
    return q_nope, q_rope, c_kv, k_rope


def _mla_attend(p, cfg: ArchConfig, q_nope, q_rope, c_kv, k_rope, mask):
    """Latent attention: expand k_nope/v from the compressed latent."""
    B, T, _ = c_kv.shape
    H = cfg.n_heads
    nd, vd = cfg.mla_nope_dim, cfg.mla_v_dim
    kv = (c_kv @ p["wkv_b"]).reshape(B, T, H, nd + vd)
    k_nope, v = kv[..., :nd], kv[..., nd:]
    s1 = jnp.einsum("bshd,bthd->bhst", q_nope, k_nope)
    s2 = jnp.einsum("bshd,btd->bhst", q_rope, k_rope)
    scale = 1.0 / jnp.sqrt(nd + q_rope.shape[-1])
    scores = ((s1 + s2) * scale).astype(jnp.float32)
    if mask.ndim == 2:
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    else:
        scores = jnp.where(mask[:, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhst,bthd->bshd", probs, v)
    return out.reshape(B, -1, H * vd) @ p["wo"]


def blockwise_mla(p, cfg: ArchConfig, q_nope, q_rope, c_kv, k_rope,
                  chunk: int = 0):
    """Causal online-softmax MLA — expands k/v from the compressed latent
    chunk-by-chunk, so neither the score matrix nor the expanded KV is ever
    materialized at full length."""
    B, S, H, nd = q_nope.shape
    vd = cfg.mla_v_dim
    chunk = min(chunk or _FLASH_CHUNK["size"], S)
    while S % chunk != 0:
        chunk //= 2
    c = S // chunk
    scale = 1.0 / jnp.sqrt(nd + q_rope.shape[-1])
    cc = jnp.moveaxis(c_kv.reshape(B, c, chunk, -1), 1, 0)
    cr = jnp.moveaxis(k_rope.reshape(B, c, chunk, -1), 1, 0)
    q_pos = jnp.arange(S)

    @jax.checkpoint
    def body(carry, inp):
        m, l, acc = carry
        idx, c_blk, r_blk = inp
        kv = (c_blk @ p["wkv_b"]).reshape(B, chunk, H, nd + vd)
        k_nope, v = kv[..., :nd], kv[..., nd:]
        s = (jnp.einsum("bshd,bthd->bhst", q_nope, k_nope)
             + jnp.einsum("bshd,btd->bhst", q_rope, r_blk)).astype(jnp.float32)
        s = s * scale
        k_pos = idx * chunk + jnp.arange(chunk)
        mask = k_pos[None, :] <= q_pos[:, None]
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))          # (B,H,S)
        corr = jnp.exp(m - m_new)
        pr = jnp.exp(s - m_new[..., None])
        l = l * corr + jnp.sum(pr, axis=-1)
        pv = jnp.einsum("bhst,bthd->bshd", pr.astype(v.dtype), v)
        acc = acc * jnp.moveaxis(corr, 2, 1)[..., None].astype(acc.dtype) + pv
        return (m_new, l, acc), None

    m0 = jnp.full((B, H, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, S), jnp.float32)
    acc0 = jnp.zeros((B, S, H, vd), c_kv.dtype)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0),
                                  (jnp.arange(c), cc, cr))
    out = acc / jnp.maximum(jnp.moveaxis(l, 2, 1), 1e-30)[..., None].astype(acc.dtype)
    return out.reshape(B, S, H * vd) @ p["wo"]


def mla_attention(p, cfg: ArchConfig, x, positions, cache=None,
                  cache_index=None, use_flash: bool = False):
    """Returns (out, new_cache). Cache stores the *compressed* latent:
    (c_kv: (B, S_max, kv_rank), k_rope: (B, S_max, rope_dim))."""
    B, S, _ = x.shape
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(p, cfg, x, positions)
    if cache is None:
        if use_flash and S > 1:
            return blockwise_mla(p, cfg, q_nope, q_rope, c_kv, k_rope), \
                (c_kv, k_rope)
        mask = jnp.tril(jnp.ones((S, S), bool))
        out = _mla_attend(p, cfg, q_nope, q_rope, c_kv, k_rope, mask)
        return out, (c_kv, k_rope)
    cc, cr = cache
    cc = jax.lax.dynamic_update_slice_in_dim(cc, c_kv.astype(cc.dtype), cache_index, axis=1)
    cr = jax.lax.dynamic_update_slice_in_dim(cr, k_rope.astype(cr.dtype), cache_index, axis=1)
    T = cc.shape[1]
    valid = jnp.arange(T)[None, :] <= positions[:, -1:]       # absolute positions
    mask = jnp.broadcast_to(valid[:, None, :], (B, S, T))
    out = _mla_attend(p, cfg, q_nope, q_rope, cc, cr, mask)
    return out, (cc, cr)


def mla_cache_spec(cfg: ArchConfig, batch: int, seq: int, dtype):
    return (jax.ShapeDtypeStruct((batch, seq, cfg.mla_kv_rank), dtype),
            jax.ShapeDtypeStruct((batch, seq, cfg.mla_rope_dim), dtype))


# ---------------------------------------------------------------------------
# Cross-attention (VLM image layers)
# ---------------------------------------------------------------------------

def xattn_params(b: Builder, cfg: ArchConfig):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    return {
        "wq": b.param((d, cfg.n_heads * hd), ("embed", "heads")),
        "wk": b.param((d, cfg.n_kv_heads * hd), ("embed", "kv")),
        "wv": b.param((d, cfg.n_kv_heads * hd), ("embed", "kv")),
        "wo": b.param((cfg.n_heads * hd, d), ("heads", "embed")),
        "gate": b.param((1,), (None,), init="zeros"),
    }


def cross_attention(p, cfg: ArchConfig, x, kv_src):
    """x: (B, S, D) text; kv_src: (B, N_img, D) patch embeddings (stub
    frontend). Gated output (zero-init gate, llama-3.2 style)."""
    B, S, _ = x.shape
    K, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    H = cfg.n_heads
    G = H // K
    q = (x @ p["wq"]).reshape(B, S, K, G, hd)
    k = (kv_src @ p["wk"]).reshape(B, -1, K, hd)
    v = (kv_src @ p["wv"]).reshape(B, -1, K, hd)
    mask = jnp.ones((S, k.shape[1]), bool)
    out = _gqa_scores_combine(q, k, v, mask).reshape(B, S, H * hd)
    return jnp.tanh(p["gate"]) * (out @ p["wo"])
