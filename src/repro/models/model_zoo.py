"""Composable decoder stack covering all 10 assigned architectures.

A model is ``n_superblocks`` scanned repetitions of a *superblock* (the
arch's ``block_pattern``), plus optional tail blocks and an optional shared
transformer block invoked once per superblock (Zamba2). Scan-over-layers
keeps the HLO small (one superblock body compiled once) — essential for
XLA's latency-hiding scheduler.

Modes:
  · ``forward``      — teacher-forced training forward (no caches kept)
  · ``prefill``      — forward + KV/state caches (padded to ``cache_len``)
  · ``decode_step``  — one token with functional cache update
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models import ssm
from repro.models.layers import (
    cross_entropy,
    embed,
    embed_params,
    mlp,
    mlp_params,
    rmsnorm,
    rmsnorm_params,
)
from repro.models.module import Builder
from repro.models.moe import moe_mlp, moe_params

from repro.models.sharding_ctx import (
    constrain_acts as _constrain_acts,
    constrain_logits as _constrain_logits,
    set_activation_sharding,
)

# ---------------------------------------------------------------------------
# Block level
# ---------------------------------------------------------------------------

def _attn_params(b: Builder, cfg: ArchConfig):
    return attn.mla_params(b, cfg) if cfg.attn_type == "mla" \
        else attn.gqa_params(b, cfg)


def _attn_apply(p, cfg, x, positions, cache, cache_index, use_flash):
    fn = attn.mla_attention if cfg.attn_type == "mla" else attn.gqa_attention
    return fn(p, cfg, x, positions, cache=cache, cache_index=cache_index,
              use_flash=use_flash)


def block_params(b: Builder, cfg: ArchConfig, kind: str):
    d = cfg.d_model
    if kind == "attn":
        return {"n1": rmsnorm_params(b, d), "attn": _attn_params(b, cfg),
                "n2": rmsnorm_params(b, d), "mlp": mlp_params(b, d, cfg.d_ff)}
    if kind == "moe":
        return {"n1": rmsnorm_params(b, d), "attn": _attn_params(b, cfg),
                "n2": rmsnorm_params(b, d), "moe": moe_params(b, cfg)}
    if kind == "xattn":
        return {"n1": rmsnorm_params(b, d), "xattn": attn.xattn_params(b, cfg),
                "n2": rmsnorm_params(b, d), "mlp": mlp_params(b, d, cfg.d_ff)}
    if kind == "mamba2":
        return {"n1": rmsnorm_params(b, d), "mamba": ssm.mamba2_params(b, cfg)}
    if kind == "mlstm":
        return {"n1": rmsnorm_params(b, d), "lstm": ssm.mlstm_params(b, cfg)}
    if kind == "slstm":
        return {"n1": rmsnorm_params(b, d), "lstm": ssm.slstm_params(b, cfg)}
    raise ValueError(kind)


def block_apply(p, cfg: ArchConfig, kind: str, x, positions, cache,
                cache_index, img, use_flash):
    """Returns (x, new_cache, aux_loss)."""
    eps = cfg.norm_eps
    if kind in ("attn", "moe"):
        h, new_cache = _attn_apply(p["attn"], cfg, rmsnorm(p["n1"], x, eps),
                                   positions, cache, cache_index, use_flash)
        x = x + h.astype(x.dtype)
        if kind == "attn":
            x = x + mlp(p["mlp"], rmsnorm(p["n2"], x, eps)).astype(x.dtype)
            return x, new_cache, jnp.float32(0.0)
        h, aux = moe_mlp(p["moe"], cfg, rmsnorm(p["n2"], x, eps),
                         no_drop=(x.shape[1] == 1))
        return x + h.astype(x.dtype), new_cache, aux
    if kind == "xattn":
        x = x + attn.cross_attention(p["xattn"], cfg,
                                     rmsnorm(p["n1"], x, eps), img).astype(x.dtype)
        x = x + mlp(p["mlp"], rmsnorm(p["n2"], x, eps)).astype(x.dtype)
        return x, (), jnp.float32(0.0)
    if kind == "mamba2":
        h, new_state = ssm.mamba2_block(p["mamba"], cfg,
                                        rmsnorm(p["n1"], x, eps), cache)
        return x + h.astype(x.dtype), new_state, jnp.float32(0.0)
    if kind == "mlstm":
        h, new_state = ssm.mlstm_block(p["lstm"], cfg,
                                       rmsnorm(p["n1"], x, eps), cache)
        return x + h.astype(x.dtype), new_state, jnp.float32(0.0)
    if kind == "slstm":
        h, new_state = ssm.slstm_block(p["lstm"], cfg,
                                       rmsnorm(p["n1"], x, eps), cache)
        return x + h.astype(x.dtype), new_state, jnp.float32(0.0)
    raise ValueError(kind)


def block_cache_spec(cfg: ArchConfig, kind: str, batch: int, cache_len: int,
                     dtype):
    if kind in ("attn", "moe"):
        return attn.mla_cache_spec(cfg, batch, cache_len, dtype) \
            if cfg.attn_type == "mla" \
            else attn.gqa_cache_spec(cfg, batch, cache_len, dtype)
    if kind == "xattn":
        return ()
    if kind == "mamba2":
        return ssm.mamba2_state_spec(cfg, batch, dtype)
    if kind == "mlstm":
        return ssm.mlstm_state_spec(cfg, batch, dtype)
    if kind == "slstm":
        return ssm.slstm_state_spec(cfg, batch, dtype)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Superblock / stack
# ---------------------------------------------------------------------------

def superblock_params(b: Builder, cfg: ArchConfig):
    p = {f"b{i}": block_params(b, cfg, kind)
         for i, kind in enumerate(cfg.block_pattern)}
    return p


def shared_block_params(b: Builder, cfg: ArchConfig):
    """Zamba2-style shared attention+MLP block (one copy, many invocations)."""
    d = cfg.d_model
    return {"n1": rmsnorm_params(b, d), "attn": attn.gqa_params(b, cfg),
            "n2": rmsnorm_params(b, d), "mlp": mlp_params(b, d, cfg.d_ff)}


def superblock_apply(p, shared_p, cfg: ArchConfig, x, positions, caches,
                     shared_cache, cache_index, img, use_flash):
    """Returns (x, new_caches, new_shared_cache, aux)."""
    aux = jnp.float32(0.0)
    new_caches = []
    for i, kind in enumerate(cfg.block_pattern):
        c = caches[i] if caches is not None else None
        x, nc, a = block_apply(p[f"b{i}"], cfg, kind, x, positions, c,
                               cache_index, img, use_flash)
        new_caches.append(nc)
        aux = aux + a
    new_shared = shared_cache
    if shared_p is not None:
        h, new_shared = attn.gqa_attention(
            shared_p["attn"], cfg, rmsnorm(shared_p["n1"], x, cfg.norm_eps),
            positions, cache=shared_cache, cache_index=cache_index,
            use_flash=use_flash)
        x = x + h.astype(x.dtype)
        x = x + mlp(shared_p["mlp"],
                    rmsnorm(shared_p["n2"], x, cfg.norm_eps)).astype(x.dtype)
    return x, tuple(new_caches), new_shared, aux


class Model:
    """Functional model wrapper for one architecture config."""

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg

    # -- parameters ---------------------------------------------------------

    def _build(self, b: Builder):
        cfg = self.cfg
        p: Dict[str, Any] = {}
        p["embed"] = embed_params(b, cfg.vocab_size, cfg.d_model)
        if cfg.n_codebooks > 1:
            p["codebook_embeds"] = b.param(
                (cfg.n_codebooks - 1, cfg.vocab_size, cfg.d_model),
                (None, "vocab", "embed"), scale=0.02)
        p["blocks"] = b.vmapped(
            lambda bb: superblock_params(bb, cfg), cfg.resolved_superblocks)
        if cfg.tail_blocks:
            p["tail"] = [block_params(b, cfg, k) for k in cfg.tail_blocks]
        if cfg.shared_block_every:
            p["shared"] = shared_block_params(b, cfg)
        p["final_norm"] = rmsnorm_params(b, cfg.d_model)
        if cfg.n_codebooks > 1:
            p["heads"] = b.param((cfg.n_codebooks, cfg.d_model, cfg.vocab_size),
                                 (None, "embed", "vocab"))
        elif not cfg.tie_embeddings:
            p["head"] = b.param((cfg.d_model, cfg.vocab_size),
                                ("embed", "vocab"))
        return p

    def init(self, key):
        return self._build(Builder("init", key))

    def abstract_params(self):
        return self._build(Builder("shape"))

    def param_axes(self):
        return self._build(Builder("axes"))

    # -- caches -------------------------------------------------------------

    def cache_spec(self, batch: int, cache_len: int, dtype=jnp.bfloat16):
        cfg = self.cfg
        n_sb = cfg.resolved_superblocks

        def stack(spec):
            return jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((n_sb,) + s.shape, s.dtype),
                spec)

        sb = tuple(block_cache_spec(cfg, k, batch, cache_len, dtype)
                   for k in cfg.block_pattern)
        spec: Dict[str, Any] = {"blocks": stack(sb)}
        if cfg.tail_blocks:
            spec["tail"] = tuple(
                block_cache_spec(cfg, k, batch, cache_len, dtype)
                for k in cfg.tail_blocks)
        if cfg.shared_block_every:
            spec["shared"] = stack(
                attn.gqa_cache_spec(cfg, batch, cache_len, dtype))
        return spec

    def init_cache(self, batch: int, cache_len: int, dtype=jnp.bfloat16):
        """Zero caches — except LSTM stabilizer states, which start at -inf
        (empty history) so the first recurrent step matches the parallel
        form exactly."""
        cfg = self.cfg
        spec = self.cache_spec(batch, cache_len, dtype)

        def init_block(kind, c):
            zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), c)
            if kind == "mlstm":
                C, n, m = zeros
                return (C, n, jnp.full(m.shape, -1e30, m.dtype))
            if kind == "slstm":
                c_, n_, m_, h_ = zeros
                return (c_, n_, jnp.full(m_.shape, -1e30, m_.dtype), h_)
            return zeros

        out = {"blocks": tuple(
            init_block(k, spec["blocks"][i])
            for i, k in enumerate(cfg.block_pattern))}
        if cfg.tail_blocks:
            out["tail"] = tuple(
                init_block(k, spec["tail"][i])
                for i, k in enumerate(cfg.tail_blocks))
        if cfg.shared_block_every:
            out["shared"] = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                         spec["shared"])
        return out

    # -- embedding / head ----------------------------------------------------

    @staticmethod
    def _cast_params(params, act_dtype):
        """Compute copy of params in the activation dtype (mixed precision);
        master weights stay fp32 in the optimizer."""
        return jax.tree.map(
            lambda p: p.astype(act_dtype)
            if jnp.issubdtype(p.dtype, jnp.floating) else p, params)

    def _embed_tokens(self, params, tokens, act_dtype):
        cfg = self.cfg
        if cfg.n_codebooks > 1:
            # tokens: (B, S, n_codebooks); sum codebook embeddings (stub
            # EnCodec frontend per assignment)
            x = embed(params["embed"], tokens[..., 0])
            for cb in range(cfg.n_codebooks - 1):
                x = x + params["codebook_embeds"][cb][tokens[..., cb + 1]]
            return x.astype(act_dtype)
        return embed(params["embed"], tokens).astype(act_dtype)

    def _logits(self, params, x):
        cfg = self.cfg
        x = x.astype(jnp.float32)
        if cfg.n_codebooks > 1:
            out = jnp.einsum("bsd,cdv->bscv", x,
                             params["heads"].astype(jnp.float32))
        elif cfg.tie_embeddings:
            out = x @ params["embed"]["table"].astype(jnp.float32).T
        else:
            out = x @ params["head"].astype(jnp.float32)
        return _constrain_logits(out)

    # -- core stack ----------------------------------------------------------

    def _stack(self, params, x, positions, caches, cache_index, img,
               use_flash, want_cache, remat):
        cfg = self.cfg
        shared_p = params.get("shared")
        has_shared = bool(cfg.shared_block_every)

        def scan_fn(carry, xs):
            x, aux = carry
            x = _constrain_acts(x)
            if caches is None:
                blk_p, sb_cache, sh_cache = xs, None, None
            elif has_shared:
                blk_p, (sb_cache, sh_cache) = xs
            else:
                blk_p, sb_cache = xs
                sh_cache = None
            x, new_sb, new_sh, a = superblock_apply(
                blk_p, shared_p, cfg, x, positions, sb_cache, sh_cache,
                cache_index, img, use_flash)
            if want_cache:
                out = (new_sb, new_sh) if has_shared else new_sb
            else:
                out = None
            return (x, aux + a), out

        body = jax.checkpoint(scan_fn) if remat else scan_fn
        if caches is None:
            xs = params["blocks"]
        elif has_shared:
            xs = (params["blocks"], (caches["blocks"], caches["shared"]))
        else:
            xs = (params["blocks"], caches["blocks"])

        (x, aux), scanned_caches = lax.scan(body, (x, jnp.float32(0.0)), xs)

        new_tail = []
        if cfg.tail_blocks:
            for i, kind in enumerate(cfg.tail_blocks):
                c = None if caches is None else caches["tail"][i]
                x, nc, a = block_apply(params["tail"][i], cfg, kind, x,
                                       positions, c, cache_index, img,
                                       use_flash)
                new_tail.append(nc)
                aux = aux + a

        cache_out = None
        if want_cache:
            if has_shared:
                cache_out = {"blocks": scanned_caches[0],
                             "shared": scanned_caches[1]}
            else:
                cache_out = {"blocks": scanned_caches}
            if cfg.tail_blocks:
                cache_out["tail"] = tuple(new_tail)
        return x, aux, cache_out

    # -- public entry points --------------------------------------------------

    def forward(self, params, tokens, img=None, act_dtype=jnp.float32,
                use_flash: bool = False, remat: bool = False):
        """Training forward. Returns (logits, final_hidden, aux_loss)."""
        B, S = tokens.shape[0], tokens.shape[1]
        params = self._cast_params(params, act_dtype)
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        x = self._embed_tokens(params, tokens, act_dtype)
        x, aux, _ = self._stack(params, x, positions, None, None, img,
                                use_flash, want_cache=False, remat=remat)
        x = rmsnorm(params["final_norm"], x, self.cfg.norm_eps)
        return self._logits(params, x), x, aux

    def prefill(self, params, tokens, img=None, cache_len: Optional[int] = None,
                act_dtype=jnp.bfloat16, use_flash: bool = False):
        """Prefill forward; returns (logits, cache) with caches filled.

        For simplicity the cache is built at ``cache_len == S`` via the
        fresh-cache path of each block (paddable by the caller).
        """
        B, S = tokens.shape[0], tokens.shape[1]
        params = self._cast_params(params, act_dtype)
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        x = self._embed_tokens(params, tokens, act_dtype)
        x, aux, cache = self._stack(params, x, positions, None, None, img,
                                    use_flash, want_cache=True, remat=False)
        x = rmsnorm(params["final_norm"], x, self.cfg.norm_eps)
        return self._logits(params, x[:, -1:]), cache

    def decode_step(self, params, tokens, cache, index, img=None,
                    act_dtype=jnp.bfloat16):
        """One decode step. tokens: (B, 1) (or (B,1,n_codebooks));
        index: scalar int32 — absolute position / cache write offset.
        Returns (logits, new_cache)."""
        B = tokens.shape[0]
        params = self._cast_params(params, act_dtype)
        positions = jnp.broadcast_to(index[None, None], (B, 1)) \
            if jnp.ndim(index) == 0 else index
        x = self._embed_tokens(params, tokens, act_dtype)
        idx = index if jnp.ndim(index) == 0 else index[0, 0]
        x, aux, cache = self._stack(params, x, positions, cache, idx, img,
                                    False, want_cache=True, remat=False)
        x = rmsnorm(params["final_norm"], x, self.cfg.norm_eps)
        return self._logits(params, x), cache

    # -- loss -----------------------------------------------------------------

    def loss(self, params, batch, act_dtype=jnp.float32,
             use_flash: bool = False, remat: bool = False,
             gw_align: bool = False, gw_key=None):
        """Causal LM loss (+ optional GW alignment auxiliary loss)."""
        cfg = self.cfg
        logits, hidden, aux = self.forward(
            params, batch["tokens"], img=batch.get("image_embeds"),
            act_dtype=act_dtype, use_flash=use_flash, remat=remat)
        if cfg.n_codebooks > 1:
            ce = cross_entropy(logits, batch["labels"], cfg.vocab_size)
        else:
            ce = cross_entropy(logits, batch["labels"], cfg.vocab_size)
        loss = ce + 0.01 * aux
        if gw_align:
            from repro.core.align import gw_alignment_loss
            # align final-layer geometry to embedding geometry (structure
            # preservation — the paper's technique as a training feature)
            emb = self._embed_tokens(params, batch["tokens"], act_dtype)
            loss = loss + 0.1 * gw_alignment_loss(gw_key, hidden, emb)
        return loss, {"ce": ce, "aux": aux}


def build_model(cfg: ArchConfig) -> Model:
    return Model(cfg)
