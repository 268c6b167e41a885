"""Activation-sharding context shared by model components.

Set by launch/train under a mesh; no-op on single-device smoke tests.
Constraints are divisibility-guarded so the same model code runs everywhere.
"""
from __future__ import annotations

import jax

_ACT_SHARD = {"enabled": False, "dp": ("data",), "dp_size": 1,
              "model_size": 1, "sp": False}


def set_activation_sharding(enabled: bool, dp=("data",), dp_size: int = 1,
                            model_size: int = 1, sp: bool = False):
    """``sp=True`` additionally shards the sequence dim of the residual
    stream over 'model' (sequence parallelism): per-layer activation saves
    under remat shrink by the TP degree; XLA inserts the SP all-gather at
    layer entry (Korthikanti et al. pattern)."""
    _ACT_SHARD.update(enabled=enabled, dp=tuple(dp), dp_size=dp_size,
                      model_size=model_size, sp=sp)


def constrain(x, *spec_entries):
    """with_sharding_constraint with divisibility guards.

    spec entries: 'dp' (data axes), 'model', None. An entry is dropped when
    it does not divide the corresponding dim.
    """
    if not _ACT_SHARD["enabled"]:
        return x
    from jax.sharding import PartitionSpec as P
    out = []
    for dim, e in zip(x.shape, spec_entries):
        if e == "dp":
            ok = dim % _ACT_SHARD["dp_size"] == 0 and dim > 1
            out.append(_ACT_SHARD["dp"] if ok else None)
        elif e == "model":
            out.append("model" if dim % _ACT_SHARD["model_size"] == 0
                       and dim > 1 else None)
        else:
            out.append(None)
    return jax.lax.with_sharding_constraint(x, P(*out))


def constrain_acts(x):
    if _ACT_SHARD["sp"] and x.ndim >= 3:
        return constrain(x, "dp", "model", *([None] * (x.ndim - 2)))
    return constrain(x, "dp", *([None] * (x.ndim - 1)))


def constrain_logits(x):
    return constrain(x, "dp", *([None] * (x.ndim - 2)), "model")
