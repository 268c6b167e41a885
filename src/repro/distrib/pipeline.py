"""GPipe-style pipeline parallelism over a ``pipe`` mesh axis.

Provided as a composable module (tested on a multi-device host mesh). The
sharding rules use DP/FSDP/TP/EP meshes —
on TPU ICI those dominate PP (MaxText practice); PP becomes relevant on
DCN-linked superpods, where this schedule applies across the `pipe` axis.

Implementation: ``shard_map`` over the pipe axis; each stage holds its own
layer stack; microbatches stream through with ``ppermute`` handoffs. The
schedule is the standard GPipe fill-drain: ``n_micro + n_stages - 1`` ticks.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map


def pipeline_forward(mesh: Mesh, stage_fn, n_stages: int, n_micro: int):
    """Build a pipelined forward: x (n_micro, mb, ...) -> (n_micro, mb, ...).

    ``stage_fn(stage_params, x)`` applies one stage. ``stage_params`` must
    have a leading axis of size n_stages (one slice per stage).
    """

    def pipelined(stage_params, x):
        def per_stage(params_local, x_local):
            # params_local: this stage's params (leading axis 1); x_local:
            # microbatches on stage 0, zeros elsewhere.
            params_local = jax.tree.map(lambda a: a[0], params_local)
            stage_id = lax.axis_index("pipe")
            n_ticks = n_micro + n_stages - 1
            mb_shape = x_local.shape[1:]

            def tick(carry, t):
                buf, outputs = carry
                # stage 0 injects microbatch t (if in range)
                inject = jnp.where(t < n_micro, 1, 0)
                mb_in = lax.dynamic_index_in_dim(
                    x_local, jnp.minimum(t, n_micro - 1), 0, keepdims=False)
                cur = jnp.where((stage_id == 0) & (inject == 1), mb_in, buf)
                # run the stage
                y = stage_fn(params_local, cur)
                # last stage records its output at slot t - (n_stages - 1)
                slot = t - (n_stages - 1)
                write = (stage_id == n_stages - 1) & (slot >= 0)
                outputs = lax.cond(
                    write,
                    lambda o: lax.dynamic_update_index_in_dim(
                        o, y, jnp.maximum(slot, 0), 0),
                    lambda o: o, outputs)
                # hand off to the next stage
                nxt = lax.ppermute(
                    y, "pipe",
                    [(i, (i + 1) % n_stages) for i in range(n_stages)])
                return (nxt, outputs), None

            buf0 = jnp.zeros(mb_shape, x_local.dtype)
            outs0 = jnp.zeros((n_micro,) + mb_shape, x_local.dtype)
            (_, outputs), _ = lax.scan(
                tick, (buf0, outs0), jnp.arange(n_micro + n_stages - 1))
            # only the last stage holds real outputs; psum broadcasts them
            # (all other stages contribute zeros)
            return lax.psum(outputs, "pipe")

        return shard_map(
            per_stage, mesh=mesh,
            in_specs=(P("pipe"), P()),       # params split by stage; x replicated
            out_specs=P(),                    # outputs replicated (from last stage)
            check_vma=False,
        )(stage_params, x)

    return pipelined
