"""jit-able step functions shared by the trainer and server."""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.model_zoo import Model
from repro.optim import adamw


def make_train_step(model: Model, base_lr: float = 3e-4, warmup: int = 100,
                    total_steps: int = 10000, act_dtype=jnp.bfloat16,
                    remat: bool = True, use_flash: bool = False,
                    gw_align: bool = False):
    lr_fn = adamw.cosine_schedule(base_lr, warmup, total_steps)

    def train_step(params, opt_state, batch):
        gw_key = jax.random.fold_in(jax.random.PRNGKey(17), opt_state.step)

        def loss_fn(p):
            return model.loss(p, batch, act_dtype=act_dtype, remat=remat,
                              use_flash=use_flash, gw_align=gw_align,
                              gw_key=gw_key)

        (loss, parts), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        lr = lr_fn(opt_state.step + 1)      # step counter increments in update
        new_params, new_state, gnorm = adamw.update(grads, opt_state, params, lr)
        metrics = {"loss": loss, "ce": parts["ce"], "aux": parts["aux"],
                   "gnorm": gnorm, "lr": lr}
        return new_params, new_state, metrics

    return train_step


def make_prefill_step(model: Model, act_dtype=jnp.bfloat16,
                      use_flash: bool = False):
    def prefill_step(params, batch):
        return model.prefill(params, batch["tokens"],
                             img=batch.get("image_embeds"),
                             act_dtype=act_dtype, use_flash=use_flash)
    return prefill_step


def make_decode_step(model: Model, act_dtype=jnp.bfloat16):
    def decode_step(params, batch):
        return model.decode_step(params, batch["tokens"], batch["cache"],
                                 batch["index"],
                                 img=batch.get("image_embeds"),
                                 act_dtype=act_dtype)
    return decode_step
