"""The jit'd train / serve step builders (shared by the trainer and tests).

``build_train_step`` returns a donated, fully-sharded
``(params, opt_state, [err_state], batch) -> (params, opt_state, metrics)``.
Microbatching (gradient accumulation) is a lax.scan over batch splits;
gradient compression (int8 + error feedback) is optional.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.optim import adamw as opt
from repro.optim import compression as comp


def build_train_step(
    model,
    ocfg: opt.AdamWConfig,
    *,
    accum_steps: int = 1,
    grad_compression: bool = False,
):
    def loss_fn(params, batch):
        return model.train_loss(params, batch)

    def train_step(params, opt_state, batch, err_state=None):
        if accum_steps == 1:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        else:
            def split(x):
                B = x.shape[0]
                return x.reshape((accum_steps, B // accum_steps) + x.shape[1:])

            micro = jax.tree.map(split, batch)

            def body(acc, mb):
                l, g = jax.value_and_grad(loss_fn)(params, mb)
                return (
                    acc[0] + l / accum_steps,
                    jax.tree.map(lambda a, b: a + b / accum_steps, acc[1], g),
                ), None

            zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss, grads), _ = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32), zero), micro
            )
        if grad_compression:
            grads, err_state = comp.compress_grads(grads, err_state)
        params, opt_state, metrics = opt.apply_updates(params, grads, opt_state, ocfg)
        metrics["loss"] = loss
        if grad_compression:
            return params, opt_state, err_state, metrics
        return params, opt_state, metrics

    return train_step


def build_eval_step(model):
    def eval_step(params, batch):
        return model.train_loss(params, batch)

    return eval_step
