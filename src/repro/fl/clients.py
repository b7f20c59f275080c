"""Local client solvers (Alg. 1 lines 4-8).

The paper uses full-batch GD locally ("we use GD in UE local training",
§III-B) and cites DANE [22] as the training algorithm; DANE's inexact
Newton step is implemented as the prox-regularized local objective solved
by ``inner_steps`` of GD.

All solvers are shaped for ``jax.vmap`` over a stacked UE axis: they take
(params, batch) for ONE UE and run ``a`` local iterations with
``jax.lax.fori_loop`` / ``lax.scan`` (jit-friendly, no python loop).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp


def gd_local_steps(loss_fn: Callable, a: int, lr: float):
    """a iterations of full-batch gradient descent on the UE's own data."""

    def run(params, batch):
        def body(_, p):
            g = jax.grad(lambda q: loss_fn(q, batch)[0])(p)
            return jax.tree.map(lambda x, gg: x - lr * gg, p, g)

        return jax.lax.fori_loop(0, a, body, params)

    return run


def dane_local_steps(loss_fn: Callable, a: int, lr: float,
                     mu_prox: float = 0.1, eta_grad: float = 1.0):
    """DANE [22] local update, shaped for Alg. 1's gradient exchange.

    Each UE minimizes

        F_n(w) - <grad F_n(w0) - eta * g_bar, w> + (mu/2) ||w - w0||^2

    where ``g_bar`` is the aggregated global gradient at w0 (Alg. 1 line 5
    broadcasts it).  ``a`` inner GD steps approximate the argmin (the
    "inexact" Newton step).
    """

    def run(params, batch, g_bar):
        g0 = jax.grad(lambda q: loss_fn(q, batch)[0])(params)

        def inner_obj(p):
            f, _ = loss_fn(p, batch)
            lin = sum(jnp.vdot(gl0 - eta_grad * gb, pl)
                      for gl0, gb, pl in zip(jax.tree.leaves(g0),
                                             jax.tree.leaves(g_bar),
                                             jax.tree.leaves(p)))
            prox = sum(jnp.sum((pl - wl) ** 2)
                       for pl, wl in zip(jax.tree.leaves(p),
                                         jax.tree.leaves(params)))
            return f - lin + 0.5 * mu_prox * prox

        def body(_, p):
            g = jax.grad(inner_obj)(p)
            return jax.tree.map(lambda x, gg: x - lr * gg, p, g)

        return jax.lax.fori_loop(0, a, body, params)

    return run


def global_gradient(loss_fn: Callable, stacked_params, stacked_batch, weights):
    """Alg. 1 line 5: weighted mean of per-UE gradients at the shared point."""
    grads = jax.vmap(lambda p, b: jax.grad(
        lambda q: loss_fn(q, b)[0])(p))(stacked_params, stacked_batch)
    w = weights / jnp.sum(weights)
    return jax.tree.map(
        lambda g: jnp.tensordot(w, g.astype(jnp.float32), axes=1,
                                precision=jax.lax.Precision.HIGHEST), grads)
