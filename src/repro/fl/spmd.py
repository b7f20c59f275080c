"""SPMD backend — the HFL schedule as jax collectives (DESIGN.md §3).

Mapping:  UE -> one device of an ('edge', 'ue') mesh;  edge aggregation
(eq. 6) -> size-weighted ``psum`` over the 'ue' sub-axis every ``a`` local
steps;  cloud aggregation (eq. 10) -> weighted ``psum`` over BOTH axes
every ``a*b`` steps.  On the production 2-pod mesh the 'edge' axis is the
pod axis, so the cloud round crosses the slow DCN exactly as the paper's
edge->cloud backhaul is the slow link.

Parameters live in the STACKED layout: every leaf has a leading UE axis of
size (E*U) sharded over ('edge','ue') — each device owns one UE's drifting
replica (local-SGD semantics; there is no single global param state
between cloud rounds, faithfully to Alg. 1).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.fl import clients
from repro.fl.aggregate import psum_weighted_mean


def stack_for_mesh(params, num_edges: int, ues_per_edge: int):
    """Replicate a single param pytree into the (E*U, ...) stacked layout."""
    n = num_edges * ues_per_edge
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), params)


def make_hfl_cloud_round(loss_fn: Callable, mesh, *, a: int, b: int,
                         lr: float, solver: str = "gd", dane_mu: float = 0.1):
    """jit(shard_map) executing ONE cloud round = b edge rounds x a local
    steps, with the paper's aggregation points as axis-scoped psums.

    Args (to the returned fn), all with leading UE axis (E*U,) sharded
    over ('edge','ue'):
      stacked_params, stacked_batch, weights (the D_n of eq. 6/10).
    """
    E = mesh.shape["edge"]
    U = mesh.shape["ue"]
    local_gd = clients.gd_local_steps(loss_fn, a, lr)
    local_dane = clients.dane_local_steps(loss_fn, a, lr, mu_prox=dane_mu)

    def shard_fn(p, batch, w):
        # strip the per-device singleton UE axis
        p = jax.tree.map(lambda x: x[0], p)
        batch = jax.tree.map(lambda x: x[0], batch)
        w = w[0]

        def wavg(q, axis):
            # Single flat collective per aggregation event: ravel the
            # pytree into one contiguous vector so the psum is ONE
            # all-reduce, not one per leaf (the same engine the sharded
            # cloud aggregate of repro.fl.aggregate reduces through).
            flat, unravel = jax.flatten_util.ravel_pytree(
                jax.tree.map(lambda x: x.astype(jnp.float32), q))
            return unravel(psum_weighted_mean(w * flat, w, axis))

        def edge_round(_, q):
            if solver == "dane":
                g_local = jax.grad(lambda z: loss_fn(z, batch)[0])(q)
                g_bar = wavg(g_local, ("edge", "ue"))     # Alg. 1 line 5
                q = local_dane(q, batch, g_bar)
            else:
                q = local_gd(q, batch)
            q = wavg(q, "ue")                             # eq. (6)
            # The psum over 'ue' erases the 'ue' varying mark; restore it
            # so the fori_loop carry keeps a stable type.
            return jax.tree.map(
                lambda x: jax.lax.pcast(x, ("ue",), to="varying"), q)

        q = jax.lax.fori_loop(0, b, edge_round, p)
        q = wavg(q, ("edge", "ue"))                       # eq. (10)
        return jax.tree.map(lambda x: x[None], q)

    spec_ue = P(("edge", "ue"))
    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(spec_ue, spec_ue, spec_ue),
        out_specs=spec_ue)
    return jax.jit(fn)


def hfl_spmd_round(loss_fn, mesh, stacked_params, stacked_batch, weights,
                   *, a: int, b: int, lr: float, solver: str = "gd"):
    """Convenience one-shot wrapper around make_hfl_cloud_round."""
    fn = make_hfl_cloud_round(loss_fn, mesh, a=a, b=b, lr=lr, solver=solver)
    return fn(stacked_params, stacked_batch, weights)


# ---------------------------------------------------------------------------
# Production-scale integration: HFL local-SGD for the transformer substrate
# ---------------------------------------------------------------------------

def make_local_sgd_train_step(model, optimizer, *, mesh, a: int, b: int):
    """HFL-scheduled train step for the big-model substrate.

    Standard data-parallel training syncs gradients EVERY step; under the
    paper's schedule each data-parallel group (edge) lets replicas drift
    for ``a`` steps, averages params within the pod every ``a`` steps and
    across pods every ``a*b`` — turning the per-step all-reduce over the
    slow axis into a 1/(a*b) amortized one.  This is what
    ``plan_from_roofline`` optimizes (a, b) for.

    Implementation note: with FSDP the param state is sharded, not
    replicated, so drift is expressed by REDUCING GRADIENT SYNC FREQUENCY:
    every step applies the local (unsynced) gradient; at edge boundaries
    params are averaged over the 'data' axis, at cloud boundaries over
    ('pod','data').  Returns step_fn(params, opt_state, batch, step_idx).
    """
    del b  # cloud cadence handled by the caller's step index math

    def wavg(params, axes):
        return jax.tree.map(
            lambda x: jax.lax.pmean(x.astype(jnp.float32), axes).astype(x.dtype),
            params)

    def step_fn(params, opt_state, batch, sync: str):
        (loss, metrics), grads = jax.value_and_grad(
            model.loss, has_aux=True)(params, batch)
        new_params, new_opt = optimizer.update(grads, opt_state, params)
        if sync == "edge":
            new_params = wavg(new_params, ("data",))
        elif sync == "cloud":
            axes = tuple(ax for ax in ("pod", "data") if ax in mesh.axis_names)
            new_params = wavg(new_params, axes)
        metrics = dict(metrics)
        metrics["loss"] = loss
        return new_params, new_opt, metrics

    return step_fn
