"""Plain references of what the timed path computes, written from the
paper's equations in ``jax.numpy``; nothing here imports the program.

* ``sync_rounds``: Alg. 1's synchronous cloud round: per UE ``a`` steps
  of full-batch GD (``jax.grad`` of the model's plain loss), ``b`` times
  followed by the eq. 6 weighted mean of each edge's UEs, then the eq. 10
  weighted mean over all UEs.
* ``cohort_cycle``: one edge's departure cycle from a cloud model: the
  same ``b`` x (``a`` GD steps + eq. 6) over that edge's UEs only, with
  the weights of the UEs that take part.
* ``merge_replay``: the staleness-weighted cloud merges,
  ``g <- (1 - lam) g + lam row`` with ``lam = mass * decay**stale / W``.

Each runs at a stated dtype: ``float32`` at ``highest`` matmul precision
(the reference), or a lower one (``bfloat16``), which is the control that
a sound comparison must reject.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: Matmul precision each dtype is computed at.
PRECISION = {"float32": "highest", "bfloat16": "default"}


def _gd(loss, a: int, lr: float):
    def run(p, batch):
        def body(_, q):
            g = jax.grad(loss)(q, batch)
            return jax.tree.map(lambda x, gg: (x - lr * gg).astype(x.dtype),
                                q, g)
        return jax.lax.fori_loop(0, a, body, p)
    return run


def _cast(tree, dtype):
    return jax.tree.map(lambda x: jnp.asarray(x, dtype), tree)


def _host(tree):
    return jax.tree.map(lambda x: np.asarray(x, np.float32), tree)


class Pieces:
    """The jitted parts at one dtype, traced under its matmul precision;
    build once to share over many ``cohort_cycle`` calls."""

    def __init__(self, loss, a: int, lr: float, dtype: str):
        self.dtype = jnp.dtype(dtype)
        self.precision = PRECISION[dtype]
        hp = (jax.lax.Precision.HIGHEST if dtype == "float32"
              else jax.lax.Precision.DEFAULT)
        self.local = jax.jit(jax.vmap(_gd(loss, a, lr)))
        # (M, N) mixing matrix times the (N, ...) rows: the eq. 6 means.
        self.mix = jax.jit(lambda P, rows: jax.tree.map(
            lambda x: jnp.tensordot(P, x, axes=1, precision=hp), rows))
        self.loss = jax.jit(loss)
        self.vloss = jax.jit(jax.vmap(loss, in_axes=(None, 0)))

    def __enter__(self):
        self._ctx = jax.default_matmul_precision(self.precision)
        self._ctx.__enter__()
        return self

    def __exit__(self, *exc):
        return self._ctx.__exit__(*exc)


def _blocks(n: int, block: int):
    return [(s, min(s + block, n)) for s in range(0, n, block)]


def sync_rounds(loss, init, images, labels, weights, group_ids, *, a: int,
                b: int, lr: float, rounds: int, dtype: str, block: int,
                test: dict):
    """``rounds`` synchronous cloud rounds from ``init`` (a host pytree).

    Returns one ``(cloud params (host float32 pytree), test loss, train
    loss)`` per round; the train loss is the ``D_n``-weighted mean of the
    UEs' losses, as the program reports it.
    """
    pc = Pieces(loss, a, lr, dtype)
    dt = pc.dtype
    w = np.asarray(weights, np.float64)
    gids = np.asarray(group_ids)
    n = gids.shape[0]
    groups = np.unique(gids)
    onehot = (gids[None, :] == groups[:, None]).astype(np.float64)
    mix = jnp.asarray(onehot * w[None, :] /
                      (onehot * w[None, :]).sum(1, keepdims=True), dt)
    slot = jnp.asarray(np.searchsorted(groups, gids))
    wc = jnp.asarray(w / w.sum(), dt)
    data = {"images": jnp.asarray(images, dt), "labels": jnp.asarray(labels)}
    test = {"images": jnp.asarray(test["images"], dt),
            "labels": jnp.asarray(test["labels"])}
    cloud = _cast(init, dt)
    out = []
    with pc:
        for _ in range(rounds):
            rows = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), cloud)
            for _ in range(b):
                parts = [pc.local(jax.tree.map(lambda x: x[s:e], rows),
                                  jax.tree.map(lambda x: x[s:e], data))
                         for s, e in _blocks(n, block)]
                rows = jax.tree.map(lambda *xs: jnp.concatenate(xs), *parts)
                means = pc.mix(mix, rows)                          # eq. 6
                rows = jax.tree.map(lambda m: m[slot], means)
            cloud = jax.tree.map(                                   # eq. 10
                lambda x: jnp.tensordot(wc, x, axes=1, precision=(
                    jax.lax.Precision.HIGHEST if dtype == "float32"
                    else jax.lax.Precision.DEFAULT)), rows)
            test_loss = float(pc.loss(cloud, test))
            per_ue = np.concatenate([np.asarray(pc.vloss(
                cloud, jax.tree.map(lambda x: x[s:e], data)), np.float64)
                for s, e in _blocks(n, block)])
            out.append((_host(cloud), test_loss,
                        float((w / w.sum()) @ per_ue)))
    return out


def cohort_cycle(loss, g, images, labels, weights, *, a: int, b: int,
                 lr: float, dtype: str, pieces=None):
    """One edge's cycle from the cloud model ``g`` (host pytree) over its
    UEs' data (``images``/``labels``, rows padded to a common count) with
    eq. 6 weights ``weights`` (zero for padding and for UEs left out).
    Returns the edge model (host float32 pytree)."""
    pc = pieces or Pieces(loss, a, lr, dtype)
    dt = pc.dtype
    w = np.asarray(weights, np.float64)
    n = w.shape[0]
    mix = jnp.asarray((w / w.sum())[None, :], dt)
    data = {"images": jnp.asarray(images, dt), "labels": jnp.asarray(labels)}
    mean = _cast(g, dt)
    with pc:
        for _ in range(b):
            rows = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), mean)
            rows = pc.local(rows, data)
            mean = jax.tree.map(lambda x: x[0], pc.mix(mix, rows))
    return _host(mean)


def merge_replay(g0, merges, *, decay: float, w_total: float,
                 dtype: str = "float64"):
    """Apply ``(row, mass, stale)`` merges in order to ``g0``."""
    dt = np.dtype(dtype) if dtype != "bfloat16" else jnp.bfloat16
    g = np.asarray(g0, np.float64).astype(dt)
    for row, mass, stale in merges:
        lam = np.asarray(mass * decay ** stale / w_total).astype(dt)
        g = ((np.asarray(1.0).astype(dt) - lam) * g
             + lam * np.asarray(row).astype(dt)).astype(dt)
    return np.asarray(g, np.float64)
