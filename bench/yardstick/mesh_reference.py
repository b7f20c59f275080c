"""``reference.sync_rounds`` with its UE rows spread over a cell's chips.

The same plain reference: each UE's ``a`` steps of full-batch GD
(``reference._gd`` of the model's plain loss), ``b`` times followed by
the eq. 6 mixing-matrix means of each edge, then the eq. 10 weighted mean
over all UEs, at the same dtype and matmul precision.  Nothing here
imports the program.  What differs is where the rows live: they are
padded to the same number of rows on every chip (padding rows carry
weight 0 and a copy of row 0's data) and split over a one-axis mesh of
the chips; each chip steps its own rows a block at a time (``lax.map``),
and the eq. 6 and eq. 10 sums meet in one psum over the chips.  Only
the order of the sums differs from the one-chip reference.

At ``highest`` precision a LeNet step takes minutes to compile for a
TPU, so the programs are compiled ahead from shapes alone
(``compile_programs``), each in a thread of its own: a driver starts
that before its set-up and collects it after (``Pending``).
"""
from __future__ import annotations

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench.yardstick import reference

AXIS = "ue"


@dataclasses.dataclass(frozen=True)
class Layout:
    """``n`` rows over ``chips``: ``blocks`` blocks of ``sub`` rows a chip,
    no block longer than the reference's ``block``."""
    n: int
    chips: int
    sub: int
    blocks: int

    @property
    def per_chip(self) -> int:
        return self.sub * self.blocks

    @property
    def rows(self) -> int:
        return self.chips * self.per_chip


def layout(n: int, chips: int, block: int) -> Layout:
    per = -(-n // chips)
    blocks = -(-per // block)
    return Layout(n, chips, -(-per // blocks), blocks)


def _precision(dtype: str):
    return (jax.lax.Precision.HIGHEST if dtype == "float32"
            else jax.lax.Precision.DEFAULT)


class Programs:
    """The reference's compiled programs for one set of shapes and dtype.

    ``params`` is a pytree of arrays or ``ShapeDtypeStruct``s of the
    model's parameters; ``samples`` the images a UE holds and
    ``image_shape`` one image's (H, W, C); ``groups`` the number of eq. 6
    means (edges); ``test_n`` the test images."""

    def __init__(self, loss, params, *, n: int, samples: int, image_shape,
                 groups: int, test_n: int, a: int, lr: float, dtype: str,
                 block: int, devices):
        self.dtype = dtype
        self.dt = jnp.dtype(dtype)
        self.groups = groups
        self.lay = lay = layout(n, len(devices), block)
        self.mesh = mesh = Mesh(np.asarray(devices), (AXIS,))
        self.rows_sh = NamedSharding(mesh, P(AXIS))
        self.cols_sh = NamedSharding(mesh, P(None, AXIS))
        self.rep_sh = NamedSharding(mesh, P())
        hp = _precision(dtype)
        dt = self.dt
        gd = jax.vmap(reference._gd(loss, a, lr))
        vloss = jax.vmap(loss, in_axes=(None, 0))

        def by_block(fn, *trees):
            """``fn`` over a chip's rows, ``lay.sub`` rows at a time."""
            blocked = jax.tree.map(lambda x: x.reshape(
                (lay.blocks, lay.sub) + x.shape[1:]), trees)
            out = jax.lax.map(lambda t: fn(*t), blocked)
            return jax.tree.map(lambda x: x.reshape(
                (lay.per_chip,) + x.shape[2:]), out)

        def step(rows, images, labels, mix, slot):
            data = {"images": images.astype(dt), "labels": labels}
            rows = by_block(gd, rows, data)
            means = jax.tree.map(lambda x: jax.lax.psum(          # eq. 6
                jnp.tensordot(mix, x, axes=1, precision=hp), AXIS), rows)
            return jax.tree.map(lambda m: m[slot], means)

        def cloud(rows, wc):                                       # eq. 10
            return jax.tree.map(lambda x: jax.lax.psum(
                jnp.tensordot(wc, x, axes=1, precision=hp), AXIS), rows)

        def row_losses(params, images, labels):
            data = {"images": images.astype(dt), "labels": labels}
            return by_block(lambda d: vloss(params, d), data)

        def spread(params):
            return jax.tree.map(lambda x: jnp.broadcast_to(
                x[None], (lay.per_chip,) + x.shape), params)

        def smap(fn, in_specs, out_specs):
            return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False)

        r, c, rep = P(AXIS), P(None, AXIS), P()
        self._fns = {
            "step": smap(step, (r, r, r, c, r), r),
            "cloud": smap(cloud, (r, r), rep),
            "row_losses": smap(row_losses, (rep, r, r), r),
            "spread": smap(spread, (rep,), r),
            "test_loss": loss,
        }
        k, shp = int(samples), tuple(image_shape)
        nr = lay.rows

        def sds(shape, dtype, sh):
            return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sh)

        rows_t = jax.tree.map(lambda x: sds((nr,) + tuple(x.shape), dt,
                                            self.rows_sh), params)
        par_t = jax.tree.map(lambda x: sds(x.shape, dt, self.rep_sh), params)
        imgs = sds((nr, k) + shp, jnp.float32, self.rows_sh)
        labs = sds((nr, k), jnp.int32, self.rows_sh)
        self._args = {
            "step": (rows_t, imgs, labs, sds((groups, nr), dt, self.cols_sh),
                     sds((nr,), jnp.int32, self.rows_sh)),
            "cloud": (rows_t, sds((nr,), dt, self.rows_sh)),
            "row_losses": (par_t, imgs, labs),
            "spread": (par_t,),
            "test_loss": (par_t, {
                "images": sds((test_n,) + shp, dt, self.rep_sh),
                "labels": sds((test_n,), jnp.int32, self.rep_sh)}),
        }
        self.compiled = {}

    def compile(self, name: str):
        """Compile one program (``step``, ``cloud``, ``row_losses``,
        ``spread``, ``test_loss``) under the dtype's matmul precision."""
        with jax.default_matmul_precision(reference.PRECISION[self.dtype]):
            self.compiled[name] = jax.jit(self._fns[name]).lower(
                *self._args[name]).compile()


class Pending:
    """Programs being compiled, one daemon thread each; ``result()`` waits
    for them and raises the first failure.  Daemon threads, so that a run
    that fails before it needs the reference is not held at exit."""

    def __init__(self, progs: Programs):
        self.progs = progs
        self.errors = []
        self.seconds = {}                   # compile seconds by program
        self.threads = [threading.Thread(target=self._one, args=(name,),
                                         daemon=True)
                        for name in progs._fns]
        for t in self.threads:
            t.start()

    def _one(self, name):
        t = time.perf_counter()
        try:
            self.progs.compile(name)
            self.seconds[name] = time.perf_counter() - t
        except BaseException as e:          # noqa: BLE001 - re-raised
            self.errors.append(e)

    def result(self) -> Programs:
        for t in self.threads:
            t.join()
        if self.errors:
            raise self.errors[0]
        return self.progs


def compile_programs(loss, params, **shapes) -> Pending:
    """Start compiling ``Programs(loss, params, **shapes)``."""
    return Pending(Programs(loss, params, **shapes))


def sync_rounds(progs: Programs, init, images, labels, weights, group_ids,
                *, b: int, rounds: int, test: dict):
    """``reference.sync_rounds`` over ``progs``' chips: ``rounds``
    synchronous cloud rounds from ``init`` (a host pytree); one ``(cloud
    params (host float32 pytree), test loss, train loss)`` per round."""
    lay, dt, run = progs.lay, progs.dt, progs.compiled
    w = np.asarray(weights, np.float64)
    gids = np.asarray(group_ids)
    n = gids.shape[0]
    groups = np.unique(gids)
    if n != lay.n or groups.size > progs.groups:
        raise ValueError(f"{n} rows in {groups.size} groups do not fit "
                         f"programs for {lay.n} rows in {progs.groups}")
    pad = lay.rows - n
    src = np.concatenate([np.arange(n), np.zeros(pad, np.int64)])
    w_pad = np.concatenate([w, np.zeros(pad)])
    gid_pad = np.concatenate([gids, np.full(pad, groups[0])])
    onehot = (gid_pad[None, :] == groups[:, None]).astype(np.float64)
    mix = np.zeros((progs.groups, lay.rows), np.float64)
    mix[:groups.size] = (onehot * w_pad[None, :] /
                         (onehot * w_pad[None, :]).sum(1, keepdims=True))
    slot = np.searchsorted(groups, gid_pad).astype(np.int32)

    def rows(x, sh=progs.rows_sh):
        """Host rows ``x`` in the padded order, each chip given its own."""
        return jax.make_array_from_callback(
            (lay.rows,) + x.shape[1:], sh,
            lambda idx: np.asarray(x[src[idx[0]]]))

    def put(x, sh):
        return jax.device_put(jnp.asarray(x, dt), sh)

    imgs = rows(np.asarray(images, np.float32))
    labs = rows(np.asarray(labels, np.int32))
    mix = put(mix, progs.cols_sh)
    slot = jax.device_put(slot, progs.rows_sh)
    wc = put(w_pad / w.sum(), progs.rows_sh)
    tst = jax.device_put({"images": jnp.asarray(test["images"], dt),
                          "labels": jnp.asarray(test["labels"], jnp.int32)},
                         progs.rep_sh)
    cloud = jax.device_put(reference._cast(init, dt), progs.rep_sh)
    out = []
    for _ in range(rounds):
        state = run["spread"](cloud)
        for _ in range(b):
            state = run["step"](state, imgs, labs, mix, slot)
        cloud = run["cloud"](state, wc)
        test_loss = float(run["test_loss"](cloud, tst))
        per_ue = np.asarray(run["row_losses"](cloud, imgs, labs),
                            np.float64)[:n]
        out.append((reference._host(cloud), test_loss,
                    float((w / w.sum()) @ per_ue)))
    return out
