"""Faults planted under the timed path, to show that ``correct`` catches
them: each is a context manager that patches the program in this process
and restores it on exit.  Used by the CPU tests (``bench/tests``) and by
``bench/calibrate.py --faults`` on the chip."""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np


@contextlib.contextmanager
def _patched(obj, name, value):
    orig = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield orig
    finally:
        setattr(obj, name, orig)


@contextlib.contextmanager
def half_batch():
    """Each UE's loss over the first half of its samples only."""
    from repro.models import lenet
    with contextlib.ExitStack() as stack:
        for name in ("lenet_loss", "logreg_loss"):
            orig = getattr(lenet, name)

            def loss(p, b, _orig=orig, **kw):
                k = b["labels"].shape[0] // 2
                return _orig(p, {key: v[:k] for key, v in b.items()}, **kw)
            stack.enter_context(_patched(lenet, name, loss))
        yield


@contextlib.contextmanager
def half_fleet():
    """Every edge mean (eq. 6) over half of the UEs, the mean taken over
    the rest."""
    from repro.fl import aggregate
    orig = aggregate.flat_edge_aggregate

    def edge(buf, weights, group_ids, num_groups, **kw):
        keep = jnp.arange(weights.shape[0]) % 2 == 0
        return orig(buf, weights * keep, group_ids, num_groups, **kw)
    with _patched(aggregate, "flat_edge_aggregate", edge):
        yield


@contextlib.contextmanager
def round_unchanged():
    """The cloud round returns its state unchanged."""
    from repro.fl.sim import HFLSimulator
    with _patched(HFLSimulator, "_build_cloud_round",
                  lambda self: jax.jit(lambda flat, batches: flat)):
        yield


@contextlib.contextmanager
def cloud_altered():
    """The eq. 10 mean altered by 1% where it is produced."""
    from repro.fl import aggregate
    orig = aggregate.flat_cloud_aggregate
    with _patched(aggregate, "flat_cloud_aggregate",
                  lambda *a, **kw: orig(*a, **kw) * 1.01):
        yield


@contextlib.contextmanager
def wave_unchanged():
    """A departure wave returns the state unchanged."""
    from repro.fl.sim import HFLSimulator
    with _patched(HFLSimulator, "replay_departure",
                  lambda self, *a, **kw: None):
        yield


@contextlib.contextmanager
def merge_altered():
    """Each merge publishes its edge model altered by 1%."""
    from repro.launch import service
    orig = service.HFLService._apply

    def apply(self, job, finish):
        job.row = job.row * np.float32(1.01)
        return orig(self, job, finish)
    with _patched(service.HFLService, "_apply", apply):
        yield


#: The faults each kind of traffic can have (one chip: no exchange
#: between chips to leave out).
FAULTS = {
    "sync": {f.__name__: f for f in (half_batch, half_fleet,
                                     round_unchanged, cloud_altered)},
    "service": {f.__name__: f for f in (half_batch, half_fleet,
                                        wave_unchanged, merge_altered)},
}
