"""Synchronous cloud rounds: ``HFLSimulator.run`` called again and again.

Traffic keys: ``rounds_per_call`` (cloud rounds per ``run`` call, each
evaluated, as ``run``'s default ``eval_every=1`` does), ``check_calls``
(the set-up's first calls, which the reference follows) and
``trace_seconds``.  The configuration's ``ref_block`` is the number of
UE rows per reference call.

Set-up builds one simulator and drives it through its first
``check_calls`` calls (these compile and warm every program the window
runs).  The window then times whole calls until ``--seconds`` have
passed.  After it, the simulator is freed and the plain float32
reference (``yardstick.reference.sync_rounds``) follows the first calls
from the same weights and data; ``correct`` compares

* ``loss_gap``: each round's test and train loss, relative;
* ``update_gap``: the first call's change of each parameter leaf, as the
  gap between the program's and the reference's norms (worst leaf);
* ``change_gap``: the same for the change after the last checked call.
"""
from __future__ import annotations

import gc
import math

import jax
import numpy as np

from bench import federation
from bench.yardstick import compare, reference


def _call(sim, test, rounds, session):
    with session.span("bench.call"):
        res = sim.run(test, rounds=rounds)
        jax.block_until_ready(res.final_params)
    return res


def _bad_rounds(res) -> int:
    """Rounds of a call whose test or train loss is not finite."""
    return int(np.sum(~(np.isfinite(res.test_loss)
                        & np.isfinite(res.train_loss))))


def build(cell):
    from repro.fl.sim import HFLSimulator

    cfg = cell.cfg
    fed = federation.build(cfg, cell.model, cell.seed)
    sim = HFLSimulator(fed.schedule, cell.model.program_loss(cfg), fed.init,
                       fed.ue_data(), lr=cfg["lr"],
                       samples_per_ue=cfg["samples_per_ue"],
                       seed=federation.jax_seed(cell.seed))
    return fed, sim


def first_calls(cell, sim, test, session):
    """The set-up's calls; returns ``(losses, params)`` of each."""
    out = []
    for _ in range(int(cell.traffic["check_calls"])):
        res = _call(sim, test, cell.traffic["rounds_per_call"], session)
        out.append((np.stack([res.test_loss, res.train_loss], 1),
                    jax.tree.map(np.asarray, res.final_params),
                    _bad_rounds(res) == 0))
    return out


def readings(cell, fed, calls, dtype="float32", against=None):
    """Compare ``calls`` (the program's, or another run's in the same
    form) with the reference at ``dtype``; returns ``(numbers, ref)``."""
    cfg, tr = cell.cfg, cell.traffic
    r = int(tr["rounds_per_call"])
    ref = against or reference.sync_rounds(
        cell.model.make_reference_loss(cfg), fed.init_host(), fed.images,
        fed.labels, fed.sizes, fed.group_ids, a=cfg["a"], b=cfg["b"],
        lr=cfg["lr"], rounds=r * len(calls), dtype=dtype,
        block=int(cfg["ref_block"]), test=fed.test)
    init = fed.init_host()
    gaps = []
    for c, (losses, _, _) in enumerate(calls):
        for j in range(r):
            _, t_loss, tr_loss = ref[c * r + j]
            gaps += [compare.rel_gap(losses[j, 0], t_loss),
                     compare.rel_gap(losses[j, 1], tr_loss)]
    ref_first = compare.leaf_norms(ref[r - 1][0], init)
    keep = compare.moving_leaves(ref_first)
    numbers = {
        "loss_gap": max(gaps),
        "update_gap": compare.norm_gap(
            compare.leaf_norms(calls[0][1], init), ref_first, keep),
        "change_gap": compare.norm_gap(
            compare.leaf_norms(calls[-1][1], init),
            compare.leaf_norms(ref[-1][0], init), keep),
    }
    return numbers, ref


def as_calls(ref, rounds_per_call: int):
    """A reference trajectory in the form of ``first_calls``' result."""
    out = []
    for c in range(len(ref) // rounds_per_call):
        part = ref[c * rounds_per_call:(c + 1) * rounds_per_call]
        out.append((np.array([[t, tr] for _, t, tr in part]), part[-1][0],
                    True))
    return out


def run(cell, session):
    from bench.harness import Outcome

    tr = cell.traffic
    fed, sim = build(cell)
    test = fed.test
    calls = first_calls(cell, sim, test, session)

    session.begin_window()
    n_calls = failed = 0
    while True:
        res = _call(sim, test, tr["rounds_per_call"], session)
        n_calls += 1
        failed += _bad_rounds(res)
        if session.window_over():
            break
    session.end_window()
    rounds = n_calls * int(tr["rounds_per_call"])
    round_s = session.window_s / rounds
    peak = session.memory_peak()

    del sim, res
    gc.collect()
    numbers, _ = readings(cell, fed, calls)
    ok = (failed == 0 and all(f for _, _, f in calls)
          and all(math.isfinite(v) for v in numbers.values()))
    return Outcome(e2e={"round_s": round_s}, attempted=rounds,
                   failed=failed, checks=numbers,
                   layer={"rounds": rounds, "round_s": round_s,
                          "memory_peak_bytes": peak}, ok=ok)


def calibrate(cell, session, control: bool, faults=()):
    """The program's readings without a window; with ``control`` the
    readings of the reference computed in bfloat16 in its place; and the
    readings of the program with each of ``faults`` (``bench.faults``)
    planted.  Each also gives the gap of every leaf."""
    from bench import faults as faults_lib

    fed, sim = build(cell)
    calls = first_calls(cell, sim, fed.test, session)
    del sim
    gc.collect()
    prog, ref = readings(cell, fed, calls)
    out = {"program": prog, "leaves": leaf_detail(fed, calls, ref)}
    if control:
        low = reference.sync_rounds(
            cell.model.make_reference_loss(cell.cfg), fed.init_host(),
            fed.images, fed.labels, fed.sizes, fed.group_ids,
            a=cell.cfg["a"], b=cell.cfg["b"], lr=cell.cfg["lr"],
            rounds=len(ref), dtype="bfloat16",
            block=int(cell.cfg["ref_block"]), test=fed.test)
        out["control"], _ = readings(
            cell, fed, as_calls(low, int(cell.traffic["rounds_per_call"])),
            against=ref)
    for name in faults:
        with faults_lib.FAULTS["sync"][name]():
            _, sim = build(cell)
            bad = first_calls(cell, sim, fed.test, session)
            del sim
            gc.collect()
        out.setdefault("faults", {})[name], _ = readings(cell, fed, bad,
                                                         against=ref)
    return out


def leaf_detail(fed, calls, ref):
    """Per leaf: the reference's first-call and last-call change norms and
    the program's, in ``jax.tree`` order with the leaves' paths."""
    init = fed.init_host()
    r = len(ref) // len(calls)
    paths = [jax.tree_util.keystr(k) for k, _ in
             jax.tree_util.tree_flatten_with_path(init)[0]]
    cols = {"ref_first": compare.leaf_norms(ref[r - 1][0], init),
            "prog_first": compare.leaf_norms(calls[0][1], init),
            "ref_last": compare.leaf_norms(ref[-1][0], init),
            "prog_last": compare.leaf_norms(calls[-1][1], init)}
    return {p: {k: float(v[i]) for k, v in cols.items()}
            for i, p in enumerate(paths)}
