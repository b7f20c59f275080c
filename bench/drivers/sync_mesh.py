"""Synchronous cloud rounds of a fleet whose UE rows are sharded over the
cell's chips: ``HFLSimulator(mesh=...).run`` called again and again.

Traffic keys: ``sync``'s (``rounds_per_call``, ``check_calls``,
``trace_seconds``).  The configuration's ``ref_block`` is the number of
UE rows per reference call.

Set-up, window and readings are ``sync``'s (its ``first_calls`` and
``readings``); the simulator is built with ``mesh=make_agg_mesh(1,
chips)``: whole UE rows over every chip ('data'), none of a row's
features split ('model' 1).  The reference is ``reference.sync_rounds``
with its UE blocks spread over the same chips
(``yardstick.mesh_reference``); its programs start compiling, from the
configuration's shapes alone, when the run starts, and are collected
before the window, so that their minutes of compiling at ``highest``
precision overlap the set-up and nothing compiles inside the window.
Besides ``sync``'s three readings, ``correct`` reads ``replica_gap``:
after the window, how far any edge's rows lie from the cloud model, as a
share of the cloud model's change.  The reported model is a mean over
every row, so a chip that kept a mean over its own rows would shift it
only at second order; its rows show it at first.  ``calibrate`` plants
``bench.faults_mesh``'s faults, which add ``exchange_dropped``.

What the per-layer readers of a multi-chip cell need is per chip, and
this driver hands it to them in ``layer``:

* ``chips``: the cell's chip count;
* ``chip_rows``: ``HFLSimulator.shard_rows()`` read at set-up, the real
  and padding rows and the resident bytes on each device (left out where
  the program has no such accessor);
* after a traced window, each chip's device seconds in
  ``jit_cloud_round`` (``chip_round_s``), in its collective instructions
  (``chip_collective_s``) and in its aggregation kernels
  (``chip_agg_kernel_s``), read from the trace with
  ``bench.yardstick.trace`` before the harness deletes it;
* ``memory_peak_bytes``: the largest peak over the mesh's chips.
"""
from __future__ import annotations

import gc
import math
import sys
import time

import numpy as np

from bench import federation
from bench.drivers import sync
from bench.yardstick import mesh_reference
from bench.yardstick import trace as trace_lib

ROUND_PROGRAM = "jit_cloud_round"
#: Opcodes of cross-chip collectives; ``-start``/``-done`` halves of the
#: asynchronous forms count as the collective.
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def simulator(cell, fed):
    """The cell's ``HFLSimulator`` over ``fed``, on its mesh."""
    from repro.fl.sim import HFLSimulator
    from repro.launch.mesh import make_agg_mesh

    cfg = cell.cfg
    return HFLSimulator(fed.schedule, cell.model.program_loss(cfg), fed.init,
                        fed.ue_data(), lr=cfg["lr"],
                        samples_per_ue=cfg["samples_per_ue"],
                        seed=federation.jax_seed(cell.seed),
                        mesh=make_agg_mesh(1, cell.chips))


def build(cell):
    fed = federation.build(cell.cfg, cell.model, cell.seed)
    return fed, simulator(cell, fed)


#: The reference's programs of this process, by (cell, dtype): a
#: calibration's seeds share them.
_PROGRAMS = {}


def reference_programs(cell, dtype: str) -> mesh_reference.Pending:
    """Start (once) compiling the cell's reference at ``dtype`` over its
    chips."""
    import jax

    key = (cell.name, dtype)
    if key not in _PROGRAMS:
        cfg = cell.cfg
        size, ch = int(cfg["image_size"]), int(cfg["in_channels"])
        params = jax.eval_shape(lambda: cell.model.init_params(
            jax.random.PRNGKey(0), cfg))
        _PROGRAMS[key] = mesh_reference.compile_programs(
            cell.model.make_reference_loss(cfg), params,
            n=int(cfg["num_ues"]), samples=int(cfg["samples_per_ue"]),
            image_shape=(size, size, ch), groups=int(cfg["num_edges"]),
            test_n=int(cfg["test_images"]), a=int(cfg["a"]),
            lr=float(cfg["lr"]), dtype=dtype, block=int(cfg["ref_block"]),
            devices=jax.devices()[:cell.chips])
    return _PROGRAMS[key]


def reference_rounds(cell, fed, rounds: int, dtype: str = "float32"):
    """The reference's first ``rounds`` rounds at ``dtype``, in
    ``reference.sync_rounds``' form."""
    t = time.perf_counter()
    pending = reference_programs(cell, dtype)
    progs = pending.result()
    waited = time.perf_counter() - t
    out = mesh_reference.sync_rounds(
        progs, fed.init_host(), fed.images, fed.labels, fed.sizes,
        fed.group_ids, b=int(cell.cfg["b"]), rounds=rounds, test=fed.test)
    print(f"[bench] {cell.name}: {dtype} reference of {rounds} round(s) on "
          f"{cell.chips} chips: {time.perf_counter() - t!r} s, of which "
          f"{waited!r} s waiting for its compile (compile s: "
          f"{ {k: round(v, 1) for k, v in pending.seconds.items()} })",
          file=sys.stderr, flush=True)
    return out


def replica_gap(sim, start) -> float:
    """After a cloud round every UE row holds the cloud model (eq. 10),
    on every chip: the largest distance of an edge's row from the cloud
    model (``HFLSimulator.cloud_vector``), relative to the cloud model's
    change since ``start`` (its vector before the first round).  A chip
    that kept a mean over its own rows reads its distance from the
    global one."""
    g = np.asarray(sim.cloud_vector(), np.float64)
    moved = np.linalg.norm(g - start)
    rows = [np.asarray(sim.edge_mean_row(m), np.float64)
            for m in np.unique(np.asarray(sim.group_ids))]
    return float(max(np.linalg.norm(r - g) for r in rows) / moved)


def is_collective(op: trace_lib.Op) -> bool:
    code = op.opcode
    for half in ("-start", "-done"):
        if code.endswith(half):
            code = code[:-len(half)]
    return code in COLLECTIVES


def chip_seconds(trace_dir: str) -> dict:
    """Per chip, in the traced window: device seconds of the round
    program's runs, of its collectives and of its aggregation kernels."""
    t = trace_lib.load(trace_dir)
    lo, hi = trace_lib.window(t)
    within = {ROUND_PROGRAM}
    return {
        "chip_round_s": [trace_lib.module_time_ns(d, lo, hi, within) * 1e-9
                         for d in t.devices],
        "chip_collective_s": [trace_lib.op_time_ns(
            d, lo, hi, is_collective, within=within) * 1e-9
            for d in t.devices],
        "chip_agg_kernel_s": [trace_lib.op_time_ns(
            d, lo, hi, trace_lib.is_agg_kernel, within=within) * 1e-9
            for d in t.devices],
    }


def memory_peak(sim):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in sim.mesh.devices.flat]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run(cell, session):
    from bench.harness import Outcome

    tr = cell.traffic
    pending = reference_programs(cell, "float32")
    fed, sim = build(cell)
    test = fed.test
    start = np.asarray(sim.cloud_vector(), np.float64)
    calls = sync.first_calls(cell, sim, test, session)
    pending.result()
    layer = {"chips": cell.chips}
    shard_rows = getattr(sim, "shard_rows", None)
    if shard_rows is not None:
        layer["chip_rows"] = shard_rows()

    session.begin_window()
    n_calls = failed = 0
    while True:
        res = sync._call(sim, test, tr["rounds_per_call"], session)
        n_calls += 1
        failed += sync._bad_rounds(res)
        if session.window_over():
            break
    session.end_window()
    rounds = n_calls * int(tr["rounds_per_call"])
    round_s = session.window_s / rounds
    layer.update(rounds=rounds, round_s=round_s,
                 memory_peak_bytes=memory_peak(sim))
    if session.tracing:
        layer.update(chip_seconds(session.trace_dir))

    replicas = replica_gap(sim, start)
    del sim, res
    gc.collect()
    ref = reference_rounds(cell, fed, len(calls) * int(tr["rounds_per_call"]))
    numbers, _ = sync.readings(cell, fed, calls, against=ref)
    numbers["replica_gap"] = replicas
    ok = (failed == 0 and all(f for _, _, f in calls)
          and all(math.isfinite(v) for v in numbers.values()))
    return Outcome(e2e={"round_s": round_s}, attempted=rounds,
                   failed=failed, checks=numbers, layer=layer, ok=ok)


def _note(cell, what: str, numbers: dict) -> None:
    """A calibration's reading as soon as it is made, on stderr, so that a
    run cut short keeps what it read."""
    print(f"[bench] {cell.name} seed {cell.seed}: {what} {numbers}",
          file=sys.stderr, flush=True)


def calibrate(cell, session, control: bool, faults=()):
    """``sync.calibrate`` for this cell: the program's readings without a
    window, with ``control`` those of the bfloat16 reference in its place,
    and those of the program with each of ``faults`` planted (a fresh
    simulator over the same federation).  Each also gives the gap of
    every leaf.  The faults are ``bench.faults_mesh``'s: ``bench.faults``'
    sync faults and ``exchange_dropped``."""
    from bench import faults_mesh

    reference_programs(cell, "float32")
    if control:
        reference_programs(cell, "bfloat16")
    fed, sim = build(cell)
    start = np.asarray(sim.cloud_vector(), np.float64)
    calls = sync.first_calls(cell, sim, fed.test, session)
    replicas = replica_gap(sim, start)
    del sim
    gc.collect()
    per_call = int(cell.traffic["rounds_per_call"])
    ref = reference_rounds(cell, fed, len(calls) * per_call)
    prog, _ = sync.readings(cell, fed, calls, against=ref)
    prog["replica_gap"] = replicas
    _note(cell, "program", prog)
    out = {"program": prog, "leaves": sync.leaf_detail(fed, calls, ref)}
    if control:
        low = reference_rounds(cell, fed, len(ref), "bfloat16")
        out["control"], _ = sync.readings(
            cell, fed, sync.as_calls(low, per_call), against=ref)
        _note(cell, "control", out["control"])
    for name in faults:
        with faults_mesh.FAULTS[name]():
            sim = simulator(cell, fed)
            bad = sync.first_calls(cell, sim, fed.test, session)
            replicas = replica_gap(sim, start)
            del sim
            gc.collect()
        got, _ = sync.readings(cell, fed, bad, against=ref)
        got["replica_gap"] = replicas
        out.setdefault("faults", {})[name] = got
        _note(cell, f"fault {name}", got)
    return out

