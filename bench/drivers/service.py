"""The always-on control plane: ``HFLService.run`` in chunks of updates.

Traffic keys: ``period`` (segments of one traffic period: scenario, load,
simulated seconds), ``periods`` (how often it repeats before a last
open-ended segment), ``max_staleness``, ``staleness_decay``,
``delay_seed`` (keys the cycle-time draws: every ``--seed`` replays the
same arrivals, and the seed draws the data and the weights),
``ckpt_every`` and ``keep_last_k`` (checkpoints to a temporary
directory), ``chunk_updates`` (updates per ``run`` call, a multiple of
``ckpt_every`` so that the harness adds no checkpoint), ``warmup_chunks``,
``check_waves`` and ``trace_seconds``.

The service replays a simulated clock, so it runs closed-loop: each
``run`` call processes its updates as fast as the host and chip allow.
Per-update wall times come from a wrapper on the service's public
``engine.step``; the inputs of each departure wave and each merge's row
from wrappers on ``sim.replay_departure`` and ``sim.edge_mean_row``.
``correct`` compares

* ``wave_gap``: for a sample of the window's arrivals (drawn from the
  seed), the edge model the program merged against the plain reference
  cycle (``yardstick.reference.cohort_cycle``) from the same cloud model
  and participants, as a share of that cycle's move;
* ``merge_gap``: the published cloud model at the window's end against
  a float64 replay of the window's merges (``merge_replay``) on the
  program's rows, as a share of the window's move.
"""
from __future__ import annotations

import gc
import math
import tempfile
import time

import jax
import jax.flatten_util
import numpy as np

from bench import federation
from bench.yardstick import compare, reference


class Recorder:
    """Wraps the service's public calls for timing, spans and the check."""

    def __init__(self, svc, session):
        self.stamps, self.steps = [], []
        self.waves = []          # (step, cloud vector, mask, ue_ok)
        self.rows = []           # (step, edge, device row)
        self._span = None
        eng, sim = svc.engine, svc.sim
        step, depart = eng.step, sim.replay_departure
        row, ckpt = sim.edge_mean_row, svc.checkpoint

        def wrapped_step():
            self.close_update()
            self.stamps.append(time.perf_counter())
            if session.tracing:
                self._span = session.span("bench.update")
                self._span.__enter__()
            recs = step()
            self.steps.append(recs)
            return recs

        def wrapped_depart(g, mask, ue_ok=None, agg_weights=None):
            self.waves.append((len(self.steps) - 1, svc.g.copy(),
                               np.array(mask, bool),
                               None if ue_ok is None
                               else np.array(ue_ok, bool)))
            with session.span("bench.wave"):
                return depart(g, mask, ue_ok=ue_ok, agg_weights=agg_weights)

        def wrapped_row(m):
            with session.span("bench.merge_row"):
                r = row(m)
            self.rows.append((len(self.steps) - 1, int(m), r))
            return r

        def wrapped_ckpt():
            with session.span("bench.checkpoint"):
                return ckpt()

        eng.step = wrapped_step
        sim.replay_departure = wrapped_depart
        sim.edge_mean_row = wrapped_row
        svc.checkpoint = wrapped_ckpt

    def close_update(self):
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None


def _segments(tr):
    from repro.launch.service import Segment
    period = [Segment(s["scenario"], float(s["load"]), float(s["duration"]))
              for s in tr["period"]]
    last = Segment(period[0].scenario, period[0].load, math.inf)
    return tuple(period * int(tr["periods"])) + (last,)


def _arrivals(active, rec):
    """``{(edge, cycle): (step, row)}`` for every merge row pulled."""
    pulled = {}
    by_step = {}
    for step, m, r in rec.rows:
        by_step.setdefault(step, []).append((m, r))
    for k, recs in enumerate(rec.steps):
        merges = [(int(active[m]), int(c)) for kind, ev in recs
                  if kind == "update" for m, c, _ in ev.merges]
        got = by_step.get(k, [])
        if [m for m, _ in merges] != [m for m, _ in got]:
            raise RuntimeError(f"step {k}: merges {merges} but rows "
                               f"pulled for {[m for m, _ in got]}")
        for (m, c), (_, r) in zip(merges, got):
            pulled[(m, c)] = (k, r)
    return pulled


def _departures(active, rec):
    """``{(edge, cycle): wave}`` for every wave recorded."""
    by_step = {w[0]: w for w in rec.waves}
    out = {}
    for k, recs in enumerate(rec.steps):
        for kind, ev in recs:
            if kind == "depart" and k in by_step:
                out[(int(active[ev.edge]), int(ev.cycle))] = by_step[k]
    return out


def wave_readings(cell, fed, active, rec, first_step, dtypes):
    """``wave_gap`` of the program, and of each dtype in ``dtypes`` put
    in its place, over a seeded sample of the window's arrivals."""
    cfg, tr = cell.cfg, cell.traffic
    arr, dep = _arrivals(active, rec), _departures(active, rec)
    keys = sorted(k for k, (step, _) in arr.items()
                  if step >= first_step and k in dep)
    rng = np.random.default_rng(cell.seed)
    pick = [keys[i] for i in sorted(rng.choice(
        len(keys), min(int(tr["check_waves"]), len(keys)), replace=False))]
    _, unravel = jax.flatten_util.ravel_pytree(fed.init_host())
    gids = fed.group_ids
    width = int(np.bincount(gids).max())
    loss = cell.model.make_reference_loss(cfg)
    pcs = {dt: reference.Pieces(loss, cfg["a"], cfg["lr"], dt)
           for dt in ("float32",) + tuple(dtypes)}
    gaps = {dt: [] for dt in ("program",) + tuple(dtypes)}
    for m, c in pick:
        _, g, _, ue_ok = dep[(m, c)]
        idx = np.flatnonzero(gids == m)
        w = fed.sizes[idx].astype(np.float64)
        if ue_ok is not None:
            w = w * ue_ok[idx]
        pad = width - idx.size
        sel = np.concatenate([idx, np.repeat(idx[:1], pad)])
        w = np.concatenate([w, np.zeros(pad)])
        args = (fed.images[sel], fed.labels[sel], w)
        kw = dict(a=cfg["a"], b=cfg["b"], lr=cfg["lr"])

        def cycle(dt):
            out = reference.cohort_cycle(loss, unravel(g), *args, dtype=dt,
                                         pieces=pcs[dt], **kw)
            return jax.flatten_util.ravel_pytree(out)[0]
        want = np.asarray(cycle("float32"), np.float64)
        got = np.asarray(jax.device_get(arr[(m, c)][1]), np.float64)
        gaps["program"].append(compare.rel_err(got, want, g))
        for dt in dtypes:
            gaps[dt].append(compare.rel_err(np.asarray(cycle(dt)), want, g))
    return {k: max(v) for k, v in gaps.items()}, len(pick)


def merge_readings(cell, fed, active, rec, records, g0, g1, dtypes):
    tr = cell.traffic
    arr = _arrivals(active, rec)
    rows = {}
    merges = []
    for r in records:
        if r["kind"] != "merge":
            continue
        key = (int(r["edge"]), int(r["cycle"]))
        if key not in rows:
            rows[key] = np.asarray(jax.device_get(arr[key][1]), np.float64)
        mass = float(fed.sizes[fed.group_ids == key[0]].sum())
        merges.append((rows[key], mass, int(r["stale"])))
    kw = dict(decay=float(tr["staleness_decay"]),
              w_total=float(fed.sizes.sum()))
    want = reference.merge_replay(g0, merges, **kw)
    out = {"program": compare.rel_err(g1, want, g0)}
    for dt in dtypes:
        out[dt] = compare.rel_err(
            reference.merge_replay(g0, merges, dtype=dt, **kw), want, g0)
    return out, len(merges)


def run(cell, session, control=False):
    from bench.harness import Outcome
    from repro.fl.sim import HFLSimulator
    from repro.launch.service import HFLService, ServiceConfig

    cfg, tr = cell.cfg, cell.traffic
    fed = federation.build(cfg, cell.model, cell.seed)
    sim = HFLSimulator(fed.schedule, cell.model.program_loss(cfg), fed.init,
                       fed.ue_data(), lr=cfg["lr"],
                       samples_per_ue=cfg["samples_per_ue"],
                       seed=federation.jax_seed(cell.seed), mode="async",
                       max_staleness=int(tr["max_staleness"]),
                       staleness_decay=float(tr["staleness_decay"]))
    chunk = int(tr["chunk_updates"])
    with tempfile.TemporaryDirectory(prefix="bench-ckpt-") as ckpt:
        svc = HFLService(sim, ServiceConfig(
            segments=_segments(tr), max_staleness=int(tr["max_staleness"]),
            staleness_decay=float(tr["staleness_decay"]),
            delay_seed=int(tr["delay_seed"]), ckpt_dir=ckpt,
            ckpt_every=int(tr["ckpt_every"]),
            keep_last_k=int(tr["keep_last_k"])))
        # The shed path's wave twin, compiled now: an empty mask commits
        # no row, so the state is unchanged.
        n = fed.group_ids.shape[0]
        sim.replay_departure(sim.place_cloud_vector(svc.g),
                             np.zeros(n, bool), ue_ok=np.ones(n, bool))
        rec = Recorder(svc, session)
        for _ in range(int(tr["warmup_chunks"])):
            svc.run(svc.events_done + chunk)
            rec.close_update()

        session.begin_window()
        g0, s0 = svc.g.copy(), svc.summary()
        trace0, step0, wave0 = len(svc.trace), len(rec.steps), len(rec.waves)
        while True:
            with session.span("bench.chunk"):
                svc.run(svc.events_done + chunk)
                rec.close_update()
            if session.window_over():
                break
        session.end_window()
        g1, s1 = svc.g.copy(), svc.summary()
        records = svc.trace[trace0:]
    peak = session.memory_peak()
    active = np.asarray(svc.active)
    del svc, sim                      # free the program's device state
    gc.collect()

    bounds = rec.stamps[step0 + 1:] + [session.t1]
    per_update = np.diff([session.t0] + bounds)
    updates = int(s1["events"] - s0["events"])
    if per_update.size != updates:
        raise RuntimeError(f"{per_update.size} engine steps timed for "
                           f"{updates} updates")
    waves = len(rec.waves) - wave0

    lows = ("bfloat16",) if control else ()
    wave, n_waves = wave_readings(cell, fed, active, rec, step0, lows)
    merge, n_merges = merge_readings(cell, fed, active, rec, records, g0,
                                     g1, lows)
    print(f"[bench] checked {n_waves} waves and {n_merges} merges of the "
          f"window", flush=True)
    checks = {"wave_gap": wave["program"], "merge_gap": merge["program"]}
    layer = {"updates": updates, "waves": waves,
             "ckpt_wall": s1["ckpt_wall"] - s0["ckpt_wall"],
             "run_wall": s1["run_wall"] - s0["run_wall"],
             "memory_peak_bytes": peak}
    if control:
        layer["control"] = {"wave_gap": wave["bfloat16"],
                            "merge_gap": merge["bfloat16"]}
    return Outcome(
        e2e={"service_updates_per_s": updates / session.window_s,
             "service_update_p95_ms": float(np.percentile(per_update, 95)
                                            * 1e3)},
        attempted=updates,
        failed=int(s1["shed"] - s0["shed"] + s1["fault_shed"]
                   - s0["fault_shed"]),
        checks=checks, layer=layer,
        ok=bool(np.all(np.isfinite(g1))))


def calibrate(cell, session, control: bool, faults=()):
    """The program's readings over a window of ``session.seconds``; with
    ``control`` those of the reference computed in bfloat16 in its place;
    and the program's with each of ``faults`` planted."""
    import time as time_lib

    from bench import faults as faults_lib

    out = run(cell, session, control=control)
    res = {"program": out.checks}
    if control:
        res["control"] = out.layer["control"]
    for name in faults:
        with faults_lib.FAULTS["service"][name]():
            bad = run(cell, type(session)(time_lib.perf_counter(),
                                          session.seconds, None))
        res.setdefault("faults", {})[name] = bad.checks
    return res
