"""In-program observability: the ``hfl.*`` named scopes of the jitted
bodies and their instruction map, the ``hfl.*`` host spans of the sync
run and the service (read back from a profiler trace), and the departure
waves' row counters."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import schedule
from repro.core.problem import HFLProblem
from repro.data import partition, synthetic
from repro.fl.sim import HFLSimulator
from repro.launch.service import (HFLService, Segment, ServiceConfig,
                                  default_service_sim)
from repro.models import lenet
from repro.roofline.hlo_cost import instruction_scopes, parse_module

SCOPES = ("hfl.local_step", "hfl.edge_agg", "hfl.cloud_agg",
          "hfl.wave_select", "hfl.merge")


def _sync_sim(**kw):
    prob = HFLProblem(num_edges=2, num_ues=8, seed=0, samples_lo=30,
                      samples_hi=60)
    sch = schedule.plan(prob)
    n = int(prob.samples.sum())
    train = synthetic.logreg_data(seed=0, n=n, dim=12, num_classes=4)
    parts = partition.size_partition(np.random.default_rng(0), n,
                                     prob.samples.astype(int))
    ue_data = [{k: train[k][ix] for k in train} for ix in parts]
    init = lenet.logreg_init(jax.random.PRNGKey(0), 12, 4)
    test = synthetic.logreg_data(seed=1, n=64, dim=12, num_classes=4)
    sim = HFLSimulator(sch, lambda p, b: lenet.logreg_loss(p, b, l2=1e-3),
                       init, ue_data, lr=0.02, **kw)
    return sim, test


def _service(tmp_path, fleet=(8, 2), **kw):
    sim = default_service_sim(*fleet, max_staleness=2)
    cfg = ServiceConfig(segments=(Segment("iid_campus", 1.0, 30.0),
                                  Segment("iid_campus", 4.0, float("inf"))),
                        max_staleness=2, ckpt_dir=str(tmp_path / "ckpt"),
                        ckpt_every=5, keep_last_k=1, **kw)
    return HFLService(sim, cfg)


def _host_spans(log_dir, prefix="hfl."):
    """``[(name, start_ns, end_ns)]`` of the host events named
    ``prefix...`` in the profiler trace under ``log_dir``."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    pd = ProfileData.from_file(path)
    return sorted((e.name, e.start_ns, e.start_ns + e.duration_ns)
                  for plane in pd.planes if plane.name == "/host:CPU"
                  for line in plane.lines for e in line.events
                  if e.name.startswith(prefix))


def _parents(spans, child, parent):
    """For each ``child`` span, whether some ``parent`` span holds it."""
    outer = [(s, e) for n, s, e in spans if n == parent]
    return [any(s <= cs and ce <= e for s, e in outer)
            for n, cs, ce in spans if n == child]


def test_scope_map_names_every_scope():
    def f(x, w, m):
        def body(_, c):
            with jax.named_scope("hfl.local_step"):
                c = jnp.tanh(c @ w)
            with jax.named_scope("hfl.edge_agg"):
                return c - c.mean(0, keepdims=True)
        with jax.named_scope("hfl.wave_select"):
            x = jnp.where(m[:, None], x, 0.0)
        c = jax.lax.fori_loop(0, 3, body, x)
        with jax.named_scope("hfl.cloud_agg"):
            g = c.mean(0)
        with jax.named_scope("hfl.merge"):
            return 0.5 * g + 0.5 * c[0]

    args = (jnp.ones((8, 4)), jnp.ones((4, 4)), jnp.ones(8, bool))
    hlo = jax.jit(f).lower(*args).compile().as_text()
    scopes = instruction_scopes(hlo)
    assert set(scopes.values()) == set(SCOPES)
    comps, entry = parse_module(hlo)
    assert set(scopes) <= {op.name for c in comps.values() for op in c.ops}
    # nothing outside a scope is mapped: the parameters and the loop
    params = {op.name for op in comps[entry].ops
              if op.kind in ("parameter", "while")}
    assert params and not params & set(scopes)


def test_scope_map_takes_the_outermost_scope():
    hlo = ('ENTRY %main (p: f32[2]) -> f32[2] {\n'
           '  %p = f32[2]{0} parameter(0), metadata={op_name="p"}\n'
           '  ROOT %fusion.3 = f32[2]{0} fusion(%p), kind=kLoop, '
           'calls=%fc, metadata={op_name="jit(f)/hfl.local_step/vmap/'
           'hfl.edge_agg/mul" stack_frame_id=2}\n'
           '}\n')
    assert instruction_scopes(hlo) == {"fusion.3": "hfl.local_step"}


def test_op_scopes_map_the_round_and_the_wave_programs():
    sim = default_service_sim(8, 2, max_staleness=2)
    maps = sim.op_scopes()
    assert {"jit_cloud_round", "jit_depart_cycle", "jit_merge"} <= set(maps)
    assert set(maps["jit_cloud_round"].values()) == {
        "hfl.local_step", "hfl.edge_agg", "hfl.cloud_agg"}
    assert set(maps["jit_depart_cycle"].values()) == {
        "hfl.local_step", "hfl.edge_agg", "hfl.wave_select"}
    assert set(maps["jit_merge"].values()) == {"hfl.merge"}
    # the runtime-weight twins, once built, use the same names
    sim._weighted_ops()
    maps = sim.op_scopes()
    assert set(maps["jit_faulty_cloud_round"].values()) == {
        "hfl.local_step", "hfl.edge_agg", "hfl.cloud_agg"}
    assert set(maps["jit_faulty_depart"].values()) == {
        "hfl.local_step", "hfl.edge_agg", "hfl.wave_select"}


def test_op_scopes_map_the_gathered_wave_programs():
    """Cohorts of 3, 6 and 3 rows: the wave ladder is (8, 12), so each
    twin is built at an 8-row index and at the 12-row mask, both under
    the twin's own name and both with the wave's scopes."""
    sim = default_service_sim(12, 3, max_staleness=2)
    sim._weighted_ops()
    maps = sim.op_scopes()
    for twin, name in [(sim._depart_cycle, "jit_depart_cycle"),
                       (sim._faulty_depart, "jit_faulty_depart")]:
        progs = sim._wave_programs(twin)
        assert set(progs) == {8, 12}
        gathered = progs[8].as_text()
        assert gathered.split(None, 2)[1].rstrip(",") == name
        assert "s32[8]" in gathered
        assert set(instruction_scopes(gathered).values()) == {
            "hfl.local_step", "hfl.edge_agg", "hfl.wave_select"}
        assert set(maps[name].values()) == {
            "hfl.local_step", "hfl.edge_agg", "hfl.wave_select"}


def test_op_scopes_leave_the_run_unchanged():
    a, test = _sync_sim()
    b, _ = _sync_sim()
    a.op_scopes()
    ra, rb = a.run(test, rounds=2), b.run(test, rounds=2)
    np.testing.assert_array_equal(ra.test_loss, rb.test_loss)
    np.testing.assert_array_equal(np.asarray(a._flat), np.asarray(b._flat))


@pytest.mark.parametrize("kw", [{}, {"sampler": "uniform"}],
                         ids=["plain", "sampled"])
def test_sync_run_writes_round_and_eval_spans(tmp_path, kw):
    if kw:
        from repro.fl import sampling
        kw = {"sampler": sampling.make_sampler(kw["sampler"], 0.5)}
    sim, test = _sync_sim(**kw)
    sim.run(test, rounds=1)                       # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        res = sim.run(test, rounds=3, eval_every=2)
    spans = _host_spans(tmp_path)
    names = [n for n, _, _ in spans]
    assert names.count("hfl.round") == 3
    assert names.count("hfl.eval") == len(res.times) == 2
    assert all(_parents(spans, "hfl.eval", "hfl.round"))


def test_service_run_writes_nested_spans(tmp_path):
    svc = _service(tmp_path)
    svc.run(5)                                    # compile outside the trace
    with jax.profiler.trace(str(tmp_path / "trace")):
        svc.run(15)
    spans = _host_spans(tmp_path / "trace")
    names = [n for n, _, _ in spans]
    assert names.count("hfl.update") == 10
    assert names.count("hfl.engine_step") == 10
    assert names.count("hfl.checkpoint") == 2
    for child, parent in [("hfl.engine_step", "hfl.update"),
                          ("hfl.merge_row", "hfl.update"),
                          ("hfl.publish", "hfl.update"),
                          ("hfl.masks", "hfl.update"),
                          ("hfl.wave", "hfl.update"),
                          ("hfl.ckpt_state", "hfl.checkpoint"),
                          ("hfl.ckpt_write", "hfl.checkpoint")]:
        inside = _parents(spans, child, parent)
        assert inside and all(inside), (child, parent)
    assert not any(_parents(spans, "hfl.checkpoint", "hfl.update"))


def _recorded_waves(sim):
    """Wrap ``sim.replay_departure``: the masks of the waves it runs."""
    got = []
    depart = sim.replay_departure

    def record(g, mask, ue_ok=None, agg_weights=None):
        got.append(np.array(mask, bool))
        return depart(g, mask, ue_ok=ue_ok, agg_weights=agg_weights)
    sim.replay_departure = record
    return got


def _bucket(sim, mask):
    return next(b for b in sim._wave_ladder if b >= mask.sum())


def test_wave_row_counters_sum_the_masks(tmp_path):
    """Cohorts of 3, 6 and 3 rows, ladder (8, 12): each wave trains the
    smallest bucket that holds its cohorts and keeps their rows."""
    svc = _service(tmp_path, fleet=(12, 3))
    sim = svc.sim
    trained0, kept0 = sim.wave_rows_trained, sim.wave_rows_kept
    assert (trained0, kept0) == (12, 12)      # the initial all-edge wave
    got = _recorded_waves(sim)
    s = svc.run(30)
    assert got
    assert s["wave_rows_trained"] - trained0 == sum(_bucket(sim, m)
                                                    for m in got)
    assert s["wave_rows_kept"] - kept0 == sum(int(m.sum()) for m in got)
    assert 0 < s["wave_rows_kept"] < s["wave_rows_trained"]


def test_summary_reports_the_wave_buckets(tmp_path):
    """``summary()["wave_bucket_runs"]`` counts the waves per bucket
    size, the initial all-edge wave in the 12-row bucket included."""
    svc = _service(tmp_path, fleet=(12, 3))
    assert svc.summary()["wave_bucket_runs"] == {12: 1}
    got = _recorded_waves(svc.sim)
    s = svc.run(30)
    want = {12: 1}
    for m in got:
        want[_bucket(svc.sim, m)] = want.get(_bucket(svc.sim, m), 0) + 1
    assert s["wave_bucket_runs"] == want
    assert want.get(8, 0) > 0
    assert sum(b * k for b, k in want.items()) == s["wave_rows_trained"]
