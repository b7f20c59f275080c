"""Async simulator mode: sync-trajectory parity at max_staleness=0,
staleness-bounded progress, determinism, and argument validation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import schedule
from repro.core.problem import HFLProblem
from repro.data import partition, synthetic
from repro.fl.sim import HFLSimulator
from repro.models import lenet


def _loss_fn(p, b):
    return lenet.logreg_loss(p, b, l2=1e-3)


@pytest.fixture(scope="module")
def async_setup():
    prob = HFLProblem(num_edges=2, num_ues=8, epsilon=0.25, seed=0,
                      samples_lo=50, samples_hi=120)
    sch = schedule.plan(prob)
    train = synthetic.logreg_data(seed=0, n=800, dim=12, num_classes=4)
    test = synthetic.logreg_data(seed=1, n=200, dim=12, num_classes=4)
    rng = np.random.default_rng(0)
    parts = partition.size_partition(rng, 800, prob.samples.astype(int))
    ue_data = [{k: train[k][ix] for k in train} for ix in parts]
    init = lenet.logreg_init(jax.random.PRNGKey(0), 12, 4)
    return sch, init, ue_data, test


def test_async_staleness_zero_matches_sync_trajectory(async_setup):
    """The acceptance bar: mode='async', max_staleness=0 reproduces the
    synchronous trajectory (clock AND model) to <= 1e-5."""
    sch, init, ue_data, test = async_setup
    rounds = 5
    res_s = HFLSimulator(sch, _loss_fn, init, ue_data,
                         lr=0.02).run(test, rounds=rounds)
    res_a = HFLSimulator(sch, _loss_fn, init, ue_data, lr=0.02,
                         mode="async", max_staleness=0).run(test,
                                                            rounds=rounds)
    np.testing.assert_allclose(res_a.times, res_s.times, rtol=1e-12)
    np.testing.assert_allclose(res_a.test_loss, res_s.test_loss, atol=1e-5)
    np.testing.assert_allclose(res_a.train_loss, res_s.train_loss, atol=1e-5)
    np.testing.assert_allclose(res_a.test_acc, res_s.test_acc, atol=1e-5)
    for la, ls in zip(jax.tree.leaves(res_a.final_params),
                      jax.tree.leaves(res_s.final_params)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(ls), atol=1e-5)
    assert res_a.timeline is not None and res_s.timeline is None


def test_async_staleness_beats_sync_clock_and_converges(async_setup):
    sch, init, ue_data, test = async_setup
    rounds = 5
    sim = HFLSimulator(sch, _loss_fn, init, ue_data, lr=0.02,
                       mode="async", max_staleness=2)
    res = sim.run(test, rounds=rounds)
    # equal communication work, strictly earlier finish than eq. 34
    assert res.times[-1] < rounds * sch.cloud_round_time
    assert np.all(np.diff(res.times) > 0)
    assert np.all(np.isfinite(res.test_loss))
    assert res.test_acc[-1] > 0.9
    # one eval per cloud update; quota = rounds * active edges
    m_active = int((sch.assoc.sum(0) > 0).sum())
    assert len(res.times) == rounds * m_active


def test_async_run_is_deterministic(async_setup):
    sch, init, ue_data, test = async_setup
    r1 = HFLSimulator(sch, _loss_fn, init, ue_data, lr=0.02, mode="async",
                      max_staleness=2).run(test, rounds=3)
    r2 = HFLSimulator(sch, _loss_fn, init, ue_data, lr=0.02, mode="async",
                      max_staleness=2).run(test, rounds=3)
    np.testing.assert_array_equal(r1.times, r2.times)
    np.testing.assert_array_equal(r1.test_loss, r2.test_loss)


def test_async_slow_edge_does_not_block_progress(async_setup):
    """Stretch one edge's backhaul to a crawl: with a staleness allowance
    the cloud still receives early merges long before the straggler's
    first full cycle lands."""
    sch, init, ue_data, test = async_setup
    prob = sch.problem
    slow = int(sch.assoc.sum(0).argmax())
    orig = prob.backhaul
    backhaul = orig.copy()
    backhaul[slow] = backhaul[slow] / 1e3       # ~1000x slower upload
    prob.backhaul = backhaul
    try:
        sim = HFLSimulator(sch, _loss_fn, init, ue_data, lr=0.02,
                           mode="async", max_staleness=3)
        res = sim.run(test, rounds=3)
        from repro.core import delay
        cyc = delay.edge_cycle_time(prob, sch.assoc, sch.a, sch.b)
        early = res.times[res.times < cyc[slow]]
        assert early.size > 0, "fast edges must reach the cloud first"
        assert np.all(np.isfinite(res.test_loss))
    finally:
        prob.backhaul = orig


def test_async_eval_every_thins_eval_points(async_setup):
    sch, init, ue_data, test = async_setup
    sim = HFLSimulator(sch, _loss_fn, init, ue_data, lr=0.02,
                       mode="async", max_staleness=1)
    res = sim.run(test, rounds=3, eval_every=3)
    m_active = int((sch.assoc.sum(0) > 0).sum())
    total = 3 * m_active
    expect = total // 3 + (1 if total % 3 else 0)
    assert len(res.times) == expect


def test_async_argument_validation(async_setup):
    sch, init, ue_data, _ = async_setup
    with pytest.raises(ValueError):
        HFLSimulator(sch, _loss_fn, init, ue_data, mode="bogus")
    with pytest.raises(ValueError):
        HFLSimulator(sch, _loss_fn, init, ue_data, mode="async",
                     solver="dane")
    with pytest.raises(ValueError):
        HFLSimulator(sch, _loss_fn, init, ue_data, mode="async",
                     max_staleness=-1)


def test_delay_model_deterministic_parity_sync_and_async(async_setup):
    """HFLSimulator(delay_model=DeterministicDelays()) reproduces the
    constant-delay clock bit-exactly and the trajectory to <= 1e-5, in
    BOTH modes."""
    from repro.core import stochastic
    sch, init, ue_data, test = async_setup
    det = stochastic.DeterministicDelays()
    for kw in (dict(), dict(mode="async", max_staleness=2)):
        r0 = HFLSimulator(sch, _loss_fn, init, ue_data, lr=0.02,
                          **kw).run(test, rounds=3)
        r1 = HFLSimulator(sch, _loss_fn, init, ue_data, lr=0.02,
                          delay_model=det, **kw).run(test, rounds=3)
        np.testing.assert_array_equal(r1.times, r0.times)
        np.testing.assert_allclose(r1.test_loss, r0.test_loss, atol=1e-5)
        np.testing.assert_allclose(r1.train_loss, r0.train_loss, atol=1e-5)


def test_delay_model_stochastic_clock_is_seeded(async_setup):
    """A stochastic model keeps the run deterministic per seed (same seed
    => identical clock AND trace) and produces a different clock under a
    different seed; the sync stochastic clock is strictly increasing."""
    from repro.core import stochastic
    sch, init, ue_data, test = async_setup
    model = stochastic.scenario("urban_stragglers").model
    mk = lambda seed: HFLSimulator(sch, _loss_fn, init, ue_data, lr=0.02,
                                   mode="async", max_staleness=2,
                                   delay_model=model, delay_seed=seed)
    r1, r2, r3 = (mk(5).run(test, rounds=3), mk(5).run(test, rounds=3),
                  mk(6).run(test, rounds=3))
    np.testing.assert_array_equal(r1.times, r2.times)
    np.testing.assert_array_equal(r1.test_loss, r2.test_loss)
    assert not np.array_equal(r1.times, r3.times)
    rs = HFLSimulator(sch, _loss_fn, init, ue_data, lr=0.02,
                      delay_model=model, delay_seed=5).run(test, rounds=3)
    assert np.all(np.diff(rs.times) > 0)
    assert not np.allclose(np.diff(rs.times), np.diff(rs.times)[0])


def test_delay_model_requires_problem(async_setup):
    import dataclasses
    from repro.core import stochastic
    sch, init, ue_data, _ = async_setup
    bare = dataclasses.replace(sch, problem=None)
    with pytest.raises(ValueError):
        HFLSimulator(bare, _loss_fn, init, ue_data,
                     delay_model=stochastic.scenario("iid_campus").model)


def test_async_requires_problem_for_cycle_times(async_setup):
    import dataclasses
    sch, init, ue_data, test = async_setup
    bare = dataclasses.replace(sch, problem=None)
    sim = HFLSimulator(bare, _loss_fn, init, ue_data, mode="async")
    with pytest.raises(ValueError):
        sim.run(test, rounds=1)


# ---------------------------------------------------------------------------
# Departure waves in row buckets: a wave gathers the departing cohorts'
# rows into the smallest bucket of the ladder that holds them.
# ---------------------------------------------------------------------------

#: Cohort sizes of the bucket fleet: W = 8, ladder (8, 16, 32, 40).
COHORTS = (8, 8, 8, 8, 5, 3)
LADDER = (8, 16, 32, 40)
#: wave -> (departing edges, bucket it takes)
WAVES = {
    "empty": ((), 8),
    "one_padded": ((4,), 8),
    "one_exact": ((0,), 8),
    "two_exact": ((0, 1), 16),
    "three_exact": ((0, 4, 5), 16),
    "three_padded": ((0, 1, 4), 32),
    "four_exact": ((0, 1, 2, 3), 32),
    "full_fleet": ((0, 1, 2, 3, 4, 5), 40),
}


def _bucket_sim(mesh=None):
    """An async simulator over 40 UEs whose cohorts are COHORTS, their
    rows interleaved, with every row holding its own state."""
    import dataclasses
    m = len(COHORTS)
    prob = HFLProblem(num_edges=m, num_ues=sum(COHORTS), epsilon=0.25,
                      seed=0, samples_lo=20, samples_hi=60)
    sch = schedule.plan(prob)
    gids = np.random.default_rng(1).permutation(
        np.repeat(np.arange(m), COHORTS))
    sch = dataclasses.replace(sch, a=3, b=2,
                              assoc=np.eye(m, dtype=sch.assoc.dtype)[gids])
    train = synthetic.logreg_data(seed=0, n=sum(COHORTS) * 12, dim=12,
                                  num_classes=4)
    ue_data = [{k: v[12 * i:12 * (i + 1)] for k, v in train.items()}
               for i in range(sum(COHORTS))]
    init = lenet.logreg_init(jax.random.PRNGKey(0), 12, 4)
    sim = HFLSimulator(sch, _loss_fn, init, ue_data, lr=0.05, mode="async",
                       max_staleness=2, mesh=mesh)
    rng = np.random.default_rng(2)
    sim.set_flat_state(rng.normal(size=sim._flat.shape).astype(np.float32))
    g = sim.place_cloud_vector(
        rng.normal(size=sim._flat.shape[1]).astype(np.float32))
    return sim, g, gids


def _wave_mask(gids, edges):
    return np.isin(gids, edges)


def _shed(gids, mask):
    """Drop every third departing row, keeping one per cohort."""
    ue_ok = np.ones(gids.size, bool)
    rows = np.flatnonzero(mask)
    ue_ok[rows[1::3]] = False
    for m in np.unique(gids[rows]):
        ue_ok[rows[gids[rows] == m][0]] = True
    return ue_ok


@pytest.fixture(scope="module", params=["jnp", "pallas"])
def bucket_sim(request):
    """The bucket fleet on the jnp aggregation and on the Pallas kernels
    (interpret mode off the chip), both wave twins built."""
    from repro.fl import aggregate
    orig = aggregate._select_kernel
    if request.param == "pallas":
        aggregate._select_kernel = lambda use_kernel: True
    try:
        sim, g, gids = _bucket_sim()
        sim._weighted_ops()
        sim._wave_programs(sim._depart_cycle)
        sim._wave_programs(sim._faulty_depart)
        yield sim, g, gids
    finally:
        aggregate._select_kernel = orig


@pytest.mark.parametrize("twin", ["depart_cycle", "faulty_depart"])
@pytest.mark.parametrize("wave", list(WAVES))
def test_bucketed_wave_matches_the_full_buffer_wave(bucket_sim, wave, twin):
    """A gathered wave commits what the full-buffer program commits (to
    1e-6 relative), leaves every other row bit-identical and puts no NaN
    in any row; the full-fleet wave is the full-buffer program itself."""
    sim, g, gids = bucket_sim
    edges, bucket = WAVES[wave]
    mask = _wave_mask(gids, edges)
    ue_ok = _shed(gids, mask) if twin == "faulty_depart" else None
    before = sim.flat_state()
    runs = dict(sim.wave_bucket_runs)
    sim.replay_departure(g, mask, ue_ok=ue_ok)
    got = sim.flat_state()
    assert sim.wave_bucket_runs[bucket] == runs.get(bucket, 0) + 1

    sim.set_flat_state(before)
    if ue_ok is None:
        flat = sim._depart_cycle(sim._flat, g, sim._hot_batches,
                                 jnp.asarray(mask))
    else:
        w_edge, _ = sim._fault_round_weights(ue_ok)
        flat = sim._faulty_depart(sim._flat, g, sim._hot_batches,
                                  jnp.asarray(mask), w_edge)
    want = np.asarray(flat)
    sim.set_flat_state(before)

    assert np.all(np.isfinite(got))
    np.testing.assert_array_equal(got[~mask], before[~mask])
    if bucket == gids.size:
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got[mask], want[mask], rtol=1e-6,
                                   atol=1e-6 * np.abs(want).max())


def test_wave_ladder_and_counters_count_what_ran():
    """The ladder comes from the largest cohort; a twin's first wave
    compiles all of it; the counters add the bucket rows each wave
    trained, the rows it kept and one run per bucket."""
    sim, g, gids = _bucket_sim()
    assert sim._wave_ladder == LADDER
    for edges, _ in WAVES.values():
        sim.replay_departure(g, _wave_mask(gids, edges))
        assert set(sim._wave_exec[sim._depart_cycle]) == set(LADDER)
    buckets = [b for _, b in WAVES.values()]
    assert sim.wave_rows_trained == sum(buckets)
    assert sim.wave_rows_kept == sum(
        int(_wave_mask(gids, e).sum()) for e, _ in WAVES.values())
    assert sim.wave_bucket_runs == {b: buckets.count(b) for b in LADDER}
    # a mask that splits a cohort trains the whole buffer
    part = _wave_mask(gids, (0,))
    part[np.flatnonzero(part)[0]] = False
    sim.replay_departure(g, part)
    assert sim.wave_bucket_runs[40] == buckets.count(40) + 1


def test_lenet_paper_ladder():
    """The paper's 5 edges x 20 UEs: W=20 gives buckets 24, 40 and 80
    rows below the fleet's 100; MLR's 10 x 100 gives 104, 200, 400."""
    from repro.fl.sim import _wave_ladder
    assert _wave_ladder(np.repeat(np.arange(5), 20)) == (24, 40, 80, 100)
    assert _wave_ladder(np.repeat(np.arange(10), 100)) == (104, 200, 400,
                                                           1000)


def test_mesh_wave_keeps_the_full_buffer_program():
    """Under a mesh every wave, one edge included, is the jitted
    full-buffer twin with the (N_hot,) mask: the one bucket is N_hot."""
    from jax.sharding import Mesh
    from repro.launch.mesh import DATA_AXIS, MODEL_AXIS
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                (DATA_AXIS, MODEL_AXIS))
    sim, g, gids = _bucket_sim(mesh=mesh)
    n = sim._flat.shape[0]
    assert sim._wave_ladder == (n,)
    before = sim.flat_state()
    mask = _wave_mask(gids, (4,))
    sim.replay_departure(g, sim._participation_hot(mask[None])[0])
    assert sim._wave_exec[sim._depart_cycle] == {n: sim._depart_cycle}
    assert sim.wave_bucket_runs == {n: 1} and sim.wave_rows_trained == n
    got = sim.flat_state()
    assert np.all(np.isfinite(got))
    assert not np.array_equal(got, before)
