"""The aggregation kernels compile for a TPU v5e, at real widths.

Nothing here runs: each test lowers and compiles for devices of a
described ``v5e:2x2`` topology, which the TPU compiler accepts without a
chip attached, and checks that the Pallas kernel (``tpu_custom_call``)
is in the compiled program.  Widths: the paper's §V-A deployment (N=100
UEs, M=5 edges, the 44,426-parameter LeNet) and a fleet past the
unblocked client limit (N=1024), each at the block width
``pick_agg_blk_f`` chooses.  The topology is described inside a fixture,
so only the worker that runs this file loads the TPU library.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

F_LENET = 44_426            # LeNet (configs/lenet_mnist.CONFIG) parameters
WIDTHS = [(100, 5), (1024, 16)]       # (N, M)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described device can be written to the persistent
    # cache but never read back without the chip: keep the cache off.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:           # noqa: BLE001 - any failure to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_case(name, n, m, sharding):
    from repro.kernels import hier_aggregate as ha
    from repro.kernels.ops import pick_agg_blk_f

    blk = pick_agg_blk_f(n, m if name.startswith("segment") else 1, F_LENET)
    f = -(-F_LENET // blk) * blk

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)

    cases = {
        "segment_aggregate": (
            lambda x, w, oh, gw: ha.hier_segment_aggregate_2d(
                x, w, oh, gw, blk_f=blk),
            (sds(n, f), sds(n), sds(m, n), sds(m))),
        "segment_sum": (
            lambda x, w, oh: ha.hier_segment_sum_2d(x, w, oh, blk_f=blk),
            (sds(n, f), sds(n), sds(m, n))),
        "bcast_aggregate": (
            lambda x, w: ha.hier_bcast_aggregate_2d(x, w, blk_f=blk),
            (sds(n, f), sds(n))),
        "aggregate": (
            lambda x, w: ha.hier_aggregate_2d(x, w, blk_f=blk),
            (sds(n, f), sds(n))),
    }
    return cases[name]


@pytest.mark.parametrize("n,m", WIDTHS, ids=[f"N{n}M{m}" for n, m in WIDTHS])
@pytest.mark.parametrize("name", ["segment_aggregate", "segment_sum",
                                  "bcast_aggregate", "aggregate"])
def test_aggregation_kernel_compiles_for_v5e(one_chip, name, n, m):
    fn, args = _kernel_case(name, n, m, one_chip)
    assert "tpu_custom_call" in _compiled_text(fn, *args)


@pytest.fixture(scope="module")
def mesh_case(topo):
    """The paper's federation on a 2x2 ('data', 'model') mesh of described
    devices, in the padded ``ShardedFlatLayout`` form the simulator uses."""
    from repro.core import schedule
    from repro.core.problem import HFLProblem
    from repro.fl.flatten import FlatLayout, ShardedFlatLayout
    from repro.launch.mesh import DATA_AXIS, MODEL_AXIS

    sched = schedule.plan(HFLProblem(num_edges=5, num_ues=100, seed=0))
    gids = np.asarray(sched.assoc.argmax(1))
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), (DATA_AXIS, MODEL_AXIS))
    layout = FlatLayout.of({"w": jnp.zeros((gids.size, F_LENET))})
    sl = ShardedFlatLayout.build(layout, mesh, num_rows=gids.size,
                                 group_ids=gids)
    buf = jax.ShapeDtypeStruct((sl.n_padded, sl.f_padded), jnp.float32,
                               sharding=NamedSharding(mesh, sl.spec))
    rows = NamedSharding(mesh, sl.row_spec)
    w = jax.ShapeDtypeStruct((sl.n_padded,), jnp.float32, sharding=rows)
    g = jax.ShapeDtypeStruct((sl.n_padded,), jnp.int32, sharding=rows)
    return mesh, sched.num_edges, buf, w, g


@pytest.fixture
def native_kernels(monkeypatch):
    """Off the chip the kernel wrappers choose interpret mode; compile the
    native kernels instead, as the chip would."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_sharded_edge_aggregate_compiles_for_v5e(mesh_case, native_kernels):
    from repro.fl import aggregate

    mesh, num_edges, buf, w, g = mesh_case
    text = _compiled_text(
        lambda b, ww, gg: aggregate.flat_edge_aggregate(
            b, ww, gg, num_edges, use_kernel=True, mesh=mesh), buf, w, g)
    assert "tpu_custom_call" in text
    # eq. 6 is collective-free: edges never straddle a data shard
    assert "all-reduce" not in text


def test_sharded_cloud_aggregate_compiles_for_v5e(mesh_case, native_kernels):
    from repro.fl import aggregate

    mesh, _, buf, w, _ = mesh_case
    text = _compiled_text(
        lambda b, ww: aggregate.flat_cloud_aggregate(
            b, ww, use_kernel=True, mesh=mesh), buf, w)
    assert "tpu_custom_call" in text
    # eq. 10 meets across the data shards in one psum
    assert "all-reduce" in text


# The MLR service cell's departure waves: 1,000 UEs in 10 cohorts of 100,
# the 784x10 logistic regression (7,850 parameters), waves gathered into
# the row buckets of its ladder below the whole buffer.
MLR_BUCKETS = (104, 200, 400)


@pytest.fixture(scope="module")
def mlr_wave_sim():
    from repro.core.schedule import HFLSchedule
    from repro.fl.sim import HFLSimulator
    from repro.models import lenet

    n, m = 1000, 10
    gids = np.repeat(np.arange(m), n // m)
    sched = HFLSchedule(a=2, b=2, rounds=1, assoc=np.eye(m)[gids],
                        total_delay=0.0, cloud_round_time=1.0,
                        edge_round_time=np.ones(m))
    rng = np.random.default_rng(0)
    ue_data = [{"images": rng.normal(size=(2, 784)).astype(np.float32),
                "labels": rng.integers(0, 10, 2).astype(np.int32)}
               for _ in range(n)]
    sim = HFLSimulator(sched, lambda p, b: lenet.logreg_loss(p, b, l2=1e-3),
                       lenet.logreg_init(jax.random.PRNGKey(0), 784, 10),
                       ue_data, mode="async", max_staleness=4)
    assert sim._wave_ladder == MLR_BUCKETS + (n,)
    sim._weighted_ops()
    return sim


@pytest.mark.parametrize("bucket", MLR_BUCKETS)
@pytest.mark.parametrize("twin", ["depart_cycle", "faulty_depart"])
def test_gathered_wave_compiles_for_v5e(one_chip, mlr_wave_sim,
                                        native_kernels, monkeypatch, twin,
                                        bucket):
    """Each bucket of the wave ladder compiles for the chip with the eq. 6
    kernel at the block width ``pick_agg_blk_f`` gives its row count, under
    the program name the benchmark reads."""
    from repro.fl import aggregate

    monkeypatch.setattr(aggregate, "_select_kernel", lambda use_kernel: True)
    sim = mlr_wave_sim
    n, f = sim._flat.shape

    def sds(x, dtype=None):
        return jax.ShapeDtypeStruct(np.shape(x), dtype or x.dtype,
                                    sharding=one_chip)
    args = [sds(sim._flat), sds(sim._flat[0]),
            jax.tree.map(sds, sim._hot_batches),
            jax.ShapeDtypeStruct((bucket,), jnp.int32, sharding=one_chip)]
    fn = sim._depart_cycle
    if twin == "faulty_depart":
        fn = sim._faulty_depart
        args.append(sds(sim._hot_weights))
    text = fn.lower(*args).compile().as_text()
    assert text.split(None, 2)[1].rstrip(",") == f"jit_{twin}"
    assert "tpu_custom_call" in text
