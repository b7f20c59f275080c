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
