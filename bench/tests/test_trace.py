"""The trace reduction on small traces recorded on a TPU v5e: traced runs
of the tiny cells, kept with the result line each run printed."""
import json
import os

import pytest

from bench.yardstick import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELLS = ["mlr-tiny.sync", "mlr-tiny.service"]


def recorded(cell):
    d = os.path.join(DATA, cell)
    with open(os.path.join(d, "result.json")) as f:
        return os.path.join(d, "trace.xplane.pb.gz"), json.load(f)


def sweep_busy(path, lo, hi):
    """Busy time by a sweep over start and end points: the number of
    instructions running, counted up and down."""
    import gzip

    from jax.profiler import ProfileData
    with gzip.open(path, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    points = []
    for plane in pd.planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                for e in line.events:
                    s = max(e.start_ns, lo)
                    t = min(e.start_ns + e.duration_ns, hi)
                    if t > s:
                        points += [(s, 1), (t, -1)]
    busy, depth, last = 0.0, 0, None
    for x, d in sorted(points, key=lambda p: (p[0], -p[1])):
        if depth > 0:
            busy += x - last
        depth += d
        last = x
    return busy


@pytest.mark.parametrize("cell", CELLS)
def test_reduction_matches_the_run_and_a_sweep(cell):
    path, result = recorded(cell)
    t = trace.load(path)
    s = trace.summarize(t)
    lo, hi = trace.window(t)
    assert s.window_s == pytest.approx((hi - lo) * 1e-9, rel=1e-12)
    assert s.busy_s == pytest.approx(sweep_busy(path, lo, hi) * 1e-9,
                                     rel=1e-9)
    assert 0.0 < s.busy_s <= s.window_s
    assert s.busy_s == result["device"]["busy_s"]
    assert s.window_s == result["device"]["window_s"]
    assert s.device_ops == result["breakdown"]["device_ops"]
    assert s.idle_gaps == result["breakdown"]["idle_gaps"]
    idle = sum(v for _, v in s.idle_gaps)
    assert idle == pytest.approx(s.window_s - s.busy_s, rel=1e-9)


def test_sync_trace_names_the_round_and_its_kernels():
    path, result = recorded("mlr-tiny.sync")
    s = trace.summarize(trace.load(path))
    assert s.module_runs["jit_cloud_round"] > 0
    assert 0.0 < s.agg_kernel_s["jit_cloud_round"] < s.module_s[
        "jit_cloud_round"]
    for name in ("mfu.sync", "local_step_ms.sync", "agg_roofline.sync",
                 "idle_share.sync"):
        assert result["metrics"][name]["value"] > 0


def test_service_trace_names_the_waves():
    path, result = recorded("mlr-tiny.service")
    s = trace.summarize(trace.load(path))
    assert s.module_runs.get("jit_depart_cycle", 0) > 0
    assert any(name.startswith("bench.chunk")
               for name, _ in s.idle_gaps)
    assert result["metrics"]["wave_ms.service"]["value"] > 0


def test_hlo_names_parse():
    assert trace.parse_op(
        "%hier_segment_aggregate.7 = f32[1024,8064]{1,0:T(8,128)} "
        "custom-call(f32[1024,8064]{1,0} %x)") == (
        "hier_segment_aggregate.7", "f32[1024,8064]{1,0:T(8,128)}",
        "custom-call")
    assert trace.parse_op("%while.3 = (s32[], f32[8]{0}) while((s32[], "
                          "f32[8]{0}) %t)")[2] == "while"
    assert trace.module_name("jit_cloud_round(5295170185746817458)") == (
        "jit_cloud_round")
