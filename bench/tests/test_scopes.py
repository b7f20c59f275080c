"""The reduction of the program's own spans and scopes, on a hand-built
trace: two rounds of a cloud-round program, evaluation after each, and a
service update with its control-plane spans."""
import pytest

from bench.yardstick import scopes
from bench.yardstick import trace as trace_lib

MS = 1e6


def op(instr, start_ms, dur_ms, opcode="fusion"):
    return trace_lib.Op(instr, "f32[8]", opcode, start_ms * MS, dur_ms * MS)


def span(name, start_ms, dur_ms):
    return (name, start_ms * MS, dur_ms * MS)


@pytest.fixture
def sync():
    """Rounds at [0, 10) and [20, 30) ms; each: a 6 ms local step, 2 ms
    of edge and 1 ms of cloud aggregation, 1 ms in no scope.  Evaluation
    spans [9, 20) and [29, 40): 1 ms of each overlaps the round still on
    the device, an eager 2 ms program runs inside each."""
    ops, modules = [], []
    for r0 in (0.0, 20.0):
        modules.append(("jit_cloud_round", r0 * MS, 10 * MS))
        ops += [op("while.1", r0, 8, "while"),
                op("fusion.1", r0, 6), op("hier_edge.2", r0 + 6, 2,
                                          "custom-call"),
                op("fusion.3", r0 + 8, 1), op("copy.4", r0 + 9, 1)]
        modules.append(("jit__lambda", (r0 + 12) * MS, 2 * MS))
        ops.append(op("reduce.1", r0 + 12, 2))
    dev = trace_lib.DeviceTrace("/device:TPU:0", modules, ops)
    hfl = [span("hfl.round", 0, 20), span("hfl.eval", 9, 11),
           span("hfl.round", 20, 20), span("hfl.eval", 29, 11)]
    bench = [span("bench.window", 0, 40), span("bench.call", 0, 40)]
    op_scopes = {"jit_cloud_round": {"fusion.1": "hfl.local_step",
                                     "hier_edge.2": "hfl.edge_agg",
                                     "fusion.3": "hfl.cloud_agg"}}
    return dev, hfl, bench, op_scopes


def test_scoped_time_splits_the_round_program(sync):
    dev, _, _, op_scopes = sync
    by = scopes.scope_seconds(scopes.instr_seconds(dev, 0, 40 * MS),
                              op_scopes, "jit_cloud_round")
    assert by == pytest.approx({"hfl.local_step": 12e-3,
                                "hfl.edge_agg": 4e-3,
                                "hfl.cloud_agg": 2e-3, None: 2e-3})
    s = trace_lib.summarize(trace_lib.Trace([dev], [
        span("bench.window", 0, 40)]))
    assert sum(by.values()) == pytest.approx(s.module_s["jit_cloud_round"])


def test_scoped_time_of_a_program_without_scopes_is_unscoped(sync):
    dev, _, _, _ = sync
    by = scopes.scope_seconds(scopes.instr_seconds(dev, 0, 40 * MS), {},
                              "jit_cloud_round")
    assert by == pytest.approx({None: 20e-3})
    assert scopes.scope_seconds({}, {}, "jit_cloud_round") == {}


def test_eval_time_leaves_out_the_round_on_the_device(sync):
    dev, hfl, _, _ = sync
    # each eval span is 11 ms, 1 ms of it under the round's run
    assert scopes.eval_seconds(hfl, dev, 0, 40 * MS) == pytest.approx(20e-3)
    # clipped to the window
    assert scopes.eval_seconds(hfl, dev, 0, 15 * MS) == pytest.approx(5e-3)
    assert scopes.eval_seconds([], dev, 0, 40 * MS) == 0.0


def test_gaps_are_named_by_both_chains(sync):
    dev, hfl, bench, _ = sync
    got = dict(scopes.idle_gaps(bench, hfl, dev, 0, 40 * MS))
    # idle: [10, 12), [14, 20), [30, 32), [34, 40) ms
    assert got == pytest.approx(
        {"bench.call>hfl.round>hfl.eval": 16e-3})
    plain = dict(trace_lib.idle_gaps(trace_lib.Trace([dev], bench), dev,
                                     0, 40 * MS))
    assert plain == pytest.approx({"bench.call": 16e-3})
    # a trace without program spans keeps today's names
    assert scopes.idle_gaps(bench, [], dev, 0, 40 * MS) == \
        trace_lib.idle_gaps(trace_lib.Trace([dev], bench), dev, 0, 40 * MS)


def test_control_time_counts_the_innermost_program_span():
    """One update: masks [2, 3) between the previous wave's op and the new
    wave (which runs [3, 10)), merge-row pull [10, 11), publish [11, 12),
    the update's tail to 12.5; a checkpoint [13, 16) outside the update;
    the harness's bench.update from 1 ms to the end."""
    dev = trace_lib.DeviceTrace(
        "/device:TPU:0", [("jit_depart_cycle", 0, 2 * MS),
                          ("jit_depart_cycle", 3 * MS, 7 * MS)],
        [op("fusion.37", 0, 2), op("fusion.37", 3, 7)])
    hfl = [span("hfl.update", 0, 12.5), span("hfl.engine_step", 0, 1),
           span("hfl.masks", 2, 1), span("hfl.wave", 3, 0.5),
           span("hfl.merge_row", 10, 1), span("hfl.publish", 11, 1),
           span("hfl.checkpoint", 13, 3), span("hfl.ckpt_state", 13, 1)]
    bench = [span("bench.chunk", 0, 20), span("bench.update", 1, 19)]
    by = scopes.idle_by_innermost(hfl, dev, 0, 20 * MS)
    assert by == pytest.approx({
        "hfl.masks": 1e-3, "hfl.merge_row": 1e-3, "hfl.publish": 1e-3,
        "hfl.update": 0.5e-3, "hfl.ckpt_state": 1e-3,
        "hfl.checkpoint": 2e-3, None: 4.5e-3})
    assert scopes.ctrl_seconds(hfl, dev, 0, 20 * MS) == pytest.approx(
        2.5e-3)
    # gaps are named by their midpoints, the bench chain first
    named = scopes.idle_gaps(bench, hfl, dev, 0, 20 * MS)
    assert dict(named) == pytest.approx({
        "bench.chunk>bench.update>hfl.update>hfl.masks": 1e-3,
        "bench.chunk>bench.update>hfl.checkpoint": 10e-3})
    assert [n for n, _ in trace_lib.idle_gaps(
        trace_lib.Trace([dev], bench), dev, 0, 20 * MS)] == [
        "bench.chunk>bench.update"]
