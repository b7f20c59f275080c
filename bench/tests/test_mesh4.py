"""The four-chip FEMNIST cell's readers and driver: per-chip figures are
read on the slowest (or worst) chip, never summed over chips; the driver
runs a tiny mesh cell end to end through the unchanged harness."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from bench import harness
from bench.drivers import sync_mesh
from bench.models import lenet5
from bench.tests import tiny
from bench.yardstick import counters, mesh_reference, trace

CELL = "lenet5-femnist-leaf.mesh4"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def reader(name):
    return harness.Registry(tiny.REPO).reader(name).read


@pytest.fixture
def ctx():
    """A hand-made traced run of two rounds on four chips: chip 1 trains
    longest, chip 2 spends most in collectives, chip 3 holds the most
    padding; the layout of the cell's 3,560 rows (LPT packing of its 20
    edges)."""
    cfg = harness.load_json(os.path.join(
        tiny.REPO, "bench/configs/lenet5-femnist-leaf.json"))
    rows = {0: (890, 0), 1: (889, 1), 2: (886, 4), 3: (885, 5)}
    return {
        "cfg": cfg, "model": lenet5, "chips": 4, "rounds": 2,
        "round_s": 0.85, "peaks": {"bf16_flops_per_s": 197e12},
        "chip_round_s": [1.0, 1.2, 0.9, 1.1],
        "chip_collective_s": [0.1, 0.05, 0.3, 0.1],
        "chip_agg_kernel_s": [0.01, 0.01, 0.01, 0.01],
        "chip_rows": {d: {"rows": r, "pad_rows": p, "flat_bytes": 1,
                          "batch_bytes": 1} for d, (r, p) in rows.items()},
    }


def test_step_is_the_slowest_chips_not_the_sum(ctx):
    got = reader("step_ms.mesh4")(ctx)
    assert got == pytest.approx(1e3 * (1.2 - 0.05 - 0.01) / 2)
    summed = 1e3 * (sum(ctx["chip_round_s"]) - sum(ctx["chip_collective_s"])
                    - sum(ctx["chip_agg_kernel_s"])) / 2
    assert got < summed


def test_collectives_are_the_worst_chips_not_the_sum(ctx):
    got = reader("collective_ms.mesh4")(ctx)
    assert got == pytest.approx(1e3 * 0.3 / 2)
    assert got < 1e3 * sum(ctx["chip_collective_s"]) / 2


def test_pad_share_is_the_chip_with_most_padding(ctx):
    got = reader("pad_share.mesh4")(ctx)
    assert got == pytest.approx(100 * 5 / 890)
    pooled = 100 * 10 / 3560
    assert got > pooled


def test_mfu_divides_by_every_chips_peak(ctx):
    got = reader("mfu.mesh4")(ctx)
    flops = counters.sync_round_train_flops(ctx["cfg"], lenet5)
    assert flops == pytest.approx(7.9767e12, rel=1e-4)
    assert got == pytest.approx(100 * flops / 0.85 / (4 * 197e12))


@pytest.mark.parametrize("name", ["step_ms.mesh4", "collective_ms.mesh4",
                                  "pad_share.mesh4", "mfu.mesh4"])
def test_readers_find_nothing_without_their_inputs(ctx, name):
    """An untraced window, a trace without the round program, or a
    program without the layout counter: the metric is left out."""
    bare = {k: ctx[k] for k in ("cfg", "model", "rounds", "peaks")}
    bare["round_s"] = ctx["round_s"]
    assert reader(name)(bare) is None
    if name in ("step_ms.mesh4", "collective_ms.mesh4"):
        silent = dict(ctx, chip_round_s=[0.0] * 4)
        assert reader(name)(silent) is None


@pytest.mark.parametrize("name", ["step_ms.mesh4", "collective_ms.mesh4"])
def test_a_round_without_collectives_is_left_out(ctx, name):
    """The mesh round always holds the cloud psum: a trace in which no
    chip shows a collective was misread, and reads as nothing, not 0."""
    missed = dict(ctx, chip_collective_s=[0.0] * 4)
    assert reader(name)(missed) is None


def test_collective_opcodes():
    def op(code):
        return trace.Op("x", "f32[4]", code, 0.0, 1.0)
    for code in ("all-reduce", "all-reduce-start", "all-reduce-done",
                 "all-gather-start", "reduce-scatter", "collective-permute",
                 "collective-permute-done", "all-to-all"):
        assert sync_mesh.is_collective(op(code)), code
    for code in ("fusion", "custom-call", "copy-start", "reduce"):
        assert not sync_mesh.is_collective(op(code)), code


@pytest.mark.parametrize("n,chips,block,sub,blocks", [
    (3550, 4, 355, 296, 3),      # the cell: 888 rows a chip, 2 padding
    (24, 4, 6, 6, 1),
    (30, 4, 4, 4, 2),
    (7, 1, 7, 7, 1),
])
def test_reference_rows_split_evenly_over_the_chips(n, chips, block, sub,
                                                    blocks):
    lay = mesh_reference.layout(n, chips, block)
    assert (lay.sub, lay.blocks) == (sub, blocks)
    assert lay.sub <= block
    assert n <= lay.rows < n + chips * lay.blocks


def test_per_chip_seconds_of_a_one_chip_trace_are_the_summary():
    """On one chip the per-chip figures equal the harness's sums."""
    path = os.path.join(DATA, "mlr-tiny.sync", "trace.xplane.pb.gz")
    chips = sync_mesh.chip_seconds(path)
    summary = trace.summarize(trace.load(path))
    assert chips["chip_round_s"] == [
        pytest.approx(summary.module_s["jit_cloud_round"])]
    assert chips["chip_agg_kernel_s"] == [
        pytest.approx(summary.agg_kernel_s["jit_cloud_round"])]
    assert chips["chip_collective_s"] == [0.0]


def test_the_cell_names_its_files_and_readers():
    reg = harness.Registry(tiny.REPO)
    w = reg.cell(CELL)
    assert w["chips"] == 4
    cfg = reg.config(w["config"])
    assert cfg["num_ues"] % cfg["ref_block"] == 0
    assert "a" in cfg["reduced"]
    assert reg.traffic(w["traffic"])["driver"] == "sync_mesh"
    assert set(reg.limits(CELL)) == {"loss_gap", "update_gap", "change_gap",
                                     "replica_gap"}
    assert [m["name"] for m in reg.per_layer(CELL)] == [
        "mfu.mesh4", "step_ms.mesh4", "collective_ms.mesh4",
        "pad_share.mesh4"]


RUN = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import json, sys, time
    repo, root = sys.argv[1], sys.argv[2]
    sys.path[:0] = [repo, os.path.join(repo, "src")]
    from bench import harness
    res = harness.run_cell(root, "femnist-tiny.mesh4", 2**31 + 1618, 1.0,
                           False, t_start=time.perf_counter(),
                           require_tpu=False)
    print("RESULT " + json.dumps(res))
""")


def test_tiny_mesh_cell_runs_and_is_correct(tmp_path):
    """A FEMNIST-shaped cell on four forced CPU devices through the
    harness as it stands: new files and entries only."""
    root = tiny.make_root(str(tmp_path))
    cfg = harness.load_json(os.path.join(
        tiny.REPO, "bench/configs/lenet5-femnist-leaf.json"))
    cfg.update(name="femnist-tiny", num_ues=24, num_edges=4, a=1, b=2,
               samples_per_ue=8, test_images=64, ref_block=6)
    tiny.write(root, "bench/configs/femnist-tiny.json", cfg)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "femnist-tiny", "source": "test",
                             "file": "bench/configs/femnist-tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "femnist-tiny.mesh4",
                               "config": "femnist-tiny",
                               "traffic": "sync1_mesh4", "chips": 4,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("femnist-tiny.mesh4")
    tiny.write(root, "BENCHMARK.json", bench)
    tiny.write(root, "bench/limits/femnist-tiny.mesh4.json",
               harness.load_json(os.path.join(
                   tiny.REPO, "bench/limits", f"{CELL}.json")))
    r = subprocess.run([sys.executable, "-c", RUN, tiny.REPO, root],
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    res = json.loads(next(ln for ln in r.stdout.splitlines()
                          if ln.startswith("RESULT "))[len("RESULT "):])
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"setup_s", "round_s"}
    assert res["device"]["count"] == 4
    assert res["attempted"] > 0 and res["failed"] == 0
