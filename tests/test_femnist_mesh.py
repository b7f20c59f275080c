"""The LEAF FEMNIST cell's sharded round on a 4x1 mesh of CPU devices.

A FEMNIST-shaped fleet (LeNet-5 at its published widths, 62 classes,
28x28x1 images) is built through the benchmark's mesh driver
(``bench/drivers/sync_mesh.py``) at a size the CPU runs in seconds: 24
UEs under 4 edges, k = 8 samples, a = 1, b = 2, two cloud rounds, the UE
rows sharded over four forced host devices ('data' 4, 'model' 1).  Its
rounds are compared with the plain reference
(``bench/yardstick/reference.sync_rounds``) in float32 at ``highest``
precision, by the cell's own readings (``bench.drivers.sync.readings``),
and the same readings of the reference computed in bfloat16 must fail,
as must the program with the exchange between devices left out
(``bench.faults_mesh.exchange_dropped``) or with every edge mean over half
its UEs (``half_fleet``).  The reference the cell runs, spread over the
four devices (``bench/yardstick/mesh_reference.py``), must read as the
one-device reference does.

The pytest process keeps one CPU device, so the fleet runs in a
subprocess that forces four (the pattern of ``test_fl_shard.py``); the
fixture runs it once and the tests read its report.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: Loss gap (relative, worst round of test and train loss).  On the CPU
#: the program's f32 convolutions and products are exact f32 like the
#: reference's at ``highest``; the two differ only in the order of their
#: sums (a grouped convolution over the vmapped rows against one per UE;
#: the segment means of eq. 6 against a mixing matrix), about 1e-7 each,
#: which two rounds of two steps carry to a few 1e-7 of the loss.
LOSS_TOL = 1e-5
#: Leaf change-norm gaps after the first and the last round (worst
#: moving leaf against the reference's norm).  The same sums, and a
#: discontinuity of the step: a max-pooling window whose two largest
#: inputs lie within rounding of each other (this fleet has windows 5e-7
#: apart) sends its gradient to whichever the rounding makes larger.  At
#: this seed conv1's change after round 2 reads 1.3e-4 while every other
#: leaf and round reads about 1e-7; the one-device program given the same
#: state differs from this one by as much, so it is rounding meeting that
#: discontinuity, not the sharding.  1e-3 leaves room for a few such
#: windows, and the bfloat16 control reads above 0.1.
NORM_TOL = 1e-3
#: Distance of an edge's row from the cloud model after a round, relative
#: to the cloud model's change (``sync_mesh.replica_gap``).  Every row
#: holds the same broadcast mean, so it reads only the rounding of the
#: mean recomputed over the rows (1e-7 of the parameters), magnified by
#: the parameters' size over a round's change: a few 1e-6.
REPLICA_TOL = 1e-4

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import json, re, sys, time
    root = sys.argv[1]
    sys.path[:0] = [root, os.path.join(root, "src")]
    import numpy as np
    from bench import harness
    from bench.drivers import sync, sync_mesh
    from bench.models import lenet5
    from bench.yardstick import reference

    path = os.path.join(root, "bench/configs/lenet5-femnist-leaf.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg.update(num_ues=24, num_edges=4, a=1, b=2, samples_per_ue=8,
               test_images=64, ref_block=6)
    traffic = {"driver": "sync_mesh", "rounds_per_call": 1,
               "check_calls": 2}
    cell = harness.Cell(name="femnist-cpu", cfg=cfg, traffic=traffic,
                        model=lenet5, seed=2**31 + 1617, chips=4)
    fed, sim = sync_mesh.build(cell)
    rows = sim.shard_rows()
    hlo = sim._cloud_round.lower(sim._flat, sim._hot_batches).compile(
        ).as_text()
    convs = []
    for line in hlo.splitlines():
        m = re.search(r"= f32\\[([0-9,]*)\\]\\S* convolution\\(", line)
        if m:
            convs.append(int(np.prod([int(d) for d in m.group(1).split(",")])))
    collectives = re.findall(
        r"= \\S+ (all-reduce|all-gather|reduce-scatter|collective-permute"
        r"|all-to-all)(?:-start)?\\(", hlo)
    session = harness.Session(time.perf_counter(), 1.0, None)
    start = np.asarray(sim.cloud_vector(), np.float64)
    calls = sync.first_calls(cell, sim, fed.test, session)
    replicas = sync_mesh.replica_gap(sim, start)
    program, ref = sync.readings(cell, fed, calls)
    program["replica_gap"] = replicas
    low = reference.sync_rounds(
        lenet5.reference_loss, fed.init_host(), fed.images, fed.labels,
        fed.sizes, fed.group_ids, a=cfg["a"], b=cfg["b"], lr=cfg["lr"],
        rounds=len(ref), dtype="bfloat16", block=cfg["ref_block"],
        test=fed.test)
    control, _ = sync.readings(cell, fed, sync.as_calls(low, 1),
                               against=ref)
    from bench import faults_mesh
    faults = {}
    for name in ("exchange_dropped", "half_fleet"):
        with faults_mesh.FAULTS[name]():
            bad_sim = sync_mesh.simulator(cell, fed)
            bad = sync.first_calls(cell, bad_sim, fed.test, session)
            faults[name], _ = sync.readings(cell, fed, bad, against=ref)
            faults[name]["replica_gap"] = sync_mesh.replica_gap(bad_sim,
                                                                start)
    spread = sync_mesh.reference_rounds(cell, fed, len(ref))
    mesh_ref, _ = sync.readings(cell, fed, sync.as_calls(spread, 1),
                                against=ref)
    print("REPORT " + json.dumps({
        "num_ues": cfg["num_ues"], "rows": rows, "convs": convs,
        "collectives": collectives, "program": program,
        "control": control, "faults": faults, "mesh_ref": mesh_ref,
        # conv1's output is the largest per-row tensor of the LeNet step
        "row_conv_elems": cfg["samples_per_ue"] * 24 * 24 * 6}))
""")


@pytest.fixture(scope="module")
def report():
    r = subprocess.run([sys.executable, "-c", SCRIPT, REPO],
                       capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stderr[-3000:]
    line = next(ln for ln in r.stdout.splitlines()
                if ln.startswith("REPORT "))
    return json.loads(line[len("REPORT "):])


def test_layout_counter_holds_every_row_once(report):
    rows = report["rows"]
    assert len(rows) == 4
    assert sum(r["rows"] for r in rows.values()) == report["num_ues"]
    held = {r["rows"] + r["pad_rows"] for r in rows.values()}
    assert len(held) == 1                     # every chip the same rows
    assert all(r["flat_bytes"] > 0 and r["batch_bytes"] > 0
               for r in rows.values())


def test_no_convolution_runs_over_another_devices_rows(report):
    per_device = next(iter(report["rows"].values()))
    held = per_device["rows"] + per_device["pad_rows"]
    assert report["convs"], "no convolution found in the compiled round"
    assert max(report["convs"]) <= held * report["row_conv_elems"]


def test_the_cloud_psum_is_the_only_collective(report):
    assert report["collectives"] == ["all-reduce"]


def test_mesh_round_matches_the_float32_reference(report):
    p = report["program"]
    assert p["loss_gap"] <= LOSS_TOL, p
    assert p["update_gap"] <= NORM_TOL, p
    assert p["change_gap"] <= NORM_TOL, p


def test_every_row_holds_the_cloud_model(report):
    assert report["program"]["replica_gap"] <= REPLICA_TOL, report["program"]


def test_bfloat16_control_fails_a_tolerance(report):
    c = report["control"]
    assert (c["loss_gap"] > LOSS_TOL or c["update_gap"] > NORM_TOL
            or c["change_gap"] > NORM_TOL), c


@pytest.mark.parametrize("fault", ["exchange_dropped", "half_fleet"])
def test_planted_fault_fails_a_tolerance(report, fault):
    f = report["faults"][fault]
    assert (f["loss_gap"] > LOSS_TOL or f["update_gap"] > NORM_TOL
            or f["change_gap"] > NORM_TOL
            or f["replica_gap"] > REPLICA_TOL), f


def test_reference_over_four_devices_reads_as_the_one_device_one(report):
    """Only the order of the sums differs: both are exact-f32 on the
    CPU, so they agree to rounding, within the program's tolerances."""
    m = report["mesh_ref"]
    assert m["loss_gap"] <= LOSS_TOL, m
    assert m["update_gap"] <= NORM_TOL and m["change_gap"] <= NORM_TOL, m
