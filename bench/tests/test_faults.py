"""Each fault a cell can have, planted under the timed path at a tiny
size on the CPU, makes ``correct`` come out false; so does the control,
the reference computed in bfloat16 in the program's place.  The limits
are the real cells' (``tiny.CELLS``)."""
import time

import pytest

from bench import faults, harness
from bench.tests import tiny


def run(root, cell):
    return harness.run_cell(root, cell, 31, 1.0, False,
                            t_start=time.perf_counter(), require_tpu=False)


@pytest.mark.parametrize("fault", sorted(faults.FAULTS["sync"]))
@pytest.mark.parametrize("cell", ["lenet-tiny.sync", "mlr-tiny.sync"])
def test_sync_fault_is_not_correct(tmp_path, cell, fault):
    root = tiny.make_root(str(tmp_path))
    with faults.FAULTS["sync"][fault]():
        res = run(root, cell)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", sorted(faults.FAULTS["service"]))
def test_service_fault_is_not_correct(tmp_path, fault):
    root = tiny.make_root(str(tmp_path))
    with faults.FAULTS["service"][fault]():
        res = run(root, "mlr-tiny.service")
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell", sorted(tiny.CELLS))
def test_bfloat16_control_is_not_correct(tmp_path, cell):
    root = tiny.make_root(str(tmp_path))
    reg = harness.Registry(root)
    w = reg.cell(cell)
    cfg, traffic = reg.config(w["config"]), reg.traffic(w["traffic"])
    c = harness.Cell(cell, cfg, traffic, reg.model(cfg["model"]), 5, 1)
    got = reg.driver(traffic["driver"]).calibrate(
        c, harness.Session(time.perf_counter(), 1.0, None), True)
    limits = reg.limits(cell)
    assert harness.judge(got["program"], limits), got
    assert not harness.judge(got["control"], limits), got
