"""The harness: no chip, no result; cells, mixes and metrics found by
name; each driver end to end at a tiny size on the CPU."""
import json
import os
import subprocess
import sys
import time

import pytest

from bench import harness
from bench.tests import tiny


def run(root, cell, seconds=1.0):
    return harness.run_cell(root, cell, 2**31 + 99, seconds, False,
                            t_start=time.perf_counter(), require_tpu=False)


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "mlr-mnist-fedprox.sync", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tiny.REPO, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode == 2
    assert "TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_every_cell_names_existing_files():
    reg = harness.Registry(tiny.REPO)
    for w in reg.bench["workloads"]:
        cfg = reg.config(w["config"])
        reg.model(cfg["model"])
        reg.driver(reg.traffic(w["traffic"])["driver"])
        assert set(reg.limits(w["name"]))
        assert [m["name"] for m in reg.end_to_end(w["name"])][0] == "setup_s"
        for m in reg.per_layer(w["name"]):
            assert callable(reg.reader(m["name"]).read)


@pytest.mark.parametrize("cell", sorted(tiny.CELLS))
def test_cell_runs_and_is_correct(tmp_path, cell):
    root = tiny.make_root(str(tmp_path))
    res = run(root, cell)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    reg = harness.Registry(root)
    assert set(res["metrics"]) == {m["name"] for m in reg.end_to_end(cell)}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["attempted"] > 0 and res["failed"] == 0


def test_new_files_are_found_without_editing_any(tmp_path):
    """A configuration, a traffic mix and a per-layer metric added as new
    files and new entries run through the unchanged harness."""
    root = tiny.make_root(str(tmp_path))
    before = {p: open(p, "rb").read()
              for p in harness_files(os.path.join(root, "bench"))}
    cfg = dict(tiny.MLR, name="mlr-other", num_ues=9, num_edges=3)
    tiny.write(root, "bench/configs/mlr-other.json", cfg)
    tiny.write(root, "bench/traffic/other-sync.json",
               dict(tiny.SYNC, rounds_per_call=3, check_calls=1))
    tiny.write(root, "bench/limits/mlr-other.other.json",
               {"loss_gap": 1e-3, "update_gap": 1e-3, "change_gap": 1e-3})
    with open(os.path.join(root, "bench", "metrics", "rounds.other.py"),
              "w") as f:
        f.write("def read(ctx):\n    return float(ctx['rounds'])\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "mlr-other", "source": "test",
                             "file": "bench/configs/mlr-other.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "mlr-other.other",
                               "config": "mlr-other",
                               "traffic": "other-sync", "chips": 1,
                               "why": "test"})
    bench["end_to_end"][1]["workloads"].append("mlr-other.other")
    bench["per_layer"].append({"name": "rounds.other", "unit": "rounds",
                               "better": "higher", "source": "host_clock",
                               "layer": "test", "moves": "round_s",
                               "workloads": ["mlr-other.other"]})
    tiny.write(root, "BENCHMARK.json", bench)

    res = run(root, "mlr-other.other")
    assert res["correct"] and "round_s" in res["metrics"]
    reg = harness.Registry(root)
    assert [m["name"] for m in reg.per_layer("mlr-other.other")] == [
        "rounds.other"]
    assert reg.reader("rounds.other").read({"rounds": 3}) == 3.0
    for p, content in before.items():
        with open(p, "rb") as f:
            assert f.read() == content, p


def harness_files(bench_dir):
    for d in ("drivers", "models", "metrics", "yardstick"):
        for name in os.listdir(os.path.join(bench_dir, d)):
            if name.endswith(".py"):
                yield os.path.join(bench_dir, d, name)
    for name in ("harness.py", "run.py", "federation.py"):
        yield os.path.join(bench_dir, name)
