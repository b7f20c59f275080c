"""A throwaway checkout root with the benchmark's code and tiny cells,
for CPU tests of the harness (the real cells need the chip)."""
from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

LENET = {
    "name": "lenet-tiny", "source": "test", "model": "lenet5",
    "image_size": 12, "in_channels": 1, "num_classes": 4,
    "conv_channels": [2, 3], "kernel_size": 3, "fc_dims": [8, 6],
    "num_ues": 6, "num_edges": 2, "deployment_seed": 0,
    "sizes": {"kind": "paper_uniform", "low": 5, "high": 12, "area": 500.0,
              "cycles_lo": 1e4, "cycles_hi": 1e5},
    "problem": {}, "labels_per_ue": 4, "a": 2, "b": 2,
    "samples_per_ue": 8, "lr": 0.05, "test_images": 32,
    "param_dtype": "float32", "matmul_precision": "default",
    "ref_block": 3, "reduced": [], "assumed": []}

MLR = {
    "name": "mlr-tiny", "source": "test", "model": "mlr",
    "image_size": 6, "in_channels": 1, "num_classes": 4, "l2": 1e-3,
    "init_std": 0.01, "num_ues": 12, "num_edges": 3, "deployment_seed": 0,
    "sizes": {"kind": "fedprox_lognormal", "mean": 8.0, "std": 6.0},
    "problem": {"model_bits": 4000.0, "edge_model_bits": 4000.0},
    "labels_per_ue": 2, "a": 21, "b": 8, "samples_per_ue": 35, "lr": 0.03,
    "test_images": 32, "param_dtype": "float32",
    "matmul_precision": "default", "ref_block": 12, "reduced": [],
    "assumed": []}

SYNC = {"driver": "sync", "rounds_per_call": 2, "check_calls": 2,
        "trace_seconds": 1}

SERVICE = {
    "driver": "service",
    "period": [{"scenario": "iid_campus", "load": 1.0, "duration": 60.0},
               {"scenario": "urban_stragglers", "load": 4.0,
                "duration": 20.0}],
    "periods": 20, "max_staleness": 4, "staleness_decay": 0.9,
    "delay_seed": 0,
    "ckpt_every": 10, "keep_last_k": 2, "chunk_updates": 10,
    "warmup_chunks": 1, "check_waves": 4, "trace_seconds": 1}

#: Each tiny cell: its configuration, its mix, and the real cell whose
#: metrics it reports and whose correctness limits it is held to.
CELLS = {
    "lenet-tiny.sync": ("lenet-tiny", "tiny-sync",
                        "lenet5-mnist-paper.sync"),
    "mlr-tiny.sync": ("mlr-tiny", "tiny-sync", "mlr-mnist-fedprox.sync"),
    "mlr-tiny.service": ("mlr-tiny", "tiny-service",
                         "mlr-mnist-fedprox.service"),
}


def make_root(tmp: str) -> str:
    """Copy ``bench/`` and ``BENCHMARK.json`` under ``tmp``, link ``src``,
    and add the tiny configurations, mixes, cells and limits as new
    files and entries."""
    root = os.path.join(tmp, "checkout")
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for cfg in (LENET, MLR):
        path = f"bench/configs/{cfg['name']}.json"
        write(root, path, cfg)
        bench["configs"].append({"name": cfg["name"], "source": "test",
                                 "file": path, "reduced": [], "why": "test"})
    write(root, "bench/traffic/tiny-sync.json", SYNC)
    write(root, "bench/traffic/tiny-service.json", SERVICE)
    for cell, (cfg, traffic, twin) in CELLS.items():
        bench["workloads"].append({"name": cell, "config": cfg,
                                   "traffic": traffic, "chips": 1,
                                   "why": "test"})
        shutil.copy(os.path.join(root, "bench", "limits", f"{twin}.json"),
                    os.path.join(root, "bench", "limits", f"{cell}.json"))
        for m in bench["end_to_end"] + bench["per_layer"]:
            if twin in m.get("workloads", []):
                m["workloads"].append(cell)
    write(root, "BENCHMARK.json", bench)
    return root


def write(root: str, rel: str, obj) -> None:
    path = os.path.join(root, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)
