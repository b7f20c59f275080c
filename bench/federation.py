"""A configuration made concrete from a seed: the deployment, its plan
and the federated data, built through the program's public API
(``HFLProblem``, ``schedule.plan``) from inputs the benchmark draws."""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from bench.yardstick import data


def jax_seed(seed: int) -> int:
    """A 31-bit seed for ``jax.random`` and the service's delay draws,
    derived from any whole ``--seed``."""
    return int(np.random.default_rng(seed).integers(0, 2**31 - 1))


def fleet_sizes(cfg: dict) -> np.ndarray:
    """The deployment's ``D_n``, fixed by ``deployment_seed``."""
    s = cfg["sizes"]
    if s["kind"] == "paper_uniform":
        return data.paper_sizes(cfg["deployment_seed"], cfg["num_ues"],
                                s["area"], s["cycles_lo"], s["cycles_hi"],
                                s["low"], s["high"])
    if s["kind"] == "fedprox_lognormal":
        return data.fedprox_sizes(cfg["deployment_seed"], cfg["num_ues"],
                                  s["mean"], s["std"])
    raise ValueError(f"unknown sizes kind {s['kind']!r}")


@dataclasses.dataclass
class Federation:
    cfg: dict
    seed: int
    sizes: np.ndarray            # (N,) D_n, the aggregation weights
    group_ids: np.ndarray        # (N,) edge of each UE, from plan()
    schedule: object             # repro.core.schedule.HFLSchedule
    images: np.ndarray           # (N, k, H, W, C) float32
    labels: np.ndarray           # (N, k) int32
    test: dict
    init: dict                   # parameter pytree on the device, f32

    def ue_data(self):
        return [{"images": self.images[i], "labels": self.labels[i]}
                for i in range(self.images.shape[0])]

    def init_host(self):
        return jax.tree.map(np.asarray, self.init)


def build(cfg: dict, model, seed: int) -> Federation:
    from repro.core import schedule as schedule_lib
    from repro.core.problem import HFLProblem

    sizes = fleet_sizes(cfg)
    problem = HFLProblem(num_edges=cfg["num_edges"], num_ues=cfg["num_ues"],
                         seed=cfg["deployment_seed"],
                         **cfg.get("problem", {}))
    problem.samples = sizes.astype(float)
    sched = schedule_lib.plan(problem)
    # a, b are pinned so that a change to the planner cannot change a
    # round's work; the association is the plan's.
    sched.a, sched.b = int(cfg["a"]), int(cfg["b"])
    images, labels, test = data.federation_data(
        seed, sizes, cfg["samples_per_ue"], cfg["labels_per_ue"],
        cfg["num_classes"], cfg["image_size"], cfg["in_channels"],
        cfg["test_images"])
    init = model.init_params(jax.random.PRNGKey(jax_seed(seed)), cfg)
    return Federation(cfg=cfg, seed=seed, sizes=sizes,
                      group_ids=np.asarray(sched.assoc).argmax(1),
                      schedule=sched, images=images, labels=labels,
                      test=test, init=init)
