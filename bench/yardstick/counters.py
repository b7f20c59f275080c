"""Work counted from shapes: what a cloud round must compute and move,
whatever implements it."""
from __future__ import annotations


def sync_round_train_flops(cfg: dict, model) -> float:
    """FLOPs of one cloud round's local steps: N UEs x k samples x a x b
    full-batch GD steps, each the model's required forward + backward
    FLOPs per sample (``model.train_flops_per_sample``)."""
    steps = cfg["num_ues"] * cfg["samples_per_ue"] * cfg["a"] * cfg["b"]
    return float(steps) * model.train_flops_per_sample(cfg)


def sync_round_agg_min_bytes(cfg: dict, model) -> float:
    """Least HBM bytes of one cloud round's aggregations in fp32.

    b edge aggregations (eq. 6) each read the N rows once and write the
    M distinct edge means once; the cloud aggregation (eq. 10) reads the
    N rows once and writes one mean.  A scatter-back of the means to all
    N rows is not counted: it is not needed to compute the round.
    """
    n, m, b = cfg["num_ues"], cfg["num_edges"], cfg["b"]
    f = model.num_params(cfg)
    return 4.0 * (b * (n * f + m * f) + (n * f + f))
