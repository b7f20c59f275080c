"""Generators, counters and comparisons of the yardstick."""
import json
import os

import numpy as np
import pytest

from bench.models import lenet5, mlr
from bench.yardstick import compare, counters, data, peaks
from bench.tests.tiny import REPO


def config(name):
    with open(os.path.join(REPO, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


def test_paper_sizes_are_the_programs_draw():
    from repro.core.problem import HFLProblem
    cfg = config("lenet5-mnist-paper")
    s = cfg["sizes"]
    got = data.paper_sizes(cfg["deployment_seed"], cfg["num_ues"], s["area"],
                           s["cycles_lo"], s["cycles_hi"], s["low"],
                           s["high"])
    prob = HFLProblem(num_edges=cfg["num_edges"], num_ues=cfg["num_ues"],
                      seed=cfg["deployment_seed"])
    np.testing.assert_array_equal(got, prob.samples.astype(np.int64))
    assert got.sum() == 60658 and np.median(got) == cfg["samples_per_ue"]


def test_fedprox_sizes_match_the_published_shape():
    cfg = config("mlr-mnist-fedprox")
    d = data.fedprox_sizes(cfg["deployment_seed"], cfg["num_ues"], 69.0,
                           106.0)
    assert d.shape == (1000,) and d.min() >= 1
    assert np.median(d) == cfg["samples_per_ue"]
    assert 55 < d.mean() < 80 and 70 < d.std() < 140
    np.testing.assert_array_equal(d, data.fedprox_sizes(0, 1000, 69.0, 106.0))


@pytest.mark.parametrize("labels_per_ue", [2, 4])
def test_federation_data_is_deterministic_in_the_seed(labels_per_ue):
    sizes = np.array([3, 9, 5, 12])
    a = data.federation_data(2**31 + 7, sizes, 6, labels_per_ue, 4, 8, 1, 10)
    b = data.federation_data(2**31 + 7, sizes, 6, labels_per_ue, 4, 8, 1, 10)
    c = data.federation_data(8, sizes, 6, labels_per_ue, 4, 8, 1, 10)
    for x, y in zip(a[:2], b[:2]):
        np.testing.assert_array_equal(x, y)
    np.testing.assert_array_equal(a[2]["images"], b[2]["images"])
    assert not np.array_equal(a[0], c[0])
    assert a[0].shape == (4, 6, 8, 8, 1) and a[1].shape == (4, 6)
    per_ue = [len(np.unique(row)) for row in a[1]]
    assert max(per_ue) <= labels_per_ue


def test_lenet_counts_by_hand():
    cfg = config("lenet5-mnist-paper")
    assert lenet5.num_params(cfg) == 44426
    assert lenet5.fwd_macs_per_sample(cfg) == 281640
    assert lenet5.layer_macs(cfg) == [86400, 153600, 30720, 10080, 840]
    assert lenet5.train_flops_per_sample(cfg) == 2 * (3 * 281640 - 86400)
    flops = counters.sync_round_train_flops(cfg, lenet5)
    assert flops == 100 * 649 * 17 * 8 * 1517040.0


def test_lenet_init_matches_the_program_layout():
    import jax
    from repro.configs.lenet_mnist import LeNetConfig
    from repro.models import lenet
    cfg = config("lenet5-mnist-paper")
    mine = lenet5.init_params(jax.random.PRNGKey(0), cfg)
    theirs = lenet.lenet_init(jax.random.PRNGKey(0), LeNetConfig())
    assert (jax.tree.structure(mine) == jax.tree.structure(theirs))
    assert ([x.shape for x in jax.tree.leaves(mine)]
            == [x.shape for x in jax.tree.leaves(theirs)])


def test_mlr_counts_by_hand():
    cfg = config("mlr-mnist-fedprox")
    assert mlr.num_params(cfg) == 7850
    assert mlr.fwd_macs_per_sample(cfg) == 7840
    assert mlr.train_flops_per_sample(cfg) == 4 * 7840
    flops = counters.sync_round_train_flops(cfg, mlr)
    assert flops == 1000 * 35 * 21 * 8 * 31360.0


def test_least_aggregation_bytes():
    cfg = config("lenet5-mnist-paper")
    f = 44426
    want = 4.0 * (8 * (100 * f + 5 * f) + (100 * f + f))
    assert counters.sync_round_agg_min_bytes(cfg, lenet5) == want
    assert 166e6 < want < 168e6


def test_references_match_the_program_losses():
    import jax
    import jax.numpy as jnp
    cfg = config("lenet5-mnist-paper")
    imgs, labels, _ = data.federation_data(3, np.array([5]), 5, 10, 10, 28,
                                           1, 1)
    batch = {"images": jnp.asarray(imgs[0]), "labels": jnp.asarray(labels[0])}
    p = lenet5.init_params(jax.random.PRNGKey(3), cfg)
    want = float(lenet5.program_loss(cfg)(p, batch)[0])
    assert abs(float(lenet5.reference_loss(p, batch)) - want) < 1e-5
    cfg = config("mlr-mnist-fedprox")
    p = mlr.init_params(jax.random.PRNGKey(3), cfg)
    want = float(mlr.program_loss(cfg)(p, batch)[0])
    got = float(mlr.make_reference_loss(cfg)(p, batch))
    assert abs(got - want) < 1e-5


def test_norm_gap_takes_the_worst_moving_leaf():
    base = [np.zeros(3), np.zeros(2), np.zeros(4)]
    ref = [np.array([3.0, 0, 0]), np.array([1e-9, 0]), np.array([2.0, 0, 0,
                                                                   0])]
    prog = [np.array([3.3, 0, 0]), np.array([5e-9, 0]), np.array([2.0, 0, 0,
                                                                    0])]
    r = compare.leaf_norms(ref, base)
    keep = compare.moving_leaves(r)
    assert keep.tolist() == [True, False, True]
    assert abs(compare.norm_gap(compare.leaf_norms(prog, base), r, keep)
               - 0.1) < 1e-12


def test_unknown_device_has_no_peaks():
    assert peaks.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
