"""Seeded inputs for the benchmark's federations.

Copies of the program's generators (``repro.data.synthetic``,
``repro.data.partition`` and the ``D_n`` draw of ``repro.core.problem``),
kept here so that no later change to the program can change what a cell
feeds it, plus the FedProx MNIST draw (Li et al., arXiv:1812.06127, §5:
1,000 devices, two digits each, power-law sizes of mean 69 and standard
deviation 106).
"""
from __future__ import annotations

import numpy as np

#: Seed of the fixed class means (``repro.data.synthetic``'s constant):
#: every UE and the test set share one set of class patterns.
CLASS_MEAN_SEED = 12345
NOISE = 0.8


def class_means(num_classes: int, size: int, channels: int) -> np.ndarray:
    rng = np.random.default_rng(CLASS_MEAN_SEED)
    return rng.normal(0.0, 1.0, (num_classes, size, size, channels))


def class_gaussian_images(rng: np.random.Generator, labels: np.ndarray,
                          means: np.ndarray) -> np.ndarray:
    """Images ~ N(mean[label], NOISE^2 I), float32 (B, H, W, C)."""
    noise = rng.normal(0.0, NOISE, (labels.shape[0],) + means.shape[1:])
    return (means[labels] + noise).astype(np.float32)


def paper_sizes(deployment_seed: int, num_ues: int, area: float,
                cyc_lo: float, cyc_hi: float, lo: int, hi: int) -> np.ndarray:
    """The paper's §V-A ``D_n ~ U{lo..hi}``, drawn in the order
    ``HFLProblem`` draws it (positions, CPU cycles, then sizes), so the
    same deployment seed gives the same fleet."""
    rng = np.random.default_rng(deployment_seed)
    rng.uniform(0, area, size=(num_ues, 2))
    rng.uniform(cyc_lo, cyc_hi, num_ues)
    return rng.integers(lo, hi + 1, num_ues).astype(np.int64)


def fedprox_sizes(deployment_seed: int, num_ues: int, mean: float,
                  std: float) -> np.ndarray:
    """Power-law device sizes with FedProx's published moments: a
    lognormal of that mean and standard deviation, at least one sample."""
    rng = np.random.default_rng(deployment_seed)
    sig2 = np.log1p((std / mean) ** 2)
    mu = np.log(mean) - sig2 / 2.0
    d = np.round(rng.lognormal(mu, np.sqrt(sig2), num_ues))
    return np.maximum(d, 1).astype(np.int64)


def federation_data(seed: int, sizes: np.ndarray, samples_per_ue: int,
                    labels_per_ue: int, num_classes: int, image_size: int,
                    channels: int, test_images: int):
    """Per-UE stacked data at the common size ``samples_per_ue`` and a
    test set, all from ``seed``.

    Each UE draws ``sizes[n]`` labels from its own ``labels_per_ue``
    classes (all classes when that equals ``num_classes``) and then, as
    ``HFLSimulator`` does, resamples them to ``samples_per_ue`` (with
    replacement only when it holds fewer).  Only the kept samples are
    turned into images.  Returns ``(images (N, k, H, W, C) float32,
    labels (N, k) int32, test dict)``.
    """
    rng = np.random.default_rng(seed)
    means = class_means(num_classes, image_size, channels)
    n, k = sizes.shape[0], int(samples_per_ue)
    labels = np.empty((n, k), np.int32)
    for i, d in enumerate(sizes):
        d = int(d)
        if labels_per_ue >= num_classes:
            own = rng.integers(0, num_classes, d)
        else:
            cls = rng.choice(num_classes, labels_per_ue, replace=False)
            own = cls[rng.integers(0, labels_per_ue, d)]
        keep = (np.arange(k) if d == k else
                rng.choice(d, size=k, replace=d < k))
        labels[i] = own[keep]
    images = class_gaussian_images(rng, labels.reshape(-1), means)
    images = images.reshape((n, k) + images.shape[1:])
    test_labels = rng.integers(0, num_classes, test_images).astype(np.int32)
    test = {"images": class_gaussian_images(rng, test_labels, means),
            "labels": test_labels}
    return images, labels, test
