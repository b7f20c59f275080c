"""Multinomial logistic regression clients (FedProx's convex MNIST
model, 784 -> 10 with an l2 term: Assumption 1's strongly convex case).

``init_params`` and ``reference_loss`` are the benchmark's own; only
``program_loss`` imports the program (``repro.models.lenet.logreg_loss``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _dim(cfg) -> int:
    return cfg["image_size"] * cfg["image_size"] * cfg["in_channels"]


def num_params(cfg) -> int:
    return (_dim(cfg) + 1) * cfg["num_classes"]


def fwd_macs_per_sample(cfg) -> int:
    return _dim(cfg) * cfg["num_classes"]


def train_flops_per_sample(cfg) -> float:
    """Forward product and weight gradient, 2 FLOPs per MAC each; the
    input needs no gradient, and the bias, softmax and l2 terms are
    elementwise and not counted."""
    return 4.0 * fwd_macs_per_sample(cfg)


def init_params(key, cfg):
    """N(0, init_std^2) weights, zero bias, float32."""
    d, k = _dim(cfg), cfg["num_classes"]

    def make(key):
        return {"w": jax.random.normal(key, (d, k)) * cfg["init_std"],
                "b": jnp.zeros((k,))}
    return jax.jit(make)(key)


def program_loss(cfg):
    from repro.models import lenet
    l2 = float(cfg["l2"])

    def loss(p, b):
        return lenet.logreg_loss(p, b, l2=l2)
    return loss


def make_reference_loss(cfg):
    l2 = float(cfg["l2"])

    def reference_loss(params, batch):
        """Mean cross-entropy plus (l2/2)||params||^2, in the dtype of
        ``params``."""
        x = batch["images"].reshape(batch["images"].shape[0], -1)
        ll = jax.nn.log_softmax(x @ params["w"] + params["b"])
        ce = -jnp.mean(jnp.take_along_axis(ll, batch["labels"][:, None], 1))
        return ce + 0.5 * l2 * (jnp.sum(params["w"] ** 2)
                                + jnp.sum(params["b"] ** 2))
    return reference_loss
