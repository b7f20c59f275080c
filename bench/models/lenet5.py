"""LeNet-5 clients (the paper's §V-A model).

``init_params`` and ``reference_loss`` are the benchmark's own: the
weights are made here from the seed and handed to the program, and the
reference is a plain ``jax.numpy`` LeNet written apart from
``repro.models.lenet`` (max-pooling by reshape, not ``reduce_window``).
Only ``program_loss`` imports the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _sizes(cfg):
    ks, c1, c2 = cfg["kernel_size"], *cfg["conv_channels"]
    s1 = cfg["image_size"] - ks + 1           # conv1 output side
    p1 = s1 // 2
    s2 = p1 - ks + 1                          # conv2 output side
    p2 = s2 // 2
    f1, f2 = cfg["fc_dims"]
    return ks, c1, c2, s1, s2, p2 * p2 * c2, f1, f2


def num_params(cfg) -> int:
    ks, c1, c2, _, _, flat, f1, f2 = _sizes(cfg)
    cin, k = cfg["in_channels"], cfg["num_classes"]
    return ((ks * ks * cin + 1) * c1 + (ks * ks * c1 + 1) * c2
            + (flat + 1) * f1 + (f1 + 1) * f2 + (f2 + 1) * k)


def layer_macs(cfg):
    """Forward multiply-accumulates per image, layer by layer."""
    ks, c1, c2, s1, s2, flat, f1, f2 = _sizes(cfg)
    cin, k = cfg["in_channels"], cfg["num_classes"]
    return [s1 * s1 * c1 * ks * ks * cin, s2 * s2 * c2 * ks * ks * c1,
            flat * f1, f1 * f2, f2 * k]


def fwd_macs_per_sample(cfg) -> int:
    return sum(layer_macs(cfg))


def train_flops_per_sample(cfg) -> float:
    """Required FLOPs of one GD step per image: the forward pass, the
    weight gradients of every layer and the input gradients of every
    layer but the first (2 FLOPs per MAC).  Pooling, tanh and softmax are
    elementwise and not counted."""
    macs = layer_macs(cfg)
    return 2.0 * (3 * sum(macs) - macs[0])


def init_params(key, cfg):
    """He-normal dense layers and N(0, 0.1^2) convolutions, zero biases,
    float32, in the layout ``repro.models.lenet`` takes."""
    ks, c1, c2, _, _, flat, f1, f2 = _sizes(cfg)
    cin, ncls = cfg["in_channels"], cfg["num_classes"]

    def make(key):
        k = jax.random.split(key, 5)

        def dense(kk, i, o):
            return {"w": jax.random.normal(kk, (i, o)) * jnp.sqrt(2.0 / i),
                    "b": jnp.zeros((o,))}
        return {
            "conv1": {"w": jax.random.normal(k[0], (ks, ks, cin, c1)) * 0.1,
                      "b": jnp.zeros((c1,))},
            "conv2": {"w": jax.random.normal(k[1], (ks, ks, c1, c2)) * 0.1,
                      "b": jnp.zeros((c2,))},
            "fc1": dense(k[2], flat, f1),
            "fc2": dense(k[3], f1, f2),
            "out": dense(k[4], f2, ncls),
        }
    return jax.jit(make)(key)


def program_loss(cfg):
    from repro.models import lenet
    return lenet.lenet_loss


def _conv(x, w, b):
    y = jax.lax.conv_general_dilated(
        x, w, (1, 1), "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + b


def _maxpool2(x):
    """2x2 max-pooling, stride 2, an odd last row or column dropped."""
    b, h, w, c = x.shape
    x = x[:, :h // 2 * 2, :w // 2 * 2]
    return x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def reference_logits(params, images):
    x = _maxpool2(jnp.tanh(_conv(images, params["conv1"]["w"],
                                 params["conv1"]["b"])))
    x = _maxpool2(jnp.tanh(_conv(x, params["conv2"]["w"],
                                 params["conv2"]["b"])))
    x = x.reshape(x.shape[0], -1)
    x = jnp.tanh(x @ params["fc1"]["w"] + params["fc1"]["b"])
    x = jnp.tanh(x @ params["fc2"]["w"] + params["fc2"]["b"])
    return x @ params["out"]["w"] + params["out"]["b"]


def reference_loss(params, batch):
    """Mean cross-entropy, computed in the dtype of ``params``."""
    logits = reference_logits(params, batch["images"])
    ll = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(ll, batch["labels"][:, None], 1))


def make_reference_loss(cfg):
    return reference_loss
