"""Plain reference for LeNet-5 as ``models/extra.py`` builds it (5x5 SAME
convolutions with bias and ReLU, 2x2 max-pools, three dense layers), in
float32 jax.numpy at HIGHEST precision, and its FLOPs from the file's shapes.
A configuration of a family the harness has never heard of: its whole
reference is this file, named by the configuration's ``reference`` key."""

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.common import HIGHEST, cross_entropy_sum, exact


def _conv(P, name, x, rnd):
    y = lax.conv_general_dilated(
        rnd(x), rnd(P[f"{name}/kernel"]), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
    return jax.nn.relu(y + P[f"{name}/b"])


def _pool(x):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 2, 2, 1), (1, 2, 2, 1),
                             "VALID")


def loss_and_grads(P, images, labels, config, rnd=exact):
    def loss(P):
        h = _pool(_conv(P, "conv1", images, rnd))
        h = _pool(_conv(P, "conv2", h, rnd)).reshape(images.shape[0], -1)
        for name in ("fc1", "fc2", "fc3"):
            h = jnp.matmul(rnd(h), rnd(P[f"{name}/w"]),
                           precision=HIGHEST) + P[f"{name}/b"]
            if name != "fc3":
                h = jax.nn.relu(h)
        return cross_entropy_sum(h, labels) / labels.shape[0]

    value, grads = jax.value_and_grad(loss)(P)
    return value, grads, {}


def train_flops_per_sample(config, sample_shape) -> float:
    hw, _, cin = sample_shape
    total = 0.0
    for cout in config["conv_channels"]:
        total += 2.0 * hw * hw * 5 * 5 * cin * cout
        hw, cin = hw // 2, cout
    feats = hw * hw * cin
    for out in (*config["dense_widths"], config["num_classes"]):
        total += 2.0 * feats * out
        feats = out
    return 3.0 * total
