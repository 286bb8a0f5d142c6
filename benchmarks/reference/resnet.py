"""Plain reference for bottleneck ResNets (He et al. 2015, arXiv:1512.03385;
ResNet-50 is ``stage_blocks`` 3, 4, 6, 3): forward, loss and gradients in
float32 jax.numpy at HIGHEST matmul precision; no kernels, no mixed
precision. Depth and widths are the configuration file's ``stage_blocks``,
``stage_widths`` and ``bottleneck_expansion``; so are the FLOPs
(``train_flops_per_sample``), which nothing else in the benchmark counts.

Departures from the paper that the program makes and this file follows, so
that both compute the same function: the stride-2 of a bottleneck sits on
its 3x3 convolution ("v1.5", as torchvision and every MLPerf ResNet-50),
and "SAME" padding is XLA's (one more pixel after than before on an even
input). BatchNorm in training mode normalizes by the biased batch variance
over the whole (global) batch, eps 1e-5.

Parameters are a flat dict, e.g. ``stem/kernel``, ``stem/bn/scale``,
``group2_block1/conv1``, ``group2_block1/bn_proj/bias``, ``fc/w``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.common import HIGHEST, cross_entropy_sum, exact

BN_EPS = 1e-5
BN_MOMENTUM = 0.1


def _conv(x, w, stride, rnd):
    """``rnd`` rounds the operands and what is kept of the result: the
    identity for the reference, the control's precision for a control
    (which computes where the configuration computes, in a lower type)."""
    return rnd(lax.conv_general_dilated(
        rnd(x), rnd(w), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST))


def _bn(P, name, x, stats):
    """Normalizes by the batch; records the running variance after this one
    batch (torch semantics: 0.9 * 1 + 0.1 * unbiased batch variance) in
    ``stats`` — quantization noise in ``x`` adds its power to it."""
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    n = x.size // x.shape[-1]
    stats[f"{name}/var"] = lax.stop_gradient(
        (1 - BN_MOMENTUM) + BN_MOMENTUM * var * (n / (n - 1)))
    return ((x - mean) * lax.rsqrt(var + BN_EPS) * P[f"{name}/scale"]
            + P[f"{name}/bias"])


def _block(P, name, x, stride, rnd):
    st = {}
    y = rnd(jax.nn.relu(_bn(P, f"{name}/bn1",
                            _conv(x, P[f"{name}/conv1"], 1, rnd), st)))
    y = rnd(jax.nn.relu(_bn(P, f"{name}/bn2",
                            _conv(y, P[f"{name}/conv2"], stride, rnd), st)))
    y = rnd(_bn(P, f"{name}/bn3", _conv(y, P[f"{name}/conv3"], 1, rnd), st))
    if f"{name}/proj" in P:
        x = rnd(_bn(P, f"{name}/bn_proj",
                    _conv(x, P[f"{name}/proj"], stride, rnd), st))
    return rnd(jax.nn.relu(y + x)), st


def forward(P, images, config, rnd=exact):
    """Training-mode logits [B, classes] for images [B, H, W, 3], and every
    BatchNorm's running variance after this batch."""
    stats = {}
    h = rnd(jax.nn.relu(_bn(P, "stem/bn",
                            _conv(images, P["stem/kernel"], 2, rnd), stats)))
    h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    for g, blocks in enumerate(config["stage_blocks"], start=1):
        for b in range(1, blocks + 1):
            name = f"group{g}_block{b}"
            stride = 2 if (b == 1 and g > 1) else 1
            sub = {k: v for k, v in P.items() if k.startswith(name + "/")}
            # one block at a time is live in the backward pass (the batch
            # statistics forbid blocks of rows, so the split is by layer)
            h, st = jax.checkpoint(
                lambda s, x, name=name, stride=stride:
                _block(s, name, x, stride, rnd))(sub, h)
            stats.update(st)
    h = rnd(jnp.mean(h, axis=(1, 2)))
    return (jnp.matmul(h, rnd(P["fc/w"]), precision=HIGHEST) + P["fc/b"],
            stats)


def loss_and_grads(P, images, labels, config, rnd=exact):
    """(loss, gradients, BatchNorm running variances after this batch)."""
    def loss(P):
        logits, stats = forward(P, images, config, rnd)
        return cross_entropy_sum(logits, labels) / labels.shape[0], stats

    (value, stats), grads = jax.value_and_grad(loss, has_aux=True)(P)
    return value, grads, stats


def forward_flops(config, image: int) -> float:
    """Forward FLOPs for one ``image`` x ``image`` input: convolutions and
    the classifier only, one multiply-add = 2 FLOPs (BatchNorm, ReLU and
    pooling count as 0)."""
    def conv(hw, k, cin, cout):
        return 2.0 * hw * hw * k * k * cin * cout

    expansion = config["bottleneck_expansion"]
    hw = -(-image // 2)  # SAME padding: ceil
    cin = config["stem_width"]
    total = conv(hw, 7, config["channels"], cin)  # stem, stride 2
    hw = -(-hw // 2)  # 3x3 max-pool, stride 2
    for g, (width, blocks) in enumerate(zip(config["stage_widths"],
                                            config["stage_blocks"])):
        for b in range(blocks):
            stride = 2 if (b == 0 and g > 0) else 1
            out_hw = -(-hw // stride)
            total += conv(hw, 1, cin, width)  # 1x1 at the input resolution
            total += conv(out_hw, 3, width, width)  # 3x3 carries the stride
            total += conv(out_hw, 1, width, expansion * width)
            if b == 0:
                total += conv(out_hw, 1, cin, expansion * width)  # projection
            hw, cin = out_hw, expansion * width
    return total + 2.0 * cin * config["num_classes"]


def train_flops_per_sample(config, sample_shape) -> float:
    """Training = 3 x forward (backward is two matmuls per forward matmul);
    recomputed operations are never counted."""
    return 3.0 * forward_flops(config, sample_shape[0])
