"""Shared pieces of the plain references: precision, the lower-precision
controls, losses and the two optimizers, all in straightforward jax.numpy.
Nothing here imports the program."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def exact(x):
    """The reference's own precision: float32 as it is."""
    return x


def _straight_through(round_fn):
    """Round in the forward pass; cotangents pass unrounded (a backward
    through the rounding would round them with no scale, and fp8 flushes
    gradients to zero)."""
    return lambda x: x + lax.stop_gradient(round_fn(x) - x)


# lax.reduce_precision, not a pair of converts: XLA:TPU removes a
# float32 -> bfloat16 -> float32 round trip as "excess precision" (a control
# built from converts read exactly like the reference on the chip)
@_straight_through
def fp8(x):
    """Control for a bfloat16 configuration: values rounded to float8_e4m3
    (4 exponent bits, 3 of mantissa) with a per-tensor scale, the usual fp8
    recipe."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 224.0
    return lax.reduce_precision(x / s, exponent_bits=4, mantissa_bits=3) * s


@_straight_through
def bf16(x):
    """Control for a float32 configuration: values rounded to bfloat16."""
    return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


@_straight_through
def bits16(x):
    """16 significant bits: not a control, a probe of how ill-conditioned a
    compared number is."""
    return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=15)


ROUNDINGS = {"float32": exact, "float8_e4m3": fp8, "bfloat16": bf16,
             "bits16": bits16}


def cross_entropy_sum(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def sgd_momentum(params, grads, mom_buf, hp):
    """torch.optim.SGD: buf = mu*buf + (g + wd*p); p -= lr*buf."""
    new_p, new_m = {}, {}
    for k in params:
        g = grads[k] + hp["weight_decay"] * params[k]
        new_m[k] = hp["momentum"] * mom_buf[k] + g
        new_p[k] = params[k] - hp["lr"] * new_m[k]
    return new_p, new_m


def adam(params, grads, m, v, step, hp):
    """torch.optim.Adam (L2 weight decay added to the gradient)."""
    b1, b2, eps = hp["beta1"], hp["beta2"], hp["eps"]
    bc1, bc2 = 1.0 - b1 ** step, 1.0 - b2 ** step
    new_p, new_m, new_v = {}, {}, {}
    for k in params:
        g = grads[k] + hp["weight_decay"] * params[k]
        new_m[k] = b1 * m[k] + (1.0 - b1) * g
        new_v[k] = b2 * v[k] + (1.0 - b2) * jnp.square(g)
        denom = jnp.sqrt(new_v[k]) / jnp.sqrt(bc2) + eps
        new_p[k] = params[k] - (hp["lr"] / bc1) * new_m[k] / denom
    return new_p, new_m, new_v
