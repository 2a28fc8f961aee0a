"""Plain float32 reference of a chain of VALID, stride-1 convolutions
with ReLU, in ``jax.lax`` at "highest" precision, from the weights the
benchmark drew (``bench.lib.cnn_weights``); nothing of the system under
test.  ``fp8=True`` is the control: input maps and weights
rounded to float8 e4m3 with one scale per tensor, the step below the
bfloat16 multiplies of the configuration's default-precision convs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _fp8(x):
    s = jnp.max(jnp.abs(x)) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@functools.partial(jax.jit, static_argnames=("fp8",))
def forward(x, weights, *, fp8=False):
    """``x`` NHWC float32; ``weights`` a list of OIHW float32 kernels."""
    for w in weights:
        if fp8:
            x, w = _fp8(x), _fp8(w)
        x = jax.nn.relu(jax.lax.conv_general_dilated(
            x, w, window_strides=(1, 1), padding="VALID",
            dimension_numbers=("NHWC", "OIHW", "NHWC"),
            precision=jax.lax.Precision.HIGHEST))
    return x
