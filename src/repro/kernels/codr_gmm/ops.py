"""jit'd public wrapper for the grouped CoDR matmul over an expert
stack.  Off-TPU the Pallas kernel runs in interpret mode; on a TPU
backend it compiles to Mosaic."""
from __future__ import annotations

import jax

from repro.core.codr_linear import PackedWeight
from repro.kernels.codr_gmm.kernel import codr_gmm_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def codr_gmm(x: jax.Array, group_sizes: jax.Array, w: PackedWeight, *,
             max_group: int | None = None,
             interpret: bool | None = None) -> jax.Array:
    """Rows of ``x`` sorted by expert, each times its expert's decoded
    matrix of the ``(E, K, N)`` pack ``w``; float32 ``(m, N)``."""
    if interpret is None:
        interpret = not _on_tpu()
    e = w.packed.shape[0]
    return codr_gmm_pallas(
        x, group_sizes, w.packed, w.table.reshape(e, -1),
        w.scale.reshape(e), bits=w.bits, n=w.shape[1],
        max_group=max_group, interpret=interpret)
