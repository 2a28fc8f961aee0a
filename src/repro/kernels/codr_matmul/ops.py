"""jit'd public wrapper for the CoDR compressed matmul.

Off-TPU the Pallas kernel runs in interpret mode; on a TPU backend
``interpret=False`` compiles to Mosaic.
"""
from __future__ import annotations

import jax

from repro.core.codr_linear import PackedWeight
from repro.kernels.codr_matmul.kernel import codr_matmul_pallas

# Capability facts consumed by the backend registry
# (repro.core.backends.CodrMatmulBackend) — this kernel only has a matmul
# (linear-layer) datapath; conv layers never route here.
KERNEL_CAPS = {
    "kinds": ("linear",),
    "integer_activations": False,  # float activations, f32 accumulation
    "interpret_on_cpu": True,
    "packed_matmul": True,         # executes PackedLinear params leaves
    "description": "Pallas fused decode+matmul (unique-index pack, "
                   "output-stationary MXU tiles)",
}


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def codr_matmul(x: jax.Array, w: PackedWeight, *, bm: int | None = None,
                bn: int | None = None, bk: int | None = None,
                interpret: bool | None = None) -> jax.Array:
    """``y = x @ decode(w)`` with the decode fused into the matmul tiles."""
    if interpret is None:
        interpret = not _on_tpu()
    return codr_matmul_pallas(
        x, w.packed, w.table, w.scale.reshape(-1),
        bits=w.bits, n=w.shape[1], bm=bm, bn=bn, bk=bk, interpret=interpret)
