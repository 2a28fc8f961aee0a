"""Conv weights of a CoDR CNN configuration, drawn from the seed on the
host: Gaussian, zeroed outside ``density``, and put on the symmetric
int8 grid with one scale per layer (``w = q * s``, ``max |q| = 127``).
Weights already on that grid pass the encoder's quantization unchanged,
so the plain reference can use them as drawn.  Program-free.
"""
from __future__ import annotations

import numpy as np

from bench.lib import traffic


def conv_weights(layers: list[dict], seed: int, density: float
                 ) -> list[tuple[np.ndarray, np.float32]]:
    """``[(q int8 OIHW, scale float32)]``, one pair per layer."""
    g = traffic.rng(seed, 4)
    out = []
    for spec in layers:
        w = g.normal(size=(spec["out_channels"], spec["in_channels"],
                           spec["kernel"], spec["kernel"])) * 0.5
        w[g.random(w.shape) > density] = 0.0
        s = np.float32(np.abs(w).max() / 127.0)
        q = np.clip(np.rint(w / s), -127, 127).astype(np.int8)
        out.append((q, s))
    return out


def dense(q: np.ndarray, s) -> np.ndarray:
    return q.astype(np.float32) * np.float32(s)
