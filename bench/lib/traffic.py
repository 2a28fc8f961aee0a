"""The one traffic generator.  A mix is a JSON file under
``bench/traffic/`` that states its parameters; this module turns it and
a seed into the requests of one run.

Every seed gets the same requests at the same times: each size and gap
is the quantile of its distribution at the midpoints ``(i + 1/2) / n``
of ``n`` equal strata, put in an order drawn once from a fixed stream;
the seed draws only the token ids or pixels (and the runner the
weights).  The order alone moves a 95th percentile of some 80 requests
by a third from seed to seed, which no bound could hold.

Distributions (a ``{"dist": ...}`` object):

* ``lognormal``: ``median``, ``sigma``; optional ``grid`` (snap to the
  nearest value) or ``min``/``max`` (clip).
* ``choice``: ``values`` with ``weights``.
* ``uniform_int``: integers ``min`` .. ``max`` inclusive.

Mix kinds:

* ``open_loop``: Poisson arrivals at ``rate_per_s`` from ``-ramp_s``
  until the window closes; ``server`` holds the batcher's geometry.
* ``backlog``: ``n_requests`` submitted before the window opens.
* ``closed_loop``: one client sends ``batch`` images of
  ``image_hw`` x ``image_hw`` x ``channels`` and waits for each result;
  ``distinct_batches`` different batches are cycled.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parents[1] / "traffic"


def load(name: str, directory: Path = TRAFFIC_DIR) -> dict:
    """The mix named ``name`` (``<directory>/<name>.json``)."""
    with open(directory / f"{name}.json") as f:
        return json.load(f)


def strata(n: int) -> np.ndarray:
    """Midpoints of ``n`` equal probability strata."""
    return (np.arange(n) + 0.5) / n


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` values of distribution ``spec``, one per stratum, ascending."""
    u = strata(n)
    kind = spec["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        v = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
        if "grid" in spec:
            grid = np.asarray(sorted(spec["grid"]), np.float64)
            v = grid[np.abs(v[:, None] - grid[None, :]).argmin(axis=1)]
        else:
            v = np.clip(np.rint(v), spec.get("min", 1),
                        spec.get("max", np.inf))
        return v.astype(np.int64)
    if kind == "choice":
        vals = np.asarray(spec["values"], np.int64)
        w = np.asarray(spec["weights"], np.float64)
        cum = np.cumsum(w / w.sum())
        return vals[np.minimum(np.searchsorted(cum, u), len(vals) - 1)]
    if kind == "uniform_int":
        lo, hi = int(spec["min"]), int(spec["max"])
        return lo + np.floor(u * (hi - lo + 1)).astype(np.int64)
    raise ValueError(f"unknown distribution {kind!r}")


ORDER_SEED = 0            # the fixed stream the schedule's order comes from


def rng(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per purpose, from any non-negative seed."""
    return np.random.default_rng([int(seed), stream])


@dataclasses.dataclass
class Request:
    due_s: float            # offset from the window's opening
    prompt: np.ndarray      # int32 token ids
    max_new_tokens: int


def n_requests(mix: dict, seconds: float) -> int:
    if mix["kind"] == "open_loop":
        return int(math.ceil(mix["rate_per_s"]
                             * (mix.get("ramp_s", 0.0) + seconds)))
    if mix["kind"] == "backlog":
        return int(mix["n_requests"])
    raise ValueError(f"mix kind {mix['kind']!r} sends no requests")


def requests(mix: dict, seed: int, seconds: float, vocab: int
             ) -> list[Request]:
    """The requests of one run, ordered by due time."""
    n = n_requests(mix, seconds)
    order = rng(ORDER_SEED, 0)
    prompts = order.permutation(quantiles(mix["prompt_len"], n))
    outs = order.permutation(quantiles(mix["output_len"], n))
    if mix["kind"] == "open_loop":
        gaps = -np.log1p(-strata(n)) / mix["rate_per_s"]
        due = np.cumsum(order.permutation(gaps)) - mix.get("ramp_s", 0.0)
    else:
        due = np.zeros(n)
    toks = rng(seed, 1)
    return [Request(float(d), toks.integers(0, vocab, int(p), np.int32),
                    int(o)) for d, p, o in zip(due, prompts, outs)]


def prompt_lengths(mix: dict) -> list[int]:
    """Every prompt length the mix can send (the shapes to warm up)."""
    spec = mix["prompt_len"]
    if "grid" in spec:
        return sorted(int(v) for v in spec["grid"])
    if spec["dist"] == "choice":
        return sorted(int(v) for v in spec["values"])
    raise ValueError("prompt lengths must come from a finite set, so that "
                     "set-up can compile each one")


def images(mix: dict, seed: int) -> list[np.ndarray]:
    """``distinct_batches`` NHWC float32 batches of integer pixels."""
    g = rng(seed, 2)
    shape = (mix["batch"], mix["image_hw"], mix["image_hw"],
             mix["channels"])
    return [g.integers(0, 256, shape).astype(np.float32)
            for _ in range(mix["distinct_batches"])]
