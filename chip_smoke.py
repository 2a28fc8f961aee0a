#!/usr/bin/env python3
"""Smoke run of both CoDR serving lanes on a TPU, through the library's
own entry points, at published widths.

    python chip_smoke.py             # one chip: the LM phase, then the CNN phase
    python chip_smoke.py --chips 4   # only the sharded CNN lane over four chips

* LM phase: ``qwen2.5-3b`` at its published widths, depth cut to 4 of its
  36 layers, random weights from ``--seed``.  ``compile_params`` packs the
  projections at U=16 for the ``codr_matmul`` backend, and
  ``run_serve_continuous`` streams 8 requests (prompts of about 128
  tokens, 32 new tokens each) through a ``ContinuousBatcher`` of 8 slots
  with an int8 paged KV pool.
* CNN phase: the first two VGG16 conv layers at the published 226x226
  input, batch 8, UCR+RLE weights decoded from their bitstreams, on the
  ``tiled`` backend.
* ``--chips 4``: the same CNN on the ``sharded`` backend over a tile mesh
  of the four chips, compared with ``tiled`` on one chip.

Each check prints its deviation next to its bound and the reason for the
bound; a failed check raises, so the script exits non-zero.  The last line
of a passing run is one JSON object naming the device.  Without a TPU the
script exits non-zero before it runs anything.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import repro.api as codr  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import run_serve_continuous  # noqa: E402
from repro.models import get_model  # noqa: E402

LM_ARCH = "qwen2.5-3b"
LM_LAYERS = 4
CNN_NET = "vgg16"
CNN_CONV = 2
CNN_BATCH = 8

# Bounds, each relative to the largest magnitude of the reference output.
# The kernel alone, from f32 activations, against decode-then-matmul at
# "highest" precision: at most one bf16 MXU pass (2**-9 relative per
# operand); a mis-decoded column is off by order 1.
KERNEL_BOUND = 1e-2
# Whole prefill logits, codr_matmul lane against the tiled lane: the
# tiled lane rounds weights and activations to bf16, and a TPU runs both
# lanes' f32 dots as one bf16 pass at default precision, compounded over
# the layers and the unembed (a TPU v5e measured 0.0258 at 4 layers).
LANE_BOUND = 0.05
# A TPU runs f32 convolutions at its default precision from bf16-rounded
# operands (2**-9 relative per operand), the reference at "highest";
# two layers of that stay well under 1%, a wrong weight does not.
CNN_BOUND = 1e-2


def check(name: str, dev: float, bound: float, reason: str) -> None:
    """Print one check; raise when ``dev`` exceeds ``bound``."""
    verdict = "PASS" if dev <= bound else "FAIL"
    print(f"check {name}: {verdict} deviation {dev!r} (bound {bound!r}: "
          f"{reason})")
    if dev > bound:
        raise AssertionError(f"check {name} failed: {dev!r} > {bound!r}")


def rel_dev(got, ref) -> float:
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-30))


def with_backend(params, backend: str):
    """The same packed leaves, executed by another registered backend."""
    from repro.core.codr_linear import PackedEmbedding, PackedLinear
    packed = (PackedLinear, PackedEmbedding)
    return jax.tree_util.tree_map(
        lambda l: dataclasses.replace(l, backend=backend)
        if isinstance(l, packed) else l,
        params, is_leaf=lambda l: isinstance(l, packed))


def check_kernel(params, seed: int) -> None:
    """``codr_matmul`` on the first layer's pack of each projection shape
    in ``params``, against decode-then-matmul at full f32 precision."""
    from repro.core.codr_linear import PackedLinear, codr_matmul_ref
    from repro.kernels.codr_matmul.ops import codr_matmul
    rng = np.random.default_rng(seed + 3)
    done = set()
    for leaf in jax.tree_util.tree_leaves(
            params, is_leaf=lambda l: isinstance(l, PackedLinear)):
        if not isinstance(leaf, PackedLinear) or leaf.weight.shape in done:
            continue
        done.add(leaf.weight.shape)
        k, n = leaf.weight.shape
        lead = (0,) * (leaf.weight.packed.ndim - 2)
        w = jax.tree.map(lambda a: a[lead], leaf.weight)
        x = jnp.asarray(rng.standard_normal((8, k)), jnp.float32)
        y = codr_matmul(x, w)
        with jax.default_matmul_precision("highest"):
            ref = codr_matmul_ref(x, w)
        check(f"codr_matmul_kernel_{k}x{n}", rel_dev(y, ref), KERNEL_BOUND,
              "at most one bf16 MXU pass vs the f32 reference")


def lm_phase(cfg, *, n_requests: int, n_slots: int, prompt_len: int,
             gen_len: int, seed: int) -> None:
    """Serve ``cfg`` from packed weights on ``codr_matmul`` through the
    continuous batcher with an int8 paged KV pool, then check it."""
    print(f"lm: {cfg.name} d_model {cfg.d_model}, heads {cfg.n_heads}/"
          f"{cfg.n_kv_heads}x{cfg.head_dim}, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}, tied {cfg.tied_embeddings}, layers "
          f"{cfg.n_layers}; {n_requests} requests of ~{prompt_len} prompt "
          f"+ {gen_len} new tokens over {n_slots} slots")
    t0 = time.monotonic()
    res = run_serve_continuous(
        cfg, n_requests=n_requests, n_slots=n_slots, prompt_len=prompt_len,
        gen_len=gen_len, max_len=2 * (prompt_len + gen_len), use_codr=True,
        codr_unique=16, codr_backend="codr_matmul", check=True, seed=seed,
        kv_dtype="int8")
    print(f"lm: set-up encode {res['encode_s']!r} s (host); serving and "
          f"checking {time.monotonic() - t0 - res['encode_s']!r} s wall, "
          f"compilation included")
    assert res["checked"] == n_requests, res["checked"]
    print(f"check lm_stream_vs_solo: PASS {res['checked']}/{n_requests} "
          f"streams token-identical to a solo decode (bound: exact; the "
          f"pooled step is the same compiled program, rows do not mix)")
    print(f"check lm_int8_kv_vs_dense_kv: PASS teacher-forced deviation "
          f"{res['check_dev']!r} of the logit spread (bound 0.10: int8 KV "
          f"quantization floor; prefill rows bit-exact)")

    batcher, compiled = res["batcher"], res["compiled"]
    check_kernel(compiled.params, seed)
    prompt = np.random.default_rng(seed + 1).integers(
        0, cfg.vocab_size, size=prompt_len).astype(np.int32)
    fused = batcher.replay_logits(prompt, [0])[0]
    api = get_model(cfg)
    tiled = jax.jit(lambda p, t: api.prefill(p, {"tokens": t}, cfg)[0])(
        with_backend(compiled.params, "tiled"), jnp.asarray(prompt[None]))
    check("lm_codr_matmul_vs_tiled_prefill",
          rel_dev(fused, np.asarray(tiled, np.float32).reshape(-1)),
          LANE_BOUND, "bf16 weights and activations in the tiled lane, "
          "one bf16 MXU pass per f32 dot in both lanes")

    hlo = batcher.lower_decode_step().compile().as_text()
    n_calls = hlo.count("tpu_custom_call")
    print(f"lm: pooled decode step HLO contains tpu_custom_call: "
          f"{n_calls > 0} ({n_calls} occurrences)")
    if jax.default_backend() == "tpu" and not n_calls:
        raise AssertionError("codr_matmul did not lower to a Mosaic kernel")


def cnn_spec(ri: int, seed: int):
    """The first ``CNN_CONV`` VGG16 conv layers at input ``ri``x``ri``.
    The linear head ``from_paper_cnn`` appends reads the unpooled map
    (31.5M weights at 226x226) and is not a paper layer, so it is cut."""
    spec = codr.ModelSpec.from_paper_cnn(
        CNN_NET, n_conv=CNN_CONV, ri=ri, ci=ri,
        rng=np.random.default_rng(seed))
    return codr.ModelSpec([ls for ls in spec if ls.kind == "conv"])


def cnn_images(batch: int, ri: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed + 2)
    return rng.integers(0, 256, size=(batch, ri, ri, 3)).astype(np.float32)


def cnn_phase(*, batch: int, ri: int, seed: int) -> None:
    """Run the CNN from its bitstreams on ``tiled`` and check it against
    the dense oracle on the decoded weights at full f32 precision."""
    t0 = time.monotonic()
    model = codr.compile(cnn_spec(ri, seed), backend="tiled")
    print(f"cnn: {model!r}, input {batch}x{ri}x{ri}x3; set-up encode "
          f"{time.monotonic() - t0!r} s (host)")
    x = cnn_images(batch, ri, seed)
    t0 = time.monotonic()
    y = jax.block_until_ready(model.run(x))
    print(f"cnn: first run {time.monotonic() - t0!r} s wall, compilation "
          f"included; output {tuple(y.shape)}")
    assert np.isfinite(np.asarray(y)).all(), "non-finite CNN output"
    with jax.default_matmul_precision("highest"):
        ref = model.quantized_reference(x)
    check("cnn_tiled_vs_quantized_reference", rel_dev(y, ref), CNN_BOUND,
          "default-precision convs from bf16-rounded operands")


def sharded_phase(*, batch: int, ri: int, seed: int) -> None:
    """The CNN on ``sharded`` over a tile mesh of every local device,
    compared with ``tiled`` on one device."""
    from repro.core.backends import get_backend
    model = codr.compile(cnn_spec(ri, seed), backend="sharded")
    backend = get_backend("sharded")
    devices = jax.devices()
    print(f"sharded: mesh {dict(backend.mesh.shape)} over device ids "
          f"{[d.id for d in backend.mesh.devices.flat]}")
    for layer in model.model.layers:
        where = backend.placement(layer)
        print(f"sharded: {layer.name} tile shards by device id {where}")
        if sorted(where) != sorted(d.id for d in devices):
            raise AssertionError(f"{layer.name}: tiles not on every device")
    x = cnn_images(batch, ri, seed)
    ys = jax.block_until_ready(model.run(x))
    print(f"sharded: output {tuple(ys.shape)} on device ids "
          f"{sorted(d.id for d in ys.sharding.device_set)}")
    yt = jax.block_until_ready(model.run(x, backend="tiled"))
    print(f"sharded: tiled output on device ids "
          f"{sorted(d.id for d in yt.sharding.device_set)}")
    exact = bool(np.array_equal(np.asarray(ys), np.asarray(yt)))
    print(f"sharded: bit-for-bit equal to tiled: {exact}")
    check("cnn_sharded_vs_tiled", rel_dev(ys, yt), CNN_BOUND,
          "per-device convs over a channel split may tile their "
          "reductions differently at default precision")


class CompileClock:
    """Sums JAX's own tracing, lowering and backend-compile durations."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += duration
            self.programs += event == self.EVENTS[-1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded CNN lane over four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 1
    if args.chips == 4 and len(devices) != 4:
        print(f"chip_smoke: --chips 4 needs 4 devices, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    print(f"device: {dev.platform} {dev.device_kind!r} x{len(devices)}; "
          f"compile cache {cache_dir}")
    clock = CompileClock()

    def timed(name, fn, **kw):
        t0, c0, n0 = time.monotonic(), clock.seconds, clock.programs
        fn(**kw)
        print(f"phase {name}: {time.monotonic() - t0!r} s wall, of it "
              f"{clock.seconds - c0!r} s tracing and compiling "
              f"{clock.programs - n0} programs")

    if args.chips == 4:
        timed("sharded", sharded_phase, batch=CNN_BATCH, ri=226,
              seed=args.seed)
    else:
        full = get_config(LM_ARCH)
        print(f"reduced: n_layers {full.n_layers} -> {LM_LAYERS}")
        timed("lm", lm_phase, cfg=dataclasses.replace(full,
                                                      n_layers=LM_LAYERS),
              n_requests=8, n_slots=8, prompt_len=128, gen_len=32,
              seed=args.seed)
        timed("cnn", cnn_phase, batch=CNN_BATCH, ri=226, seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
