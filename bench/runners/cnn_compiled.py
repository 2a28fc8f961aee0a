"""A CoDR CNN compiled with ``codr.compile`` and served by
``CompiledModel.run`` in a closed loop: one client sends host batches
and blocks on each result before the next call.  The results stay on
the device.

The weights are the configuration's own, drawn from its
``weight_seed``; ``--seed`` draws the images and the sample.  The
program compiles the decoded weights into its executable as constants,
so weights drawn from ``--seed`` would give every run a program of its
own to compile in set-up.

A reservoir sample of the window's calls, drawn from the seed, keeps
its outputs; after the window they are compared with the plain
reference on the same inputs: the largest deviation relative to the
reference's largest magnitude is the number compared.
"""
from __future__ import annotations

import gc
import time

import jax
from bench.lib import cnn_weights, harness, traffic
from bench.lib.harness import CallRecord, Outcome

N_SAMPLE = 2             # call outputs kept for the check
# No host events in the traced stretch: they slow this loop's transfer
# of each host batch some 20 times over (TPU v5e), and the stretch would
# then read the profiler's idle time, not the loop's.
PROFILE_OPTIONS = {"host_tracer_level": 0, "python_tracer_level": 0}


def model_numbers(c: dict) -> dict:
    """Shapes of each convolution, for the work the metrics count."""
    hw, convs, flops = c["input_hw"], [], 0.0
    for spec in c["conv_layers"]:
        convs.append((hw, spec["in_channels"], spec["out_channels"],
                      spec["kernel"]))
        ho = hw - spec["kernel"] + 1
        flops += 2.0 * ho * ho * spec["out_channels"] * spec["in_channels"] \
            * spec["kernel"] ** 2
        hw = ho
    return {"convs": convs, "flops_per_image": flops}


def rel_dev(got, ref) -> float:
    """Largest deviation relative to the reference's largest magnitude
    (on the device: the maps are hundreds of MB)."""
    num = jax.numpy.max(jax.numpy.abs(got - ref))
    return float(num / jax.numpy.maximum(jax.numpy.max(jax.numpy.abs(ref)),
                                         1e-30))


def reference_dev(config: dict, samples, xs,
                  control: bool = False) -> float:
    """Largest relative deviation of the sampled outputs from the plain
    reference; with ``control``, of the control's outputs on the same
    inputs (the reference with float8 e4m3 operands, one precision below
    the configuration's bfloat16 multiplies) put in their place."""
    ref = harness.load_module(
        harness.BENCH_DIR / "configs" / config["reference"], "cnn_reference")
    ws = [jax.numpy.asarray(cnn_weights.dense(q, s))
          for q, s in weights(config)]
    dev = 0.0
    for i, y in samples:
        x = jax.numpy.asarray(xs[i])
        r = ref.forward(x, ws)
        got = ref.forward(x, ws, fp8=True) if control else y
        dev = max(dev, rel_dev(got, r))
    return dev


def weights(config: dict):
    """The configuration's conv weights, the same in every run."""
    return cnn_weights.conv_weights(config["conv_layers"],
                                    config["weight_seed"], config["density"])


def build(config: dict):
    import repro.api as codr
    specs = [codr.LayerSpec.conv(cnn_weights.dense(q, s), activation="relu",
                                 name=f"conv{i}")
             for i, (q, s) in enumerate(weights(config))]
    return codr.compile(codr.ModelSpec(specs), codr.EncodeConfig(
        n_unique=config["n_unique"]), backend=config["backend"])


def run(run: harness.Run) -> Outcome:
    c, mix = run.config, run.mix
    model = build(c)
    xs = traffic.images(mix, run.seed)
    jax.block_until_ready(model.run(xs[0]))          # compile or load
    pick = traffic.rng(run.seed, 5)
    draws = pick.random(1 << 16)
    samples: list[tuple[int, jax.Array]] = []
    calls, n = [], 0
    run.end_setup()
    t0 = run.open_window()
    t1 = run.t1
    now = t0
    while now < run.end:
        i = n % len(xs)
        y = model.run(xs[i])
        y.block_until_ready()
        end = time.monotonic()
        calls.append(CallRecord(now, end, mix["batch"]))
        # reservoir sample of the window's outputs, drawn from the seed
        if end <= t1:
            if n < N_SAMPLE:
                samples.append((i, y))
            else:
                j = int(draws[n % len(draws)] * (n + 1))
                if j < N_SAMPLE:
                    samples[j] = (i, y)
        n += 1
        run.tick(end)
        now = end
    run.close_window()
    peak = harness.peak_bytes()
    del model, y
    gc.collect()
    t = time.monotonic()
    correct, checks = judge(c, reference_dev(c, samples, xs))
    run.log(f"reference over {len(samples)} sampled calls: "
            f"{time.monotonic() - t!r} s")
    control = None
    if run.control:
        control = judge(c, reference_dev(c, samples, xs, control=True))
    attempted = sum(1 for r in calls if r.end <= t1)
    return Outcome(correct=correct, attempted=attempted, failed=0,
                   checks=checks, memory_peak_bytes=peak, calls=calls,
                   model=model_numbers(c), control=control)


def judge(config: dict, dev: float) -> tuple[bool, list]:
    """``(correct, checks)`` for a deviation from the reference."""
    limit = config["limits"]["max_rel_dev"]
    return dev <= limit, [("max_rel_dev", dev, limit)]
