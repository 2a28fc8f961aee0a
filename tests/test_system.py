"""End-to-end system behaviour: the paper's pipeline wired through the
framework — offline encode → compressed serving; full training run on
real (synthetic-structured) data; dry-run cell builder sanity."""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_variant
from repro.models import get_model

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_quickstart_example_runs():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "examples/quickstart.py", "--steps", "5"],
        capture_output=True, text=True, timeout=600, cwd=REPO, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "loss" in out.stdout


def test_codr_end_to_end_compressed_serving(key):
    """Paper pipeline on a transformer: quantize+UCR+RLE the weights,
    then serve — logits stay finite, measured bits beat int8."""
    from repro.core.serving import codr_compress_params
    cfg = smoke_variant(get_config("qwen2.5-3b"))
    api = get_model(cfg)
    params = api.init_params(key, cfg)
    cparams, reports = codr_compress_params(params, n_unique=16)
    tokens = jax.random.randint(key, (2, 12), 0, cfg.vocab_size)
    lgc, _ = api.prefill(cparams, {"tokens": tokens}, cfg)
    assert np.isfinite(np.asarray(lgc, np.float32)).all()
    bits = sum(r.codr_bits for r in reports) / sum(r.n_weights
                                                   for r in reports)
    assert bits < 8.0


def test_smm_conv_matches_float_conv_through_kernel(rng):
    """CNN path: float conv ≈ scale × SMM(int8) through the Pallas
    kernel — the paper's inference model end-to-end."""
    import jax.lax as lax
    from repro.core import ucr
    from repro.kernels.smm_conv import smm_conv
    w = rng.normal(size=(8, 4, 3, 3)).astype(np.float32)
    w[rng.random(w.shape) < 0.5] = 0
    x = rng.integers(-8, 8, size=(4, 12, 12)).astype(np.float32)
    code = ucr.encode_conv_layer(w, t_m=4, t_n=2)
    y_smm = np.asarray(smm_conv(jnp.asarray(x), code)) * float(code.scale)
    y_ref = np.asarray(lax.conv_general_dilated(
        jnp.asarray(x)[None], jnp.asarray(w), (1, 1), "VALID",
        dimension_numbers=("NCHW", "OIHW", "NCHW"))[0])
    denom = np.abs(y_ref).max() + 1e-6
    assert np.abs(y_smm - y_ref).max() / denom < 0.05


def test_benchmark_harness_importable():
    from benchmarks import run as bench_run
    assert callable(bench_run.main)


@pytest.mark.parametrize("shape_name", ["train_4k", "decode_32k"])
def test_dryrun_cell_builder_abstract(shape_name):
    """build_cell produces coherent abstract shapes/shardings on a tiny
    mesh (the 512-device path is exercised by repro.launch.dryrun)."""
    from repro.configs.base import SHAPES
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import build_cell
    mesh = make_host_mesh(1, 1)
    shape = dataclasses.replace(SHAPES[shape_name], global_batch=2,
                                seq_len=64)
    cfg = smoke_variant(get_config("granite-moe-1b-a400m"))
    fn, arg_shapes, in_sh, _ = build_cell(cfg, shape, mesh)
    with mesh:
        lowered = jax.jit(fn, in_shardings=in_sh).lower(*arg_shapes)
        assert lowered.compile() is not None
