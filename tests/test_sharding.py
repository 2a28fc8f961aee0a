"""Sharding rules + a real (tiny-mesh) pjit train step."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import get_config, smoke_variant
from repro.configs.base import ShapeConfig
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import (batch_spec, build_cell, input_specs,
                                serve_param_fsdp)
from repro.sharding.rules import param_spec


@pytest.fixture(scope="module")
def mesh16():
    """Abstract 16×16 mesh for spec (not placement) checks."""
    devs = np.array(jax.devices() * 256)[:256].reshape(16, 16)
    return Mesh(devs, ("data", "model"))


def test_param_spec_column_parallel(mesh16):
    # scan-stacked params carry a leading (n_periods,) axis — unsharded
    s = param_spec("stack/b0/mixer/q_proj", (4, 2048, 4096), mesh16)
    assert s == P(None, "data", "model")
    s = param_spec("prologue/0/mixer/q_proj", (2048, 4096), mesh16)
    assert s == P("data", "model")


def test_param_spec_row_parallel(mesh16):
    s = param_spec("stack/b0/mixer/o_proj", (4, 4096, 2048), mesh16)
    assert s == P(None, "model", "data")


def test_param_spec_embed(mesh16):
    s = param_spec("embed", (151936, 2048), mesh16)
    assert s == P("model", "data")


def test_param_spec_experts(mesh16):
    s = param_spec("stack/b0/mlp/w_experts_in", (4, 160, 5120, 1536), mesh16)
    # stacked scan axis first → untouched; experts over model
    assert s[0] is None and s[1] == "model"


def test_param_spec_indivisible_left_unsharded(mesh16):
    s = param_spec("stack/b0/mixer/q_proj", (100, 100), mesh16)
    assert s == P(None, None)


def test_param_spec_norms_replicated(mesh16):
    assert param_spec("stack/b0/norm1/w", (2048,), mesh16) == P(None)


def test_batch_spec_divisibility(mesh16):
    assert batch_spec(mesh16, 256) == P(("data",))
    assert batch_spec(mesh16, 3) == P()


def test_input_specs_cover_all_shapes():
    from repro.configs.base import SHAPES
    for arch in ("qwen3-32b", "deepseek-v2-236b", "seamless-m4t-medium",
                 "internvl2-26b"):
        cfg = get_config(arch)
        for shape in SHAPES.values():
            specs = input_specs(cfg, shape)
            assert "tokens" in specs or "token" in specs


def test_serve_fsdp_heuristic(mesh16):
    assert serve_param_fsdp(get_config("command-r-plus-104b"), mesh16)
    assert not serve_param_fsdp(get_config("qwen2.5-3b"), mesh16)


def test_pjit_train_step_on_host_mesh(key):
    """Real execution of the sharded train step on a 1×1 mesh."""
    cfg = smoke_variant(get_config("qwen2.5-3b"))
    mesh = make_host_mesh(1, 1)
    shape = ShapeConfig("tiny", 32, 2, "train")
    fn, arg_shapes, in_sh, _ = build_cell(cfg, shape, mesh)
    api_params, opt, batch_specs = arg_shapes
    # materialize real values matching the abstract shapes
    params = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), api_params)
    params = jax.tree.map(
        lambda p: jax.random.normal(key, p.shape, jnp.float32).astype(p.dtype)
        * 0.02, params)
    opt_state = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), opt)
    batch = {"tokens": jax.random.randint(key, (2, 32), 0, cfg.vocab_size)}
    with mesh:
        step = jax.jit(fn, in_shardings=in_sh)
        p2, o2, metrics = step(params, opt_state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert int(o2["step"]) == 1
    # params actually changed
    delta = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), params, p2)
    assert max(jax.tree.leaves(delta)) > 0
