"""``chip_smoke.py``: its phases at smoke sizes on the CPU (kernels in
interpret mode), and its refusal to run without a TPU."""
import importlib.util
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config, smoke_variant
from repro.core.codr_linear import PackedEmbedding, PackedLinear

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_tpu(chip_smoke, capsys):
    assert chip_smoke.main([]) == 1
    out, err = capsys.readouterr()
    assert out == ""                            # no result line
    assert "no TPU" in err


def test_lm_phase_smoke(chip_smoke, capsys):
    chip_smoke.lm_phase(smoke_variant(get_config("qwen2.5-3b")),
                        n_requests=3, n_slots=2, prompt_len=6, gen_len=3,
                        seed=0)
    out = capsys.readouterr().out
    assert out.count("check codr_matmul_kernel_") >= 2   # q/o and d_ff shapes
    assert ": FAIL" not in out
    assert "check lm_codr_matmul_vs_tiled_prefill: PASS" in out
    # interpret mode off the TPU: no Mosaic call in the CPU program
    assert "contains tpu_custom_call: False" in out


def test_cnn_phase_smoke(chip_smoke, capsys):
    chip_smoke.cnn_phase(batch=2, ri=20, seed=0)
    assert "check cnn_tiled_vs_quantized_reference: PASS" in \
        capsys.readouterr().out


def test_sharded_phase_smoke(chip_smoke, capsys):
    chip_smoke.sharded_phase(batch=2, ri=20, seed=0)
    out = capsys.readouterr().out
    assert "bit-for-bit equal to tiled: True" in out
    assert "check cnn_sharded_vs_tiled: PASS" in out


def test_cnn_spec_keeps_published_conv_layers(chip_smoke):
    spec = chip_smoke.cnn_spec(226, seed=0)
    assert [tuple(ls.weight.shape) for ls in spec] == [(64, 3, 3, 3),
                                                       (64, 64, 3, 3)]


def test_with_backend_rebinds_the_same_packed_leaves(chip_smoke):
    import repro.api as codr
    from repro.models import get_model
    cfg = smoke_variant(get_config("qwen2.5-3b"))
    params = get_model(cfg).init_params(jax.random.PRNGKey(0), cfg)
    cp = codr.compile_params(params, codr.EncodeConfig(n_unique=16),
                             accounting=False)
    tiled = chip_smoke.with_backend(cp.params, "tiled")
    packed = (PackedLinear, PackedEmbedding)
    leaves = [leaf for leaf in jax.tree_util.tree_leaves(
        tiled, is_leaf=lambda x: isinstance(x, packed))
        if isinstance(leaf, packed)]
    assert leaves and {leaf.backend for leaf in leaves} == {"tiled"}
    for a, b in zip(jax.tree_util.tree_leaves(cp.params),
                    jax.tree_util.tree_leaves(tiled)):
        assert a is b                           # same arrays, no re-encode
    np.testing.assert_array_equal(
        np.asarray(jax.tree_util.tree_leaves(tiled)[0]),
        np.asarray(jax.tree_util.tree_leaves(cp.params)[0]))
