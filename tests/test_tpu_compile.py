"""Compile the main path's kernels for a described TPU v5e at published
widths.  Nothing runs: the TPU compiler refuses here what interpret mode
accepts (block tiling, scalar reads from vector memory, VMEM limits).

The topology is described inside a fixture, never at import, so every
test worker collects the same tests and only the worker given this file
loads the TPU library.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# qwen2.5-3b projections (K, N): q/o, k/v, gate/up, down
QWEN_PROJECTIONS = [(2048, 2048), (2048, 256), (2048, 11008), (11008, 2048)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs in /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("k,n", QWEN_PROJECTIONS)
@pytest.mark.parametrize("m", [8, 128, 256, 512, 1024])
def test_codr_matmul_compiles_for_v5e(m, k, n, one_chip, no_compile_cache):
    from repro.kernels.codr_matmul.kernel import codr_matmul_pallas
    bits = 4
    args = (_shape(one_chip, (m, k), jnp.float32),
            _shape(one_chip, (k, n * bits // 32), jnp.uint32),
            _shape(one_chip, (1 << bits,), jnp.float32),
            _shape(one_chip, (1,), jnp.float32))
    compiled = jax.jit(lambda x, p, t, s: codr_matmul_pallas(
        x, p, t, s, bits=bits, n=n)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# DeepSeek-V2-Lite's expert stacks (K, N): gate/up, down; 64 experts.
# Rows: the pooled decode step's 64 slots x top-6, and a 1,024-token
# prompt's 1,024 x 6, with the most one expert can hold (the tokens)
@pytest.mark.parametrize("k,n", [(2048, 1408), (1408, 2048)])
@pytest.mark.parametrize("m,max_group", [(384, 64), (6144, 1024)])
def test_codr_gmm_compiles_for_v5e(m, max_group, k, n, one_chip,
                                   no_compile_cache):
    from repro.kernels.codr_gmm.kernel import codr_gmm_pallas
    bits, e = 4, 64
    args = (_shape(one_chip, (m, k), jnp.bfloat16),
            _shape(one_chip, (e,), jnp.int32),
            _shape(one_chip, (e, k, n * bits // 32), jnp.uint32),
            _shape(one_chip, (e, 1 << bits), jnp.float32),
            _shape(one_chip, (e,), jnp.float32))
    compiled = jax.jit(lambda x, g, p, t, s: codr_gmm_pallas(
        x, g, p, t, s, bits=bits, n=n, max_group=max_group)).lower(
            *args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_codr_matmul_ragged_k_compiles_for_v5e(one_chip, no_compile_cache):
    """DeepSeek-V2-Lite's dense down projection (K = 10,944, no multiple
    of 128 divides it) at a 1,024-token prompt: ragged K blocks fit the
    scoped VMEM where one whole-K block did not."""
    from repro.kernels.codr_matmul.kernel import codr_matmul_pallas
    bits, m, k, n = 4, 1024, 10944, 2048
    args = (_shape(one_chip, (m, k), jnp.float32),
            _shape(one_chip, (k, n * bits // 32), jnp.uint32),
            _shape(one_chip, (1 << bits,), jnp.float32),
            _shape(one_chip, (1,), jnp.float32))
    compiled = jax.jit(lambda x, p, t, s: codr_matmul_pallas(
        x, p, t, s, bits=bits, n=n)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_smm_conv_refused_for_v5e(one_chip, no_compile_cache):
    """``smm_kernel`` is not offered on a TPU because Mosaic refuses the
    kernel (``TPU_REFUSAL``); this pins that reason to the compiler's
    answer at VGG16 conv1 (64 -> 64 channels, 3x3, 56x56 crop)."""
    from repro.kernels.smm_conv.kernel import smm_conv_pallas
    b, n, ri, m_tiles, u, l_max, t_m = 8, 64, 56, 16, 17, 36, 4
    ro = ri - 2
    args = (_shape(one_chip, (b, n, ri, ri), jnp.float32),
            _shape(one_chip, (m_tiles, n, u), jnp.float32),
            _shape(one_chip, (m_tiles, n, l_max, 4), jnp.int32))
    fn = jax.jit(lambda x, d, e: smm_conv_pallas(x, d, e, t_m=t_m, ro=ro,
                                                 co=ro))
    with pytest.raises(Exception, match="divisible by 8 and 128"):
        fn.lower(*args).compile()


def _served_shapes(cfg, one_chip, n_slots, max_len):
    """Packed params (given as shapes, in ``compile_params``' leaf rules)
    and an int8 paged KV pool of ``cfg``, on the described chip."""
    from repro.core.api import EMBED_INCLUDE, PACK_INCLUDE
    from repro.core.codr_linear import (PackedEmbedding, PackedLinear,
                                        PackedWeight)
    from repro.models import cache as cache_mod
    from repro.models import get_model
    api = get_model(cfg)

    def packed(shape):
        *lead, k, n = shape
        n_pad = n + (-n) % 8
        return PackedWeight(
            _shape(one_chip, (*lead, k, n_pad // 8), jnp.uint32),
            _shape(one_chip, (*lead, 16), jnp.float32),
            _shape(one_chip, tuple(lead), jnp.float32), 4, (k, n_pad))

    def leaf(path, x):
        p = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path)
        if x.ndim >= 2 and x.size >= 1024:
            if x.ndim == 2 and any(t in p for t in EMBED_INCLUDE):
                return PackedEmbedding(packed(x.shape), d_model=x.shape[1])
            if any(t in p for t in PACK_INCLUDE):
                return PackedLinear(packed(x.shape), out_features=x.shape[-1])
        return _shape(one_chip, x.shape, x.dtype)

    params = jax.tree_util.tree_map_with_path(leaf, jax.eval_shape(
        lambda: api.init_params(jax.random.PRNGKey(0), cfg)))
    spec = cache_mod.PagedSpec(page_size=16, max_len=max_len,
                               n_slots=n_slots, kv_dtype="int8")
    pool = jax.tree.map(lambda x: _shape(one_chip, x.shape, x.dtype),
                        jax.eval_shape(lambda: api.init_cache(
                            cfg, n_slots, max_len, paged=spec)))
    return api, params, pool


def test_qwen_pooled_decode_step_compiles_for_v5e(one_chip, no_compile_cache,
                                                  monkeypatch):
    """The whole pooled decode step of 4 full-width qwen2.5-3b layers,
    from packed weights (given as shapes) and an int8 paged KV pool:
    every projection lowers to the Mosaic kernel."""
    from repro.configs import get_config
    from repro.kernels.codr_matmul import ops as mm_ops
    # the kernel wrapper asks the default backend (the CPU here)
    monkeypatch.setattr(mm_ops, "_on_tpu", lambda: True)
    cfg = dataclasses.replace(get_config("qwen2.5-3b"), n_layers=4)
    n_slots = 8
    api, params, pool = _served_shapes(cfg, one_chip, n_slots, 320)
    vec = _shape(one_chip, (n_slots,), jnp.int32)
    compiled = jax.jit(lambda p, c, t, i: api.decode_step(
        p, c, t, i, cfg)).lower(params, pool, vec, vec).compile()
    # q, k, v, o, gate, up, down in the scanned layer body
    assert compiled.as_text().count("tpu_custom_call") >= 7


def test_dsv2_lite_pooled_decode_step_compiles_for_v5e(one_chip,
                                                       no_compile_cache,
                                                       monkeypatch):
    """DeepSeek-V2-Lite's pooled decode step at published widths, its
    dense layer 0 and two MoE layers, 64 slots of an int8 paged latent
    pool: projections through ``codr_matmul``, expert stacks through
    ``codr_gmm``, and the program fits one chip's memory."""
    from repro.configs import get_config
    from repro.kernels.codr_gmm import ops as gmm_ops
    from repro.kernels.codr_matmul import ops as mm_ops
    monkeypatch.setattr(mm_ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(gmm_ops, "_on_tpu", lambda: True)
    cfg = dataclasses.replace(get_config("deepseek-v2-lite"), n_layers=3)
    n_slots = 64
    api, params, pool = _served_shapes(cfg, one_chip, n_slots, 1280)
    vec = _shape(one_chip, (n_slots,), jnp.int32)
    # as the batcher serves it: with the experts' count
    compiled = jax.jit(lambda p, c, t, i: api.decode_step(
        p, c, t, i, cfg, count_experts=True)).lower(
            params, pool, vec, vec).compile()
    text = compiled.as_text()
    assert "%codr_gmm" in text and text.count("tpu_custom_call") >= 10
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 30
