"""Per-kernel validation: Pallas (interpret mode on CPU) vs pure-jnp
oracles, swept over shapes, dtypes, and unique-count budgets."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ucr
from repro.core.codr_linear import pack_unique, unpack_unique
from repro.core.serving import restrict_unique
from repro.kernels.codr_matmul import codr_matmul, kernel
from repro.kernels.codr_matmul.ref import codr_matmul_ref
from repro.kernels.smm_conv import smm_conv, smm_conv_ref


def _packed(rng, k, n, n_unique, dtype=jnp.float32):
    w = rng.normal(size=(k, n)).astype(np.float32)
    q, s = ucr.quantize_int8(w)
    q = restrict_unique(q, n_unique)
    return pack_unique(q, s, dtype=dtype)


# ---------------------------------------------------------------------------
# codr_matmul (performance kernel)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mkn", [(64, 64, 64), (128, 256, 128),
                                 (32, 384, 512), (256, 128, 256)])
@pytest.mark.parametrize("n_unique", [4, 16])
def test_codr_matmul_shapes(mkn, n_unique, rng):
    m, k, n = mkn
    pw = _packed(rng, k, n, n_unique)
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    y = codr_matmul(x, pw, bm=64, bn=64, bk=64, interpret=True)
    yr = codr_matmul_ref(x, pw.packed, pw.table, pw.scale.reshape(-1),
                         bits=pw.bits, n=n)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_codr_matmul_dtypes(dtype, rng):
    pw = _packed(rng, 128, 128, 16, dtype=dtype)
    x = jnp.asarray(rng.normal(size=(64, 128)), dtype=dtype)
    y = codr_matmul(x, pw, interpret=True)
    yr = codr_matmul_ref(x, pw.packed, pw.table, pw.scale.reshape(-1),
                         bits=pw.bits, n=128)
    assert y.dtype == dtype
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("blocks", [(32, 32, 32), (64, 128, 32),
                                    (128, 64, 128)])
def test_codr_matmul_block_sweep(blocks, rng):
    bm, bn, bk = blocks
    pw = _packed(rng, 128, 256, 16)
    x = jnp.asarray(rng.normal(size=(128, 128)).astype(np.float32))
    y = codr_matmul(x, pw, bm=bm, bn=bn, bk=bk, interpret=True)
    yr = codr_matmul_ref(x, pw.packed, pw.table, pw.scale.reshape(-1),
                         bits=pw.bits, n=256)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("m", [256, 1024, 1100])
def test_codr_matmul_default_blocks_prompt_rows(m, rng):
    """Prompt-sized calls on the default blocks: one row block up to
    ``ROW_CAP`` rows, equal row blocks above (1100 rows: two)."""
    pw = _packed(rng, 256, 512, 16)
    x = jnp.asarray(rng.normal(size=(m, 256)).astype(np.float32))
    y = codr_matmul(x, pw, interpret=True)
    yr = codr_matmul_ref(x, pw.packed, pw.table, pw.scale.reshape(-1),
                         bits=pw.bits, n=512)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-3, atol=2e-4)
    assert kernel.row_blocks(m) == (1 if m <= kernel.ROW_CAP else 2)


# qwen2.5-3b projections (K, N) at 4 bits -> the pooled decode step's blocks
QWEN_DECODE_BLOCKS = {(2048, 2048): (256, 512), (2048, 256): (32, 512),
                      (2048, 11008): (256, 512), (11008, 2048): (256, 256)}


@pytest.mark.parametrize("m", [8, 32, 64, 128])
def test_codr_matmul_decode_step_blocks_pinned(m):
    """Up to 128 rows the default blocks are (m, 2048 columns, 512
    contraction rows) fitted to the tiling, as before the row rule."""
    for (k, n), (bw, bk) in QWEN_DECODE_BLOCKS.items():
        assert kernel._blocks(m, k, n // 8, 4) == (m, bw, bk), (k, n)


def test_codr_matmul_chat_prompts_one_pass():
    """Every prompt length of the chat mix decodes each weight block
    once, and its blocks fit the scoped VMEM the kernel asks for, at
    every index width."""
    import json
    from pathlib import Path
    chat = Path(__file__).resolve().parents[1] / "bench/traffic/chat.json"
    grid = json.loads(chat.read_text())["prompt_len"]["grid"]
    for m in grid + [kernel.ROW_CAP]:
        assert kernel.row_blocks(m) == 1, m
        for bits in (1, 2, 4, 8, 16):
            for k, n in QWEN_DECODE_BLOCKS:
                blocks = kernel._blocks(m, k, n * bits // 32, bits)
                assert blocks[0] == m
                assert kernel.vmem_bytes(*blocks, bits) <= \
                    kernel.VMEM_LIMIT, (m, bits, k, n)


def test_codr_matmul_ragged_k_block(rng):
    """A K with no multiple of 128 among its divisors (DeepSeek-V2-Lite's
    dense FFN: 10,944 = 64 x 171) takes target-sized K blocks, the last
    one ragged over activations padded with zeros."""
    assert kernel.k_block(10944, 256) == 256
    assert kernel.k_block(11008, 256) == 256 and kernel.k_block(2048, 512) \
        == 512
    pw = _packed(rng, 704, 256, 16)             # 704 = 64 x 11 > 512
    assert kernel._blocks(8, 704, 32, 4)[2] == 512
    x = jnp.asarray(rng.normal(size=(8, 704)).astype(np.float32))
    y = codr_matmul(x, pw, interpret=True)
    yr = codr_matmul_ref(x, pw.packed, pw.table, pw.scale.reshape(-1),
                         bits=pw.bits, n=256)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-3, atol=2e-4)


# ---------------------------------------------------------------------------
# codr_gmm (grouped kernel over a packed expert stack)
# ---------------------------------------------------------------------------

def _expert_stack(rng, e, k, n):
    from repro.core.codr_linear import pack_projection
    w = rng.normal(size=(e, k, n)).astype(np.float32)
    return pack_projection(w, n_unique=16).weight


@pytest.mark.parametrize("sizes", [
    [5, 0, 17, 3, 0, 9, 1, 12],          # uneven, empty experts
    [0, 0, 0, 40, 0, 0, 0, 0],           # every row to one expert
    [0, 0, 0, 0, 0, 0, 0, 1],            # one row, the last expert
    [33, 1, 0, 30],                      # groups across a row block
])
def test_codr_gmm_matches_decode_then_ragged_dot(sizes, rng):
    """Each row times its own expert's decoded matrix, as decoding the
    whole stack and ``lax.ragged_dot`` give it; rows past the groups'
    total come out 0."""
    from repro.kernels.codr_gmm import codr_gmm
    e, k, n = len(sizes), 256, 256
    pw = _expert_stack(rng, e, k, n)
    m = sum(sizes) + 3
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    gs = jnp.asarray(sizes, jnp.int32)
    y = codr_gmm(x, gs, pw, interpret=True)
    dense = jax.vmap(lambda p, t: unpack_unique(p, t, bits=pw.bits, n=n))(
        pw.packed, pw.table) * pw.scale[:, None, None]
    yr = np.array(jax.lax.ragged_dot(x, dense, gs,
                                     precision=jax.lax.Precision.HIGHEST))
    yr[sum(sizes):] = 0.0
    np.testing.assert_allclose(np.asarray(y), yr, rtol=2e-3, atol=2e-4)


def test_codr_gmm_more_than_one_row_block(rng, monkeypatch):
    """An expert holding more rows than one row block takes several,
    each decoding the expert's blocks again (``codr_matmul``'s rule);
    rows and experts past a block's end are skipped."""
    from repro.kernels.codr_gmm import codr_gmm
    monkeypatch.setattr(kernel, "ROW_CAP", 16)
    sizes = [20, 0, 37, 5]
    pw = _expert_stack(rng, 4, 128, 256)
    x = jnp.asarray(rng.normal(size=(sum(sizes), 128)).astype(np.float32))
    gs = jnp.asarray(sizes, jnp.int32)
    assert kernel.row_blocks(37) == 3
    y = codr_gmm(x, gs, pw, max_group=37, interpret=True)
    dense = jax.vmap(lambda p, t: unpack_unique(p, t, bits=pw.bits, n=256))(
        pw.packed, pw.table) * pw.scale[:, None, None]
    yr = jax.lax.ragged_dot(x, dense, gs,
                            precision=jax.lax.Precision.HIGHEST)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-3,
                               atol=2e-4)


def test_codr_gmm_skipped_experts_read_no_words():
    """An expert without rows points its weight blocks at the block
    already in VMEM: its own last, the nearest earlier expert's last,
    or, before any expert with rows, the first one's first."""
    from repro.kernels.codr_gmm.kernel import _weight_source
    widx, wlast = _weight_source(jnp.asarray([0, 0, 4, 0, 2, 0]))
    assert widx.tolist() == [2, 2, 2, 2, 4, 4]
    assert wlast.tolist() == [0, 0, 1, 1, 1, 1]


def test_codr_gmm_layout_aligns_groups():
    """Groups start on the sublane tile; each row lands in its group's
    span, rows past the total nowhere; one row block of room is left."""
    from repro.kernels.codr_gmm.kernel import ALIGN, layout
    starts, dest, m_tot = layout(jnp.asarray([3, 0, 9, 1]), 15, 16)
    assert starts.tolist() == [0, 8, 8, 24]
    assert dest.tolist() == [0, 1, 2, 8, 9, 10, 11, 12, 13, 14, 15, 16, 24,
                             m_tot, m_tot]
    assert m_tot % ALIGN == 0 and m_tot >= 24 + 16


def test_pack_unpack_roundtrip(rng):
    for n_unique in (2, 4, 16, 256):
        w = rng.normal(size=(32, 64)).astype(np.float32)
        q, s = ucr.quantize_int8(w)
        q = restrict_unique(q, n_unique)
        pw = pack_unique(q, s, dtype=jnp.float32)
        dense = unpack_unique(pw.packed, pw.table, bits=pw.bits, n=64)
        np.testing.assert_allclose(np.asarray(dense), q.astype(np.float32))


def test_compression_ratio_scales_with_unique_budget(rng):
    w = rng.normal(size=(256, 256)).astype(np.float32)
    q, s = ucr.quantize_int8(w)
    r16 = pack_unique(restrict_unique(q, 16), s).compression_vs_bf16
    r4 = pack_unique(restrict_unique(q, 4), s).compression_vs_bf16
    assert r4 > r16 > 3.0          # 4-bit pack ≈ 4x vs bf16, 2-bit ≈ 8x


# ---------------------------------------------------------------------------
# smm_conv (faithful-mechanism kernel)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(4, 3, 3, 3, 10, 10), (8, 2, 2, 2, 8, 8),
                                   (8, 5, 1, 1, 6, 6)])
@pytest.mark.parametrize("density", [0.2, 0.8])
def test_smm_conv_kernel_exact(shape, density, rng):
    m, n, rk, ck, ri, ci = shape
    w = rng.normal(size=(m, n, rk, ck)).astype(np.float32)
    w[rng.random(w.shape) > density] = 0
    code = ucr.encode_conv_layer(w, t_m=4, t_n=2)
    x = rng.integers(-8, 8, size=(n, ri, ci)).astype(np.int8)
    got = smm_conv(jnp.asarray(x), code, interpret=True)
    ref = smm_conv_ref(x, code)
    assert float(jnp.abs(got - ref).max()) == 0.0


@pytest.mark.parametrize("stride", [1, 2, 3])
@pytest.mark.parametrize("shape", [(6, 2, 3, 3, 11, 11), (4, 3, 2, 2, 12, 12)])
def test_smm_conv_kernel_stride_parity(shape, stride, rng):
    """Strided crossbar routing in the Pallas kernel == strided dense
    conv oracle, bit-exact."""
    m, n, rk, ck, ri, ci = shape
    w = rng.normal(size=(m, n, rk, ck)).astype(np.float32)
    w[rng.random(w.shape) > 0.5] = 0
    code = ucr.encode_conv_layer(w, t_m=2, t_n=2)
    x = rng.integers(-8, 8, size=(n, ri, ci)).astype(np.int8)
    got = smm_conv(jnp.asarray(x), code, stride=stride, interpret=True)
    ref = smm_conv_ref(x, code, stride=stride)
    assert got.shape == ref.shape
    assert float(jnp.abs(got - ref).max()) == 0.0


def test_smm_conv_batched_one_dispatch(rng):
    """The batched entry point covers the whole batch with one kernel
    call (batch grid dim) and matches the per-sample results."""
    from repro.kernels.smm_conv import smm_conv_batched
    w = rng.normal(size=(4, 2, 3, 3)).astype(np.float32)
    w[rng.random(w.shape) > 0.5] = 0
    code = ucr.encode_conv_layer(w, t_m=2, t_n=2)
    x = rng.integers(-8, 8, size=(3, 2, 9, 9)).astype(np.int8)
    got = smm_conv_batched(jnp.asarray(x, jnp.float32), code, interpret=True)
    for b in range(3):
        ref = smm_conv_ref(x[b], code)
        assert float(jnp.abs(got[b] - ref).max()) == 0.0


def test_smm_conv_all_zero_layer(rng):
    w = np.zeros((4, 2, 3, 3), dtype=np.float32)
    code = ucr.encode_conv_layer(w, t_m=4, t_n=2)
    x = rng.integers(-8, 8, size=(2, 8, 8)).astype(np.int8)
    got = smm_conv(jnp.asarray(x), code, interpret=True)
    assert float(jnp.abs(got).max()) == 0.0


def test_smm_kernel_backend_refused_on_tpu(monkeypatch):
    """Mosaic refuses the smm_conv kernel, so on a TPU the backend says
    so at compile time instead of falling back to interpret mode."""
    import repro.api as codr
    spec = codr.ModelSpec.from_paper_cnn("vgg16", n_conv=1, ri=8, ci=8)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="Mosaic refuses the smm_conv"):
        codr.compile(spec, backend="smm_kernel")


# ---------------------------------------------------------------------------
# flash_attention (fused production kernel — EXPERIMENTS §Perf Pair 2 fix)
# ---------------------------------------------------------------------------

from repro.kernels.flash_attention import (flash_attention_kernel,
                                           flash_attention_ref)


@pytest.mark.parametrize("shape", [(2, 128, 4, 2, 32), (1, 256, 8, 8, 16),
                                   (2, 96, 4, 1, 64)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_kernel(shape, causal, key=None):
    import jax
    key = jax.random.PRNGKey(0)
    b, s, hq, hkv, d = shape
    q = jax.random.normal(key, (b, s, hq, d), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, hkv, d),
                          jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, hkv, d),
                          jnp.float32)
    got = flash_attention_kernel(q, k, v, causal=causal, bq=64, bk=64,
                                 interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_flash_attention_kernel_block_sweep(rng):
    import jax
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (1, 128, 2, 2, ), jnp.float32)  # placeholder
    b, s, h, d = 1, 128, 2, 32
    q = jax.random.normal(key, (b, s, h, d), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, h, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, h, d))
    ref = flash_attention_ref(q, k, v, causal=True)
    for bq, bk in ((32, 32), (128, 64), (64, 128)):
        got = flash_attention_kernel(q, k, v, causal=True, bq=bq, bk=bk,
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)
