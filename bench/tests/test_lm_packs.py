"""The on-device pack generator: the tree ``compile_params`` would give,
and logits through ``codr_matmul`` (interpret mode) equal to those
through ``tiled``."""
import jax
import jax.numpy as jnp
import numpy as np

from bench.lib.lm_packs import make_params
from bench.runners.lm_batcher import model_config

TINY = dict(name="tiny-qwen2", hidden_size=64, num_attention_heads=4,
            num_key_value_heads=2, intermediate_size=128, vocab_size=512,
            num_hidden_layers=2, rope_theta=1e6, attention_bias=True,
            tie_word_embeddings=True)
SEED = 2**31 + 77


def test_tree_matches_compile_params_output():
    from repro.core import api
    from repro.core.codr_linear import PackedEmbedding, PackedLinear
    from repro.models import get_model
    cfg = model_config(TINY)
    ours = make_params(cfg, SEED)
    ref = api.compile_params(
        get_model(cfg).init_params(jax.random.PRNGKey(0), cfg),
        api.EncodeConfig(n_unique=16), accounting=False)
    # structure includes the static aux: bits, shape, out_features, backend
    assert jax.tree_util.tree_structure(ours) == \
        jax.tree_util.tree_structure(ref.params)
    for a, b in zip(jax.tree_util.tree_leaves(ours),
                    jax.tree_util.tree_leaves(ref.params)):
        assert a.shape == b.shape and a.dtype == b.dtype
    packed = (PackedLinear, PackedEmbedding)
    leaves = [l for l in jax.tree_util.tree_leaves(
        ours, is_leaf=lambda l: isinstance(l, packed))
        if isinstance(l, packed)]
    assert len(leaves) == 8 and all(l.weight.bits == 4 for l in leaves)
    for l in leaves:
        tbl = np.asarray(l.weight.table).reshape(-1, 16)
        assert np.all(np.diff(tbl, axis=1) > 0)            # sorted, distinct
        assert np.all(tbl == np.rint(tbl)) and np.abs(tbl).max() <= 127


def test_same_seed_same_packs_other_seed_other_packs():
    cfg = model_config(TINY)
    a = jax.tree_util.tree_leaves(make_params(cfg, SEED))
    b = jax.tree_util.tree_leaves(make_params(cfg, SEED))
    c = jax.tree_util.tree_leaves(make_params(cfg, SEED + 1))
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not all(np.array_equal(x, y) for x, y in zip(a, c))


def test_codr_matmul_logits_equal_tiled_logits():
    from repro.models import get_model
    cfg = model_config(TINY)
    api = get_model(cfg)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 512, (1, 24)),
                       jnp.int32)
    out = {}
    for backend in ("codr_matmul", "tiled"):
        p = make_params(cfg, SEED, backend=backend)
        lg, _ = jax.jit(lambda p, t: api.prefill(p, {"tokens": t}, cfg))(
            p, toks)
        out[backend] = np.asarray(lg, np.float32).reshape(-1)
    a, b = out["codr_matmul"], out["tiled"]
    # both lanes decode the same weights; tiled multiplies in bf16, the
    # kernel in f32: equal to within bf16 rounding of the logits
    assert np.abs(a - b).max() <= 2e-2 * np.abs(b).max()
    assert a.argmax() == b.argmax()
