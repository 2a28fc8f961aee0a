"""``repro.launch.serve`` driver: encdec cache handling + the --codr
decode-fused transformer serving path."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_variant
from repro.launch.serve import run_serve, run_serve_continuous
from repro.models import get_model

ENCDEC = smoke_variant(get_config("seamless-m4t-medium"))
QWEN = smoke_variant(get_config("qwen2.5-3b"))


def test_serve_encdec_pads_self_cache_and_generates():
    """encdec serving continues from the prefill cache: the decoder
    self-attention KV is padded out to prompt+gen length (the old path
    left it at prompt length behind dead `if False` code and replayed
    against a zeroed cross cache)."""
    res = run_serve(ENCDEC, batch=2, prompt_len=4, gen_len=3,
                    verbose=False)
    assert res["family"] == "encdec"
    assert res["gen"].shape == (2, 3)
    assert res["cache_self_len"] == 4 + 3      # padded to total
    assert np.isfinite(res["gen"]).all()


def test_serve_encdec_gen_len_zero():
    res = run_serve(ENCDEC, batch=1, prompt_len=4, gen_len=0,
                    verbose=False)
    assert res["gen"].shape == (1, 0)
    assert res["cache_self_len"] == 4          # nothing to pad


def test_encdec_decode_from_padded_prefill_cache_matches_prefill(key):
    """The padded-cache decode step must reproduce a one-token-longer
    prefill: proves the pad leaves masked tail positions inert AND that
    the kept cross-attention cache carries the real encoder output."""
    import repro.models.common as common
    import repro.models.encdec as encdec_mod
    old = common.DEFAULT_DTYPE
    common.DEFAULT_DTYPE = jnp.float32
    encdec_mod.DEFAULT_DTYPE = jnp.float32
    try:
        cfg = ENCDEC
        cfg = dataclasses.replace(cfg, remat=False)
        api = get_model(cfg)
        params = api.init_params(key, cfg)
        prefix = jax.random.normal(key, (1, cfg.frontend_seq, cfg.d_model))
        tokens = jax.random.randint(key, (1, 5), 0, cfg.vocab_size)
        lg_full, _ = api.prefill(params, {"tokens": tokens,
                                          "prefix": prefix}, cfg)
        lg4, cache = api.prefill(params, {"tokens": tokens[:, :4],
                                          "prefix": prefix}, cfg)
        pad = 5 - cache["self"][0].shape[2]
        cache = {**cache, "self": tuple(
            jnp.pad(kv, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
            for kv in cache["self"])}
        lg_step, _ = api.decode_step(params, cache, tokens[:, 4],
                                     jnp.int32(4), cfg)
        ref = np.asarray(lg_full[:, -1], np.float32)
        got = np.asarray(lg_step, np.float32)
        rel = np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-6)
        assert rel < 1e-4, rel
    finally:
        common.DEFAULT_DTYPE = old
        encdec_mod.DEFAULT_DTYPE = old


@pytest.mark.parametrize("backend", ["codr_matmul", "tiled"])
def test_serve_codr_lm_decode_fused(backend):
    """The acceptance path: an repro.models LM served end-to-end from
    the packed representation, HBM bytes measured on the pack."""
    res = run_serve(QWEN, batch=2, prompt_len=4, gen_len=3,
                    use_codr=True, codr_backend=backend, verbose=False)
    assert res["gen"].shape == (2, 3)
    assert res["backend"] == backend
    assert 0 < res["hbm_bytes"] < res["dense_bf16_bytes"]
    assert res["n_packed"] > 0


def test_serve_codr_encdec():
    res = run_serve(ENCDEC, batch=1, prompt_len=4, gen_len=2,
                    use_codr=True, codr_backend="tiled", verbose=False)
    assert res["gen"].shape == (1, 2)
    assert res["hbm_bytes"] > 0


def test_serve_continuous_checked():
    """The CI smoke contract through the importable driver: concurrent
    mixed-length requests streamed off the slot pool, every output
    asserted bit-identical to the sequential reference (check=True
    raises on any divergence)."""
    res = run_serve_continuous(QWEN, n_requests=4, n_slots=2,
                               prompt_len=4, gen_len=3, check=True,
                               verbose=False)
    assert res["checked"] == 4
    assert len(res["gen"]) == 4
    assert all(len(s) == 3 for s in res["gen"])
    assert res["peak_active"] <= 2              # pool bound respected
    assert res["prefills_run"] == 4


def test_serve_continuous_packed_ckpt_int8(tmp_path):
    """--packed-ckpt end to end: first boot compiles + saves the
    artifact, serves from the int8 paged KV pool, and check verifies
    against the dense-cache reference; a second boot mmap-loads the
    same artifact and reproduces the first run's outputs exactly (the
    artifact, not the RNG, carries the weights)."""
    import os
    path = str(tmp_path / "ck.codr")
    res = run_serve_continuous(QWEN, n_requests=3, n_slots=2,
                               prompt_len=4, gen_len=3, check=True,
                               packed_ckpt=path, verbose=False)
    assert os.path.isdir(path)
    assert res["checked"] == 3
    assert res["kv_dtype"] == "int8"            # packed boot defaults paged
    assert res["kv_page_size"] == 4
    assert res["boot_s"] is not None
    assert res["kv_bytes"] > 0
    res2 = run_serve_continuous(QWEN, n_requests=3, n_slots=2,
                                prompt_len=4, gen_len=3, check=True,
                                packed_ckpt=path, verbose=False)
    assert res2["gen"] == res["gen"]


def test_serve_continuous_bf16_paged_matches_dense(tmp_path):
    """kv_dtype=bf16 with a page size is the escape hatch: identical
    streamed tokens to the dense-pool run, same params."""
    kw = dict(n_requests=3, n_slots=2,
              prompt_len=4, gen_len=3, verbose=False)
    dense = run_serve_continuous(QWEN, **kw)
    paged = run_serve_continuous(QWEN, kv_dtype="bf16", kv_page_size=4,
                                 check=True, **kw)
    assert paged["gen"] == dense["gen"]
    assert paged["checked"] == 3
