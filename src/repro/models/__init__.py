"""Model zoo facade: uniform API over decoder-only and encoder-decoder
families.

    api = get_model(cfg)
    params = api.init_params(key, cfg)
    loss = api.train_loss(params, batch, cfg)
    logits, cache = api.prefill(params, batch, cfg)
    logits, cache = api.decode_step(params, cache, token, pos, cfg)
"""
from __future__ import annotations

import types

import jax.numpy as jnp

from repro.models import encdec, lm


def get_model(cfg) -> types.SimpleNamespace:
    if cfg.family == "encdec":
        def prefill(params, batch, cfg):
            return encdec.prefill(params, batch["prefix"], batch["tokens"],
                                  cfg)

        def init_cache(cfg, batch, seq, dtype=jnp.bfloat16, paged=None):
            if paged is not None:
                raise NotImplementedError(
                    "paged KV cache is decoder-only for now "
                    "(enc-dec caches carry a cross-attention half)")
            return encdec.init_cache(cfg, batch, seq,
                                     enc_seq=cfg.frontend_seq or seq,
                                     dtype=dtype)

        return types.SimpleNamespace(
            init_params=encdec.init_params, train_loss=encdec.train_loss,
            prefill=prefill, decode_step=encdec.decode_step,
            init_cache=init_cache)

    def prefill(params, batch, cfg, **kw):
        return lm.prefill(params, batch["tokens"], cfg,
                          prefix=batch.get("prefix"), **kw)

    def init_cache(cfg, batch, seq, dtype=jnp.bfloat16, paged=None):
        return lm.init_cache(cfg, batch, seq, dtype=dtype, paged=paged)

    return types.SimpleNamespace(
        init_params=lm.init_params, train_loss=lm.train_loss,
        prefill=prefill, decode_step=lm.decode_step, init_cache=init_cache)
