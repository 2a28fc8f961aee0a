"""Whole runs of each kind of cell at a small size on the CPU (the
harness's look for a chip skipped): sound, ``correct`` is true; with
the timed path broken underneath, ``correct`` comes out false.  One
chip per cell, so there is no exchange between chips to leave out."""
import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import harness
from bench.tests import tiny


@pytest.fixture
def batching():
    from repro.core import batching
    return batching


def _frozen_state(batching):
    class Frozen(batching.ContinuousBatcher):
        """Decode steps return the KV pool they were given."""

        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            step = self._step_fn
            self._step_fn = lambda p, pool, t, pos: (
                step(p, pool, t, pos)[0], pool)
    return Frozen


def _half_batch(batching):
    class Half(batching.ContinuousBatcher):
        """Decode steps leave out the first half of the slots (the ones
        a light load fills): they get the mean of the other rows."""

        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            step, h = self._step_fn, self.n_slots // 2

            def half(p, pool, t, pos):
                lg, new = step(p, pool, t, pos)
                mean = jnp.mean(lg[h:], axis=0, keepdims=True)
                return lg.at[:h].set(jnp.broadcast_to(mean, lg[:h].shape)),\
                    new
            self._step_fn = half
    return Half


def _altered_token(batching):
    class Altered(batching.ContinuousBatcher):
        """The second token of every request is changed as produced."""

        def submit(self, *a, **k):
            h = super().submit(*a, **k)
            emit = h._emit

            def altered(tok, row=None):
                emit((tok + 1) % self.cfg.vocab_size
                     if len(h._tokens) == 1 else tok, row)
            h._emit = altered
            return h
    return Altered


@pytest.mark.parametrize("cell", ["lm_qwen25_3b.chat",
                                  "lm_qwen25_3b.docqa_backlog"])
def test_lm_cell_sound_is_correct(cell):
    r = tiny.run(cell)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("fault", [_frozen_state, _half_batch,
                                   _altered_token])
def test_lm_fault_is_not_correct(fault, batching, monkeypatch):
    monkeypatch.setattr(batching, "ContinuousBatcher", fault(batching))
    r = tiny.run("lm_qwen25_3b.chat")
    assert not r["correct"], r["checks"]


def test_cnn_cell_sound_is_correct():
    r = tiny.run("cnn_vgg16.b32", seconds=1.0)
    assert r["correct"], r["checks"]
    assert r["metrics"]["images_per_s"]["value"] > 0


def test_cnn_program_is_the_same_for_every_seed(monkeypatch):
    # the program compiles the weights into its executable, so a program
    # that changed with the seed would compile in every run's set-up
    from repro.core.api import CompiledModel
    texts, inputs = set(), []
    run = CompiledModel.run

    def lowered(self, batch, **k):
        y = run(self, batch, **k)       # builds the chain's jit
        if not getattr(self, "_lowered", False):
            self._lowered = True
            x = jnp.asarray(batch, jnp.float32)
            texts.add(self.model._run_tiled.lower(x).as_text())
            inputs.append(np.asarray(batch))
        return y
    monkeypatch.setattr(CompiledModel, "run", lowered)
    c = tiny.cell("cnn_vgg16.b1")
    for seed in (tiny.SEED, tiny.SEED + 1):
        r = harness.run_cell(
            tiny.spec(), c, seed=seed, seconds=0.5, trace=False,
            t_start=time.monotonic(), clock=harness.CompileClock(),
            config=tiny.config(c["config"]), mix=tiny.mix(c["traffic"]))
        assert r["correct"], r["checks"]
    assert len(inputs) == 2 and not np.array_equal(*inputs)
    assert len(texts) == 1


def _half_images(run):
    def half(self, batch, **k):
        y = run(self, batch[: len(batch) // 2], **k)
        return jnp.concatenate([y, jnp.broadcast_to(
            y.mean(axis=0, keepdims=True), y.shape)], axis=0)
    return half


def _altered_answer(run):
    def altered(self, batch, **k):
        y = run(self, batch, **k)
        return y.at[0, 0, 0, 0].add(0.5 * jnp.max(jnp.abs(y)))
    return altered


@pytest.mark.parametrize("fault", [_half_images, _altered_answer])
def test_cnn_fault_is_not_correct(fault, monkeypatch):
    from repro.core.api import CompiledModel
    monkeypatch.setattr(CompiledModel, "run", fault(CompiledModel.run))
    r = tiny.run("cnn_vgg16.b32", seconds=1.0)
    assert not r["correct"], r["checks"]
    assert np.isfinite(r["checks"]["max_rel_dev"]["value"])
