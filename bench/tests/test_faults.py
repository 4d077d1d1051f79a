"""A whole run on the CPU (the look for a chip skipped) with the timed path
broken underneath: ``correct`` has to come out false for every fault the
cell can have, and true with nothing broken."""

import jax.numpy as jnp
import pytest

from bench.run import execute
from bench.tests import tiny


@pytest.fixture
def stepfn(monkeypatch):
    from repro.core import stepfn as mod
    return mod


def test_sound_train_run_is_correct():
    assert execute(tiny.job(tiny.TRAIN))["correct"] is True


def test_state_left_unchanged(stepfn, monkeypatch):
    orig = stepfn.make_train_step

    def frozen(*a, **kw):
        step = orig(*a, **kw)
        return lambda state, batch: (state, step(state, batch)[1])
    monkeypatch.setattr(stepfn, "make_train_step", frozen)
    assert execute(tiny.job(tiny.TRAIN))["correct"] is False


def test_half_batch_left_out(stepfn, monkeypatch):
    orig = stepfn.make_train_step

    def half(*a, **kw):
        step = orig(*a, **kw)

        def run(state, batch):
            n = batch["tokens"].shape[0] // 2
            return step(state, {k: v[:n] for k, v in batch.items()})
        return run
    monkeypatch.setattr(stepfn, "make_train_step", half)
    assert execute(tiny.job(tiny.TRAIN))["correct"] is False


def test_sound_serve_run_is_correct():
    assert execute(tiny.job(tiny.SERVE))["correct"] is True


def test_served_token_altered(stepfn, monkeypatch):
    orig = stepfn.make_paged_serve_step
    vocab = tiny.WIDTHS["vocab_size"]

    def altered(*a, **kw):
        step = orig(*a, **kw)

        def run(*args):
            nxt, pool = step(*args)
            return (nxt + 1) % vocab, pool
        return run
    monkeypatch.setattr(stepfn, "make_paged_serve_step", altered)
    assert execute(tiny.job(tiny.SERVE))["correct"] is False
