"""A run with the timed path broken underneath comes out not correct.

Each test skips the look for a chip and drives the rest of a run
(``run.execute``) on a tiny cell on the CPU, once sound and once with one
fault planted in the program: a served token altered where it is produced;
a training step that returns its state unchanged; a training step whose
loss leaves out half of the batch's tokens.  (The cells run on one chip,
so there is no exchange between chips to leave out.)"""
from __future__ import annotations

import sys
import time

import pytest

from bench import spec
from bench.tests import tiny

sys.path.insert(0, str(spec.BENCH))
import run  # noqa: E402

DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def execute(cell):
    return run.execute(cell, 4000000007, 1.5, False, DEVICE, {}, time.time())


@pytest.mark.parametrize("fault", [None, "altered_token"])
def test_serving(monkeypatch, fault):
    from repro.serving import engine

    if fault:
        emit = engine.Engine._emit

        def altered(self, req, tok):
            # every request's second token comes out one id off
            emit(self, req, (tok + 1) % 256 if len(req.out) == 1 else tok)

        monkeypatch.setattr(engine.Engine, "_emit", altered)
    line = execute(tiny.serve_cell())
    assert line["correct"] is (fault is None), line["checks"]
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("fault", [None, "unchanged_state", "half_batch"])
def test_training(monkeypatch, fault):
    from repro.launch import steps

    make = steps.make_train_step

    def broken(cfg, mesh, opt_cfg=None, **kw):
        step = make(cfg, mesh, opt_cfg, **kw)
        if fault == "unchanged_state":
            return lambda state, batch: (state, step(state, batch)[1])
        n = tiny.train_cell().traffic["seq_len"]
        return lambda state, batch: step(state, dict(
            batch, labels=batch["labels"].at[:, n // 2:].set(-100)))

    if fault:
        monkeypatch.setattr(steps, "make_train_step", broken)
    line = execute(tiny.train_cell())
    assert line["correct"] is (fault is None), line["checks"]
