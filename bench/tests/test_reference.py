"""The plain reference against the program at a small size on the CPU:
the same weights give the same logits, loss and gradients in float32, and
the float8 control lies far further away."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, spec, weights
from bench.tests import tiny

N = 96          # six 16-token blocks: selection picks among real choices


@pytest.fixture(scope="module", params=[False, True], ids=["nobias", "bias"])
def setup(request):
    conf = tiny.config("h2o-danube-3-4b", bias=request.param)
    a = spec.arch_of(conf)
    cfg = dataclasses.replace(spec.program_config(conf), dtype="float32",
                              attn_impl="sparse_union")
    params = weights.make(a, 20260917, dtype=jnp.float32)
    toks = jax.random.randint(jax.random.key(3), (1, N), 0, a["vocab"])
    return a, cfg, params, toks


def _ref_logits(params, toks, a, num):
    return reference.logits_at(params, toks[0], jnp.arange(N),
                               tuple(sorted(a.items())), num)


def test_weights_have_the_program_layout(setup):
    from repro.models import build

    a, cfg, params, _ = setup
    want = jax.eval_shape(build(cfg).init, jax.random.key(0))
    assert (jax.tree.structure(want) == jax.tree.structure(params))
    assert all(w.shape == p.shape for w, p in zip(jax.tree.leaves(want),
                                                  jax.tree.leaves(params)))


def test_logits_match_program(setup):
    from repro.models import transformer

    a, cfg, params, toks = setup
    with jax.default_matmul_precision("highest"):
        got = transformer.lm_logits(params, {"tokens": toks}, cfg)[0]
    want = _ref_logits(params, toks, a, reference.Num(False))
    got = np.asarray(got)[:, :a["vocab"]]
    scale = np.abs(want).max()
    assert np.abs(got - want).max() / scale < 1e-5


def test_fp8_control_is_far(setup):
    a, _, params, toks = setup
    f32 = np.asarray(_ref_logits(params, toks, a, reference.Num(False)))
    f8 = np.asarray(_ref_logits(params, toks, a, reference.Num(True)))
    assert np.abs(f8 - f32).max() / np.abs(f32).max() > 1e-2


def test_loss_and_grads_match_program(setup):
    from repro.models import transformer

    a, cfg, params, toks = setup
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(
            lambda p: transformer.lm_loss(p, batch, cfg)[0])(params)
    lr, gr = jax.value_and_grad(lambda p: reference.loss(
        p, batch, a, reference.Num(False), chunk_rows=32, head_rows=32))(
            params)
    assert abs(float(lp) - float(lr)) < 1e-5 * abs(float(lr))
    pairs = [(np.asarray(x), np.asarray(y))
             for x, y in zip(jax.tree.leaves(gp), jax.tree.leaves(gr))]
    # a leaf whose gradient is nought (pe_k: a key shift under softmax) is
    # measured against the median leaf, as the benchmark's check does
    med = np.median([np.linalg.norm(y) for _, y in pairs])
    for x, y in pairs:
        assert np.linalg.norm(x - y) <= 1e-5 * max(np.linalg.norm(y), med)
