"""Optimizer / checkpoint / data / runtime / mamba / HLO-analysis tests."""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ckpt
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.optim import AdamWConfig, apply_updates, init_opt_state
from repro.optim.schedule import cosine_with_warmup
from repro.runtime.fault_tolerance import (Heartbeat, StragglerMonitor,
                                           elastic_mesh_for)


# ------------------------------------------------------------------ optim
def test_adamw_converges_on_quadratic():
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, grad_clip=0.0)
    params = {"w": jnp.array([5.0, -3.0])}
    state = init_opt_state(params, cfg)
    target = jnp.array([1.0, 2.0])
    for _ in range(200):
        grads = {"w": 2 * (params["w"] - target)}
        params, state, _ = apply_updates(params, grads, state, cfg)
    np.testing.assert_allclose(params["w"], target, atol=1e-2)


def test_adamw_skips_nonfinite_grads():
    cfg = AdamWConfig(lr=0.1)
    params = {"w": jnp.ones(3)}
    state = init_opt_state(params, cfg)
    bad = {"w": jnp.array([jnp.nan, 1.0, 1.0])}
    new_params, new_state, m = apply_updates(params, bad, state, cfg)
    assert bool(m["skipped"])
    np.testing.assert_allclose(new_params["w"], params["w"])
    assert int(new_state["step"]) == 0


def test_grad_clip_bounds_update():
    cfg = AdamWConfig(lr=1.0, grad_clip=1.0, weight_decay=0.0)
    params = {"w": jnp.zeros(4)}
    state = init_opt_state(params, cfg)
    huge = {"w": jnp.full(4, 1e6)}
    _, _, m = apply_updates(params, huge, state, cfg)
    assert float(m["grad_norm"]) > 1e5  # raw norm reported pre-clip


def test_schedule_warmup_and_decay():
    s = jnp.arange(0, 1000)
    lr = cosine_with_warmup(s, warmup=100, total=1000)
    assert float(lr[0]) == 0.0
    assert float(lr[99]) <= 1.0 and float(lr[100]) == pytest.approx(1.0, abs=0.02)
    assert float(lr[-1]) < float(lr[200])


# ------------------------------------------------------------------ ckpt
def test_checkpoint_roundtrip(tmp_path):
    state = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
             "nested": {"b": jnp.ones(4, jnp.bfloat16)},
             "step": jnp.array(7)}
    ckpt.save(tmp_path, 10, state)
    restored, step = ckpt.restore_latest(tmp_path, state)
    assert step == 10
    np.testing.assert_allclose(restored["a"], state["a"])
    assert restored["nested"]["b"].dtype == jnp.bfloat16


def test_checkpoint_retention_and_latest(tmp_path):
    state = {"a": jnp.zeros(2)}
    for s in (1, 2, 3, 4, 5):
        ckpt.save(tmp_path, s, state, keep=2)
    steps = sorted(int(p.name.split("_")[1]) for p in tmp_path.glob("step_*"))
    assert steps == [4, 5]
    assert ckpt.latest_step(tmp_path) == 5


def test_checkpoint_damaged_falls_back(tmp_path):
    state = {"a": jnp.zeros(2)}
    ckpt.save(tmp_path, 1, state)
    ckpt.save(tmp_path, 2, state)
    # damage newest: remove a leaf file
    victim = next((tmp_path / "step_2").glob("*.npy"))
    victim.unlink()
    assert ckpt.latest_step(tmp_path) == 1


# ------------------------------------------------------------------ data
def test_data_deterministic_and_resumable():
    data = SyntheticLM(DataConfig(vocab=64, seq_len=32, global_batch=2, seed=3))
    b1 = data.batch_at(17)
    b2 = data.batch_at(17)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = data.batch_at(18)
    assert not np.array_equal(b1["tokens"], b3["tokens"])
    # labels are next-token shifted
    np.testing.assert_array_equal(b1["labels"][:, :-1], b1["tokens"][:, 1:])
    assert (np.asarray(b1["labels"][:, -1]) == -100).all()


# ------------------------------------------------------------------ runtime
def test_straggler_monitor():
    mon = StragglerMonitor(factor=2.0, window=20)
    flags = [mon.record(0.1) for _ in range(10)]
    assert not any(flags)
    assert mon.record(0.5) is True
    assert mon.flagged == 1


def test_heartbeat_staleness(tmp_path):
    hb = Heartbeat(tmp_path / "hb.json")
    hb.beat(5, loss=1.0)
    assert not hb.stale(timeout_s=60)
    rec = json.loads((tmp_path / "hb.json").read_text())
    assert rec["step"] == 5


def test_elastic_mesh_shapes():
    assert elastic_mesh_for(256) == ((16, 16), ("data", "model"))
    assert elastic_mesh_for(24) == ((3, 8), ("data", "model"))
    assert elastic_mesh_for(7) == ((7, 1), ("data", "model"))


# ------------------------------------------------------------------ mamba
def test_ssd_chunked_matches_naive_recurrence():
    from repro.models.mamba2 import ssd_chunked

    b, l, h, p, n = 2, 32, 3, 4, 5
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    bb = jax.random.normal(ks[3], (b, l, h, n))
    cc = jax.random.normal(jax.random.fold_in(ks[3], 1), (b, l, h, n))

    # naive sequential recurrence
    state = jnp.zeros((b, h, p, n))
    ys = []
    for t in range(l):
        decay = jnp.exp(dt[:, t] * a)                      # (b,h)
        state = state * decay[:, :, None, None] + jnp.einsum(
            "bh,bhn,bhp->bhpn", dt[:, t], bb[:, t], x[:, t])
        ys.append(jnp.einsum("bhn,bhpn->bhp", cc[:, t], state))
    y_naive = jnp.stack(ys, axis=1)

    for chunk in (8, 16, 32):
        y, final = ssd_chunked(x, dt, a, bb, cc, chunk)
        np.testing.assert_allclose(y, y_naive, atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(final, state, atol=1e-4, rtol=1e-4)


def test_mamba_decode_continues_forward():
    """Prefill state + one decode step == forward over S+1 tokens."""
    from repro.configs.base import ModelConfig, SSMConfig
    from repro.models.mamba2 import (init_mamba, mamba_decode_step,
                                     mamba_forward)

    cfg = ModelConfig(d_model=32, dtype="float32",
                      ssm=SSMConfig(d_state=8, d_conv=4, expand=2, head_dim=8,
                                    chunk=8))
    p = init_mamba(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 17, 32))
    y_full, _ = mamba_forward(p, x, cfg)
    y_pre, (conv, ssm) = mamba_forward(p, x[:, :16], cfg)
    y_t, _, _ = mamba_decode_step(p, x[:, 16], conv, ssm, cfg)
    np.testing.assert_allclose(y_t, y_full[:, 16], atol=1e-4, rtol=1e-4)


def test_compile_cache_dir(monkeypatch):
    """The persistent compile cache stays where JAX_COMPILATION_CACHE_DIR
    puts it; with the variable unset it goes to <repo>/.jax_cache."""
    from repro.launch.compile_cache import enable_compile_cache

    prev = jax.config.jax_compilation_cache_dir
    try:
        # JAX reads the variable itself at start-up; mirror that here
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        jax.config.update("jax_compilation_cache_dir", "/elsewhere/cache")
        assert enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == "/elsewhere/cache"

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        repo_cache = pathlib.Path(__file__).resolve().parents[1] / ".jax_cache"
        assert enable_compile_cache() == str(repo_cache)
        assert jax.config.jax_compilation_cache_dir == str(repo_cache)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


# ------------------------------------------------------------ hlo analysis
def test_hlo_analyzer_trip_count_correction():
    from repro.launch.hlo_analysis import analyze

    def f_scan(x, w):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        y, _ = jax.lax.scan(body, x, w)
        return y

    def f_unroll(x, w):
        for i in range(4):
            x = jnp.tanh(x @ w[i])
        return x

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((4, 64, 64), jnp.float32)
    rs = analyze(jax.jit(f_scan).lower(x, w).compile().as_text())
    ru = analyze(jax.jit(f_unroll).lower(x, w).compile().as_text())
    expected = 4 * 2 * 64 ** 3
    assert rs["flops"] == expected
    assert ru["flops"] == expected
