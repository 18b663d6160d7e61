"""Multi-device tests (8 forced host devices, run in a subprocess so the
main pytest process keeps its single-device view)."""
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import warnings; warnings.filterwarnings("ignore")
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.launch.mesh import make_mesh

    # ---- collective matmul == all_gather + matmul ----
    from repro.parallel.collective_matmul import all_gather_matmul
    mesh = make_mesh((8,), ("model",))
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
    w = jax.random.normal(jax.random.PRNGKey(1), (32, 48))
    with jax.set_mesh(mesh):
        y = jax.jit(lambda x, w: all_gather_matmul(x, w, mesh))(x, w)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w),
                               rtol=2e-4, atol=2e-4)
    print("collective_matmul OK")

    # ---- pipeline forward == sequential layers ----
    from repro.parallel.pipeline import make_pipelined_backbone
    mesh_p = make_mesh((4,), ("pipe",))
    n_layers, d = 8, 16
    ws = jax.random.normal(jax.random.PRNGKey(2), (n_layers, d, d)) * 0.3
    block = lambda w, h: jnp.tanh(h @ w)
    xs = jax.random.normal(jax.random.PRNGKey(3), (4, 2, 8, d))  # (micro,B,S,D)
    ref = xs
    for i in range(n_layers):
        ref = jnp.tanh(ref @ ws[i])
    fn = make_pipelined_backbone(block, n_layers, 4, mesh_p)
    with jax.set_mesh(mesh_p):
        out = jax.jit(fn)(ws, xs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    print("pipeline OK")

    # ---- sharded train step on a 2x4 mesh (FSDP x TP) ----
    from repro.configs import get_config, reduced
    from repro.launch.steps import make_train_step
    from repro.models import build
    from repro.models.registry import make_reduced_batch
    from repro.optim import AdamWConfig, init_opt_state
    from repro.parallel import partition
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = reduced(get_config("h2o-danube-3-4b"))
    mesh2 = make_mesh((2, 4), ("data", "model"))
    model = build(cfg)
    ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh2, s), t,
                                is_leaf=lambda s: isinstance(s, P))
    with jax.set_mesh(mesh2):
        params = model.init(jax.random.PRNGKey(0))
        pspecs = partition.param_specs(params, mesh2)
        from repro.optim import opt_state_specs
        state = {"params": params, "opt": init_opt_state(params, AdamWConfig())}
        sspecs = {"params": pspecs, "opt": opt_state_specs(pspecs, AdamWConfig())}
        state = jax.device_put(state, ns(sspecs))
        batch = make_reduced_batch(cfg, jax.random.PRNGKey(1), 4, 64)
        step = jax.jit(make_train_step(cfg, mesh2, AdamWConfig()),
                       in_shardings=(ns(sspecs), None),
                       out_shardings=(ns(sspecs), None), donate_argnums=(0,))
        state, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"]))
    print("sharded_train_step OK loss", float(metrics["loss"]))
""")


def _child_env():
    """A clean child env on the CPU backend (the forced host devices above
    only exist there), with the repo's sources importable."""
    env = {k: os.environ[k] for k in ("PATH", "HOME", "TMPDIR")
           if k in os.environ}
    return dict(env, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")


@pytest.mark.slow
def test_multidevice_suite():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, env=_child_env(), cwd=REPO, timeout=1200)
    assert "collective_matmul OK" in r.stdout, r.stdout + r.stderr
    assert "pipeline OK" in r.stdout, r.stdout + r.stderr
    assert "sharded_train_step OK" in r.stdout, r.stdout + r.stderr
