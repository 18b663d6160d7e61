"""Partitioning rules, mesh construction, serve engine, whisper decode."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, reduced
from repro.launch.mesh import make_mesh
from repro.models import build
from repro.parallel import partition
from repro.parallel.axes import axis_rules, resolve


def test_param_specs_rules():
    cfg = reduced(get_config("codeqwen1.5-7b"))
    model = build(cfg)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    mesh = make_mesh((1, 1), ("data", "model"))
    specs = partition.param_specs(params, mesh)
    flat = jax.tree_util.tree_flatten_with_path(specs)[0]
    # every leaf got a spec of matching rank
    pflat = jax.tree_util.tree_flatten_with_path(params)[0]
    for (kp, spec), (_, leaf) in zip(flat, pflat):
        assert len(spec) <= leaf.ndim


def test_divisibility_filter_drops_nondividing_axes():
    mesh = make_mesh((1, 1), ("data", "model"))  # sizes 1 divide everything
    spec = partition._filter_spec(P("data", "model"), (4, 6), mesh)
    assert spec == P("data", "model")

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 4, "model": 16}

    spec = partition._filter_spec(P("data", "model"), (8, 24), FakeMesh())
    assert spec == P("data", None)  # 24 % 16 != 0 -> model dropped


def test_batch_specs_seq_fallback():
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 8, "model": 2}

    batch = {"tokens": jax.ShapeDtypeStruct((1, 1024), jnp.int32)}
    specs = partition.batch_specs(batch, FakeMesh())
    assert specs["tokens"] == P(None, "data")  # B=1 -> shard the sequence


def test_axis_rules_override():
    with axis_rules({"seq_sp": None}):
        spec = resolve("batch", "seq_sp", "embed", shape=(8, 64, 32))
        assert spec[1] is None


def test_train_step_under_mesh_constraint_paths():
    """Exercise with_sharding_constraint paths on a real (1,1) mesh."""
    from repro.launch.steps import make_train_step
    from repro.optim import AdamWConfig, init_opt_state
    from repro.models.registry import make_reduced_batch

    cfg = reduced(get_config("olmoe-1b-7b"))
    mesh = make_mesh((1, 1), ("data", "model"))
    model = build(cfg)
    with jax.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0))
        state = {"params": params,
                 "opt": init_opt_state(params, AdamWConfig())}
        batch = make_reduced_batch(cfg, jax.random.PRNGKey(1), 4, 64)
        step = make_train_step(cfg, mesh, AdamWConfig(), num_microbatches=2)
        state, metrics = jax.jit(step, donate_argnums=(0,))(state, batch)
        assert np.isfinite(float(metrics["loss"]))


def test_serve_engine_end_to_end():
    from repro.launch.serve import Engine, Request

    cfg = reduced(get_config("h2o-danube-3-4b"))
    eng = Engine(cfg, batch_slots=2, max_len=96)
    reqs = [Request(i, jax.random.randint(jax.random.PRNGKey(i), (48,), 0,
                                          cfg.vocab), max_new=8)
            for i in range(2)]
    stats = eng.run(reqs, new_tokens=8)
    assert len(stats["outputs"][0]) == 8
    assert all(0 <= t < cfg.vocab for t in stats["outputs"][0])


def test_production_mesh_requires_512_devices():
    import pytest
    from repro.launch.mesh import make_production_mesh
    with pytest.raises(RuntimeError):
        make_production_mesh()  # only 1 device in the test process
