"""Mesh-sharded paged serving (8 forced host devices, run in a subprocess
so the main pytest process keeps its single-device view).

Covers: exact token parity of ``ShardedEngine`` on 2x4 and 4x2
(data, model) meshes against the single-device ``Engine`` on mixed-length
continuous-batching traffic — with and without the prefix cache — the
1x1-mesh fallback to the plain engine, and the structured
``MeshLayoutError`` cases (model axis vs n_kv_heads, data axis vs slots).
"""
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
    import jax
    import numpy as np
    from repro.configs import get_config, reduced
    from repro.launch.mesh import make_mesh
    from repro.serving import Engine, MeshLayoutError, ShardedEngine

    # reduced defaults are 4 q-heads / 2 kv-heads — too small for a model
    # axis of 4, so widen the head axes (algorithm unchanged)
    cfg = reduced(get_config("h2o-danube-3-4b"), n_heads=8, n_kv_heads=4)

    def run(prompts, mesh=None, prefix=False, n_slots=4):
        eng = Engine(cfg, n_slots=n_slots, max_len=96, mesh=mesh,
                     prefix_cache=prefix)
        reqs = [eng.submit(p, max_new=4) for p in prompts]
        summary = eng.run()
        return eng, [list(r.out) for r in reqs], summary

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, size=(n,)).astype(np.int32)
               for n in (9, 21, 14, 33, 17, 8)]     # 6 reqs > 4 slots

    ref_eng, ref_out, _ = run(prompts)
    assert type(ref_eng) is Engine

    # ---- exact token parity on both mesh factorizations ----
    for shape in ((2, 4), (4, 2)):
        mesh = make_mesh(shape, ("data", "model"))
        eng, out, _ = run(prompts, mesh=mesh)
        assert isinstance(eng, ShardedEngine), type(eng)
        assert eng.n_data * eng.n_model == 8
        assert out == ref_out, (shape, out, ref_out)
        print("parity %dx%d OK" % shape)

    # ---- prefix-cache parity: 6 of 8 prompts share a 48-token prefix ----
    shared = rng.integers(0, cfg.vocab, size=(48,)).astype(np.int32)
    pp = []
    for i in range(8):
        if i % 4 != 3:
            tail = rng.integers(0, cfg.vocab, size=(
                int(rng.integers(1, 16)),)).astype(np.int32)
            pp.append(np.concatenate([shared, tail]))
        else:
            pp.append(rng.integers(0, cfg.vocab, size=(
                int(rng.integers(8, 40)),)).astype(np.int32))
    _, ref_pp, _ = run(pp)                 # reference: prefix cache OFF
    for shape in ((2, 4), (4, 2)):
        mesh = make_mesh(shape, ("data", "model"))
        _, out, s = run(pp, mesh=mesh, prefix=True)
        assert out == ref_pp, (shape, out, ref_pp)
        assert s["prefix_blocks_reused"] > 0, s
        print("prefix parity %dx%d OK reused" % shape,
              s["prefix_blocks_reused"])

    # ---- 1x1 mesh routes to the plain engine, same tokens ----
    eng11, out11, _ = run(prompts, mesh=make_mesh((1, 1), ("data", "model")))
    assert type(eng11) is Engine, type(eng11)
    assert out11 == ref_out
    print("mesh 1x1 OK")

    # ---- structured layout errors ----
    try:
        ShardedEngine(cfg, n_slots=4, max_len=96,
                      mesh=make_mesh((1, 8), ("data", "model")))
        raise SystemExit("expected MeshLayoutError (model axis)")
    except MeshLayoutError as e:
        assert "n_kv_heads" in str(e), e
        assert (4, 2) in e.valid and (2, 4) in e.valid, e.valid
    try:
        ShardedEngine(cfg, n_slots=5, max_len=96,
                      mesh=make_mesh((2, 4), ("data", "model")))
        raise SystemExit("expected MeshLayoutError (data axis)")
    except MeshLayoutError as e:
        assert "n_slots" in str(e), e
    print("layout errors OK")
""")


def _child_env():
    """A clean child env on the CPU backend (the forced host devices above
    only exist there), with the repo's sources importable."""
    env = {k: os.environ[k] for k in ("PATH", "HOME", "TMPDIR")
           if k in os.environ}
    return dict(env, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")


@pytest.mark.slow
def test_sharded_serving_suite():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, env=_child_env(), cwd=REPO, timeout=1200)
    assert "parity 2x4 OK" in r.stdout, r.stdout + r.stderr
    assert "parity 4x2 OK" in r.stdout, r.stdout + r.stderr
    assert "prefix parity 2x4 OK" in r.stdout, r.stdout + r.stderr
    assert "prefix parity 4x2 OK" in r.stdout, r.stdout + r.stderr
    assert "mesh 1x1 OK" in r.stdout, r.stdout + r.stderr
    assert "layout errors OK" in r.stdout, r.stdout + r.stderr
