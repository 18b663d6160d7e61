"""End-to-end train / prefill latency (paper Fig. 5 & 6 analogue).

Measures REAL wall time of the full train_step / prefill for a reduced-size
model on CPU, comparing NSA(FSA sparse path) vs full attention — the shape of
the paper's comparison at a scale this container can execute.
"""
from __future__ import annotations

import dataclasses
import time

import jax

from repro.configs import get_config, reduced
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_train_step
from repro.models import build
from repro.models.registry import make_reduced_batch
from repro.optim import AdamWConfig, init_opt_state


def _time(fn, *args, reps=3):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def bench_arch(arch: str, seq: int = 256, batch: int = 2):
    rows = []
    mesh = make_mesh((1, 1), ("data", "model"))
    for attn, label in (("nsa", "fsa"), ("full", "full")):
        cfg = dataclasses.replace(reduced(get_config(arch)),
                                  attention=attn, n_layers=4)
        model = build(cfg)
        with jax.set_mesh(mesh):
            params = model.init(jax.random.PRNGKey(0))
            batch_data = make_reduced_batch(cfg, jax.random.PRNGKey(1),
                                            batch, seq)
            state = {"params": params,
                     "opt": init_opt_state(params, AdamWConfig())}
            step = jax.jit(make_train_step(cfg, mesh, AdamWConfig()))
            us_train = _time(step, state, batch_data)
            # prefill = loss fwd only
            fwd = jax.jit(lambda p, b: model.loss(p, b)[0])
            us_prefill = _time(fwd, params, batch_data)
        rows.append((f"{arch}/{label}", us_train, us_prefill))
    return rows


def main():
    print("e2e_bench,config,train_us_per_step,prefill_us")
    for arch in ("codeqwen1.5-7b", "h2o-danube-3-4b", "olmoe-1b-7b"):
        for name, tr, pf in bench_arch(arch):
            print(f"e2e_bench,{name},{tr:.0f},{pf:.0f}")


if __name__ == "__main__":
    main()
