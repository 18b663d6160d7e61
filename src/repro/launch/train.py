"""Fault-tolerant training driver.

Single-host entry point (on a real cluster each host runs this under
``jax.distributed.initialize``; the mesh spans all hosts).  Features:
auto-resume from the newest valid checkpoint, deterministic step-indexed
data (bit-identical restart), heartbeat, straggler monitor, graceful
preemption, async checkpointing, non-finite-gradient skipping (inside the
jitted step), optional gradient accumulation.

Example (CPU, ~100M model):
  PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m \
      --steps 50 --batch 8 --seq 512 --mesh 1x1 --reduced
"""
from __future__ import annotations

import argparse
import time

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import telemetry
from repro.checkpoint import ckpt
from repro.configs import get_config, reduced
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_train_step
from repro.models import build
from repro.optim import AdamWConfig, init_opt_state
from repro.parallel import partition
from repro.runtime.fault_tolerance import (FTConfig, GracefulStop, Heartbeat,
                                           StragglerMonitor)


def train_loop(cfg, *, steps: int, batch: int, seq: int, mesh,
               ft: FTConfig | None = None, opt_cfg: AdamWConfig | None = None,
               num_microbatches: int = 1, log_every: int = 10,
               frames_stub: bool = False, quiet: bool = False):
    ft = ft or FTConfig()
    opt_cfg = opt_cfg or AdamWConfig()
    model = build(cfg)
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=seq,
                                  global_batch=batch))

    ns = lambda tree: jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                                   is_leaf=lambda x: isinstance(x, P))

    with jax.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0))
        pspecs = partition.param_specs(params, mesh)
        from repro.optim import opt_state_specs
        state = {"params": params, "opt": init_opt_state(params, opt_cfg)}
        state_specs = {"params": pspecs,
                       "opt": opt_state_specs(pspecs, opt_cfg)}
        state = jax.device_put(state, ns(state_specs))

        # --- auto-resume ---
        restored, start_step = ckpt.restore_latest(
            ft.ckpt_dir, state, shardings=ns(state_specs))
        if restored is not None:
            state = restored
            if not quiet:
                print(f"[train] resumed from step {start_step}")
        start = int(start_step or 0)

        step_fn = jax.jit(
            make_train_step(cfg, mesh, opt_cfg,
                            num_microbatches=num_microbatches),
            in_shardings=(ns(state_specs), None),
            out_shardings=(ns(state_specs), None),
            donate_argnums=(0,))

        hb = Heartbeat(ft.heartbeat_path)
        mon = StragglerMonitor(ft.straggler_factor, ft.window)
        stopper = GracefulStop()
        writer = None
        losses = []

        for step in range(start, steps):
            t0 = time.time()
            batch_data = data.batch_at(step)
            if frames_stub:
                batch_data["frames"] = jax.random.normal(
                    jax.random.fold_in(jax.random.PRNGKey(7), step),
                    (batch, cfg.enc_seq, cfg.d_model), jnp.dtype(cfg.dtype))
            if cfg.family == "vlm":
                batch_data["img_embeds"] = jax.random.normal(
                    jax.random.fold_in(jax.random.PRNGKey(8), step),
                    (batch, cfg.n_img_tokens, cfg.d_model),
                    jnp.dtype(cfg.dtype))
            with telemetry.span("train.step") as sp:
                state, metrics = step_fn(state, batch_data)
                sp.sync(metrics)  # device-synced ms, not dispatch latency
            loss = float(metrics["loss"])
            losses.append(loss)
            dt = time.time() - t0
            straggler = mon.record(dt)
            hb.beat(step, loss=loss, dt=dt)
            if not quiet and (step % log_every == 0 or straggler):
                flag = " STRAGGLER" if straggler else ""
                print(f"[train] step {step} loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"{dt*1e3:.0f}ms{flag}")
            if ft.ckpt_every and (step + 1) % ft.ckpt_every == 0:
                if writer is not None:
                    writer.join()
                writer = ckpt.save_async(ft.ckpt_dir, step + 1, state,
                                         keep=ft.keep)
            if stopper.stop:
                if not quiet:
                    print(f"[train] preemption at step {step}: checkpointing")
                ckpt.save(ft.ckpt_dir, step + 1, state, keep=ft.keep)
                break
        if writer is not None:
            writer.join()
    return state, losses


import jax.numpy as jnp  # noqa: E402  (used by frames stub above)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--mesh", default="1x1", help="DATAxMODEL, e.g. 2x4")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced same-family config (CPU scale)")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="checkpoints")
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    d, m = (int(x) for x in args.mesh.split("x"))
    mesh = make_mesh((d, m), ("data", "model"))
    ft = FTConfig(ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every)
    _, losses = train_loop(cfg, steps=args.steps, batch=args.batch,
                           seq=args.seq, mesh=mesh, ft=ft,
                           num_microbatches=args.microbatches,
                           frames_stub=cfg.family == "encdec")
    print(f"[train] done: first loss {losses[0]:.4f} last {losses[-1]:.4f}")


if __name__ == "__main__":
    main()
