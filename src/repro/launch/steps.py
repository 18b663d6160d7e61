"""Jittable train / serve step functions + their sharding assignments.

``make_train_step`` returns (step_fn, in_shardings, out_shardings) ready for
``jax.jit(...).lower(...)`` — used by both the real training driver and the
multi-pod dry-run.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import build
from repro.optim import AdamWConfig, apply_updates, cosine_with_warmup
from repro.parallel import partition
from repro.telemetry import named_scope


def make_train_state_specs(cfg, params_shape, mesh, opt_cfg: AdamWConfig):
    pspecs = partition.param_specs(params_shape, mesh)
    return {"params": pspecs,
            "opt": __import__("repro.optim", fromlist=["opt_state_specs"])
                   .opt_state_specs(pspecs, opt_cfg)}


def make_train_step(cfg, mesh, opt_cfg: AdamWConfig | None = None, *,
                    schedule=cosine_with_warmup, num_microbatches: int = 1):
    """Returns train_step: (state, batch) -> (state, metrics).

    ``num_microbatches`` > 1 enables gradient accumulation: the global batch
    is split along dim 0 and scanned, bounding activation memory to one
    microbatch while gradients accumulate in fp32 (sharded like params)."""
    model = build(cfg)
    opt_cfg = opt_cfg or AdamWConfig()

    def grads_and_loss(params, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: model.loss(p, batch), has_aux=True)(params)
        return loss, metrics, grads

    def train_step(state, batch):
        from repro.parallel.axes import shard as _shard

        params, opt = state["params"], state["opt"]
        m = num_microbatches
        if m > 1:
            mb = jax.tree.map(
                lambda x: x.reshape(m, x.shape[0] // m, *x.shape[1:]), batch)

            def body(carry, microbatch):
                gacc, lacc = carry
                microbatch = jax.tree.map(
                    lambda x: _shard(x, "batch", *([None] * (x.ndim - 1))),
                    microbatch)
                loss, metrics, grads = grads_and_loss(params, microbatch)
                gacc = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), gacc, grads)
                return (gacc, lacc + loss), metrics

            gzero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (gsum, lsum), metrics = jax.lax.scan(body, (gzero, 0.0), mb)
            grads = jax.tree.map(lambda g: g / m, gsum)
            loss = lsum / m
            metrics = jax.tree.map(lambda x: x[-1], metrics)
        else:
            loss, metrics, grads = grads_and_loss(params, batch)

        with named_scope("optimizer"):
            lr_scale = schedule(opt["step"])
            new_params, new_opt, opt_metrics = apply_updates(
                params, grads, opt, opt_cfg, lr_scale=lr_scale)
        metrics = {**metrics, **opt_metrics, "loss": loss,
                   "lr_scale": lr_scale}
        return {"params": new_params, "opt": new_opt}, metrics

    return train_step


def make_serve_step(cfg, mesh):
    """Decode step: (params, cache, tokens, pos) -> (logits, cache)."""
    model = build(cfg)

    def serve_step(params, cache, tokens, pos):
        logits, cache = model.decode_step(params, cache, tokens, pos)
        return logits, cache

    return serve_step


def state_shardings(cfg, mesh, opt_cfg: AdamWConfig, batch_example):
    """NamedShardings for (state, batch) of the train step."""
    model = build(cfg)
    params_shape = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    pspecs = partition.param_specs(params_shape, mesh)
    from repro.optim import opt_state_specs

    state_specs = {"params": pspecs, "opt": opt_state_specs(pspecs, opt_cfg)}
    batch_sp = partition.batch_specs(batch_example, mesh)
    ns = lambda tree: jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P))
    return ns(state_specs), ns(batch_sp), params_shape
