"""Pipeline parallelism: circular 1F1B-style schedule over a "pipe" mesh axis.

Implemented with shard_map + ppermute (the JAX-native pattern): each pipe
group owns one contiguous stage of layers; microbatch activations rotate
through stages; the bubble is (n_stages - 1) of (n_micro + n_stages - 1)
ticks.  Used as an optional alternative to FSDP for the 104B config —
cross-stage traffic is one (B_micro, S, D) activation per tick instead of
per-layer weight all-gathers, which is the right trade at very large D.

``pipeline_forward`` is schedule-correct for the forward pass; training uses
jax.grad through it (scan-of-ppermute transposes to the reverse schedule
automatically — the 1F1B memory profile then comes from remat on stage_fn).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def pipeline_forward(stage_fn, stage_params, x, *, axis: str = "pipe"):
    """Run inside shard_map over ``axis``.

    stage_fn: (params_for_stage, activations) -> activations
    stage_params: params with leading stage dim SHARDED over ``axis`` (each
        group sees its own slice with leading dim 1).
    x: (n_micro, B_micro, S, D) microbatched input, replicated over ``axis``.
    Returns (n_micro, B_micro, S, D) final-stage outputs (valid on the last
    stage; callers psum-select or gather as needed).
    """
    n_stages = jax.lax.axis_size(axis)
    stage = jax.lax.axis_index(axis)
    n_micro = x.shape[0]
    ticks = n_micro + n_stages - 1

    my_params = jax.tree.map(lambda a: a[0], stage_params)
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick(carry, t):
        inflight, outputs = carry
        # stage 0 injects microbatch t (if any); others take the rotated act
        mb_idx = jnp.clip(t, 0, n_micro - 1)
        inject = x[mb_idx]
        cur = jnp.where(stage == 0, inject, inflight)
        out = stage_fn(my_params, cur)
        # last stage records its finished microbatch (t - n_stages + 1)
        done_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
        is_done = (stage == n_stages - 1) & (t >= n_stages - 1)
        outputs = jax.lax.dynamic_update_index_in_dim(
            outputs,
            jnp.where(is_done, out, outputs[done_idx]),
            done_idx, 0)
        nxt = jax.lax.ppermute(out, axis, perm)
        return (nxt, outputs), None

    init = jax.lax.pcast((jnp.zeros_like(x[0]), jnp.zeros_like(x)), (axis,),
                         to="varying")
    (_, outputs), _ = jax.lax.scan(tick, init, jnp.arange(ticks))
    # broadcast final outputs from the last stage to all groups
    outputs = jax.lax.ppermute(
        outputs, axis, [( (n_stages - 1 + i) % n_stages, i) for i in range(n_stages)])
    # after rotation by 1 from last stage, stage 0 holds them; share via psum
    mask = (stage == 0).astype(outputs.dtype)
    return jax.lax.psum(outputs * mask, axis)


def make_pipelined_backbone(block_fn, n_layers: int, n_stages: int,
                            mesh, *, axis: str = "pipe"):
    """Wrap a per-layer block into a pipelined backbone.

    block_fn: (layer_params, x) -> x.  Layers are grouped into n_stages
    contiguous stages of n_layers // n_stages layers (stacked params).
    Returns fn(stacked_params, x_microbatched) for use under jit with
    ``mesh`` containing the ``axis`` dimension.
    """
    assert n_layers % n_stages == 0
    per = n_layers // n_stages

    def stage_fn(params_stage, x):
        def body(h, p_layer):
            return block_fn(p_layer, h), None
        # params_stage: (per, ...) slice of this stage's layers
        h, _ = jax.lax.scan(body, x, params_stage)
        return h

    def fn(stacked_params, x_micro):
        # stacked_params leading dim = n_layers -> (n_stages, per, ...)
        grouped = jax.tree.map(
            lambda a: a.reshape((n_stages, per) + a.shape[1:]), stacked_params)
        pipe = jax.shard_map(
            functools.partial(pipeline_forward, stage_fn, axis=axis),
            mesh=mesh,
            in_specs=(P(axis), P()),
            out_specs=P(),
        )
        return pipe(grouped, x_micro)

    return fn
