"""Overlapped all-gather matmul (collective matmul), shard_map + ppermute.

The Megatron TP forward needs y = x @ W with x sequence-sharded (SP) and W
column-sharded: the naive lowering all-gathers x *then* multiplies, leaving
the ICI idle during compute and the MXU idle during the gather.  The
collective matmul rotates x shards around the ring, multiplying each arriving
shard against the local W — compute hides (n-1)/n of the communication.

XLA's latency-hiding scheduler can do this rewrite itself on TPU
(`--xla_tpu_enable_async_collective_fusion` etc., see launch/xla_flags.py);
this explicit version is for when the automatic pass declines, and as the
unit-testable reference of the trick (tests/test_multidevice.py runs it on 8
forced host devices).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def _ag_matmul_body(x_shard, w_local, *, axis: str):
    """x_shard: (S/n, D) local sequence shard; w_local: (D, F/n) local cols.
    Returns (S, F/n): the full-sequence activation for the local columns."""
    n = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    s_shard = x_shard.shape[0]
    perm = [(i, (i + 1) % n) for i in range(n)]

    out = jnp.zeros((s_shard * n, w_local.shape[1]), x_shard.dtype)
    # mark the accumulator as device-varying for the shard_map scan typing
    out = jax.lax.pcast(out, (axis,), to="varying")

    def step(carry, i):
        x_cur, out = carry
        # the shard we currently hold originated at ring position (idx - i)
        src = (idx - i) % n
        y = x_cur @ w_local                      # compute overlaps the send
        x_nxt = jax.lax.ppermute(x_cur, axis, perm)
        out = jax.lax.dynamic_update_slice_in_dim(out, y, src * s_shard, 0)
        return (x_nxt, out), None

    (x_cur, out), _ = jax.lax.scan(step, (x_shard, out), jnp.arange(n))
    return out


def all_gather_matmul(x, w, mesh, *, axis: str = "model"):
    """x: (S, D) sharded P(axis, None); w: (D, F) sharded P(None, axis).
    Returns (S, F) sharded P(None, axis) — same math as (all_gather(x) @ w)."""
    fn = jax.shard_map(
        functools.partial(_ag_matmul_body, axis=axis),
        mesh=mesh,
        in_specs=(P(axis, None), P(None, axis)),
        out_specs=P(None, axis),
    )
    return fn(x, w)
