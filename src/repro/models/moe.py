"""Mixture-of-Experts layer: top-k token-choice routing with capacity factor.

Dispatch uses scatter-add into an (E, cap, D) expert buffer and combine uses
gathers — O(E·cap·D) memory, no (tokens × E × cap) one-hot tensors, so it
scales to production shapes.  Experts are sharded over the "expert" logical
axis (expert parallelism); the scatter/gather across the expert axis lowers
to the MoE all-to-all under pjit.  Shared experts (DeepSeek style) run
densely for every token.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init
from repro.parallel.axes import shard
from repro.telemetry import named_scope


def init_moe(key, cfg) -> dict:
    m = cfg.moe
    d, f = cfg.d_model, m.d_expert
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, m.num_experts), dtype, scale=0.02),
        "w_gate": dense_init(ks[1], (m.num_experts, d, f), dtype),
        "w_in": dense_init(ks[2], (m.num_experts, d, f), dtype),
        "w_out": dense_init(ks[3], (m.num_experts, f, d), dtype),
    }
    if m.num_shared:
        p["shared"] = {
            "w_gate": dense_init(ks[4], (d, f * m.num_shared), dtype),
            "w_in": dense_init(jax.random.fold_in(ks[4], 1), (d, f * m.num_shared), dtype),
            "w_out": dense_init(jax.random.fold_in(ks[4], 2), (f * m.num_shared, d), dtype),
        }
    return p


@named_scope("mlp")
def apply_moe(p, x, cfg):
    """x: (B,S,D) -> ((B,S,D), aux load-balancing loss)."""
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)

    logits = (xt @ p["router"]).astype(jnp.float32)            # (t, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, m.top_k)               # (t, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    cap = int(max(1, m.capacity_factor * t * m.top_k / m.num_experts))
    # slot of each assignment inside its expert's capacity buffer
    flat_e = top_e.reshape(-1)                                 # (t·k,) row-major:
    eo = jax.nn.one_hot(flat_e, m.num_experts, dtype=jnp.int32)
    pos_flat = ((jnp.cumsum(eo, axis=0) - eo) * eo).sum(-1)    # (t·k,)
    pos = pos_flat.reshape(t, m.top_k)
    keep = pos < cap

    xe = jnp.zeros((m.num_experts, cap, d), xt.dtype)
    for kk in range(m.top_k):                                  # unrolled, k ≤ 8
        contrib = jnp.where(keep[:, kk, None], xt, 0)
        xe = xe.at[top_e[:, kk], jnp.minimum(pos[:, kk], cap - 1)].add(contrib)
    xe = shard(xe, "expert", "cap", "embed")

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])) * \
        jnp.einsum("ecd,edf->ecf", xe, p["w_in"])
    h = shard(h, "expert", "cap", "mlp_unsharded")
    ye = jnp.einsum("ecf,efd->ecd", h, p["w_out"])             # (E,cap,D)
    ye = shard(ye, "expert", "cap", "embed")

    y = jnp.zeros_like(xt)
    for kk in range(m.top_k):
        gath = ye[top_e[:, kk], jnp.minimum(pos[:, kk], cap - 1)]
        w = (top_p[:, kk] * keep[:, kk]).astype(xt.dtype)
        y = y + gath * w[:, None]

    if m.num_shared:
        sp = p["shared"]
        hs = jax.nn.silu(xt @ sp["w_gate"]) * (xt @ sp["w_in"])
        y = y + hs @ sp["w_out"]

    # Switch-style load-balancing aux loss
    frac_tokens = jax.nn.one_hot(top_e[:, 0], m.num_experts).mean(0)
    frac_probs = probs.mean(0)
    aux = m.num_experts * jnp.sum(frac_tokens * frac_probs)
    return y.reshape(b, s, d).astype(x.dtype), aux
