"""Shared neural-net building blocks (functional, param-dict based)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.telemetry import named_scope


def dense_init(key, shape, dtype, scale: float | None = None):
    fan_in = shape[0] if len(shape) > 1 else 1
    s = scale if scale is not None else 1.0 / np.sqrt(fan_in)
    return (jax.random.normal(key, shape) * s).astype(dtype)


def rms_norm(x, scale, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return ((x32 * jax.lax.rsqrt(var + eps)) * (1.0 + scale.astype(jnp.float32))
            ).astype(x.dtype)


def layer_norm(x, scale, bias, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))


def apply_rope(x, pos, theta: float = 10000.0):
    """x: (..., S, h, d) rotary embedding at positions pos (..., S)."""
    d = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(d, theta))            # (d/2,)
    angles = pos[..., None].astype(jnp.float32) * freqs  # (..., S, d/2)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------- MLPs
def init_mlp(key, d_model: int, d_ff: int, kind: str, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"w_out": dense_init(k2, (d_ff, d_model), dtype)}
    if kind == "swiglu":
        p["w_in"] = dense_init(k1, (d_model, d_ff), dtype)
        p["w_gate"] = dense_init(k3, (d_model, d_ff), dtype)
    else:  # relu2 | gelu
        p["w_in"] = dense_init(k1, (d_model, d_ff), dtype)
    return p


@named_scope("mlp")
def apply_mlp(p, x, kind: str):
    from repro.parallel.axes import shard

    if kind == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_in"])
    elif kind == "relu2":
        h = jnp.square(jax.nn.relu(x @ p["w_in"]))
    elif kind == "gelu":
        h = jax.nn.gelu(x @ p["w_in"])
    else:
        raise ValueError(kind)
    if h.ndim == 3:
        h = shard(h, "batch", "seq", "mlp")
    else:  # decode step: (B, ff)
        h = shard(h, "batch", "mlp")
    return h @ p["w_out"]


def softcap(logits, cap: float):
    if cap and cap > 0:
        return jnp.tanh(logits / cap) * cap
    return logits


def cross_entropy(logits, labels, ignore_id: int = -100):
    """Mean token-level cross entropy with label masking. logits (..., V)."""
    mask = labels != ignore_id
    labels_safe = jnp.where(mask, labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels_safe[..., None], axis=-1)[..., 0]
    loss = -jnp.where(mask, ll, 0.0).sum() / jnp.maximum(mask.sum(), 1)
    return loss, mask.sum()
