"""NSA branch gating: sigmoid gates per (token, head, branch) from the layer
input, and the gated combine of the three branches."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.telemetry import named_scope


def init_gate_params(key: jax.Array, model_dim: int, num_heads: int, dtype=jnp.float32):
    scale = 1.0 / np.sqrt(model_dim)
    return {
        "w_gate": (jax.random.normal(key, (model_dim, num_heads, 3)) * scale).astype(dtype)
    }


@named_scope("nsa.gate")
def apply_gates(params, x: jnp.ndarray) -> jnp.ndarray:
    """x: (..., model_dim) -> gates (..., num_heads, 3) in (0, 1)."""
    logits = jnp.einsum("...m,mhb->...hb", x.astype(jnp.float32),
                        params["w_gate"].astype(jnp.float32))
    return jax.nn.sigmoid(logits)


@named_scope("nsa.gate")
def combine(gates, out_cmp, out_sel, out_win) -> jnp.ndarray:
    """Gate-weighted sum of the compressed, selected and sliding branch
    outputs, in float32.  gates: (..., h, 3); outputs: (..., h, dv)."""
    g = gates.astype(jnp.float32)
    return (g[..., 0:1] * out_cmp.astype(jnp.float32)
            + g[..., 1:2] * out_sel.astype(jnp.float32)
            + g[..., 2:3] * out_win.astype(jnp.float32))
