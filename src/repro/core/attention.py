"""NSA attention helpers + the legacy ``nsa_attention(impl=)`` entry.

The implementation dispatch moved to the capability-based registry in
``repro.attention`` (the single public API); ``nsa_attention`` here is kept
as a thin compatibility wrapper whose ``impl`` aliases map onto registry
backend names:

  "reference" — dense-mask oracle (test scales only)
  "sparse"    — chunked gather-based pure-JAX path -> "sparse_union"
  "kernel"    — Pallas kernels for selected + sliding branches -> "fsa"
                (or whichever kernel backend ``cfg.policy.backend`` names)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import compression, gating, selection
from repro.core.nsa_config import NSAConfig
from repro.core.reference import _gqa_out, _gqa_scores, _safe_softmax
from repro.telemetry import named_scope


def init_nsa_params(key: jax.Array, model_dim: int, num_heads: int, head_dim: int,
                    cfg: NSAConfig, dtype=jnp.float32):
    k1, k2 = jax.random.split(key)
    p = compression.init_compression_params(k1, cfg, head_dim, head_dim, dtype)
    p.update(gating.init_gate_params(k2, model_dim, num_heads, dtype))
    return p


def _cmp_and_select_chunk(params, cfg, k, v, k_cmp, v_cmp, sel_map, n, chunk):
    q_c, pos_c = chunk
    g = q_c.shape[1] // k.shape[1]
    vis = compression.cmp_visibility(pos_c, k_cmp.shape[0], cfg)
    p_cmp, _ = _safe_softmax(_gqa_scores(q_c, k_cmp), vis[:, None, :])
    out_cmp = _gqa_out(p_cmp, v_cmp).astype(q_c.dtype)
    scores = selection.importance_scores(p_cmp, sel_map, g)
    idx, valid = selection.select_blocks(scores, pos_c, cfg, n)
    return out_cmp, idx, valid


@named_scope("nsa.compress")
def compressed_and_selection(params, q, k, v, cfg: NSAConfig, *, q_chunk: int = 512):
    """Chunked compressed-branch output + block selection for all queries.

    q: (N, h, d) -> (out_cmp (N,h,dv), idx (N,h_k,T), valid (N,h_k,T)).
    """
    n, h, d = q.shape
    k_cmp, v_cmp = compression.compress_kv(params, k, v, cfg)
    sel_map = jnp.asarray(
        compression.cmp_to_sel_map(k_cmp.shape[0], cfg.num_kv_blocks(n), cfg)
    )
    c = min(q_chunk, n)
    pad = (c - n % c) % c
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    pos = jnp.arange(n + pad)
    body = functools.partial(
        _cmp_and_select_chunk, params, cfg, k, v, k_cmp, v_cmp, sel_map, n
    )
    out_cmp, idx, valid = jax.lax.map(
        body, (qp.reshape(-1, c, h, d), pos.reshape(-1, c))
    )
    t = idx.shape[-1]
    return (
        out_cmp.reshape(-1, h, v.shape[-1])[:n],
        idx.reshape(-1, k.shape[1], t)[:n],
        valid.reshape(-1, k.shape[1], t)[:n],
    )


def nsa_attention(params, gates, q, k, v, cfg: NSAConfig, *, impl: str = "sparse",
                  q_chunk: int = 512):
    """NSA attention, unbatched. q: (N,h,d), k/v: (N,h_k,d), gates: (N,h,3).

    Compatibility wrapper over ``repro.attention.nsa_attention`` — ``impl``
    accepts the legacy aliases ("sparse"/"kernel"/"reference") as well as
    any registered backend name or "auto".
    """
    from repro import attention as uattn  # lazy: avoids an import cycle

    return uattn.nsa_attention(params, gates, q, k, v, cfg=cfg, mode="train",
                               backend=impl, q_chunk=q_chunk)
