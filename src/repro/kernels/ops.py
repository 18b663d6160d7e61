"""Compatibility facade over ``repro.attention`` (the unified dispatch API).

The kernel *implementations* live in this package (``fsa_selected``,
``fsa_faithful``, ``nsa_selected``, ``flash_attention``, ``paged_decode``);
the *dispatch* — which organization runs for which request — lives in
``repro.attention`` (capability-based backend registry, see README
"Attention API").  This module keeps the historical entry points working:

  selected_attention   — selected branch via the policy's Pallas kernel
                         (fsa | fsa_faithful | nsa | reference)
  full_attention / sliding_attention — Pallas flash wrappers
  paged_decode_attention(_batched)   — paged serving decode; ``backend=``
                         picks the registry backend (``paged_kernel`` |
                         ``paged_gather``; default: the gather reference)

Forward runs the kernel; backward goes through the shared custom-VJP
scaffolding in ``repro.attention.vjp`` — fused Pallas backward kernels
(``fsa_selected_bwd``, the flash dq/dkv kernels) for the backends that
declare ``fused_backward``, the differentiable sparse-gather twin
(identical math, XLA-differentiable) for the rest.
"""
from __future__ import annotations

from repro.core.nsa_config import NSAConfig


def selected_attention(q, k, v, idx, valid, cfg: NSAConfig):
    """Selected-branch attention. q: (N,h,d), k/v: (S,h_K,d), idx/valid:
    (N,h_K,T).  The Pallas kernel is picked by ``cfg.policy.backend``."""
    from repro import attention as uattn

    return uattn.selected_attention(q, k, v, idx, valid, cfg)


def full_attention(q, k, v, cfg: NSAConfig, *, causal: bool = True):
    """Flash full attention. q: (N,h,d), k/v: (S,h_K,d)."""
    from repro import attention as uattn

    return uattn.flash_attention(q, k, v, cfg, causal=causal, window=None)


def sliding_attention(q, k, v, window: int, cfg: NSAConfig):
    """Flash sliding-window attention (causal)."""
    from repro import attention as uattn

    return uattn.flash_attention(q, k, v, cfg, causal=True, window=window)


def paged_decode_attention_batched(gates, q, k_pages, v_pages, page_tables,
                                   cmp_k, cmp_v, pos, cfg: NSAConfig, *,
                                   backend: str | None = None,
                                   block_s: int | None = None):
    """Batched multi-slot NSA paged decode (compat wrapper; see
    ``repro.attention.backends.paged_decode_attention`` for the semantics).

    gates: (B, h, 3); q: (B, h, d); k_pages/v_pages: (N_pages, h_k, P, d*);
    page_tables: (B, max_pages) int32; cmp_k/cmp_v: (B, N_cmp_max, h_k, d*);
    pos: (B,).  Returns (B, h, dv).
    """
    from repro import attention as uattn

    # historical default of this wrapper: the gather reference
    name = backend if backend is not None else "paged_gather"
    cache = {"page_tables": page_tables, "cmp_k": cmp_k, "cmp_v": cmp_v,
             "pos": pos}
    return uattn.nsa_attention(None, gates, q, k_pages, v_pages, cache,
                               cfg=cfg, mode="paged_decode", backend=name,
                               block_s=block_s)


def paged_decode_attention(gates, q, k_pages, v_pages, page_table,
                           cmp_k, cmp_v, pos, cfg: NSAConfig, *,
                           backend: str | None = None,
                           block_s: int | None = None):
    """One-token (single-slot) NSA paged decode; see
    ``paged_decode_attention_batched`` for the semantics.  q: (h, d);
    page_table: (max_pages,); cmp_k/cmp_v: (N_cmp_max, h_k, d*); pos: scalar.
    """
    return paged_decode_attention_batched(
        gates[None], q[None], k_pages, v_pages, page_table[None],
        cmp_k[None], cmp_v[None], pos[None], cfg, backend=backend,
        block_s=block_s)[0]
