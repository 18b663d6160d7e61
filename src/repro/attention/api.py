"""The single public attention entry point.

``nsa_attention`` covers every mode the repo serves — training / prefill
over a full sequence, dense-cache decode, and paged (serving) decode — and
every registered organization of the math.  Callers describe the request;
:func:`repro.attention.registry.resolve` picks the backend.

Shapes by mode (all unbatched over the slot/batch axis unless noted):

  train/prefill   q: (N, h, d);  k/v: (S, h_k, d);  cache unused
  decode          q: (h, d);     k/v: dense caches (S, h_k, d);
                  cache = {"cmp_k", "cmp_v", "pos"}
  paged_decode    q: (B, h, d);  k/v: page pools (P, page, h_k, d);
                  cache = {"page_tables", "cmp_k", "cmp_v", "pos"}  (batched)

``algorithm`` selects the math: "nsa" (three-branch NSA, needs
``params``/``gates``), "full" or "sliding" (plain attention; ``params``/
``gates`` may be None).
"""
from __future__ import annotations

import jax

from repro.attention.backends import SELECTED_KERNELS
from repro.attention.registry import AttentionRequest, resolve
from repro.core.nsa_config import SELECTED_IMPL_TO_BACKEND
from repro.telemetry import metrics as _metrics

# legacy ``ModelConfig.attn_impl`` spellings accepted as backend names;
# derived from the registry sources so new backends stay in sync
_SPARSE_NAMES = tuple(SELECTED_IMPL_TO_BACKEND.values())
_KERNEL_NAMES = SELECTED_KERNELS


def normalize_backend_name(backend: str, cfg) -> str:
    """Map legacy impl aliases ("sparse"/"kernel"/"gather") onto registry
    names, consulting the policy for the sub-choice they used to imply."""
    if backend == "sparse":
        b = cfg.policy.backend
        return b if b in _SPARSE_NAMES else "sparse_union"
    if backend == "gather":
        return "sparse_gather"
    if backend == "kernel":
        b = cfg.policy.backend
        return b if b in _KERNEL_NAMES else "fsa"
    return backend


def nsa_attention(params, gates, q, k, v, cache=None, *, cfg,
                  mode: str = "prefill", backend: str = "auto",
                  algorithm: str = "nsa", causal: bool = True,
                  window: int | None = None, q_chunk: int = 512,
                  block_s: int | None = None,
                  needs_grad: bool | None = None):
    """Attention through the capability-based backend registry.

    ``backend="auto"`` consults ``cfg.policy`` and then picks the best
    capable backend for the shape/mode/platform; explicit names are honored
    iff capable (else :class:`BackendResolutionError` names the capable
    alternatives).  One algorithm-spec exception: NSA train/prefill requests
    below ``cfg.min_seq_for_sparse`` run the dense ``reference`` fallback
    even for explicit backends — selection is degenerate at a handful of KV
    blocks (historical ``nsa_attention(impl=)`` behavior, kept).
    ``needs_grad`` defaults to True for mode="train".
    """
    if mode in ("train", "prefill"):
        seq_len, g = q.shape[0], q.shape[1] // k.shape[1]
    elif mode == "decode":
        seq_len, g = k.shape[0], q.shape[0] // k.shape[1]
    elif mode == "paged_decode":
        seq_len, g = 0, q.shape[1] // k.shape[1]
    else:
        raise ValueError(f"unknown attention mode: {mode}")

    request = AttentionRequest(
        mode=mode, algorithm=algorithm, seq_len=seq_len, g=g,
        needs_grad=(mode == "train") if needs_grad is None else needs_grad,
        paged=(mode == "paged_decode"), interpret=cfg.interpret,
        platform=jax.default_backend())
    fn = resolve(cfg, request, normalize_backend_name(backend, cfg))
    # dispatch accounting: one counter bump per *python-level* call (under
    # jit that is once per trace, which is what "which backend did resolve
    # pick, how often" means — executed time lives in the engine's tick
    # spans and the profiler's layer and kernel scopes)
    _metrics.registry().counter("attention_dispatch_total", backend=fn.name,
                                mode=mode, algorithm=algorithm).inc()
    return fn(params, gates, q, k, v, cache, cfg, mode,
              algorithm=algorithm, causal=causal, window=window,
              q_chunk=q_chunk, block_s=block_s)
