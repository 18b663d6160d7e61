"""Capability-based attention backend registry.

Every attention implementation in the repo registers here under a unique
name with a declared :class:`Capabilities` record.  Callers never dispatch
on strings or bools themselves: they describe *what they need* as an
:class:`AttentionRequest` and :func:`resolve` returns the best capable
backend — or raises a :class:`BackendResolutionError` that names the
capable alternatives.

This is the FSA/NSA thesis turned into an API: multiple kernel
organizations of the same math win in different regimes (GQA group size
``g``, sequence length, platform), so the *selection* of an organization is
data, not code scattered over if/elif ladders.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Protocol, runtime_checkable

from repro.telemetry import metrics as _metrics

MODES = ("train", "prefill", "decode", "paged_decode")
ALGORITHMS = ("nsa", "full", "sliding")


@dataclasses.dataclass(frozen=True)
class Capabilities:
    """What a backend can do.  ``resolve`` only ever picks a backend whose
    capabilities cover the request; an explicit backend request that falls
    outside its capabilities is a structured error, not a silent fallback."""

    modes: tuple = ("train", "prefill")   # subset of MODES
    algorithms: tuple = ("nsa",)          # subset of ALGORITHMS
    differentiable: bool = False          # safe under jax.grad (custom VJP ok)
    fused_backward: bool = False          # backward is a fused Pallas kernel
                                          # (not the XLA-twin fallback)
    min_g: int = 1                        # supported GQA group-size range
    max_g: Optional[int] = None
    paged: bool = False                   # reads KV through page tables
    interpret_ok: bool = True             # runs in Pallas interpret mode (CPU)
    priority: int = 0                     # auto-resolve score (higher wins)
    preferred_platforms: tuple = ()       # +100 priority on these platforms

    def describe(self) -> str:
        bits = [f"modes={'|'.join(self.modes)}",
                f"alg={'|'.join(self.algorithms)}"]
        if self.differentiable:
            bits.append("grad")
        if self.fused_backward:
            bits.append("fused-bwd")
        if self.min_g > 1 or self.max_g is not None:
            bits.append(f"g∈[{self.min_g},{self.max_g or '∞'}]")
        if self.paged:
            bits.append("paged")
        if not self.interpret_ok:
            bits.append("tpu-only")
        return ", ".join(bits)


@dataclasses.dataclass(frozen=True)
class AttentionRequest:
    """Shape/mode description a backend must cover.

    ``seq_len`` is the KV span (0 = unknown/irrelevant); ``g`` the GQA group
    size; ``needs_grad`` whether the call sits under ``jax.grad``;
    ``paged`` whether KV lives in paged storage; ``interpret`` whether the
    call must run without a TPU (Pallas interpret mode); ``platform`` the
    jax default backend ("cpu"/"tpu"/"gpu")."""

    mode: str = "prefill"
    algorithm: str = "nsa"
    seq_len: int = 0
    g: int = 1
    needs_grad: bool = False
    paged: bool = False
    interpret: bool = False
    platform: str = "cpu"


@runtime_checkable
class AttentionBackend(Protocol):
    """A registered implementation: a callable with ``name`` and
    ``capabilities`` attributes.  Call signature (all backends)::

        backend(params, gates, q, k, v, cache, cfg, mode, **kw)

    ``params``/``gates`` are the NSA compression/gate parameters (None for
    non-NSA algorithms); ``k``/``v`` are the raw KV storage (dense arrays or
    page pools); ``cache`` carries mode-specific auxiliary state (cmp caches,
    page tables, positions)."""

    name: str
    capabilities: Capabilities

    def __call__(self, params, gates, q, k, v, cache, cfg, mode, **kw): ...


class BackendResolutionError(ValueError):
    """No (capable) backend for a request.  Carries the requested name, the
    request, the rejection reason, the names of capable alternatives, and —
    when nothing is capable — the nearest misses: the backends failing the
    fewest capability criteria, with their first failing reason each, so
    the error names what to change instead of just what went wrong."""

    def __init__(self, requested: str, request: AttentionRequest,
                 reason: str, alternatives: tuple, near_misses: tuple = ()):
        self.requested = requested
        self.request = request
        self.reason = reason
        self.alternatives = tuple(alternatives)
        self.near_misses = tuple(near_misses)
        if self.alternatives:
            alt = (f" Capable backends for this request: "
                   f"{', '.join(self.alternatives)}.")
        else:
            alt = " No registered backend covers this request."
            if self.near_misses:
                misses = "; ".join(f"{n}: {r}" for n, r in self.near_misses)
                alt += (f" Nearest misses — {misses}."
                        f" (repro.attention.explain(cfg, request) prints the"
                        f" full capability table.)")
        super().__init__(
            f"attention backend '{requested}' cannot serve "
            f"mode={request.mode}/algorithm={request.algorithm} "
            f"(g={request.g}, seq_len={request.seq_len}, "
            f"needs_grad={request.needs_grad}, paged={request.paged}, "
            f"platform={request.platform}): {reason}.{alt}")


_REGISTRY: dict = {}


def register_backend(name: str, *, capabilities: Capabilities) -> Callable:
    """Decorator: register ``fn`` as attention backend ``name``."""

    def deco(fn):
        if name in _REGISTRY:
            raise ValueError(f"attention backend '{name}' already registered")
        fn.name = name
        fn.capabilities = capabilities
        _REGISTRY[name] = fn
        return fn

    return deco


def get_backend(name: str) -> AttentionBackend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown attention backend '{name}'; registered: "
            f"{', '.join(sorted(_REGISTRY))}") from None


def list_backends() -> dict:
    """name -> Capabilities for every registered backend (sorted by name)."""
    return {n: _REGISTRY[n].capabilities for n in sorted(_REGISTRY)}


def unsupported_reasons(caps: Capabilities,
                        req: AttentionRequest) -> tuple:
    """Every criterion of ``req`` that ``caps`` fails (empty = capable)."""
    reasons = []
    if req.mode not in caps.modes:
        reasons.append(f"mode '{req.mode}' not in declared modes {caps.modes}")
    if req.algorithm not in caps.algorithms:
        reasons.append(f"algorithm '{req.algorithm}' not in declared "
                       f"algorithms {caps.algorithms}")
    if req.needs_grad and not caps.differentiable:
        reasons.append(
            "not differentiable (no VJP), but gradients were requested")
    if req.g < caps.min_g:
        reasons.append(
            f"GQA group size g={req.g} below declared min_g={caps.min_g}")
    if caps.max_g is not None and req.g > caps.max_g:
        reasons.append(
            f"GQA group size g={req.g} above declared max_g={caps.max_g}")
    if req.paged and not caps.paged:
        reasons.append("does not read paged KV storage")
    if req.interpret and not caps.interpret_ok:
        reasons.append("requires compiled Pallas (no interpret-mode support)")
    return tuple(reasons)


def unsupported_reason(caps: Capabilities,
                       req: AttentionRequest) -> Optional[str]:
    """Why ``caps`` cannot serve ``req`` (None = it can; first reason)."""
    reasons = unsupported_reasons(caps, req)
    return reasons[0] if reasons else None


def capable_backends(req: AttentionRequest) -> tuple:
    """Names of all registered backends that can serve ``req``."""
    return tuple(n for n in sorted(_REGISTRY)
                 if unsupported_reason(_REGISTRY[n].capabilities, req) is None)


def near_misses(req: AttentionRequest, limit: int = 3) -> tuple:
    """((name, first reason), ...) for the backends failing the *fewest*
    capability criteria — the candidates a caller is closest to unlocking."""
    scored = []
    for n in sorted(_REGISTRY):
        reasons = unsupported_reasons(_REGISTRY[n].capabilities, req)
        if reasons:
            scored.append((len(reasons), n, reasons[0]))
    scored.sort()
    return tuple((n, r) for _, n, r in scored[:limit])


def explain(cfg, request: AttentionRequest, backend: str = "auto") -> str:
    """Human-readable capability table for ``request``: one row per
    registered backend with its auto-resolve score (capable) or its
    ``unsupported_reason`` (not capable), plus the backend ``resolve``
    would pick.  The debugging companion to
    :class:`BackendResolutionError`::

        print(repro.attention.explain(cfg, AttentionRequest(mode="train")))
    """
    rows = []
    for name in sorted(_REGISTRY):
        caps = _REGISTRY[name].capabilities
        reasons = unsupported_reasons(caps, request)
        if reasons:
            status = f"--    {'; '.join(reasons)}"
        else:
            status = f"OK    score={_score(caps, request)}"
        rows.append((name, caps.describe(), status))
    try:
        pick = f"resolve -> {resolve(cfg, request, backend).name}"
    except BackendResolutionError as e:
        pick = f"resolve -> FAILS: {e.reason}"
    w_name = max(len(r[0]) for r in rows)
    w_caps = max(len(r[1]) for r in rows)
    lines = [f"AttentionRequest(mode={request.mode}, "
             f"algorithm={request.algorithm}, g={request.g}, "
             f"seq_len={request.seq_len}, needs_grad={request.needs_grad}, "
             f"paged={request.paged}, interpret={request.interpret}, "
             f"platform={request.platform})",
             pick, ""]
    lines += [f"{n:<{w_name}}  [{c:<{w_caps}}]  {s}" for n, c, s in rows]
    return "\n".join(lines)


def _score(caps: Capabilities, req: AttentionRequest) -> int:
    score = caps.priority + (100 if req.platform in caps.preferred_platforms
                             else 0)
    # training under jax.grad: prefer backends whose backward pass is a fused
    # Pallas kernel over ones that pay the XLA-twin backward (the paper's
    # training-speedup claim lives in the backward)
    if req.mode == "train" and req.needs_grad and caps.fused_backward:
        score += 50
    return score


def resolve(cfg, request: AttentionRequest,
            backend: str = "auto") -> AttentionBackend:
    """Pick the backend for ``request``.

    Explicit ``backend`` names are honored iff capable (else a
    :class:`BackendResolutionError` naming capable alternatives).  For
    ``"auto"``, the mode's policy default (``cfg.policy``) is consulted
    first; if that is also "auto" the highest-scoring capable backend wins
    (platform preference included).  Below ``cfg.min_seq_for_sparse`` the
    dense ``reference`` fallback is picked for train/prefill NSA requests —
    selection is degenerate when the context is shorter than a handful of
    KV blocks, so sparsity cannot pay for its overhead there.
    """
    # decode-time paths exist only for the NSA cache layouts; a full/sliding
    # decode request is malformed, not merely unserved — fail it up front
    # rather than letting a backend crash on mismatched shapes
    if request.mode in ("decode", "paged_decode") and request.algorithm != "nsa":
        _record_fallback("error", request, requested=backend)
        raise BackendResolutionError(
            backend, request,
            f"mode '{request.mode}' is NSA-only (algorithm "
            f"'{request.algorithm}' has no cache-decode path)", ())

    # The policy's per-mode defaults name NSA organizations (that is what
    # KernelPolicy bundles); full/sliding requests never consult them — the
    # old cfg.kernel likewise only ever picked the NSA selected-branch
    # kernel, not the full/swa/cross-attention implementation.
    if backend == "auto" and cfg is not None and request.algorithm == "nsa":
        policy = getattr(cfg, "policy", None)
        if policy is not None:
            backend = {"train": policy.backend, "prefill": policy.backend,
                       "decode": policy.decode_backend,
                       "paged_decode": policy.paged_backend}[request.mode]

    # dense short-sequence fallback (algorithm spec, not a perf heuristic)
    if (cfg is not None and request.algorithm == "nsa"
            and request.mode in ("train", "prefill") and request.seq_len
            and request.seq_len < cfg.min_seq_for_sparse):
        if backend != "reference":
            _record_fallback("dense_short_seq", request, requested=backend)
        backend = "reference"

    if backend != "auto":
        b = get_backend(backend)
        reason = unsupported_reason(b.capabilities, request)
        if reason is not None:
            _record_fallback("error", request, requested=backend)
            raise BackendResolutionError(backend, request, reason,
                                         capable_backends(request),
                                         near_misses(request))
        return b

    names = capable_backends(request)
    if not names:
        _record_fallback("error", request, requested="auto")
        raise BackendResolutionError("auto", request,
                                     "no capable backend registered", (),
                                     near_misses(request))
    return _REGISTRY[max(
        names, key=lambda n: (_score(_REGISTRY[n].capabilities, request), n))]


def _record_fallback(kind: str, request: AttentionRequest, *,
                     requested: str) -> None:
    """Count + stream a resolution-fallback event (no-op when global
    telemetry is off)."""
    reg = _metrics.registry()
    reg.counter("attention_resolve_fallback_total", kind=kind,
                mode=request.mode).inc()
    reg.event("resolve_fallback", fallback=kind, requested=requested,
              mode=request.mode, algorithm=request.algorithm,
              seq_len=request.seq_len, g=request.g)
