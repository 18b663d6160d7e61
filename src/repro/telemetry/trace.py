"""Context-manager spans: wall-clock (and optionally device-synced) timing
into metric registries, plus ``jax.profiler`` annotations so the same
regions show up labeled in XLA profiles.

A span records into up to two registries — an explicit one passed by the
caller (e.g. the serving engine's private always-on registry) and the
global registry when global telemetry is enabled — as a ``span_ms``
histogram keyed by the span name, and emits a ``span`` event (name,
duration, nesting depth, parent) to the JSONL sink.  When neither registry
is live the span is a no-op that never reads the clock.

Spans wrap host-side regions with ``jax.profiler.TraceAnnotation``; a
span's ``args`` (counts such as the rows a tick dispatched) ride on that
annotation, so a profile carries them on the same clock as the device
operations.

:func:`named_scope` labels the layers *inside* jitted code with the names in
:data:`SCOPES`.  A scope is HLO metadata only: each device operation of a
profile carries the scopes around it in its op_name
(``jit(step)/transpose(jvp())/checkpoint/rematted_computation/mlp/dot``).
Pallas kernels keep a scope of their own, their kernel's name, directly
around each ``pallas_call``; layer scopes sit outside it.
"""
from __future__ import annotations

import contextlib
import threading
import time

import jax

from repro.telemetry import metrics

_STACK = threading.local()          # per-thread span nesting stack

# The layer scopes a step or tick program is labelled with, outermost
# first where they nest.  ``tick.*`` are the engine's sub-steps; the others
# are the layers of a model step.
SCOPES = (
    "tick.prefill",     # prefill sub-step of an engine tick
    "tick.decode",      # decode sub-step of an engine tick
    "embed",            # token embedding
    "attn.qkv",         # q/k/v projections and RoPE
    "nsa.compress",     # compressed branch, importance scores, top-k
    "nsa.select",       # selected branch (kernel and its layouts)
    "nsa.index",        # FSA's index lists for the selected branch
    "nsa.window",       # sliding branch
    "nsa.gate",         # branch gates and the three-branch combine
    "attn.out",         # output projection
    "kv.gather",        # token rows read through a page table
    "kv.write",         # token rows written through a page table
    "mlp",              # dense MLP or MoE
    "lm_head",          # final projection to the vocabulary and the loss
    "optimizer",        # the AdamW update
)


def _stack() -> list:
    s = getattr(_STACK, "names", None)
    if s is None:
        s = _STACK.names = []
    return s


class SpanHandle:
    """Yielded by :func:`span`: attach annotations or device-sync targets."""

    __slots__ = ("name", "labels", "fields", "_sync")

    def __init__(self, name: str, labels: dict):
        self.name = name
        self.labels = labels          # histogram series key (keep bounded!)
        self.fields = {}              # event-only payload (any cardinality)
        self._sync = None

    def annotate(self, **fields) -> None:
        """Attach event-only fields known at exit (e.g. a batch count).
        These go to the JSONL event, NOT the histogram series key — so
        unbounded values never explode metric cardinality."""
        self.fields.update(fields)

    def sync(self, tree):
        """Mark ``tree`` (jax arrays / pytree) to be blocked on before the
        end timestamp — device-synced timing instead of dispatch timing.
        Returns ``tree`` so it drops into expressions."""
        self._sync = tree
        return tree


_NOOP_HANDLE = SpanHandle("", {})


@contextlib.contextmanager
def _scope(name: str):
    with jax.named_scope(name):
        yield


def named_scope(name: str):
    """Label a *traced* region with one of :data:`SCOPES`; usable as a
    context manager or a decorator (a fresh scope per call, so a decorated
    function may re-enter itself while it is traced)."""
    if name not in SCOPES:
        raise ValueError(f"unknown scope {name!r}; scopes: {SCOPES}")
    return _scope(name)


@contextlib.contextmanager
def span(name: str, registry: metrics.Registry | None = None, *,
         args: dict | None = None, **labels):
    """Time a host-side region.

    Records a ``span_ms`` histogram sample (keyed ``span=<name>`` plus any
    ``labels``) into ``registry`` (if given and enabled) and into the global
    registry (if globally enabled), emits a ``span`` JSONL event, and opens
    a ``jax.profiler.TraceAnnotation`` so profiler captures show the region
    under the same name.  ``args`` (numbers of any cardinality) go to the
    annotation, where a profile shows them as the event's stats, and to the
    JSONL event; never to the histogram key.
    """
    targets = []
    if registry is not None and registry.enabled:
        targets.append(registry)
    g = metrics.registry()
    if g.enabled and g is not registry:
        targets.append(g)
    if not targets and metrics.sink() is None:
        yield _NOOP_HANDLE
        return

    handle = SpanHandle(name, dict(labels))
    if args:
        handle.fields.update(args)
    stack = _stack()
    parent = stack[-1] if stack else None
    stack.append(name)
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name, **(args or {})):
            yield handle
    finally:
        if handle._sync is not None:
            jax.block_until_ready(handle._sync)
        dt_ms = (time.perf_counter() - t0) * 1e3
        stack.pop()
        for reg in targets:
            reg.histogram("span_ms", span=name, **handle.labels).observe(dt_ms)
        metrics.emit_event("span", name=name, ms=dt_ms, depth=len(stack),
                           parent=parent, **handle.labels, **handle.fields)
