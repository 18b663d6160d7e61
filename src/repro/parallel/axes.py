"""Logical-axis activation sharding.

Model code annotates activations with *logical* axis names via ``shard``;
the mapping to physical mesh axes lives here, so models stay mesh-agnostic.
Outside a mesh context (unit tests, single CPU) and inside a ``shard_map``
over the whole mesh, annotations are no-ops.
"""
from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import PartitionSpec as P

# logical activation axis -> mesh axis (or tuple, or None)
DEFAULT_RULES: dict[str, object] = {
    "batch": ("pod", "data"),
    "seq": None,            # attention-internal tensors stay head-sharded
    "seq_sp": "model",      # residual stream: sequence parallelism (saved
                            # activations shard over "model"; XLA inserts the
                            # Megatron-SP all-gather/reduce-scatter pairs)
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "expert": "model",
    "vocab": "model",
    "state": None,
    "cap": None,
}

_local = threading.local()


def current_rules() -> dict[str, object]:
    return getattr(_local, "rules", DEFAULT_RULES)


@contextlib.contextmanager
def axis_rules(rules: dict[str, object]):
    """Override logical→mesh rules (e.g. enable sequence parallelism)."""
    prev = current_rules()
    _local.rules = {**prev, **rules}
    try:
        yield
    finally:
        _local.rules = prev


def resolve(*names: str | None, shape: tuple[int, ...] | None = None) -> P:
    """Map logical names to mesh axes; axes that do not divide the
    corresponding dim (e.g. 8 KV heads over a 16-way model axis) are dropped."""
    rules = current_rules()
    mesh = jax.sharding.get_abstract_mesh()
    sizes = dict(mesh.shape) if not mesh.empty else {}
    if shape is not None:  # tolerate rank mismatch (e.g. decode drops seq dim)
        names = tuple(names)[:len(shape)] + (None,) * max(0, len(shape) - len(names))
    axes = []
    used: set[str] = set()
    for i, n in enumerate(names):
        r = rules.get(n) if n is not None else None
        if r is None:
            axes.append(None)
            continue
        rt = (r,) if isinstance(r, str) else tuple(r)
        rt = tuple(a for a in rt if a in mesh.axis_names and a not in used)
        if shape is not None and rt:
            total = 1
            kept = []
            for a in rt:
                if shape[i] % (total * sizes.get(a, 1)) == 0:
                    kept.append(a)
                    total *= sizes.get(a, 1)
            rt = tuple(kept)
        used.update(rt)
        axes.append(rt if len(rt) > 1 else (rt[0] if rt else None))
    return P(*axes)


def shard(x, *names: str | None):
    """Constrain activation ``x`` to the resolved logical sharding (no-op
    outside a mesh context, and inside ``shard_map`` over every mesh axis,
    where each device already holds its local block)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.are_all_axes_manual:
        return x
    return jax.lax.with_sharding_constraint(
        x, resolve(*names, shape=tuple(x.shape)))
