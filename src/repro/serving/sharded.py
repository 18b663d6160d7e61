"""Mesh-sharded paged serving: KV-head-sharded page pools, slot-sharded
engine replicas, one ``shard_map``ped dispatch per tick.

Layout over a ``("data", "model")`` mesh of ``d x m`` devices:

* **model axis (tensor parallelism).**  The raw and compressed page pools
  shard on their KV-head dim — NSA's compression / selection / sliding
  branches are all per-kv-head independent, and GQA groups q-heads
  kv-major, so a contiguous block of ``n_kv_heads/m`` KV heads plus its
  ``n_heads/m`` query heads is a closed sub-problem.  The attention
  projections shard to match (``parallel.partition.serve_param_specs``);
  everything else (embeddings, norms, MLP/MoE, the headless NSA compression
  MLPs) is replicated, so ONE ``psum`` per attention out-projection is the
  only model-axis collective.  KV pages never cross the mesh.

* **data axis (engine replicas).**  Slots shard over "data": replica ``r``
  owns global slots ``[r*n_local, (r+1)*n_local)`` and its own page pools,
  page tables and radix prefix cache.  Page ids in every table are
  replica-LOCAL: the global pool arrays concatenate the replica slabs on
  the page dim and shard it over "data", so under ``shard_map`` each data
  shard sees exactly its own slab and local ids address it directly — each
  replica keeps its own dump page 0.  Admission stays host-side and global
  (one FIFO scheduler over the slot facade), so the jitted dispatch is
  shared while per-replica bookkeeping stays independent.

Per tick, the only arrays crossing the mesh are the (B,)-row operands in
(tokens, positions, page tables — a few int32 per slot) and the logits out
(psum over "model", slot-sharded over "data").  The Pallas paged-decode
kernel runs unmodified per shard on purely local pages.

Constructed via ``Engine(cfg, mesh=...)`` (a 1x1 mesh falls back to the
byte-identical single-device engine) or ``launch/serve --mesh dxm``.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import transformer
from repro.parallel.partition import serve_param_specs
from repro.serving.cache import PagedNSACache
from repro.serving.engine import Engine
from repro.serving.prefix import PrefixCache

__all__ = ["MeshLayoutError", "ShardedEngine", "valid_mesh_shapes"]


# -------------------------------------------------------- mesh-shape checks
def valid_mesh_shapes(n_devices: int, n_kv_heads: int, n_slots: int
                      ) -> list[tuple[int, int]]:
    """All (data, model) factorizations of ``n_devices`` this engine can
    run: model must divide the KV heads, data must divide the slots."""
    out = []
    for m in range(1, n_devices + 1):
        if n_devices % m:
            continue
        d = n_devices // m
        if n_kv_heads % m == 0 and n_slots % d == 0:
            out.append((d, m))
    return out


class MeshLayoutError(ValueError):
    """Mesh shape incompatible with the sharding layout.  ``.valid`` carries
    every usable (data, model) factorization of the same device count."""

    def __init__(self, msg: str, valid: list[tuple[int, int]]):
        hint = ", ".join(f"{d}x{m}" for d, m in valid) or "none"
        super().__init__(f"{msg}; valid (data, model) shapes: {hint}")
        self.valid = valid


def _validate_mesh(mesh, cfg, n_slots: int) -> None:
    names = tuple(mesh.axis_names)
    if set(names) != {"data", "model"}:
        raise ValueError(
            f"ShardedEngine needs a ('data', 'model') mesh, got axes {names}")
    d, m = int(mesh.shape["data"]), int(mesh.shape["model"])
    valid = valid_mesh_shapes(d * m, cfg.n_kv_heads, n_slots)
    if cfg.n_kv_heads % m or cfg.n_heads % m:
        raise MeshLayoutError(
            f"model axis {m} does not divide n_kv_heads={cfg.n_kv_heads} "
            f"(n_heads={cfg.n_heads}) — KV pages shard per whole head",
            valid)
    if n_slots % d:
        raise MeshLayoutError(
            f"data axis {d} does not divide n_slots={n_slots} — slots shard "
            f"evenly over engine replicas", valid)


# --------------------------------------------------------------- page state
class _ReplicaCache(PagedNSACache):
    """Bookkeeping-only per-replica cache: local page pools, tables and
    lengths, NO device pytree (the facade owns one global sharded pytree).
    The copy-on-write of a prefix boundary compressed page routes to the
    facade at this replica's slab offset."""

    def __init__(self, cfg, n_slots, max_len, *, num_pages, facade, replica):
        super().__init__(cfg, n_slots, max_len, num_pages=num_pages,
                         alloc_data=False)
        self._facade = facade
        self._replica = replica

    def _copy_cmp_page(self, src: int, dst: int) -> None:
        self._facade._copy_cmp_page_global(self._replica, src, dst)


class _ShardedCache:
    """Slot-sharded facade over per-replica ``PagedNSACache`` bookkeeping
    plus ONE mesh-sharded device pytree.

    Global slot ``s`` lives on replica ``s // n_local`` as local slot
    ``s % n_local`` — the same rows the "data" axis assigns to device row
    ``s // n_local``, so host bookkeeping and device sharding agree by
    construction.  The scheduler and engine only see the global surface
    (``n_slots`` slots, one ``lengths`` vector, one ``views()`` table set).
    """

    def __init__(self, cfg, n_slots: int, max_len: int, mesh, *,
                 num_pages: int | None = None):
        self.cfg = cfg
        self.mesh = mesh
        d = int(mesh.shape["data"])
        self.n_slots = n_slots
        self.n_local = n_slots // d
        self.n_replicas = d
        # ``num_pages`` is PER REPLICA (each replica's private pool)
        self.replicas = [
            _ReplicaCache(cfg, self.n_local, max_len, num_pages=num_pages,
                          facade=self, replica=r)
            for r in range(d)]
        r0 = self.replicas[0]
        self.page_size = r0.page_size
        self.max_len = r0.max_len
        self.max_pages = r0.max_pages
        self.max_cmp_tokens = r0.max_cmp_tokens
        self.max_cmp_pages = r0.max_cmp_pages
        self.num_pages = r0.num_pages          # per replica
        self.num_cmp_pages = r0.num_cmp_pages
        # the scheduler's submit-time capacity validation reads pool sizes;
        # replicas are identical, so replica 0 speaks for all of them
        self.pool = r0.pool
        self.cmp_pool = r0.cmp_pool
        self.prefix = None                     # set by the engine (a router)
        # ONE global lengths vector; each replica's ``lengths`` is a numpy
        # VIEW of its slice, so replica-local writes (alloc/free/reset) and
        # the engine's global reads always agree
        self.lengths = np.zeros((n_slots,), np.int64)
        for r, rep in enumerate(self.replicas):
            rep.lengths = self.lengths[r * self.n_local:
                                       (r + 1) * self.n_local]
        # global device pytree: replica pool slabs concatenated on the page
        # dim (sharded over "data" -> each shard sees its own slab, local
        # page ids address it directly), KV heads sharded over "model"
        self._data_spec = P(None, "data", "model", None, None)
        init = lambda: transformer.init_lm_paged_cache(
            cfg, d * self.num_pages, d * self.num_cmp_pages)
        self._shardings = jax.tree.map(
            lambda _: NamedSharding(mesh, self._data_spec),
            jax.eval_shape(init))
        # built in place: no device ever holds the whole pool
        self.data = jax.jit(init, out_shardings=self._shardings)()
        self._dev_tables = None

    # ------------------------------------------------------------ routing
    def _route(self, slot: int) -> tuple[_ReplicaCache, int]:
        return self.replicas[slot // self.n_local], slot % self.n_local

    def pages_needed(self, capacity_tokens: int) -> tuple[int, int]:
        return self.replicas[0].pages_needed(capacity_tokens)

    def can_admit(self, capacity_tokens: int, prefix=None) -> bool:
        return any(rep.can_admit(capacity_tokens, prefix)
                   for rep in self.replicas)

    def alloc_slot(self, slot: int, capacity_tokens: int, *,
                   prefix=None) -> bool:
        rep, ls = self._route(slot)
        return rep.alloc_slot(ls, capacity_tokens, prefix=prefix)

    def free_slot(self, slot: int) -> None:
        rep, ls = self._route(slot)
        rep.free_slot(ls)

    def reset(self) -> None:
        for rep in self.replicas:
            rep.reset()                # clears each replica's prefix trie too

    def utilization(self) -> dict:
        us = [rep.utilization() for rep in self.replicas]
        return {"raw": max(u["raw"] for u in us),
                "cmp": max(u["cmp"] for u in us)}

    # ---------------------------------------------------------- device IO
    def views(self, slots=None, *, layer=None, batch_size=None) -> dict:
        """Device tables for ALL slots (replica tables stacked in global
        slot order; ids stay replica-local — see class docstring).  The
        per-slot / dense-gather views are single-device debug accessors and
        are not exposed here."""
        if slots is not None or layer is not None:
            raise NotImplementedError(
                "sharded cache exposes only the all-slot device tables "
                "(views() with no arguments)")
        if self._dev_tables is None or any(rep._tables_dirty
                                           for rep in self.replicas):
            parts = [rep.views() for rep in self.replicas]
            self._dev_tables = {
                k: jnp.concatenate([pt[k] for pt in parts], axis=0)
                for k in parts[0]}
        return self._dev_tables

    def _copy_cmp_page_global(self, replica: int, src: int, dst: int) -> None:
        """Device copy of one compressed page inside ``replica``'s slab of
        the global arrays (all layers, K and V)."""
        off = replica * self.num_cmp_pages
        layers = dict(self.data["layers"])
        for key in ("cmp_k_pages", "cmp_v_pages"):
            if key in layers:
                layers[key] = layers[key].at[:, off + dst].set(
                    layers[key][:, off + src])
        # re-pin the sharding: .at[].set on a sharded array can come back
        # with a fresh layout, and the dispatch jit donates ``data``
        self.data = jax.device_put(dict(self.data, layers=layers),
                                   self._shardings)


# ------------------------------------------------------------ prefix router
class _PrefixRouter:
    """Routes prefix-cache calls to the replica that owns (or is about to
    receive) the slot.  ``Scheduler.admit`` picks the lowest free slot
    BEFORE matching, so peeking the same ``slots.index(None)`` here selects
    the replica whose pages the subsequent ``alloc_slot`` will alias."""

    def __init__(self, prefixes: list[PrefixCache], n_local: int):
        self.prefixes = prefixes
        self.n_local = n_local
        self._scheduler = None                 # bound by ShardedEngine

    def match(self, prompt):
        try:
            slot = self._scheduler.slots.index(None)
        except ValueError:
            return None
        return self.prefixes[slot // self.n_local].match(prompt)

    def insert(self, prompt, slot: int) -> int:
        return self.prefixes[slot // self.n_local].insert(
            prompt, slot % self.n_local)

    @property
    def blocks_cached(self) -> int:
        return sum(p.blocks_cached for p in self.prefixes)

    def clear(self) -> None:
        for p in self.prefixes:
            p.clear()


# ------------------------------------------------------------------- engine
class ShardedEngine(Engine):
    """``Engine`` over a ``("data", "model")`` mesh (see module docstring).

    Construct via ``Engine(cfg, ..., mesh=make_mesh((d, m), ("data",
    "model")))`` — ``Engine.__new__`` routes here whenever the mesh spans
    more than one device.  Fused-tick only: the sequential A/B engine is a
    single-device debugging path.
    """

    def __init__(self, cfg, n_slots: int = 4, max_len: int = 1024, *,
                 mesh=None, fused: bool = True, **kwargs):
        if mesh is None:
            raise ValueError("ShardedEngine requires mesh=")
        if not fused:
            raise NotImplementedError(
                "ShardedEngine is fused-tick only (fused=False is the "
                "single-device sequential A/B reference)")
        _validate_mesh(mesh, cfg, n_slots)
        self.mesh = mesh
        self.n_data = int(mesh.shape["data"])
        self.n_model = int(mesh.shape["model"])
        super().__init__(cfg, n_slots, max_len, fused=True, **kwargs)
        if isinstance(self._prefix, _PrefixRouter):
            self._prefix._scheduler = self.scheduler

    # --------------------------------------------------- construction hooks
    def _init_params(self, params, seed):
        """Params in the serving layout (attention projections head-sharded
        over "model", the rest replicated).  Params made from ``seed`` are
        generated in place, so no device holds the whole model first."""
        key = jax.random.PRNGKey(seed)
        shapes = params if params is not None else jax.eval_shape(
            self.model.init, key)
        shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s),
            serve_param_specs(shapes, self.mesh),
            is_leaf=lambda x: isinstance(x, P))
        if params is not None:
            return jax.device_put(params, shardings)
        return jax.jit(self.model.init, out_shardings=shardings)(key)

    def _make_cache(self, cfg, n_slots, max_len, *, num_pages):
        return _ShardedCache(cfg, n_slots, max_len, self.mesh,
                             num_pages=num_pages)

    def _make_prefix(self):
        prefixes = []
        for rep in self.cache.replicas:
            pc = PrefixCache(rep)
            rep.prefix = pc        # replica-local pressure eviction
            prefixes.append(pc)
        return _PrefixRouter(prefixes, self.cache.n_local)

    def _build_dispatch(self, cfg) -> None:
        mesh, m = self.mesh, self.n_model
        # each model shard runs a contiguous KV-head block and its q-head
        # group as a closed sub-problem; head_dim is pinned so hd() survives
        # the head-count division
        cfg_local = dataclasses.replace(
            cfg, head_dim=cfg.hd(), n_heads=cfg.n_heads // m,
            n_kv_heads=cfg.n_kv_heads // m)
        psum_model = lambda t: jax.lax.psum(t, "model")

        def mixed_body(params, data, pf_toks, pf_t0, pf_len, dec_toks,
                       dec_pos, dec_active, tables):
            return transformer.lm_paged_mixed_step(
                params, data, pf_toks, pf_t0, pf_len, dec_toks, dec_pos,
                dec_active, tables, cfg_local, reduce_fn=psum_model)

        def decode_body(params, data, toks, pos, tables):
            return transformer.lm_paged_decode_step(
                params, data, toks, pos, tables, cfg_local,
                reduce_fn=psum_model)

        pspecs = serve_param_specs(self.params, mesh)
        dspecs = jax.tree.map(lambda _: self.cache._data_spec,
                              self.cache.data)
        tspecs = {"page_table": P("data", None), "cmp_table": P("data", None),
                  "write_floor": P("data"), "cmp_write_floor": P("data")}
        row = P("data")
        # the varying-axes check is off: it needs a ``vma`` on every Pallas
        # kernel's out_shape, and the psum over "model" makes the logits
        # replicated by construction
        self._mixed = jax.jit(
            jax.shard_map(
                mixed_body, mesh=mesh,
                in_specs=(pspecs, dspecs, P("data", None), row, row, row,
                          row, row, tspecs),
                out_specs=(P("data", None, None), P("data", None), dspecs),
                check_vma=False),
            donate_argnums=(1,))
        self._decode = jax.jit(
            jax.shard_map(
                decode_body, mesh=mesh,
                in_specs=(pspecs, dspecs, row, row, tspecs),
                out_specs=(P("data", None), dspecs), check_vma=False),
            donate_argnums=(1,))
        self._prefill = None     # sequential path unreachable (fused-only)
