"""Device-side paged-KV indexing helpers.

Low-level (no deps besides jnp and ``repro.telemetry``'s scopes) so every
layer — kernels, model layers, the serving subsystem — can address token
rows through a page table without upward imports.  Reads run under the
``kv.gather`` scope, writes under ``kv.write``.  A page table maps a slot's
logical block index to a physical page id; page 0 is by convention a
reserved dump page (idle slots and masked writes are routed there, keeping
scatters unconditional).

Pool layout: ``(N_pages, h_K, P, d)``.  One page of one KV head is a
contiguous ``(P, d)`` tile, which is the block the paged-decode kernel
fetches per grid step: the TPU tiling needs a block's last two dims to be
multiples of (8, 128) or whole dims, so the head may not sit between the
page rows and the features.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.telemetry import named_scope


def init_pool(num_pages: int, num_heads: int, page_size: int, dim: int,
              dtype) -> jnp.ndarray:
    """Zeroed page pool in the layout above."""
    return jnp.zeros((num_pages, num_heads, page_size, dim), dtype)


@named_scope("kv.gather")
def gather_rows(pool: jnp.ndarray, table: jnp.ndarray, positions: jnp.ndarray):
    """Gather token rows through a page table.

    pool: (N_pages, h_K, P, d); table: (max_pages,) int32; positions: (M,)
    token positions (clamped into the slot's addressable range).  Returns
    (M, h_K, d).
    """
    p = pool.shape[2]
    positions = jnp.clip(positions, 0, table.shape[0] * p - 1)
    return pool[table[positions // p], :, positions % p]


@named_scope("kv.write")
def scatter_rows(pool: jnp.ndarray, table: jnp.ndarray, positions: jnp.ndarray,
                 values: jnp.ndarray, valid: jnp.ndarray | None = None,
                 min_pos: jnp.ndarray | None = None):
    """Scatter token rows through per-slot page tables.

    pool: (N_pages, h_K, P, d); table: (B, max_pages); positions: (B, M);
    values: (B, M, h_K, d).  Rows with ``valid == False`` (or positions outside
    the slot's range) are routed to dump page 0.  ``min_pos`` (B,) is a
    per-slot write floor: positions below it alias read-only shared prefix
    pages (prefix cache) and are likewise dumped.
    """
    p = pool.shape[2]
    in_range = (positions >= 0) & (positions < table.shape[1] * p)
    ok = in_range if valid is None else (valid & in_range)
    if min_pos is not None:
        ok = ok & (positions >= jnp.reshape(min_pos, (-1, 1)))
    pos_c = jnp.clip(positions, 0, table.shape[1] * p - 1)
    pages = jnp.take_along_axis(table, pos_c // p, axis=1)         # (B, M)
    pages = jnp.where(ok, pages, 0)                                # dump page
    offs = jnp.where(ok, pos_c % p, 0)
    return pool.at[pages.reshape(-1), :, offs.reshape(-1)].set(
        values.reshape((-1,) + values.shape[2:]).astype(pool.dtype))
