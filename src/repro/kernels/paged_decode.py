"""Pallas paged-decode kernel: batched multi-slot NSA decode through a page
table.

Decode is the serving hot path: every engine tick produces ONE query token
per active slot.  A single slot's query is g (< 8) rows — far below the MXU's
M = 128 — so, exactly as FSA fills the M dimension with query *tokens* that
share a KV block, this kernel fills it with *slots*: the q layout is
(h_K, B·g, d) and a block of ``block_s`` slots is folded into one M dim of
``block_s·g`` rows.  One kernel launch serves the whole batch (O(1) dispatch
per engine tick instead of O(batch)).

Page-table composition (the ``fsa_selected`` BlockSpec pattern, one level
deeper): ``fsa_selected`` prefetches a union list of *logical* KV block ids
and its kv index_map reads ``ids[hk, iq, j]``.  Here the logical ids are
first translated through the slot's page table on the host side of the
launch (``phys = page_table[ids]``), and the kv index_map reads the
*physical* page id — so the kernel touches exactly the pages the NSA
branches address, at page granularity, with zero gather traffic outside the
selected pages (page size == B_K: one selected block IS one physical page).

Grid = (h_K, num_slot_blocks, union_step):
  the two outer dims are core-parallel; the inner dim walks, slot-major, the
  per-slot step list
      [T selected pages] ++ [ceil(W/P)+1 trailing sliding-window pages]
  so step j belongs to slot ``j // steps_per_slot`` of the block and is a
  selected-branch step iff ``j % steps_per_slot < T`` (both decodable from j
  alone — no prefetched metadata needed for the schedule itself).

The selected and sliding branches are *separate softmaxes* in NSA, so the
kernel keeps two online-softmax states in VMEM scratch and emits two outputs;
the compressed branch is O(N/stride) small and stays outside (shared with the
dense-cache decode via ``sparse.decode_cmp_and_select``), as does the gate
combination.  Rows of slots other than the step's slot (and steps whose
logical block id is -1: invalid selection slots, pre-sequence window pages,
idle padding slots) are masked, which leaves their softmax state untouched.

Inputs (layouts produced by ``ops.paged_decode_attention_batched``):
  q_rows:      (h_K, B·g, d)     slot-major, group-head-minor rows
  k/v_pages:   (N_pages, h_K, P, d*)  the shared paged pools; one grid step
               fetches one (P, d*) tile: one page of one KV head
  pages:       (h_K, nsb, S)     scalar-prefetch: physical page per step
  blks:        (h_K, nsb, S)     scalar-prefetch: logical block id (-1 pad)
  pos:         (B,)              scalar-prefetch: per-slot absolute position
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def num_window_pages(window: int, page_size: int) -> int:
    """Trailing pages that can overlap a W-token sliding window."""
    return -(-window // page_size) + 1


def _kernel(pages, blks, pos, q_ref, k_ref, v_ref, o_sel_ref, o_win_ref,
            m_scr, l_scr, acc_scr, *, scale, g, block_s, page_size, window,
            num_sel, steps_per_slot):
    hk, sb, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    total_steps = pl.num_programs(2)
    rows = q_ref.shape[1]                       # block_s · g

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # the schedule is decodable from j alone (slot-major step layout)
    slot = j // steps_per_slot                  # slot within this slot block
    is_sel = (j % steps_per_slot) < num_sel     # else: sliding-window step
    blk = blks[hk, sb, j]
    p = pos[sb * block_s + slot]

    q = q_ref[0].astype(jnp.float32)                          # (rows, d)
    k = k_ref[0, 0].astype(jnp.float32)                       # (P, dk)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    row_slot = jax.lax.broadcasted_iota(jnp.int32, (rows, page_size), 0) // g
    kpos = blk * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (rows, page_size), 1)
    # sliding steps also drop keys left of the window (a scalar bound: Mosaic
    # cannot select between boolean vectors)
    lo = jnp.where(is_sel, -1, p - window)
    mask = (row_slot == slot) & (blk >= 0) & (kpos <= p) & (kpos > lo)
    s = jnp.where(mask, s, NEG_INF)

    def _accum(b):
        """Online-softmax update of branch b's state (0 = sel, 1 = win)."""
        m_prev = m_scr[b][:, 0:1]
        l_prev = l_scr[b][:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        pr = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        pv = jax.lax.dot_general(pr, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[b] = acc_scr[b] * corr + pv
        l_scr[b] = jnp.broadcast_to(corr * l_prev + jnp.sum(pr, 1, keepdims=True),
                                    l_scr[b].shape)
        m_scr[b] = jnp.broadcast_to(m_new, m_scr[b].shape)

    @pl.when(is_sel)
    def _sel_step():
        _accum(0)

    @pl.when(jnp.logical_not(is_sel))
    def _win_step():
        _accum(1)

    @pl.when(j == total_steps - 1)
    def _done():
        o_sel_ref[0] = (acc_scr[0] / jnp.maximum(l_scr[0][:, 0:1], 1e-30)
                        ).astype(o_sel_ref.dtype)
        o_win_ref[0] = (acc_scr[1] / jnp.maximum(l_scr[1][:, 0:1], 1e-30)
                        ).astype(o_win_ref.dtype)


def build_decode_steps(idx, valid, page_tables, pos, *, window: int,
                       page_size: int, block_s: int):
    """Device-side step-list construction for the kernel.

    idx/valid: (B, h_K, T) per-slot selected logical blocks; page_tables:
    (B, max_pages); pos: (B,).  B must already be padded to a multiple of
    ``block_s`` (padding slots: valid all-False, pos 0, table all dump-page).

    Returns (pages, blks): both (h_K, nsb, block_s · steps_per_slot) int32,
    slot-major along the last dim; blk == -1 marks masked steps.
    """
    b, h_k, t = idx.shape
    max_pages = page_tables.shape[1]
    n_win = num_window_pages(window, page_size)

    blk_sel = jnp.where(valid, idx, -1)                        # (B, h_K, T)
    last = pos // page_size                                    # (B,)
    first = jnp.maximum((pos - window + 1) // page_size, 0)
    wb = last[:, None] - jnp.arange(n_win)[None, :]            # (B, n_win)
    blk_win = jnp.where(wb >= first[:, None], wb, -1)
    blk_win = jnp.broadcast_to(blk_win[:, None, :], (b, h_k, n_win))
    blk_all = jnp.concatenate([blk_sel, blk_win], axis=-1)     # (B, h_K, sps)

    safe = jnp.clip(blk_all, 0, max_pages - 1)
    phys = jnp.take_along_axis(
        page_tables[:, None, :], safe.reshape(b, -1)[:, None, :], axis=2)
    phys = jnp.where(blk_all >= 0, phys.reshape(blk_all.shape), 0)

    def fold(a):  # (B, h_K, sps) -> (h_K, nsb, block_s·sps)
        return (a.transpose(1, 0, 2)
                 .reshape(h_k, b // block_s, block_s * a.shape[-1]))

    return fold(phys.astype(jnp.int32)), fold(blk_all.astype(jnp.int32))


def paged_decode(q_rows, k_pages, v_pages, pages, blks, pos, *, g: int,
                 block_s: int, num_sel: int, window: int,
                 interpret: bool = False):
    """Selected + sliding branch attention over paged KV for B folded slots.

    q_rows: (h_K, B·g, d); k/v_pages: (N_pages, h_K, P, d*); pages/blks:
    (h_K, nsb, block_s·steps_per_slot) from ``build_decode_steps``; pos: (B,).
    Returns (o_sel, o_win): each (h_K, B·g, dv) float32 (zeros where a branch
    saw no unmasked key — matching ``_safe_softmax`` on fully-masked rows).
    """
    h_k, rows_total, d = q_rows.shape
    page_size = k_pages.shape[2]
    dk = k_pages.shape[-1]
    dv = v_pages.shape[-1]
    nsb = pages.shape[1]
    total_steps = pages.shape[2]
    steps_per_slot = total_steps // block_s
    rows = block_s * g
    scale = 1.0 / (d ** 0.5)

    kernel = functools.partial(
        _kernel, scale=scale, g=g, block_s=block_s, page_size=page_size,
        window=window, num_sel=num_sel, steps_per_slot=steps_per_slot)
    out_spec = pl.BlockSpec((1, rows, dv), lambda hk, sb, j, pg, bl, ps: (hk, sb, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(h_k, nsb, total_steps),
        in_specs=[
            pl.BlockSpec((1, rows, d),
                         lambda hk, sb, j, pg, bl, ps: (hk, sb, 0)),
            # kv index_map composed through the page table: ``pg`` already
            # holds page_table[ids], so one grid step fetches one physical page
            pl.BlockSpec((1, 1, page_size, dk),
                         lambda hk, sb, j, pg, bl, ps: (pg[hk, sb, j], hk, 0, 0)),
            pl.BlockSpec((1, 1, page_size, dv),
                         lambda hk, sb, j, pg, bl, ps: (pg[hk, sb, j], hk, 0, 0)),
        ],
        out_specs=[out_spec, out_spec],
        scratch_shapes=[
            pltpu.VMEM((2, rows, 128), jnp.float32),
            pltpu.VMEM((2, rows, 128), jnp.float32),
            pltpu.VMEM((2, rows, dv), jnp.float32),
        ],
    )
    with jax.named_scope("paged_decode"):
        o_sel, o_win = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((h_k, rows_total, dv), jnp.float32),
                jax.ShapeDtypeStruct((h_k, rows_total, dv), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(pages, blks, pos, q_rows, k_pages, v_pages)
    return o_sel, o_win
