"""FSA selected-attention Pallas kernel — the paper's contribution, TPU-native.

The paper's FSA fills the matmul M dimension with *query tokens* attending to
the same KV block instead of padding the g (< 8) query heads of a GQA group.
On TPU the same pathology is worse (the MXU wants M = 128), and the idiomatic
gather is block-granular scalar-prefetch rather than per-token index tensors.

Organization (see DESIGN.md §2):
  grid = (h_K, num_q_blocks, union_cap)
       -- the two outer dims are core-parallel; the inner dim walks the
          scalar-prefetched *union list* of KV blocks selected by any token of
          this query block (ascending; padded by repeating the last entry so
          clamped index maps never refetch — the early-return analogue).
  M dim = B_Q · g  (all group heads folded in: one KV fetch serves the group,
          inheriting the paper's "stats once per KV head" amortization).
  Online softmax lives in VMEM scratch across the sequential inner steps — the
  TPU grid is sequential per core, so the paper's O_buf + reduction kernel
  (which exist to avoid GPU atomics) are unnecessary here.  The faithful
  three-kernel pipeline is kept in ``fsa_faithful.py`` for ablation.

Inputs (layouts produced by ops.py):
  q_rows:   (h_K, N·g, d)   token-major, group-head-minor rows
  k, v:     (h_K, N, d)
  sel_rows: (h_K, N·g, T)   per-row selected block ids, -1 where invalid
  kv_ids:   (h_K, nq, cap)  scalar-prefetch: union list per query block
  kv_cnt:   (h_K, nq)       scalar-prefetch: union length
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(kv_ids, kv_cnt, q_ref, k_ref, v_ref, sel_ref, o_ref, *rest,
            scale, g, block_q, block_k, seq_len, early_return=True,
            with_lse=False):
    if with_lse:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        m_scr, l_scr, acc_scr = rest
    hk, iq, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    cap = pl.num_programs(2)
    rows = q_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # early_return=False is the paper's Fig. 9 ablation: the inner loop walks
    # the full union cap, masking instead of skipping padded steps.
    @pl.when((j < kv_cnt[hk, iq]) if early_return else (j >= 0))
    def _step():
        blk = kv_ids[hk, iq, j]
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        # row r is token iq*B_Q + r//g; mask = (token selected blk) & causal
        tok = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 0) // g
        kpos = blk * block_k + jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 1)
        picked = jnp.any(sel_ref[0] == blk, axis=1, keepdims=True)
        mask = picked & (tok >= kpos) & (kpos < seq_len)
        if not early_return:
            mask &= j < kv_cnt[hk, iq]
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...][:, 0:1]
        l_prev = l_scr[...][:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        v = v_ref[0].astype(jnp.float32)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + pv
        l_scr[...] = jnp.broadcast_to(corr * l_prev + jnp.sum(p, 1, keepdims=True),
                                      l_scr.shape)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(j == cap - 1)
    def _done():
        l = l_scr[...][:, 0:1]
        o_ref[0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        if with_lse:
            m = m_scr[...][:, 0:1]
            # rows with no selected keys get +inf-like lse so exp(s-lse) -> 0
            lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)),
                            -NEG_INF)
            lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def fsa_selected(q_rows, k, v, sel_rows, kv_ids, kv_cnt, *, g: int,
                 block_q: int, block_k: int, seq_len: int | None = None,
                 interpret: bool = False, early_return: bool = True,
                 return_lse: bool = False):
    """Returns (h_K, N·g, d) selected-attention output (zeros for maskless rows).

    With ``return_lse=True`` also returns the per-row log-sum-exp in the
    flash-backward residual layout (h_K, N·g, 128) float32 (lane-broadcast;
    same convention as ``fsa_faithful``'s statistics kernel) for the fused
    backward pass."""
    h_k, rows_total, d = q_rows.shape
    dv = v.shape[-1]
    # seq_len is the logical key count: k/v may carry padding rows up to a
    # whole number of KV blocks (keys at positions >= seq_len are masked)
    seq_len = k.shape[1] if seq_len is None else seq_len
    nq = kv_ids.shape[1]
    cap = kv_ids.shape[2]
    rows = block_q * g
    t = sel_rows.shape[-1]
    scale = 1.0 / (d ** 0.5)

    kernel = functools.partial(_kernel, scale=scale, g=g, block_q=block_q,
                               block_k=block_k, seq_len=seq_len,
                               early_return=early_return, with_lse=return_lse)
    out_specs = [pl.BlockSpec((1, rows, dv),
                              lambda hk, iq, j, ids, cnt: (hk, iq, 0))]
    out_shape = [jax.ShapeDtypeStruct((h_k, rows_total, dv), q_rows.dtype)]
    if return_lse:
        out_specs.append(pl.BlockSpec((1, rows, 128),
                                      lambda hk, iq, j, ids, cnt: (hk, iq, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((h_k, rows_total, 128), jnp.float32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(h_k, nq, cap),
        in_specs=[
            pl.BlockSpec((1, rows, d), lambda hk, iq, j, ids, cnt: (hk, iq, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda hk, iq, j, ids, cnt: (hk, ids[hk, iq, j], 0)),
            pl.BlockSpec((1, block_k, dv),
                         lambda hk, iq, j, ids, cnt: (hk, ids[hk, iq, j], 0)),
            pl.BlockSpec((1, rows, t), lambda hk, iq, j, ids, cnt: (hk, iq, 0)),
        ],
        out_specs=out_specs if return_lse else out_specs[0],
        scratch_shapes=[
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, dv), jnp.float32),
        ],
    )
    with jax.named_scope("fsa_selected"):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=out_shape if return_lse else out_shape[0],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(kv_ids, kv_cnt, q_rows, k, v, sel_rows)
