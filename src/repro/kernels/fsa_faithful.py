"""FSA paper-faithful three-kernel pipeline (GPU structure, block-granular).

Mirrors the published decomposition exactly (DESIGN.md §2, ablation twin of
``fsa_selected.py``):

  1. **online-softmax statistics kernel** — pre-computes per-row log-sum-exp
     over that row's selected blocks, so the main kernel emits final-scaled
     partials (the paper's "decouple online softmax statistics").
  2. **selected-attention kernel** — the paper's loop order: grid walks KV
     blocks in the outer loop, the scalar-prefetched list of query blocks
     attending each KV block (I_i) in the inner loop; partial results go to
     an intermediate buffer ``O_buf`` addressed by the O_i slot mapping —
     no reduction in this kernel (the GPU-atomics-avoidance structure).
     Padded steps are routed to a dump slot (index ``cap``) so no masking of
     stale memory is ever needed.
  3. **reduction kernel** — accumulates the O_buf slots of each query block
     (partials are already normalized by lse, so reduction is a plain sum).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


# ---------------------------------------------------------------- kernel 1
def _stats_kernel(kv_ids, kv_cnt, q_ref, k_ref, sel_ref, lse_ref, m_scr, l_scr,
                  *, scale, g, block_q, block_k, seq_len):
    hk, iq, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    cap = pl.num_programs(2)
    rows = q_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    @pl.when(j < kv_cnt[hk, iq])
    def _step():
        blk = kv_ids[hk, iq, j]
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        tok = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 0) // g
        kpos = blk * block_k + jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 1)
        picked = jnp.any(sel_ref[0] == blk, axis=1, keepdims=True)
        mask = picked & (tok >= kpos) & (kpos < seq_len)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...][:, 0:1]
        l_prev = l_scr[...][:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_scr[...] = jnp.broadcast_to(
            jnp.exp(m_prev - m_new) * l_prev + jnp.sum(p, 1, keepdims=True),
            l_scr.shape)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(j == cap - 1)
    def _done():
        m = m_scr[...][:, 0:1]
        l = l_scr[...][:, 0:1]
        # rows with no selected keys get +inf-like lse so exp(s - lse) -> 0
        lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), -NEG_INF)
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


# ---------------------------------------------------------------- kernel 2
def _partial_kernel(q_ids, slot_ids, q_cnt, q_ref, k_ref, v_ref, sel_ref,
                    lse_ref, obuf_ref, *, scale, g, block_q, block_k, seq_len):
    hk, ib, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    rows = q_ref.shape[1]
    qb = q_ids[hk, ib, j]

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    tok = qb * block_q + jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 0) // g
    kpos = ib * block_k + jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 1)
    picked = jnp.any(sel_ref[0] == ib, axis=1, keepdims=True)
    mask = picked & (tok >= kpos) & (kpos < seq_len)
    lse = lse_ref[0][:, 0:1]
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)   # final-scaled: no rescale later
    pv = jax.lax.dot_general(p, v_ref[0].astype(jnp.float32),
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    obuf_ref[0, 0, 0] = pv.astype(obuf_ref.dtype)


# ---------------------------------------------------------------- kernel 3
def _reduce_kernel(kv_cnt, obuf_ref, o_ref, acc_scr):
    hk, iq, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    cap = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j < kv_cnt[hk, iq])
    def _step():
        acc_scr[...] += obuf_ref[0, 0, 0].astype(jnp.float32)

    @pl.when(j == cap - 1)
    def _done():
        o_ref[0] = acc_scr[...].astype(o_ref.dtype)


def fsa_faithful(q_rows, k, v, sel_rows, kv_ids, kv_cnt, q_ids, slot_ids, q_cnt,
                 *, g: int, block_q: int, block_k: int,
                 seq_len: int | None = None, interpret: bool = False,
                 return_lse: bool = False):
    """Three-kernel FSA (paper structure). Same I/O contract as fsa_selected.

    ``return_lse=True`` additionally returns the statistics kernel's per-row
    log-sum-exp (h_K, N·g, 128) float32 — the fused-backward residual (no
    extra compute: kernel 1 produces it anyway)."""
    h_k, rows_total, d = q_rows.shape
    dv = v.shape[-1]
    seq_len = k.shape[1] if seq_len is None else seq_len
    nq, cap = kv_ids.shape[1], kv_ids.shape[2]
    nb, capq = q_ids.shape[1], q_ids.shape[2]
    rows = block_q * g
    t = sel_rows.shape[-1]
    scale = 1.0 / (d ** 0.5)

    # ---- kernel 1: statistics --------------------------------------------
    stats = functools.partial(_stats_kernel, scale=scale, g=g, block_q=block_q,
                              block_k=block_k, seq_len=seq_len)
    with jax.named_scope("fsa_faithful_stats"):
        lse = pl.pallas_call(
            stats,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(h_k, nq, cap),
                in_specs=[
                    pl.BlockSpec((1, rows, d),
                                 lambda hk, iq, j, i1, c1: (hk, iq, 0)),
                    pl.BlockSpec((1, block_k, d),
                                 lambda hk, iq, j, i1, c1:
                                     (hk, i1[hk, iq, j], 0)),
                    pl.BlockSpec((1, rows, t),
                                 lambda hk, iq, j, i1, c1: (hk, iq, 0)),
                ],
                out_specs=pl.BlockSpec((1, rows, 128),
                                       lambda hk, iq, j, i1, c1: (hk, iq, 0)),
                scratch_shapes=[pltpu.VMEM((rows, 128), jnp.float32)] * 2,
            ),
            out_shape=jax.ShapeDtypeStruct((h_k, rows_total, 128),
                                           jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(kv_ids, kv_cnt, q_rows, k, sel_rows)

    # ---- kernel 2: KV-block-major partials into O_buf ---------------------
    partial = functools.partial(_partial_kernel, scale=scale, g=g,
                                block_q=block_q, block_k=block_k, seq_len=seq_len)

    def _obuf_index(hk, ib, j, qi, si, qc):
        # dump slot (cap) for padded steps so valid slots are never clobbered
        slot = jnp.where(j < qc[hk, ib], si[hk, ib, j], cap)
        return (hk, qi[hk, ib, j], slot, 0, 0)

    with jax.named_scope("fsa_faithful_partial"):
        obuf = pl.pallas_call(
            partial,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(h_k, nb, capq),
                in_specs=[
                    pl.BlockSpec((1, rows, d),
                                 lambda hk, ib, j, qi, si, qc:
                                     (hk, qi[hk, ib, j], 0)),
                    pl.BlockSpec((1, block_k, d),
                                 lambda hk, ib, j, qi, si, qc: (hk, ib, 0)),
                    pl.BlockSpec((1, block_k, dv),
                                 lambda hk, ib, j, qi, si, qc: (hk, ib, 0)),
                    pl.BlockSpec((1, rows, t),
                                 lambda hk, ib, j, qi, si, qc:
                                     (hk, qi[hk, ib, j], 0)),
                    pl.BlockSpec((1, rows, 128),
                                 lambda hk, ib, j, qi, si, qc:
                                     (hk, qi[hk, ib, j], 0)),
                ],
                out_specs=pl.BlockSpec((1, 1, 1, rows, dv), _obuf_index),
            ),
            out_shape=jax.ShapeDtypeStruct((h_k, nq, cap + 1, rows, dv),
                                           jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary")),
            interpret=interpret,
        )(q_ids, slot_ids, q_cnt, q_rows, k, v, sel_rows, lse)

    # ---- kernel 3: reduction ----------------------------------------------
    with jax.named_scope("fsa_faithful_reduce"):
        out = pl.pallas_call(
            _reduce_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(h_k, nq, cap),
                in_specs=[
                    pl.BlockSpec((1, 1, 1, rows, dv),
                                 lambda hk, iq, j, c1: (hk, iq, j, 0, 0)),
                ],
                out_specs=pl.BlockSpec((1, rows, dv),
                                       lambda hk, iq, j, c1: (hk, iq, 0)),
                scratch_shapes=[pltpu.VMEM((rows, dv), jnp.float32)],
            ),
            out_shape=jax.ShapeDtypeStruct((h_k, rows_total, dv),
                                           q_rows.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(kv_cnt, obuf)
    return (out, lse) if return_lse else out
