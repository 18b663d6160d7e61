"""NSA selected-attention baseline kernel (the design FSA improves upon).

Faithful to the vanilla NSA loop order: grid walks *query tokens* (outer) and
the token's T selected KV blocks (inner).  The g query heads sharing a KV head
form the matmul M dimension, padded to the hardware minimum (8 sublanes on
TPU, mirroring the ≥8 PTX mma constraint on Hopper) — the padding waste that
FSA eliminates.  Kept as a first-class baseline for the paper's comparisons.

Layouts:
  q:   (h_K, N, g_pad, d)  (g rows valid, padded to g_pad = max(g, 8))
  k/v: (h_K, N, d)
  idx: (h_K, N, T) int32 (-1 invalid)  — scalar prefetch
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(idx_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, g_pad, block_k, seq_len):
    hk, t, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    t_sel = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    blk = idx_ref[hk, t, j]

    @pl.when(blk >= 0)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)        # (g_pad, d)
        k = k_ref[0].astype(jnp.float32)           # (B_K, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = blk * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (g_pad, block_k), 1)
        mask = (kpos <= t) & (kpos < seq_len)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...][:, 0:1]
        l_prev = l_scr[...][:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        pv = jax.lax.dot_general(p, v_ref[0].astype(jnp.float32),
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + pv
        l_scr[...] = jnp.broadcast_to(corr * l_prev + jnp.sum(p, 1, keepdims=True),
                                      l_scr.shape)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(j == t_sel - 1)
    def _done():
        l = l_scr[...][:, 0:1]
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def nsa_selected(q_pad, k, v, idx, *, block_k: int,
                 seq_len: int | None = None, interpret: bool = False):
    """q_pad: (h_K, N, g_pad, d); idx: (h_K, N, T). Returns like q_pad.

    ``seq_len`` is the logical key count when k/v carry padding rows up to a
    whole number of KV blocks (defaults to the array length)."""
    h_k, n, g_pad, d = q_pad.shape
    dv = v.shape[-1]
    t_sel = idx.shape[-1]
    seq_len = k.shape[1] if seq_len is None else seq_len
    scale = 1.0 / (d ** 0.5)

    kernel = functools.partial(_kernel, scale=scale, g_pad=g_pad,
                               block_k=block_k, seq_len=seq_len)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(h_k, n, t_sel),
        in_specs=[
            pl.BlockSpec((1, 1, g_pad, d), lambda hk, t, j, ids: (hk, t, 0, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda hk, t, j, ids: (hk, jnp.maximum(ids[hk, t, j], 0), 0)),
            pl.BlockSpec((1, block_k, dv),
                         lambda hk, t, j, ids: (hk, jnp.maximum(ids[hk, t, j], 0), 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g_pad, dv), lambda hk, t, j, ids: (hk, t, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g_pad, 128), jnp.float32),
            pltpu.VMEM((g_pad, 128), jnp.float32),
            pltpu.VMEM((g_pad, dv), jnp.float32),
        ],
    )
    with jax.named_scope("nsa_selected"):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((h_k, n, g_pad, dv), q_pad.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary")),
            interpret=interpret,
        )(idx, q_pad, k, v)
