"""Pallas TPU flash attention (full-attention baseline + sliding-window branch).

Layouts (GQA folded per KV head):
  q: (h_K, Nq·g, d)  rows are token-major, group-head-minor
  k/v: (h_K, Nk, d)
  out: (h_K, Nq·g, d)

Grid: (h_K, num_q_blocks, num_kv_blocks) — kv innermost (sequential,
"arbitrary"); online-softmax state lives in VMEM scratch across kv steps.
Causal/window-violating kv blocks are skipped with ``pl.when`` and their HBM
fetch elided by clamping the kv index map to the last useful block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *rest, scale, g, block_q, block_k,
            offset, valid_k, causal, window, with_lse=False):
    if with_lse:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        m_scr, l_scr, acc_scr = rest
    iq, ik = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    rows = q_ref.shape[1]

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # does this kv block intersect the allowed band for this q block?
    q_lo = iq * block_q
    q_hi = q_lo + block_q - 1          # token positions (pre-group-fold)
    k_lo = ik * block_k
    k_hi = k_lo + block_k - 1
    live = k_lo < valid_k
    if causal:
        live &= k_lo <= q_hi + offset
    if window is not None:
        live &= k_hi >= q_lo + offset - (window - 1)

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        tok = q_lo + jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 0) // g
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 1)
        mask = kpos < valid_k
        if causal:
            mask &= tok + offset >= kpos
        if window is not None:
            mask &= tok + offset - kpos < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...][:, 0:1]
        l_prev = l_scr[...][:, 0:1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = corr * l_prev + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == nk - 1)
    def _done():
        l = l_scr[...][:, 0:1]
        o_ref[0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        if with_lse:
            m = m_scr[...][:, 0:1]
            # maskless rows get +inf-like lse so exp(s - lse) -> 0
            lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)),
                            -NEG_INF)
            lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _kv_band(block_q, block_k, offset, causal, window):
    """kv index-map clamp: keep skipped steps on a resident block (no HBM
    refetch).  Shared by the forward and the dQ backward (same loop order)."""

    def kv_index(hk, iq, ik, *_):
        if causal:
            hi = jnp.maximum(
                jax.lax.div((iq + 1) * block_q - 1 + offset, block_k), 0)
            ik = jnp.minimum(ik, hi)
        if window is not None:
            lo = jnp.maximum(
                (iq * block_q + offset - (window - 1)) // block_k, 0)
            ik = jnp.maximum(ik, lo)
        return (hk, ik, 0)

    return kv_index


def flash_attention(q, k, v, *, g: int, causal: bool = True,
                    window: int | None = None, block_q: int = 128,
                    block_k: int = 128, valid_k: int | None = None,
                    offset: int | None = None, interpret: bool = False,
                    return_lse: bool = False):
    """q: (h_K, Nq·g, d); k, v: (h_K, Nk, d). Returns (h_K, Nq·g, d).

    ``valid_k`` is the logical key count when k/v carry padding rows (keys at
    positions >= valid_k are masked out; defaults to the array length).
    ``offset`` aligns query token i with key position i + offset for the
    causal/window bands; it defaults to end-alignment of the *arrays*
    (Nk - Nq) — callers padding q and k by different amounts pass the
    logical offset explicitly.  ``return_lse=True`` also returns the per-row
    log-sum-exp (h_K, Nq·g, 128) float32 — the fused-backward residual."""
    h_k, rows_total, d = q.shape
    dv = v.shape[-1]
    seq_k = k.shape[1]
    seq_q = rows_total // g
    valid_k = seq_k if valid_k is None else valid_k
    offset = seq_k - seq_q if offset is None else offset
    block_q = min(block_q, seq_q)
    block_k = min(block_k, seq_k)
    nq = pl.cdiv(seq_q, block_q)
    nk = pl.cdiv(seq_k, block_k)
    rows = block_q * g
    scale = 1.0 / (d ** 0.5)

    kv_index = _kv_band(block_q, block_k, offset, causal, window)

    kernel = functools.partial(
        _kernel, scale=scale, g=g, block_q=block_q, block_k=block_k,
        offset=offset, valid_k=valid_k, causal=causal,
        window=window, with_lse=return_lse)
    out_specs = [pl.BlockSpec((1, rows, dv), lambda hk, iq, ik: (hk, iq, 0))]
    out_shape = [jax.ShapeDtypeStruct((h_k, rows_total, dv), q.dtype)]
    if return_lse:
        out_specs.append(
            pl.BlockSpec((1, rows, 128), lambda hk, iq, ik: (hk, iq, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((h_k, rows_total, 128), jnp.float32))
    with jax.named_scope("flash_attention"):
        return pl.pallas_call(
            kernel,
            grid=(h_k, nq, nk),
            in_specs=[
                pl.BlockSpec((1, rows, d), lambda hk, iq, ik: (hk, iq, 0)),
                pl.BlockSpec((1, block_k, d), kv_index),
                pl.BlockSpec((1, block_k, dv), kv_index),
            ],
            out_specs=out_specs if return_lse else out_specs[0],
            out_shape=out_shape if return_lse else out_shape[0],
            scratch_shapes=[
                pltpu.VMEM((rows, 128), jnp.float32),
                pltpu.VMEM((rows, 128), jnp.float32),
                pltpu.VMEM((rows, dv), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(q, k, v)


# =====================================================================
# fused backward (flash recurrence: p recomputed from saved out/lse)
#
#   p  = exp(s - lse)              dp = dO · Vᵀ
#   ds = p ∘ (dp - delta) · scale  delta = rowsum(dO ∘ O)
#   dQ = Σ ds·K    dV = Σ pᵀ·dO    dK = Σ dsᵀ·Q
# =====================================================================
def _band_mask(iq, ik, rows, block_q, block_k, g, offset, valid_k,
               causal, window):
    tok = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 0) // g
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 1)
    mask = kpos < valid_k
    if causal:
        mask &= tok + offset >= kpos
    if window is not None:
        mask &= tok + offset - kpos < window
    return mask


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_scr, *, scale, g, block_q, block_k, offset, valid_k,
               causal, window):
    iq, ik = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    rows = q_ref.shape[1]

    @pl.when(ik == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_lo, q_hi = iq * block_q, iq * block_q + block_q - 1
    k_lo, k_hi = ik * block_k, ik * block_k + block_k - 1
    live = k_lo < valid_k
    if causal:
        live &= k_lo <= q_hi + offset
    if window is not None:
        live &= k_hi >= q_lo + offset - (window - 1)

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = _band_mask(iq, ik, rows, block_q, block_k, g, offset,
                          valid_k, causal, window)
        p = jnp.where(mask, jnp.exp(s - lse_ref[0][:, 0:1]), 0.0)
        do = do_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, 0:1]) * scale
        acc_scr[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _done():
        dq_ref[0] = acc_scr[...]


def flash_attention_dq(q, k, v, do, lse, delta, *, g: int, causal: bool = True,
                       window: int | None = None, block_q: int = 128,
                       block_k: int = 128, valid_k: int | None = None,
                       offset: int | None = None, interpret: bool = False):
    """dQ in the forward loop order (grid (h_K, q-blocks, kv-blocks)).
    Returns (h_K, Nq·g, d) float32."""
    h_k, rows_total, d = q.shape
    dv = v.shape[-1]
    seq_k = k.shape[1]
    seq_q = rows_total // g
    valid_k = seq_k if valid_k is None else valid_k
    offset = seq_k - seq_q if offset is None else offset
    block_q = min(block_q, seq_q)
    block_k = min(block_k, seq_k)
    nq = pl.cdiv(seq_q, block_q)
    nk = pl.cdiv(seq_k, block_k)
    rows = block_q * g
    scale = 1.0 / (d ** 0.5)

    kv_index = _kv_band(block_q, block_k, offset, causal, window)
    q_index = lambda hk, iq, ik: (hk, iq, 0)
    kernel = functools.partial(
        _dq_kernel, scale=scale, g=g, block_q=block_q, block_k=block_k,
        offset=offset, valid_k=valid_k, causal=causal, window=window)
    with jax.named_scope("flash_attention_dq"):
        return pl.pallas_call(
            kernel,
            grid=(h_k, nq, nk),
            in_specs=[
                pl.BlockSpec((1, rows, d), q_index),
                pl.BlockSpec((1, block_k, d), kv_index),
                pl.BlockSpec((1, block_k, dv), kv_index),
                pl.BlockSpec((1, rows, dv), q_index),
                pl.BlockSpec((1, rows, 128), q_index),
                pl.BlockSpec((1, rows, 128), q_index),
            ],
            out_specs=pl.BlockSpec((1, rows, d), q_index),
            out_shape=jax.ShapeDtypeStruct((h_k, rows_total, d), jnp.float32),
            scratch_shapes=[pltpu.VMEM((rows, d), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(q, k, v, do, lse, delta)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_scr, dv_scr, *, scale, g, block_q, block_k,
                offset, valid_k, causal, window):
    ik, iq = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)
    rows = q_ref.shape[1]

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q_lo, q_hi = iq * block_q, iq * block_q + block_q - 1
    k_lo, k_hi = ik * block_k, ik * block_k + block_k - 1
    live = k_lo < valid_k
    if causal:
        live &= k_lo <= q_hi + offset
    if window is not None:
        live &= k_hi >= q_lo + offset - (window - 1)

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = _band_mask(iq, ik, rows, block_q, block_k, g, offset,
                          valid_k, causal, window)
        p = jnp.where(mask, jnp.exp(s - lse_ref[0][:, 0:1]), 0.0)
        do = do_ref[0].astype(jnp.float32)
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, 0:1]) * scale
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(iq == nq - 1)
    def _done():
        dk_ref[0] = dk_scr[...]
        dv_ref[0] = dv_scr[...]


def flash_attention_dkv(q, k, v, do, lse, delta, *, g: int,
                        causal: bool = True, window: int | None = None,
                        block_q: int = 128, block_k: int = 128,
                        valid_k: int | None = None, offset: int | None = None,
                        interpret: bool = False):
    """dK/dV with kv blocks in the outer (parallel) grid dim — each kv block
    owns its gradient tile, q blocks walk sequentially (mirroring the
    forward's clamp: out-of-band q steps re-touch a resident block).
    Returns (dk, dv): (h_K, Nk, d) / (h_K, Nk, dv) float32."""
    h_k, rows_total, d = q.shape
    dv_dim = v.shape[-1]
    seq_k = k.shape[1]
    seq_q = rows_total // g
    valid_k = seq_k if valid_k is None else valid_k
    offset = seq_k - seq_q if offset is None else offset
    block_q = min(block_q, seq_q)
    block_k = min(block_k, seq_k)
    nq = pl.cdiv(seq_q, block_q)
    nk = pl.cdiv(seq_k, block_k)
    rows = block_q * g
    scale = 1.0 / (d ** 0.5)

    # clamp the q index into the live band for this kv block (the transpose
    # of the forward's kv clamp)
    def q_index(hk, ik, iq):
        if causal:
            lo = jnp.maximum((ik * block_k - offset) // block_q, 0)
            iq = jnp.maximum(iq, lo)
        if window is not None:
            hi = ((ik * block_k + block_k - 1 - offset
                   + window - 1) // block_q)
            iq = jnp.minimum(iq, jnp.maximum(hi, 0))
        return (hk, iq, 0)

    kv_index = lambda hk, ik, iq: (hk, ik, 0)
    kernel = functools.partial(
        _dkv_kernel, scale=scale, g=g, block_q=block_q, block_k=block_k,
        offset=offset, valid_k=valid_k, causal=causal, window=window)
    with jax.named_scope("flash_attention_dkv"):
        return pl.pallas_call(
            kernel,
            grid=(h_k, nk, nq),
            in_specs=[
                pl.BlockSpec((1, rows, d), q_index),
                pl.BlockSpec((1, block_k, d), kv_index),
                pl.BlockSpec((1, block_k, dv_dim), kv_index),
                pl.BlockSpec((1, rows, dv_dim), q_index),
                pl.BlockSpec((1, rows, 128), q_index),
                pl.BlockSpec((1, rows, 128), q_index),
            ],
            out_specs=[
                pl.BlockSpec((1, block_k, d), kv_index),
                pl.BlockSpec((1, block_k, dv_dim), kv_index),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((h_k, nk * block_k, d), jnp.float32),
                jax.ShapeDtypeStruct((h_k, nk * block_k, dv_dim),
                                     jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, dv_dim), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(q, k, v, do, lse, delta)
