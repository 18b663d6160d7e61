"""Fused Pallas backward kernels for the FSA selected branch.

The forward saves ``(out, lse)`` and the backward recomputes the probability
panels from them (flash-attention backward recurrence) instead of saving the
O(N·T·B_K) score matrix:

  p  = exp(s - lse)                    (masked entries 0)
  dp = dO · Vᵀ
  ds = p ∘ (dp - delta) · scale        delta = rowsum(dO ∘ O)
  dQ = Σ ds · K        dV = Σ pᵀ · dO        dK = Σ dsᵀ · Q

Two kernels, two loop orders — both reuse the forward's index builders
(``repro.core.indexing``), nothing new is gathered:

* :func:`fsa_selected_dq` walks the **FSA forward order**: grid
  (h_K, q-blocks, union steps), scalar-prefetched per-q-block union lists
  (``build_qblock_union``).  dQ accumulates in VMEM scratch across the
  sequential union steps, exactly like the forward's online softmax.
* :func:`fsa_selected_dkv` walks the **selected-block order**: grid
  (h_K, KV blocks, occurrence steps), scalar-prefetched per-KV-block
  occurrence lists (the paper's I_i, from ``build_kvblock_qlists``).  Each
  KV block owns its dK/dV tile, so accumulation is private scratch — the
  TPU analogue of the atomics-free structure the paper's O_buf exists for.

Layouts match the forward: q/dO rows are (h_K, N·g, d) token-major
group-head-minor; lse/delta are (h_K, N·g, 128) float32 lane-broadcast
panels (``lse`` uses the fsa_faithful convention: +1e30 for maskless rows so
``exp(s - lse) == 0``).  Both kernels emit float32 grads; callers cast.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


# ---------------------------------------------------------------- dQ kernel
def _dq_kernel(kv_ids, kv_cnt, q_ref, k_ref, v_ref, sel_ref, do_ref, lse_ref,
               delta_ref, dq_ref, acc_scr, *, scale, g, block_q, block_k,
               seq_len):
    hk, iq, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    cap = pl.num_programs(2)
    rows = q_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

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
        lse = lse_ref[0][:, 0:1]
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        do = do_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        delta = delta_ref[0][:, 0:1]
        ds = p * (dp - delta) * scale
        acc_scr[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == cap - 1)
    def _done():
        dq_ref[0] = acc_scr[...]


def fsa_selected_dq(q_rows, k, v, sel_rows, do_rows, lse, delta, kv_ids,
                    kv_cnt, *, g: int, block_q: int, block_k: int,
                    seq_len: int | None = None, interpret: bool = False):
    """dQ in the FSA forward loop order.  Returns (h_K, N·g, d) float32."""
    h_k, rows_total, d = q_rows.shape
    dv = v.shape[-1]
    seq_len = k.shape[1] if seq_len is None else seq_len
    nq, cap = kv_ids.shape[1], kv_ids.shape[2]
    rows = block_q * g
    t = sel_rows.shape[-1]
    scale = 1.0 / (d ** 0.5)

    kernel = functools.partial(_dq_kernel, scale=scale, g=g, block_q=block_q,
                               block_k=block_k, seq_len=seq_len)
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
            pl.BlockSpec((1, rows, dv), lambda hk, iq, j, ids, cnt: (hk, iq, 0)),
            pl.BlockSpec((1, rows, 128), lambda hk, iq, j, ids, cnt: (hk, iq, 0)),
            pl.BlockSpec((1, rows, 128), lambda hk, iq, j, ids, cnt: (hk, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, rows, d),
                               lambda hk, iq, j, ids, cnt: (hk, iq, 0)),
        scratch_shapes=[pltpu.VMEM((rows, d), jnp.float32)],
    )
    with jax.named_scope("fsa_selected_dq"):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((h_k, rows_total, d), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(kv_ids, kv_cnt, q_rows, k, v, sel_rows, do_rows, lse, delta)


# ------------------------------------------------------------- dK/dV kernel
def _dkv_kernel(q_ids, q_cnt, q_ref, k_ref, v_ref, sel_ref, do_ref, lse_ref,
                delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, scale, g,
                block_q, block_k, seq_len):
    hk, ib, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    capq = pl.num_programs(2)
    rows = q_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(j < q_cnt[hk, ib])
    def _step():
        qb = q_ids[(hk * pl.num_programs(1) + ib) * capq + j]
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        tok = qb * block_q + jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 0) // g
        kpos = ib * block_k + jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 1)
        picked = jnp.any(sel_ref[0] == ib, axis=1, keepdims=True)
        mask = picked & (tok >= kpos) & (kpos < seq_len)
        lse = lse_ref[0][:, 0:1]
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        do = do_ref[0].astype(jnp.float32)
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        delta = delta_ref[0][:, 0:1]
        ds = p * (dp - delta) * scale
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == capq - 1)
    def _done():
        dk_ref[0] = dk_scr[...]
        dv_ref[0] = dv_scr[...]


def fsa_selected_dkv(q_rows, k, v, sel_rows, do_rows, lse, delta, q_ids,
                     q_cnt, *, g: int, block_q: int, block_k: int,
                     seq_len: int | None = None, interpret: bool = False):
    """dK/dV in the selected-block order (occurrence lists).

    ``q_ids`` is prefetched flattened: scalar memory pads a table's minor
    dim to 128 words, which a 3-D (h_K, nb, nq) table at nq=32 would pay 4x.

    Returns (dk, dv): (h_K, nb·B_K, d) / (h_K, nb·B_K, dv) float32 — padded
    to whole KV blocks; slice to seq_len and cast at the call site."""
    h_k, rows_total, d = q_rows.shape
    dv_dim = v.shape[-1]
    seq_len = k.shape[1] if seq_len is None else seq_len
    nb, capq = q_ids.shape[1], q_ids.shape[2]
    rows = block_q * g
    t = sel_rows.shape[-1]
    scale = 1.0 / (d ** 0.5)

    kernel = functools.partial(_dkv_kernel, scale=scale, g=g, block_q=block_q,
                               block_k=block_k, seq_len=seq_len)

    def _q_index(hk, ib, j, ids, cnt):
        return (hk, ids[(hk * nb + ib) * capq + j], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(h_k, nb, capq),
        in_specs=[
            pl.BlockSpec((1, rows, d), _q_index),
            pl.BlockSpec((1, block_k, d), lambda hk, ib, j, ids, cnt: (hk, ib, 0)),
            pl.BlockSpec((1, block_k, dv_dim),
                         lambda hk, ib, j, ids, cnt: (hk, ib, 0)),
            pl.BlockSpec((1, rows, t), _q_index),
            pl.BlockSpec((1, rows, dv_dim), _q_index),
            pl.BlockSpec((1, rows, 128), _q_index),
            pl.BlockSpec((1, rows, 128), _q_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda hk, ib, j, ids, cnt: (hk, ib, 0)),
            pl.BlockSpec((1, block_k, dv_dim),
                         lambda hk, ib, j, ids, cnt: (hk, ib, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, dv_dim), jnp.float32),
        ],
    )
    with jax.named_scope("fsa_selected_dkv"):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((h_k, nb * block_k, d), jnp.float32),
                jax.ShapeDtypeStruct((h_k, nb * block_k, dv_dim),
                                     jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(q_ids.reshape(-1), q_cnt, q_rows, k, v, sel_rows, do_rows, lse,
          delta)
