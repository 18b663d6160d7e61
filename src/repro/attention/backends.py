"""Registered attention backends.

Each backend wraps one kernel organization of the same math and registers
itself with declared capabilities.  All string/bool implementation dispatch
in the repo lives in this package; outside it, callers go through
``repro.attention.nsa_attention`` / ``resolve``.

Backends (see README "Attention API" for the full table):

  fsa            FSA-TPU Pallas kernel for the selected branch (block-union)
  fsa_faithful   paper-structure three-kernel pipeline (ablation)
  nsa            vanilla-NSA-style baseline kernel (g padded to 8)
  sparse_union   FSA organization in XLA ops (production CPU/backward path)
  sparse_gather  naive per-token gather (baseline; also the decode backend)
  reference      dense-mask oracle for every algorithm and mode
  flash_full     Pallas flash full attention
  flash_sliding  Pallas flash sliding-window attention
  paged_kernel   Pallas paged-decode kernel (serving; slots folded into M)
  paged_gather   gather-through-page-table paged decode (serving reference)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import gating, indexing, sparse
from repro.core import attention as core_attn
from repro.core.nsa_config import NSAConfig
from repro.core.paging import gather_rows
from repro.core.reference import (_gqa_out, _gqa_scores, _safe_softmax,
                                  nsa_attention_ref)
from repro.kernels import flash_attention as _flash
from repro.kernels import fsa_faithful as _faithful
from repro.kernels import fsa_selected as _fsa
from repro.kernels import fsa_selected_bwd as _fsa_bwd
from repro.kernels import nsa_selected as _nsa
from repro.kernels import paged_decode as _paged
from repro.kernels import ref as _ref
from repro.attention.registry import Capabilities, register_backend
from repro.attention.vjp import kernel_vjp
from repro.telemetry import named_scope

SELECTED_KERNELS = ("fsa", "fsa_faithful", "nsa", "reference")
# selected-branch kernels with a fused Pallas backward (others fall back to
# the XLA twin under the same kernel_vjp op)
FUSED_BWD_SELECTED = ("fsa", "fsa_faithful")


def _pad_tokens(x, n_pad):
    return jnp.pad(x, ((0, n_pad - x.shape[0]),) + ((0, 0),) * (x.ndim - 1))


def _q_padding(cfg, n):
    """(block_q, padded token count) for an N-token query sequence."""
    bq = min(cfg.q_block_size, max(8, n))
    return bq, ((n + bq - 1) // bq) * bq


def _kv_layout(k, v, block_k):
    """(S, h_K, d) k/v -> kernel layout (h_K, S_pad, d) padded to whole KV
    blocks (a partial trailing block would read out of bounds); returns the
    logical S for the kernels' key-position masks."""
    s = k.shape[0]
    s_pad = ((s + block_k - 1) // block_k) * block_k
    return (_pad_tokens(k, s_pad).transpose(1, 0, 2),
            _pad_tokens(v, s_pad).transpose(1, 0, 2), s)


def _delta_panels(do_rows, o_rows):
    """delta = rowsum(dO ∘ O) broadcast to the (h_K, N·g, 128) residual
    panel layout the backward kernels read (lane-broadcast like lse)."""
    delta = jnp.sum(do_rows.astype(jnp.float32) * o_rows.astype(jnp.float32),
                    axis=-1, keepdims=True)
    return jnp.broadcast_to(delta, delta.shape[:-1] + (128,))


# =====================================================================
# selected branch: Pallas kernel forward, fused Pallas backward for the
# FSA kernels, chunked-gather XLA twin as the fallback backward
# =====================================================================
def _normalize_selection(idxp, validp):
    """Ascending sort, duplicates invalidated (top-k selection never produces
    dups, but the kernel contract must not depend on that)."""
    key = jnp.where(validp, idxp, jnp.iinfo(jnp.int32).max // 2)
    order = jnp.argsort(key, axis=-1)
    idxp = jnp.take_along_axis(idxp, order, axis=-1)
    validp = jnp.take_along_axis(validp, order, axis=-1)
    dup = jnp.concatenate(
        [jnp.zeros_like(validp[..., :1]),
         (idxp[..., 1:] == idxp[..., :-1]) & validp[..., 1:] & validp[..., :-1]],
        axis=-1)
    validp &= ~dup
    return idxp, validp


def _selected_run(static, q, k, v, idx, valid, want_lse):
    cfg, kernel = static
    n, h, d = q.shape
    h_k = k.shape[1]
    g = h // h_k
    bq, n_pad = _q_padding(cfg, n)

    qp = _pad_tokens(q, n_pad)
    with named_scope("nsa.index"):
        idxp, validp = _normalize_selection(_pad_tokens(idx, n_pad),
                                            _pad_tokens(valid, n_pad))
        sel = jnp.where(validp, idxp, -1).astype(jnp.int32)   # (N, h_K, T)
        # rows layout for sel: repeat each token's list over the g heads
        sel_rows = jnp.repeat(sel.transpose(1, 0, 2), g, axis=1)
    q_rows = _ref.rows_from_heads(qp, h_k)
    k_t, v_t, s = _kv_layout(k, v, cfg.block_size)

    if kernel == "nsa":
        g_pad = max(g, 8)
        q_pad = qp.reshape(n_pad, h_k, g, d).transpose(1, 0, 2, 3)
        q_pad = jnp.pad(q_pad, ((0, 0), (0, 0), (0, g_pad - g), (0, 0)))
        o = _nsa.nsa_selected(q_pad, k_t, v_t, sel.transpose(1, 0, 2),
                              block_k=cfg.block_size, seq_len=s,
                              interpret=cfg.interpret)
        o = o[:, :, :g].transpose(1, 0, 2, 3).reshape(n_pad, h, -1)
        return o[:n], None

    with named_scope("nsa.index"):
        kv_ids, kv_cnt = indexing.build_qblock_union(idxp, validp, cfg, s)
    if kernel == "fsa":
        o_rows = _fsa.fsa_selected(q_rows, k_t, v_t, sel_rows, kv_ids, kv_cnt,
                                   g=g, block_q=bq, block_k=cfg.block_size,
                                   seq_len=s, interpret=cfg.interpret,
                                   return_lse=want_lse)
    elif kernel == "fsa_faithful":
        with named_scope("nsa.index"):
            q_ids, slot_ids, q_cnt = indexing.build_kvblock_qlists(
                idxp, validp, cfg, s, union_cap=kv_ids.shape[-1])
        o_rows = _faithful.fsa_faithful(q_rows, k_t, v_t, sel_rows, kv_ids,
                                        kv_cnt, q_ids, slot_ids, q_cnt, g=g,
                                        block_q=bq, block_k=cfg.block_size,
                                        seq_len=s, interpret=cfg.interpret,
                                        return_lse=want_lse)
    elif kernel == "reference":
        return _ref.selected_ref(q, k, v, idx, valid, cfg), None
    else:
        raise ValueError(f"unknown selected kernel: {kernel}")
    if want_lse:
        o_rows, lse = o_rows
        return _ref.heads_from_rows(o_rows, n_pad)[:n], (o_rows, lse, sel)
    return _ref.heads_from_rows(o_rows, n_pad)[:n], None


def _selected_fwd_impl(static, q, k, v, idx, valid):
    return _selected_run(static, q, k, v, idx, valid, want_lse=False)[0]


def _selected_fused_fwd(static, q, k, v, idx, valid):
    """Forward for the VJP: FSA kernels emit (out, lse) residuals; kernels
    without a fused backward return residuals=None (twin fallback)."""
    _, kernel = static
    want = kernel in FUSED_BWD_SELECTED
    return _selected_run(static, q, k, v, idx, valid, want_lse=want)


def _selected_fused_bwd(static, res, tensors, dout):
    """Fused dQ/dK/dV: rebuilds the forward's index lists (union lists for
    dQ, occurrence lists for dK/dV) from the saved normalized selection and
    launches the Pallas backward kernels."""
    cfg, _ = static
    o_rows, lse, sel = res
    q, k, v = tensors[:3]
    n, h, d = q.shape
    s, h_k, _ = k.shape
    g = h // h_k
    bq, n_pad = _q_padding(cfg, n)

    with named_scope("nsa.index"):
        idxp, validp = jnp.maximum(sel, 0), sel >= 0
        sel_rows = jnp.repeat(sel.transpose(1, 0, 2), g, axis=1)
        kv_ids, kv_cnt = indexing.build_qblock_union(idxp, validp, cfg, s)
        q_ids, _, q_cnt = indexing.build_kvblock_qlists(idxp, validp, cfg, s)
    q_rows = _ref.rows_from_heads(_pad_tokens(q, n_pad), h_k)
    k_t, v_t, s = _kv_layout(k, v, cfg.block_size)
    do_rows = _ref.rows_from_heads(_pad_tokens(dout, n_pad), h_k)
    delta = _delta_panels(do_rows, o_rows)
    kw = dict(g=g, block_q=bq, block_k=cfg.block_size, seq_len=s,
              interpret=cfg.interpret)
    dq_rows = _fsa_bwd.fsa_selected_dq(q_rows, k_t, v_t, sel_rows, do_rows,
                                       lse, delta, kv_ids, kv_cnt, **kw)
    dk_t, dv_t = _fsa_bwd.fsa_selected_dkv(q_rows, k_t, v_t, sel_rows,
                                           do_rows, lse, delta, q_ids, q_cnt,
                                           **kw)
    dq = _ref.heads_from_rows(dq_rows, n_pad)[:n].astype(q.dtype)
    dk = dk_t[:, :s].transpose(1, 0, 2).astype(k.dtype)
    dv = dv_t[:, :s].transpose(1, 0, 2).astype(v.dtype)
    return dq, dk, dv


def _selected_twin(static, q, k, v, idx, valid):
    """Differentiable twin of the selected kernels (chunked gather path)."""
    cfg, _ = static
    return sparse.selected_gather_chunked(q, k, v, idx, valid, cfg)


_selected_op = kernel_vjp(_selected_fwd_impl, _selected_twin, num_diff=3,
                          fused_fwd=_selected_fused_fwd,
                          fused_bwd=_selected_fused_bwd)


def default_selected_kernel(cfg: NSAConfig) -> str:
    """The Pallas selected-branch kernel the policy names (fsa if the policy
    names a non-kernel backend such as ``auto`` or ``sparse_union``)."""
    b = cfg.policy.backend
    return b if b in SELECTED_KERNELS else "fsa"


def selected_attention(q, k, v, idx, valid, cfg: NSAConfig,
                       kernel: str | None = None):
    """Selected-branch attention through the named Pallas kernel.
    q: (N,h,d), k/v: (S,h_K,d), idx/valid: (N,h_K,T)."""
    return _selected_op((cfg, kernel or default_selected_kernel(cfg)),
                        q, k, v, idx, valid)


# =====================================================================
# flash full / sliding: Pallas kernel forward, fused Pallas backward,
# chunked-reference twin kept as the VJP scaffolding fallback
# =====================================================================
def _flash_layouts(cfg, q, k, v):
    """Kernel layouts for flash.  Q pads to whole q blocks, K/V to whole kv
    blocks (a partial trailing block would read out of bounds); the padding
    amounts differ, so the *logical* causal alignment (key position of query
    token 0) and key count are passed explicitly — the kernel's default
    end-of-array alignment would shift the causal band for ragged N."""
    n, h, d = q.shape
    s, h_k, _ = k.shape
    g = h // h_k
    bq, n_pad = _q_padding(cfg, n)
    bk = min(128, s)
    q_rows = _ref.rows_from_heads(_pad_tokens(q, n_pad), h_k)
    k_t, v_t, _ = _kv_layout(k, v, bk)
    return q_rows, k_t, v_t, dict(g=g, block_q=bq, block_k=bk, valid_k=s,
                                  offset=s - n, interpret=cfg.interpret), n_pad


def _flash_run(static, q, k, v, want_lse):
    cfg, causal, window = static
    n = q.shape[0]
    q_rows, k_t, v_t, kw, n_pad = _flash_layouts(cfg, q, k, v)
    res = _flash.flash_attention(q_rows, k_t, v_t, causal=causal,
                                 window=window, return_lse=want_lse, **kw)
    if want_lse:
        o_rows, lse = res
        return _ref.heads_from_rows(o_rows, n_pad)[:n], (o_rows, lse)
    return _ref.heads_from_rows(res, n_pad)[:n], None


def _flash_fwd_impl(static, q, k, v):
    return _flash_run(static, q, k, v, want_lse=False)[0]


def _flash_fused_fwd(static, q, k, v):
    return _flash_run(static, q, k, v, want_lse=True)


def _flash_fused_bwd(static, res, tensors, dout):
    cfg, causal, window = static
    o_rows, lse = res
    q, k, v = tensors
    n = q.shape[0]
    s = k.shape[0]
    h_k = k.shape[1]
    q_rows, k_t, v_t, kw, n_pad = _flash_layouts(cfg, q, k, v)
    do_rows = _ref.rows_from_heads(_pad_tokens(dout, n_pad), h_k)
    delta = _delta_panels(do_rows, o_rows)
    dq_rows = _flash.flash_attention_dq(q_rows, k_t, v_t, do_rows, lse, delta,
                                        causal=causal, window=window, **kw)
    dk_t, dv_t = _flash.flash_attention_dkv(q_rows, k_t, v_t, do_rows, lse,
                                            delta, causal=causal,
                                            window=window, **kw)
    dq = _ref.heads_from_rows(dq_rows, n_pad)[:n].astype(q.dtype)
    dk = dk_t[:, :s].transpose(1, 0, 2).astype(k.dtype)
    dv = dv_t[:, :s].transpose(1, 0, 2).astype(v.dtype)
    return dq, dk, dv


def _flash_twin(static, q, k, v):
    _, causal, window = static
    return _ref.flash_ref_chunked(q, k, v, causal=causal, window=window)


_flash_op = kernel_vjp(_flash_fwd_impl, _flash_twin, num_diff=3,
                       fused_fwd=_flash_fused_fwd,
                       fused_bwd=_flash_fused_bwd)


def flash_attention(q, k, v, cfg: NSAConfig, *, causal: bool = True,
                    window: int | None = None):
    """Pallas flash attention (full or sliding-window)."""
    return _flash_op((cfg, causal, window), q, k, v)


# =====================================================================
# paged decode: shared compressed prologue + kernel / gather organizations
# =====================================================================
def _paged_sel_win_ref(q, k_pages, v_pages, page_table, idx, valid, pos,
                       cfg: NSAConfig):
    """Gather-through-page-table reference for ONE slot's selected + sliding
    branches.  q: (h, d); idx/valid: (h_k, T); pos: scalar.
    Returns (out_sel, out_win): each (h, dv) float32.
    """
    h, d = q.shape
    h_k, p_sz = k_pages.shape[1], k_pages.shape[2]
    g = h // h_k

    # --- selected branch: gather exactly the T physical pages per KV head
    #     (each head pulls only its own rows of its own pages) ---
    t = idx.shape[-1]
    phys = page_table[idx]                                  # (h_k, T)
    hk_i = jnp.arange(h_k)
    k_sel = jax.vmap(lambda ph, i: k_pages[ph, i])(phys, hk_i)
    v_sel = jax.vmap(lambda ph, i: v_pages[ph, i])(phys, hk_i)
    k_sel = k_sel.reshape(h_k, t * p_sz, d)                 # (h_k, T·P, d)
    v_sel = v_sel.reshape(h_k, t * p_sz, -1)
    tok_pos = (idx[..., None] * p_sz + jnp.arange(p_sz)).reshape(h_k, t * p_sz)
    sel_mask = jnp.repeat(valid, p_sz, axis=-1) & (tok_pos <= pos)
    qg = q.reshape(h_k, g, d).astype(jnp.float32)
    s_sel = jnp.einsum("kgd,ksd->kgs", qg, k_sel.astype(jnp.float32))
    s_sel = s_sel / jnp.sqrt(d).astype(jnp.float32)
    p_sel, _ = _safe_softmax(s_sel, sel_mask[:, None, :])
    out_sel = jnp.einsum("kgs,ksd->kgd", p_sel, v_sel.astype(jnp.float32))

    # --- sliding branch: the trailing window through the page table ---
    w = cfg.window_size
    win_rows = pos - (w - 1) + jnp.arange(w)
    k_win = gather_rows(k_pages, page_table, win_rows)      # (W, h_k, d)
    v_win = gather_rows(v_pages, page_table, win_rows)
    win_mask = (win_rows >= 0) & (win_rows <= pos)
    p_win, _ = _safe_softmax(_gqa_scores(q[None], k_win),
                             win_mask[None, None, :])
    out_win = _gqa_out(p_win, v_win)[0]
    return out_sel.reshape(h, -1), out_win


def paged_decode_attention(gates, q, k_pages, v_pages, page_tables,
                           cmp_k, cmp_v, pos, cfg: NSAConfig, *,
                           kernel: bool, block_s: int | None = None):
    """Batched multi-slot NSA decode reading KV through per-slot page tables —
    touches ONLY the pages the three branches address (page size == B_K, so
    one selected block is one physical page):

      compressed  all compressed-token rows (already gathered views — they
                  are O(N/stride) small)
      selected    the T pages named by ``page_table[idx]`` per slot
      sliding     the trailing ceil(W/B_K)+1 pages per slot

    gates: (B, h, 3); q: (B, h, d); k_pages/v_pages: (N_pages, h_k, P, d*);
    page_tables: (B, max_pages) int32; cmp_k/cmp_v: (B, N_cmp_max, h_k, d*);
    pos: (B,).  Returns (B, h, dv).

    ``kernel=True`` runs the Pallas paged-decode kernel: ``fsa_selected``'s
    BlockSpec pattern with the kv index_map composed through the page table
    (ids -> page_table[ids]) and B slots folded into the matmul M dimension —
    one launch per engine tick.  ``kernel=False`` is the gather reference
    (still a single batched dispatch, vmapped over slots).  The compressed
    prologue is shared with the dense-cache decode via
    ``sparse.decode_cmp_and_select`` on both paths.
    """
    b, h, d = q.shape
    h_k, p_sz = k_pages.shape[1], k_pages.shape[2]
    assert p_sz == cfg.block_size, "page size must equal the NSA block size"
    g = h // h_k
    s_max = page_tables.shape[1] * p_sz

    # --- compressed branch + top-T selection (shared with the dense path;
    #     logical block id == page-table index) ---
    out_cmp, idx, valid = jax.vmap(
        lambda q1, ck, cv, p1: sparse.decode_cmp_and_select(
            q1[None], ck, cv, p1, cfg, s_max))(q, cmp_k, cmp_v, pos)
    out_cmp = out_cmp[:, 0]                                  # (B, h, dv)
    idx, valid = idx[:, 0], valid[:, 0]                      # (B, h_k, T)

    # the selected and sliding branches run together (one kernel per tick)
    with named_scope("nsa.select"):
        if kernel:
            bs = block_s or cfg.paged_slot_block or max(1, -(-8 // g))
            bs = min(bs, b)
            pad = (-b) % bs
            if pad:
                q_p = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
                tables_p = jnp.pad(page_tables, ((0, pad), (0, 0)))
                idx_p = jnp.pad(idx, ((0, pad), (0, 0), (0, 0)))
                valid_p = jnp.pad(valid, ((0, pad), (0, 0), (0, 0)))
                pos_p = jnp.pad(pos, ((0, pad),))
            else:
                q_p, tables_p, idx_p, valid_p, pos_p = (q, page_tables, idx,
                                                        valid, pos)
            bp = b + pad
            with named_scope("nsa.index"):
                pages, blks = _paged.build_decode_steps(
                    idx_p, valid_p, tables_p, pos_p, window=cfg.window_size,
                    page_size=p_sz, block_s=bs)
            q_rows = (q_p.reshape(bp, h_k, g, d).transpose(1, 0, 2, 3)
                         .reshape(h_k, bp * g, d))
            o_sel, o_win = _paged.paged_decode(
                q_rows, k_pages, v_pages, pages, blks,
                pos_p.astype(jnp.int32), g=g, block_s=bs,
                num_sel=idx.shape[-1], window=cfg.window_size,
                interpret=cfg.interpret)
            dv = o_sel.shape[-1]
            unfold = lambda o: (o.reshape(h_k, bp, g, dv)
                                .transpose(1, 0, 2, 3).reshape(bp, h, dv)[:b])
            out_sel, out_win = unfold(o_sel), unfold(o_win)
        else:
            out_sel, out_win = jax.vmap(
                lambda q1, tb, i1, v1, p1: _paged_sel_win_ref(
                    q1, k_pages, v_pages, tb, i1, v1, p1, cfg))(
                        q, page_tables, idx, valid, pos)

    return gating.combine(gates, out_cmp, out_sel, out_win).astype(q.dtype)


# =====================================================================
# backend registrations
# =====================================================================
def _kernel_nsa(params, gates, q, k, v, cfg, kernel, q_chunk):
    """Three-branch NSA with the selected branch on a Pallas kernel and the
    sliding branch on the Pallas flash kernel (the old impl="kernel")."""
    out_cmp, idx, valid = core_attn.compressed_and_selection(
        params, q, k, v, cfg, q_chunk=q_chunk)
    with named_scope("nsa.select"):
        out_sel = selected_attention(q, k, v, idx, valid, cfg, kernel=kernel)
    with named_scope("nsa.window"):
        out_win = flash_attention(q, k, v, cfg, causal=True,
                                  window=cfg.window_size)
    return gating.combine(gates, out_cmp, out_sel, out_win).astype(q.dtype)


def _register_selected_kernel_backend(name, caps):
    @register_backend(name, capabilities=caps)
    def backend(params, gates, q, k, v, cache, cfg, mode,
                q_chunk: int = 512, **kw):
        return _kernel_nsa(params, gates, q, k, v, cfg, name, q_chunk)
    return backend


_register_selected_kernel_backend("fsa", Capabilities(
    modes=("train", "prefill"), algorithms=("nsa",), differentiable=True,
    fused_backward=True, priority=60, preferred_platforms=("tpu",)))

_register_selected_kernel_backend("fsa_faithful", Capabilities(
    modes=("train", "prefill"), algorithms=("nsa",), differentiable=True,
    fused_backward=True, priority=40, preferred_platforms=("tpu",)))

# The vanilla-NSA loop order keeps one query row per (token, head) in the
# MXU M dim, so it only fills the matmul when the GQA group is wide: the
# paper's regime analysis (and our analytic model) put its win at g >= 8.
_register_selected_kernel_backend("nsa", Capabilities(
    modes=("train", "prefill"), algorithms=("nsa",), differentiable=True,
    min_g=8, priority=20, preferred_platforms=("tpu",)))


@register_backend("sparse_union", capabilities=Capabilities(
    modes=("train", "prefill"), algorithms=("nsa",), differentiable=True,
    priority=70))
def _sparse_union_backend(params, gates, q, k, v, cache, cfg, mode,
                          q_chunk: int = 512, **kw):
    return sparse.nsa_attention_sparse(
        params, gates, q, k, v, cfg, q_chunk=q_chunk,
        selected_fn=sparse.selected_union_attention)


@register_backend("sparse_gather", capabilities=Capabilities(
    modes=("train", "prefill", "decode"), algorithms=("nsa",),
    differentiable=True, priority=20))
def _sparse_gather_backend(params, gates, q, k, v, cache, cfg, mode,
                           q_chunk: int = 512, **kw):
    if mode == "decode":
        return sparse.nsa_decode_step(params, gates, q, k, v,
                                      cache["cmp_k"], cache["cmp_v"],
                                      cache["pos"], cfg)
    return sparse.nsa_attention_sparse(
        params, gates, q, k, v, cfg, q_chunk=q_chunk,
        selected_fn=sparse.selected_gather_attention)


@register_backend("flash_full", capabilities=Capabilities(
    modes=("train", "prefill"), algorithms=("full",), differentiable=True,
    fused_backward=True, priority=5, preferred_platforms=("tpu",)))
def _flash_full_backend(params, gates, q, k, v, cache, cfg, mode,
                        causal: bool = True, **kw):
    return flash_attention(q, k, v, cfg, causal=causal, window=None)


@register_backend("flash_sliding", capabilities=Capabilities(
    modes=("train", "prefill"), algorithms=("sliding",), differentiable=True,
    fused_backward=True, priority=5, preferred_platforms=("tpu",)))
def _flash_sliding_backend(params, gates, q, k, v, cache, cfg, mode,
                           window: int | None = None, **kw):
    return flash_attention(q, k, v, cfg, causal=True,
                           window=window or cfg.window_size)


@register_backend("paged_kernel", capabilities=Capabilities(
    modes=("paged_decode",), algorithms=("nsa",), paged=True, priority=50))
def _paged_kernel_backend(params, gates, q, k, v, cache, cfg, mode,
                          block_s: int | None = None, **kw):
    return paged_decode_attention(
        gates, q, k, v, cache["page_tables"], cache["cmp_k"], cache["cmp_v"],
        cache["pos"], cfg, kernel=True, block_s=block_s)


@register_backend("paged_gather", capabilities=Capabilities(
    modes=("paged_decode",), algorithms=("nsa",), paged=True, priority=20))
def _paged_gather_backend(params, gates, q, k, v, cache, cfg, mode,
                          block_s: int | None = None, **kw):
    return paged_decode_attention(
        gates, q, k, v, cache["page_tables"], cache["cmp_k"], cache["cmp_v"],
        cache["pos"], cfg, kernel=False, block_s=block_s)


def sparse_selected_fn(cfg: NSAConfig):
    """The sparse selected-branch organization the policy names — for code
    (e.g. paged chunked prefill) that runs the sparse NSA chunk machinery
    directly and needs the union/gather choice without string dispatch of
    its own."""
    if cfg.policy.backend == "sparse_gather":
        return sparse.selected_gather_attention
    return sparse.selected_union_attention


def _reference_decode(params, gates_t, q_t, k_cache, v_cache, cache, cfg):
    """Dense-oracle one-token decode: embed the query at row ``pos`` of a
    full-shape sequence and run the dense NSA oracle.  Recomputes the
    compression caches from the raw KV (independent of the incremental
    cmp-cache emission the fast paths maintain), so it cross-checks them.
    """
    pos = cache["pos"]
    s = k_cache.shape[0]
    q_full = jnp.zeros((s,) + q_t.shape, q_t.dtype).at[pos].set(q_t)
    g_full = jnp.zeros((s,) + gates_t.shape, jnp.float32).at[pos].set(
        gates_t.astype(jnp.float32))
    out = nsa_attention_ref(params, g_full, q_full, k_cache, v_cache, cfg)
    return jnp.take(out, pos, axis=0).astype(q_t.dtype)


@register_backend("reference", capabilities=Capabilities(
    modes=("train", "prefill", "decode", "paged_decode"),
    algorithms=("nsa", "full", "sliding"), differentiable=True, paged=True,
    priority=10))
def _reference_backend(params, gates, q, k, v, cache, cfg, mode,
                       algorithm: str = "nsa", causal: bool = True,
                       window: int | None = None, q_chunk: int = 512, **kw):
    if algorithm == "full":
        return _ref.flash_ref_chunked(q, k, v, causal=causal, q_chunk=q_chunk)
    if algorithm == "sliding":
        return _ref.flash_ref_chunked(q, k, v, causal=True,
                                      window=window or cfg.window_size,
                                      q_chunk=q_chunk)
    if mode == "decode":
        return _reference_decode(params, gates, q, k, v, cache, cfg)
    if mode == "paged_decode":
        # gather full dense views through the page tables, then run the
        # dense-cache decode path on them — checks the paged organizations
        # at a different gather granularity (whole view vs selected pages)
        tables, pos = cache["page_tables"], cache["pos"]
        s_max = tables.shape[1] * k.shape[2]
        rows = jnp.arange(s_max)
        k_view = jax.vmap(gather_rows, in_axes=(None, 0, None))(k, tables, rows)
        v_view = jax.vmap(gather_rows, in_axes=(None, 0, None))(v, tables, rows)
        return jax.vmap(
            lambda g1, q1, kv1, vv1, ck, cv, p1: sparse.nsa_decode_step(
                params, g1, q1, kv1, vv1, ck, cv, p1, cfg))(
                    gates, q, k_view, v_view, cache["cmp_k"], cache["cmp_v"],
                    pos)
    return nsa_attention_ref(params, gates, q, k, v, cfg)
