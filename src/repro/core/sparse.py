"""Sparse (gather-based) NSA fast path in pure JAX.

This is the production path used by model layers for training / prefill
lowering and long-context decode.  Unlike the dense-mask oracles in
``reference.py`` it never materialises an (N, N) score matrix:

* queries are processed in chunks of ``q_chunk`` (a sequential ``lax.map``),
  bounding transient memory to O(q_chunk · T · B_K · d) per KV head;
* the selected branch gathers exactly the top-T KV blocks per token;
* the sliding branch slices a (q_chunk + W - 1) window;
* the compressed branch attends to N/stride summary tokens (linear).

Total per-token cost is O(T·B_K + W + N/stride) — sub-quadratic, which is
what makes the ``long_500k`` decode shape feasible.

The Pallas kernels in ``repro.kernels`` replace the selected branch on TPU;
this module is also their semantic twin for the dry-run (XLA can cost-analyse
it, whereas a custom call is opaque).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import compression, gating, selection
from repro.core.nsa_config import NSAConfig
from repro.core.reference import _gqa_out, _gqa_scores, _safe_softmax
from repro.telemetry import named_scope


def selected_gather_attention(q, k, v, idx, valid, cfg: NSAConfig, q_pos):
    """Gather-based selected attention for one query chunk.

    q: (C, h, d); k/v: (S, h_k, d); idx/valid: (C, h_k, T); q_pos: (C,).
    Returns (C, h, dv).
    """
    c, h, d = q.shape
    s, h_k, _ = k.shape
    g = h // h_k
    t = idx.shape[-1]
    bk = cfg.block_size

    tok = idx[..., None] * bk + jnp.arange(bk)              # (C, h_k, T, B_K)
    tok = tok.reshape(c, h_k, t * bk)
    tok_ok = (tok < s) & jnp.repeat(valid, bk, axis=-1) & (tok <= q_pos[:, None, None])
    tok_c = jnp.minimum(tok, s - 1).transpose(1, 0, 2)      # (h_k, C, S_sel)

    k_t = k.transpose(1, 0, 2)                              # (h_k, S, d)
    v_t = v.transpose(1, 0, 2)
    k_sel = jax.vmap(lambda kk, tt: kk[tt])(k_t, tok_c)     # (h_k, C, S_sel, d)
    v_sel = jax.vmap(lambda vv, tt: vv[tt])(v_t, tok_c)

    qg = q.reshape(c, h_k, g, d).astype(jnp.float32)
    scores = jnp.einsum("ckgd,kcsd->ckgs", qg, k_sel.astype(jnp.float32))
    scores = scores / jnp.sqrt(d).astype(jnp.float32)
    mask = tok_ok.transpose(0, 1, 2)[:, :, None, :]         # (C, h_k, 1, S_sel)
    probs, _ = _safe_softmax(scores, mask)
    out = jnp.einsum("ckgs,kcsd->ckgd", probs, v_sel.astype(jnp.float32))
    return out.reshape(c, h, -1).astype(q.dtype)


def selected_gather_chunked(q, k, v, idx, valid, cfg: NSAConfig,
                            q_chunk: int = 512):
    """Whole-sequence selected attention via :func:`selected_gather_attention`
    over ``q_chunk``-token chunks (sequential ``lax.map``).

    q: (N, h, d); k/v: (S, h_k, d); idx/valid: (N, h_k, T).  This is the
    differentiable XLA twin behind the selected-branch Pallas kernels'
    fallback VJP (``repro.attention.vjp.kernel_vjp``).
    """
    n = q.shape[0]
    c = min(q_chunk, n)
    pad = (c - n % c) % c
    pad_tok = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
    qp, idxp, validp = pad_tok(q), pad_tok(idx), pad_tok(valid)

    def body(args):
        q_c, i_c, v_c, pos_c = args
        return selected_gather_attention(q_c, k, v, i_c, v_c, cfg, pos_c)

    nc = (n + pad) // c
    out = jax.lax.map(body, (qp.reshape(nc, c, *q.shape[1:]),
                             idxp.reshape(nc, c, *idx.shape[1:]),
                             validp.reshape(nc, c, *valid.shape[1:]),
                             jnp.arange(n + pad).reshape(nc, c)))
    return out.reshape(n + pad, q.shape[1], -1)[:n]


def _union_setup(q, k, v, idx, valid, cfg: NSAConfig, q_pos):
    """Shared fwd/bwd machinery: union lists, gathers, scores, mask."""
    from repro.parallel.axes import shard as _shard

    c, h, d = q.shape
    s, h_k, _ = k.shape
    g = h // h_k
    bk = cfg.block_size
    b = (s + bk - 1) // bk
    cap = min(b, c * idx.shape[-1])          # static, always-correct bound

    with named_scope("nsa.index"):
        oh = jnp.zeros((c, h_k, b), bool)
        oh = oh.at[jnp.arange(c)[:, None, None],
                   jnp.arange(h_k)[None, :, None], idx].max(valid)
        present = oh.any(0).astype(jnp.int32)               # (h_k, b)
        order = jnp.argsort(1 - present, axis=-1,
                            stable=True).astype(jnp.int32)
        ids = order[:, :cap]                                # (h_k, cap)

    tok = ids[:, :, None] * bk + jnp.arange(bk)             # (h_k, cap, B_K)
    tok_flat = jnp.minimum(tok.reshape(h_k, cap * bk), s - 1)
    k_t = _shard(k.transpose(1, 0, 2), "kv_heads", None, None)
    v_t = _shard(v.transpose(1, 0, 2), "kv_heads", None, None)
    k_sel = jax.vmap(lambda kk, tt: kk[tt])(k_t, tok_flat)
    v_sel = jax.vmap(lambda vv, tt: vv[tt])(v_t, tok_flat)
    k_sel = _shard(k_sel, "kv_heads", None, None)
    v_sel = _shard(v_sel, "kv_heads", None, None)

    qg = q.reshape(c, h_k, g, d).astype(jnp.float32)
    scores = jnp.einsum("ckgd,ksd->ckgs", qg, k_sel.astype(jnp.float32))
    scores = scores / jnp.sqrt(d).astype(jnp.float32)

    slot_blk = ids[:, :, None] * jnp.ones((1, 1, bk), jnp.int32)
    slot_blk = slot_blk.reshape(h_k, cap * bk)              # (h_k, S_u)
    picked = ((idx[:, :, None, :] == slot_blk[None, :, :, None])
              & valid[:, :, None, :]).any(-1)               # (C, h_k, S_u)
    live = (jnp.arange(cap)[None, :] <
            jnp.minimum(present.sum(-1), cap)[:, None])     # (h_k, cap)
    live = jnp.repeat(live, bk, axis=-1)
    causal = q_pos[:, None, None] >= tok_flat[None, :, :]
    in_range = (tok.reshape(h_k, cap * bk) < s)[None]
    mask = picked & live[None] & causal & in_range          # (C, h_k, S_u)

    probs, _ = _safe_softmax(scores, mask[:, :, None, :])
    return probs, mask, k_sel, v_sel, tok_flat, qg


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def selected_union_attention(q, k, v, idx, valid, cfg: NSAConfig, q_pos=None):
    """FSA-organized selected attention in XLA ops (block-batched).

    Instead of gathering T blocks per *token* (which re-fetches every block
    once per selecting token — the naive path above), gather the **union** of
    blocks selected by any token of this chunk once per (chunk, KV head) and
    mask.  This is exactly the FSA kernel's data-movement strategy, expressed
    as gather+einsum so XLA (and the dry-run cost model) see it.  Traffic per
    chunk drops from C·T·B_K·d to |union|·B_K·d ≤ min(b, C·T)·B_K·d.

    Backward is a custom VJP: dK/dV are produced by a *per-KV-head-sharded*
    scatter-add (the FSA reduction step) — without it XLA all-gathers the
    full (B,S,h_K,d) f32 cotangent buffer once per chunk (measured 4.4e12
    B/dev on codeqwen train_4k; see README "Layout" and the perf notes in
    the git history of this module).

    q: (C, h, d); k/v: (S, h_k, d); idx/valid: (C, h_k, T); q_pos: (C,).
    """
    probs, _, _, v_sel, _, _ = _union_setup(q, k, v, idx, valid, cfg, q_pos)
    c, h, d = q.shape
    out = jnp.einsum("ckgs,ksd->ckgd", probs, v_sel.astype(jnp.float32))
    return out.reshape(c, h, -1).astype(q.dtype)


def _union_fwd(q, k, v, idx, valid, cfg, q_pos):
    out = selected_union_attention(q, k, v, idx, valid, cfg, q_pos)
    return out, (q, k, v, idx, valid, q_pos)


def _union_bwd(cfg, res, dout):
    from repro.parallel.axes import shard as _shard

    q, k, v, idx, valid, q_pos = res
    c, h, d = q.shape
    s, h_k, _ = k.shape
    g = h // h_k
    dv_dim = v.shape[-1]
    # recompute (remat-style: nothing big is saved across the chunk loop)
    probs, mask, k_sel, v_sel, tok_flat, qg = _union_setup(
        q, k, v, idx, valid, cfg, q_pos)
    do = dout.reshape(c, h_k, g, dv_dim).astype(jnp.float32)

    dprobs = jnp.einsum("ckgd,ksd->ckgs", do, v_sel.astype(jnp.float32))
    dv_sel = jnp.einsum("ckgs,ckgd->ksd", probs, do)
    # softmax backward (masked rows have probs==0 so flow nothing)
    inner = jnp.sum(dprobs * probs, axis=-1, keepdims=True)
    dscores = probs * (dprobs - inner) / jnp.sqrt(d).astype(jnp.float32)
    dq = jnp.einsum("ckgs,ksd->ckgd", dscores, k_sel.astype(jnp.float32))
    dk_sel = jnp.einsum("ckgs,ckgd->ksd", dscores, qg)

    # FSA reduction: scatter the per-union-slot cotangents back to K/V rows,
    # locally per KV head (sharded over "kv_heads" — no cross-shard traffic)
    dk_sel = _shard(dk_sel, "kv_heads", None, None)
    dv_sel = _shard(dv_sel, "kv_heads", None, None)

    def scat(upd, width):
        buf = jnp.zeros((h_k, s, width), jnp.float32)
        buf = jax.vmap(lambda b_, t_, u_: b_.at[t_].add(u_))(buf, tok_flat, upd)
        return _shard(buf, "kv_heads", None, None).transpose(1, 0, 2)

    dk = scat(dk_sel, d).astype(k.dtype)
    dv = scat(dv_sel, dv_dim).astype(v.dtype)
    dq = dq.reshape(c, h, d).astype(q.dtype)
    zi = jnp.zeros(idx.shape, jax.dtypes.float0)
    zv = jnp.zeros(valid.shape, jax.dtypes.float0)
    zp = jnp.zeros(q_pos.shape, jax.dtypes.float0)
    return dq, dk, dv, zi, zv, zp


selected_union_attention.defvjp(_union_fwd, _union_bwd)


def sliding_window_chunk(q, k, v, start, cfg: NSAConfig, q_pos):
    """Sliding-window attention for one query chunk.

    start: scalar — global position of the first key to slice.  Slices
    min(S, C + W - 1) keys beginning at ``start`` (clamped by dynamic_slice).
    """
    c = q.shape[0]
    s, h_k, d = k.shape
    w = cfg.window_size
    span = min(s, c + w - 1)
    start = jnp.clip(start, 0, s - span)
    k_win = jax.lax.dynamic_slice_in_dim(k, start, span, axis=0)
    v_win = jax.lax.dynamic_slice_in_dim(v, start, span, axis=0)
    key_pos = start + jnp.arange(span)
    mask = (q_pos[:, None] >= key_pos[None, :]) & (q_pos[:, None] - key_pos[None, :] < w)
    probs, _ = _safe_softmax(_gqa_scores(q, k_win), mask[:, None, :])
    return _gqa_out(probs, v_win).astype(q.dtype)


def _nsa_chunk(params, cfg, k, v, k_cmp, v_cmp, sel_map, chunk,
               selected_fn=None):
    """Process one query chunk. chunk = (q_c, gates_c, pos_c).

    ``selected_fn(q_c, k, v, idx, valid, cfg, pos_c)`` is the selected-branch
    organization — ``selected_union_attention`` (FSA block-union, the
    production default) or ``selected_gather_attention`` (naive per-token
    gather baseline).  The ``repro.attention`` registry passes it; there is
    no string dispatch here.
    """
    q_c, gates_c, pos_c = chunk
    n = k.shape[0]
    g = q_c.shape[1] // k.shape[1]

    with named_scope("nsa.compress"):
        # --- compressed branch (+ selection scores) ---
        vis = compression.cmp_visibility(pos_c, k_cmp.shape[0], cfg)
        p_cmp, _ = _safe_softmax(_gqa_scores(q_c, k_cmp), vis[:, None, :])
        out_cmp = _gqa_out(p_cmp, v_cmp)
        # --- selection ---
        scores = selection.importance_scores(p_cmp, sel_map, g)
        idx, valid = selection.select_blocks(scores, pos_c, cfg, n)

    # --- selected branch (FSA block-union unless the caller overrides) ---
    if selected_fn is None:
        selected_fn = selected_union_attention
    with named_scope("nsa.select"):
        out_sel = selected_fn(q_c, k, v, idx, valid, cfg, pos_c)

    with named_scope("nsa.window"):
        out_win = sliding_window_chunk(
            q_c, k, v, pos_c[0] - (cfg.window_size - 1), cfg, pos_c)

    out = gating.combine(gates_c, out_cmp, out_sel, out_win)
    return out.astype(q_c.dtype), (idx, valid)


def nsa_attention_sparse(
    params,
    gates: jnp.ndarray,
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    cfg: NSAConfig,
    *,
    q_chunk: int = 512,
    return_selection: bool = False,
    selected_fn=None,
):
    """Full NSA attention, sparse path. q: (N, h, d); gates: (N, h, 3).

    ``selected_fn`` picks the selected-branch organization (see
    ``_nsa_chunk``); None means the FSA block-union production path.
    """
    n, h, d = q.shape
    with named_scope("nsa.compress"):
        k_cmp, v_cmp = compression.compress_kv(params, k, v, cfg)
    sel_map = jnp.asarray(
        compression.cmp_to_sel_map(k_cmp.shape[0], cfg.num_kv_blocks(n), cfg)
    )

    c = min(q_chunk, n)
    if n % c:  # pad to a whole number of chunks
        pad = c - n % c
        q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
        gates = jnp.pad(gates, ((0, pad), (0, 0), (0, 0)))
    n_pad = q.shape[0]
    pos = jnp.arange(n_pad)

    body = functools.partial(_nsa_chunk, params, cfg, k, v, k_cmp, v_cmp,
                             sel_map, selected_fn=selected_fn)
    chunks = (
        q.reshape(n_pad // c, c, h, d),
        gates.reshape(n_pad // c, c, h, 3),
        pos.reshape(n_pad // c, c),
    )
    out, (idx, valid) = jax.lax.map(body, chunks)
    out = out.reshape(n_pad, h, -1)[:n]
    if return_selection:
        t = idx.shape[-1]
        return out, (idx.reshape(n_pad, -1, t)[:n], valid.reshape(n_pad, -1, t)[:n])
    return out


@named_scope("nsa.compress")
def decode_cmp_and_select(q_c, k_cmp, v_cmp, pos, cfg: NSAConfig,
                          seq_len: int):
    """Shared one-token decode prologue: compressed-branch attention + top-T
    block selection.  Used by both the dense-cache decode below and the
    paged decode in ``kernels.ops.paged_decode_attention_batched`` (kernel
    and gather-reference paths alike) so the paths stay provably identical.

    q_c: (1, h, d); k_cmp/v_cmp: (N_cmp, h_k, d); pos: scalar; seq_len: raw
    KV span (block ids index [0, num_kv_blocks(seq_len))).
    Returns (out_cmp (1, h, dv), idx (1, h_k, T), valid).
    """
    g = q_c.shape[1] // k_cmp.shape[1]
    # mask compressed tokens whose window is not complete or in the future
    n_cmp = k_cmp.shape[0]
    ends = jnp.arange(n_cmp) * cfg.cmp_stride + cfg.cmp_block_size - 1
    vis = (ends <= pos)[None, None, :]
    p_cmp, _ = _safe_softmax(_gqa_scores(q_c, k_cmp), vis)
    out_cmp = _gqa_out(p_cmp, v_cmp)

    sel_map = jnp.asarray(
        compression.cmp_to_sel_map(n_cmp, cfg.num_kv_blocks(seq_len), cfg))
    scores = selection.importance_scores(p_cmp, sel_map, g)
    idx, valid = selection.select_blocks(scores, pos[None], cfg, seq_len)
    return out_cmp, idx, valid


def nsa_decode_step(
    params,
    gates: jnp.ndarray,
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    k_cmp: jnp.ndarray,
    v_cmp: jnp.ndarray,
    pos: jnp.ndarray,
    cfg: NSAConfig,
):
    """One-token NSA decode. q: (h, d); caches: (S, h_k, d) / (N_cmp, h_k, d).

    ``pos`` is the absolute position of the query token; cache entries at
    positions > pos (and compressed tokens not yet complete) are masked.
    Cost: O(N_cmp + T·B_K + W) — linear in context with a small constant.
    """
    s = k_cache.shape[0]
    q_c = q[None]                                            # (1, h, d)
    pos_c = pos[None]

    out_cmp, idx, valid = decode_cmp_and_select(q_c, k_cmp, v_cmp, pos, cfg, s)
    with named_scope("nsa.select"):
        out_sel = selected_gather_attention(q_c, k_cache, v_cache, idx, valid,
                                            cfg, pos_c)
    with named_scope("nsa.window"):
        out_win = sliding_window_chunk(
            q_c, k_cache, v_cache, pos - (cfg.window_size - 1), cfg, pos_c)
    out = gating.combine(gates[None], out_cmp, out_sel, out_win)
    return out[0].astype(q.dtype)
