"""Attention layer: GQA / MLA projections around the NSA-FSA core.

Attention kinds (cfg.attention): "nsa" (paper technique), "full", "swa".

MLA (DeepSeek-V2) is implemented in *absorbed* form: attention runs in the
512-d latent space with a single shared KV head (q/k = latent ⊕ decoupled
RoPE part, v = latent), and the per-head value up-projection W_uv is applied
to the attention output.  This is mathematically identical to materialising
the 16 KV heads (associativity of the matmuls) and lets NSA's compression /
selection / sliding machinery — and the FSA kernels — operate on the latent
cache directly, which is also the correct decode-time layout.  (See the
model-zoo applicability notes in README "Layout" / ROADMAP.md.)

All attention math dispatches through ``repro.attention.nsa_attention``
(the capability-based backend registry); this layer only does projections,
caches and sharding.

Decode keeps a raw KV cache plus incrementally-updated NSA compression
caches, so per-token cost stays O(N/stride + T·B_K + W).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro import attention as uattn
from repro.core.paging import gather_rows, init_pool, scatter_rows
from repro.core import compression, gating, sparse
from repro.models.layers import apply_rope, dense_init, rms_norm
from repro.parallel.axes import shard
from repro.telemetry import named_scope


# ------------------------------------------------------------------ params
def init_attention(key, cfg) -> dict:
    d, h, hk, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd()
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 8)
    p = {}
    if cfg.mla is not None:
        m = cfg.mla
        dk_lat = m.kv_lora + m.rope_dim
        p["w_q"] = dense_init(ks[0], (d, h * (m.nope_dim + m.rope_dim)), dtype)
        p["w_dkv"] = dense_init(ks[1], (d, m.kv_lora), dtype)
        p["kv_norm"] = jnp.zeros((m.kv_lora,), dtype)
        p["w_kr"] = dense_init(ks[2], (d, m.rope_dim), dtype)
        # absorbed projections: q->latent (per head), latent->value head
        p["w_uk"] = dense_init(ks[3], (h, m.nope_dim, m.kv_lora), dtype)
        p["w_uv"] = dense_init(ks[4], (h, m.kv_lora, hd), dtype)
        p["w_o"] = dense_init(ks[5], (h * hd, d), dtype)
        attn_dk, attn_dv, attn_hk = dk_lat, m.kv_lora, 1
    else:
        p["w_q"] = dense_init(ks[0], (d, h * hd), dtype)
        p["w_k"] = dense_init(ks[1], (d, hk * hd), dtype)
        p["w_v"] = dense_init(ks[2], (d, hk * hd), dtype)
        p["w_o"] = dense_init(ks[3], (h * hd, d), dtype)
        if cfg.use_qkv_bias:
            p["b_q"] = jnp.zeros((h * hd,), dtype)
            p["b_k"] = jnp.zeros((hk * hd,), dtype)
            p["b_v"] = jnp.zeros((hk * hd,), dtype)
        attn_dk, attn_dv, attn_hk = hd, hd, hk
    if cfg.attention == "nsa":
        p["nsa"] = {
            **compression.init_compression_params(ks[6], cfg.nsa, attn_dk,
                                                  attn_dv, dtype),
            **gating.init_gate_params(ks[7], d, h, dtype),
        }
    del attn_hk
    return p


# -------------------------------------------------------------- projections
@named_scope("attn.qkv")
def _qkv(p, x, cfg, pos):
    """x: (B,S,D) -> q (B,S,h,dk), k (B,S,h_k,dk), v (B,S,h_k,dv)."""
    b, s, _ = x.shape
    h, hd = cfg.n_heads, cfg.hd()
    if cfg.mla is not None:
        m = cfg.mla
        c_kv = rms_norm(x @ p["w_dkv"], p["kv_norm"], cfg.norm_eps)   # (B,S,L)
        k_rope = apply_rope(
            (x @ p["w_kr"])[:, :, None, :], pos, cfg.rope_theta)      # (B,S,1,r)
        q = (x @ p["w_q"]).reshape(b, s, h, m.nope_dim + m.rope_dim)
        q_nope, q_rope = q[..., :m.nope_dim], q[..., m.nope_dim:]
        q_rope = apply_rope(q_rope, pos, cfg.rope_theta)
        q_lat = jnp.einsum("bshn,hnl->bshl", q_nope, p["w_uk"])       # absorbed
        q_full = jnp.concatenate([q_lat, q_rope], axis=-1)            # (B,S,h,L+r)
        k_full = jnp.concatenate([c_kv[:, :, None, :], k_rope], axis=-1)
        return q_full, k_full, c_kv[:, :, None, :]
    hk = cfg.n_kv_heads
    q = x @ p["w_q"] + (p.get("b_q", 0))
    k = x @ p["w_k"] + (p.get("b_k", 0))
    v = x @ p["w_v"] + (p.get("b_v", 0))
    q = apply_rope(q.reshape(b, s, h, hd), pos, cfg.rope_theta)
    k = apply_rope(k.reshape(b, s, hk, hd), pos, cfg.rope_theta)
    return q, k, v.reshape(b, s, hk, hd)


@named_scope("attn.out")
def _out_proj(p, o, cfg):
    """o: (B,S,h,dv_attn) -> (B,S,D)."""
    b, s = o.shape[:2]
    if cfg.mla is not None:
        o = jnp.einsum("bshl,hld->bshd", o, p["w_uv"])
    return o.reshape(b, s, -1) @ p["w_o"]


# ------------------------------------------------------------ full-sequence
def attention_forward(p, x, cfg, *, causal: bool = True):
    """Training / prefill attention over a full sequence. x: (B,S,D)."""
    b, s, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    q, k, v = _qkv(p, x, cfg, pos)
    q = shard(q, "batch", "seq", "heads")
    k = shard(k, "batch", "seq", "kv_heads")
    v = shard(v, "batch", "seq", "kv_heads")

    if cfg.attention == "nsa" and causal:
        gates = gating.apply_gates(p["nsa"], x)
        fn = lambda q1, k1, v1, g1: uattn.nsa_attention(
            p["nsa"], g1, q1, k1, v1, cfg=cfg.nsa, mode="train",
            backend=cfg.attn_impl, q_chunk=cfg.q_chunk)
        o = jax.vmap(fn)(q, k, v, gates)
    elif cfg.attention == "swa" and causal:
        fn = lambda q1, k1, v1: uattn.nsa_attention(
            None, None, q1, k1, v1, cfg=cfg.nsa, mode="train",
            algorithm="sliding", window=cfg.swa_window, q_chunk=cfg.q_chunk)
        o = jax.vmap(fn)(q, k, v)
    else:
        fn = lambda q1, k1, v1: uattn.nsa_attention(
            None, None, q1, k1, v1, cfg=cfg.nsa, mode="train",
            algorithm="full", causal=causal, q_chunk=cfg.q_chunk)
        o = jax.vmap(fn)(q, k, v)
    o = shard(o, "batch", "seq", "heads")
    return _out_proj(p, o, cfg)


def cross_attention_forward(p, x, kv_x, cfg):
    """Encoder-decoder cross attention (full, non-causal). kv_x: (B,Senc,D)."""
    b, s, _ = x.shape
    pos = jnp.zeros((b, s), jnp.int32)      # no rope on cross attention
    h, hd, hk = cfg.n_heads, cfg.hd(), cfg.n_kv_heads
    q = (x @ p["w_q"]).reshape(b, s, h, hd)
    k = (kv_x @ p["w_k"]).reshape(b, kv_x.shape[1], hk, hd)
    v = (kv_x @ p["w_v"]).reshape(b, kv_x.shape[1], hk, hd)
    o = jax.vmap(lambda a, b_, c: uattn.nsa_attention(
        None, None, a, b_, c, cfg=cfg.nsa, mode="prefill", algorithm="full",
        causal=False, q_chunk=cfg.q_chunk))(q, k, v)
    return o.reshape(b, s, -1) @ p["w_o"]


# ------------------------------------------------------------------ decode
def init_attn_cache(cfg, batch: int, max_len: int):
    dtype = jnp.dtype(cfg.dtype)
    if cfg.mla is not None:
        dk = cfg.mla.kv_lora + cfg.mla.rope_dim
        dv, hk = cfg.mla.kv_lora, 1
    else:
        dk = dv = cfg.hd()
        hk = cfg.n_kv_heads
    cache = {
        "k": jnp.zeros((batch, max_len, hk, dk), dtype),
        "v": jnp.zeros((batch, max_len, hk, dv), dtype),
    }
    if cfg.attention == "nsa":
        n_cmp = cfg.nsa.num_cmp_blocks(max_len)
        cache["cmp_k"] = jnp.zeros((batch, n_cmp, hk, dk), dtype)
        cache["cmp_v"] = jnp.zeros((batch, n_cmp, hk, dv), dtype)
    return cache


def attention_prefill(p, x, cfg, cache):
    """Run full-seq attention and populate the decode cache. x: (B,S,D)."""
    b, s, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    _, k, v = _qkv(p, x, cfg, pos)
    y = attention_forward(p, x, cfg)
    cache = dict(cache)
    cache["k"] = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), 0, 1)
    cache["v"] = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), 0, 1)
    if cfg.attention == "nsa":
        ck, cv = jax.vmap(lambda k1, v1: compression.compress_kv(p["nsa"], k1, v1, cfg.nsa))(k, v)
        n = min(ck.shape[1], cache["cmp_k"].shape[1])
        cache["cmp_k"] = cache["cmp_k"].at[:, :n].set(ck[:, :n].astype(cache["cmp_k"].dtype))
        cache["cmp_v"] = cache["cmp_v"].at[:, :n].set(cv[:, :n].astype(cache["cmp_v"].dtype))
    return y, cache


@named_scope("nsa.compress")
def _emit_cmp_token(p, cfg, win_k, win_v):
    """Compress one complete (l,)-token window into a single summary token.

    win_k/win_v: (B, l, h_k, d) -> (ck, cv): (B, h_k, d).
    """
    nsa = cfg.nsa
    one = dataclasses.replace(nsa, cmp_block_size=nsa.cmp_block_size,
                              cmp_stride=nsa.cmp_block_size)
    ck, cv = jax.vmap(lambda k1, v1: compression.compress_kv(p["nsa"], k1, v1, one)
                      )(win_k, win_v)
    return ck[:, 0], cv[:, 0]


def _update_cmp_cache(p, cfg, cache, pos):
    """Emit the newest compression token per slot if its stride boundary was
    crossed.  pos: (B,) absolute positions (per-slot, continuous batching)."""
    nsa = cfg.nsa
    l, st = nsa.cmp_block_size, nsa.cmp_stride
    b = pos.shape[0]
    new_len = pos + 1
    has_new = (new_len >= l) & ((new_len - l) % st == 0)     # (B,)
    j = jnp.maximum((new_len - l) // st, 0)                  # cmp token index
    rows = (j * st)[:, None] + jnp.arange(l)[None, :]        # (B, l)
    b_idx = jnp.arange(b)
    win_k = cache["k"][b_idx[:, None], rows]                 # (B, l, h_k, d)
    win_v = cache["v"][b_idx[:, None], rows]
    ck, cv = _emit_cmp_token(p, cfg, win_k, win_v)

    cache = dict(cache)
    tgt = jnp.where(has_new, jnp.minimum(j, cache["cmp_k"].shape[1] - 1), 0)
    sel = has_new[:, None, None]
    new_ck = jnp.where(sel, ck.astype(cache["cmp_k"].dtype), cache["cmp_k"][b_idx, tgt])
    new_cv = jnp.where(sel, cv.astype(cache["cmp_v"].dtype), cache["cmp_v"][b_idx, tgt])
    cache["cmp_k"] = cache["cmp_k"].at[b_idx, tgt].set(new_ck)
    cache["cmp_v"] = cache["cmp_v"].at[b_idx, tgt].set(new_cv)
    return cache


def attention_decode(p, x_t, cache, pos, cfg):
    """One decode step. x_t: (B,D); pos: scalar or (B,) absolute positions.

    A (B,) vector enables continuous batching: every slot decodes at its own
    depth into the cache (variable-length traffic).  Scalar pos broadcasts.
    """
    b = x_t.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    x1 = x_t[:, None, :]
    q, k, v = _qkv(p, x1, cfg, pos[:, None])             # (B,1,h,dk) ...
    b_idx = jnp.arange(b)
    cache = dict(cache)
    cache["k"] = cache["k"].at[b_idx, pos].set(k[:, 0].astype(cache["k"].dtype))
    cache["v"] = cache["v"].at[b_idx, pos].set(v[:, 0].astype(cache["v"].dtype))

    if cfg.attention == "nsa":
        cache = _update_cmp_cache(p, cfg, cache, pos)
        gates = gating.apply_gates(p["nsa"], x_t)        # (B,h,3)
        fn = lambda q1, kc, vc, ck, cv, g1, p1: uattn.nsa_attention(
            p["nsa"], g1, q1, kc, vc, {"cmp_k": ck, "cmp_v": cv, "pos": p1},
            cfg=cfg.nsa, mode="decode")
        o = jax.vmap(fn)(q[:, 0], cache["k"], cache["v"],
                         cache["cmp_k"], cache["cmp_v"], gates, pos)
    else:
        window = cfg.swa_window if cfg.attention == "swa" else None
        span = cache["k"].shape[1]
        key_pos = jnp.arange(span)
        mask = key_pos[None, :] <= pos[:, None]          # (B, span)
        if window is not None:
            mask &= key_pos[None, :] > (pos[:, None] - window)
        from repro.core.reference import _gqa_out, _gqa_scores, _safe_softmax
        def fn(q1, kc, vc, m1):
            scores = _gqa_scores(q1, kc)
            probs, _ = _safe_softmax(scores, m1[None, None, :])
            return _gqa_out(probs, vc).astype(q1.dtype)
        o = jax.vmap(fn)(q[:, 0:1], cache["k"], cache["v"], mask)
        o = o[:, 0]
    o = o.reshape(b, 1, cfg.n_heads, -1)
    return _out_proj(p, o, cfg)[:, 0], cache


# ------------------------------------------------------------- paged decode
def init_paged_attn_cache(cfg, num_pages: int, num_cmp_pages: int):
    """Per-layer paged KV storage: raw-token pages + compressed-token pages.

    Page size equals ``cfg.nsa.block_size`` so a selected NSA block IS one
    physical page — the selected branch reads exactly the pages the page
    table names.  Page 0 of each pool is a reserved dump page (never
    allocated); idle slots and masked writes land there.
    """
    dtype = jnp.dtype(cfg.dtype)
    pp = cfg.nsa.block_size
    if cfg.mla is not None:
        dk = cfg.mla.kv_lora + cfg.mla.rope_dim
        dv, hk = cfg.mla.kv_lora, 1
    else:
        dk = dv = cfg.hd()
        hk = cfg.n_kv_heads
    cache = {
        "k_pages": init_pool(num_pages, hk, pp, dk, dtype),
        "v_pages": init_pool(num_pages, hk, pp, dv, dtype),
    }
    if cfg.attention == "nsa":
        cache["cmp_k_pages"] = init_pool(num_cmp_pages, hk, pp, dk, dtype)
        cache["cmp_v_pages"] = init_pool(num_cmp_pages, hk, pp, dv, dtype)
    return cache


def _paged_emit_cmp(p, cfg, layer_cache, tables, pos, active=None):
    """Per-slot stride-boundary compressed-token emission on paged storage.

    pos: (B,) position of the token just written; emits cmp token
    ``j = (pos+1-l)/st`` for slots that crossed a boundary, writing it through
    the compressed-page table (dump page 0 otherwise).  ``active`` (B,) bool
    additionally masks slots whose decode row is inert this dispatch (fused
    mixed tick: slots mid-prefill carry REAL page tables, so their ride-along
    emission must be forced onto the dump page).
    """
    nsa = cfg.nsa
    l, st = nsa.cmp_block_size, nsa.cmp_stride
    new_len = pos + 1
    has_new = (new_len >= l) & ((new_len - l) % st == 0)           # (B,)
    if active is not None:
        has_new &= active
    j = jnp.maximum((new_len - l) // st, 0)
    rows = (j * st)[:, None] + jnp.arange(l)[None, :]              # (B, l)
    win_k = jax.vmap(gather_rows, in_axes=(None, 0, 0))(
        layer_cache["k_pages"], tables["page_table"], rows)        # (B,l,hk,dk)
    win_v = jax.vmap(gather_rows, in_axes=(None, 0, 0))(
        layer_cache["v_pages"], tables["page_table"], rows)
    ck, cv = _emit_cmp_token(p, cfg, win_k, win_v)                 # (B,hk,d)

    layer_cache = dict(layer_cache)
    layer_cache["cmp_k_pages"] = scatter_rows(
        layer_cache["cmp_k_pages"], tables["cmp_table"], j[:, None],
        ck[:, None], valid=has_new[:, None],
        min_pos=tables.get("cmp_write_floor"))
    layer_cache["cmp_v_pages"] = scatter_rows(
        layer_cache["cmp_v_pages"], tables["cmp_table"], j[:, None],
        cv[:, None], valid=has_new[:, None],
        min_pos=tables.get("cmp_write_floor"))
    return layer_cache


def paged_attention_decode(p, x_t, layer_cache, tables, pos, cfg, *,
                           active=None):
    """One decode step on paged KV storage (continuous batching).

    x_t: (B, D); pos: (B,) per-slot absolute positions;
    tables: {"page_table": (B, max_pages), "cmp_table": (B, max_cmp_pages)}.
    ``active`` (B,) bool masks rows that must ride along inertly (all writes
    to the dump page) — the fused mixed tick passes the decode-slot mask so
    slots mid-prefill, which carry real page tables, stay untouched.

    The NSA path reads only the pages its branches touch: compressed pages,
    the top-T selected pages (page == NSA block), and the sliding-window
    pages — one batched dispatch through ``repro.attention`` (the Pallas
    paged-decode kernel unless ``cfg.nsa.policy.paged_backend`` says
    otherwise).
    """
    b = x_t.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    q, k, v = _qkv(p, x_t[:, None, :], cfg, pos[:, None])
    kv_valid = None if active is None else active[:, None]
    layer_cache = dict(layer_cache)
    layer_cache["k_pages"] = scatter_rows(
        layer_cache["k_pages"], tables["page_table"], pos[:, None], k,
        valid=kv_valid, min_pos=tables.get("write_floor"))
    layer_cache["v_pages"] = scatter_rows(
        layer_cache["v_pages"], tables["page_table"], pos[:, None], v,
        valid=kv_valid, min_pos=tables.get("write_floor"))

    if cfg.attention == "nsa":
        layer_cache = _paged_emit_cmp(p, cfg, layer_cache, tables, pos,
                                      active=active)
        gates = gating.apply_gates(p["nsa"], x_t)                  # (B,h,3)
        n_cmp_max = tables["cmp_table"].shape[1] * cfg.nsa.block_size
        cmp_rows = jnp.arange(n_cmp_max)
        cmp_k = jax.vmap(gather_rows, in_axes=(None, 0, None))(
            layer_cache["cmp_k_pages"], tables["cmp_table"], cmp_rows)
        cmp_v = jax.vmap(gather_rows, in_axes=(None, 0, None))(
            layer_cache["cmp_v_pages"], tables["cmp_table"], cmp_rows)
        # one batched dispatch for the whole slot batch; the registry
        # resolves cfg.nsa.policy.paged_backend ("auto" -> paged_kernel)
        o = uattn.nsa_attention(
            p["nsa"], gates, q[:, 0], layer_cache["k_pages"],
            layer_cache["v_pages"],
            {"page_tables": tables["page_table"], "cmp_k": cmp_k,
             "cmp_v": cmp_v, "pos": pos},
            cfg=cfg.nsa, mode="paged_decode")
    else:
        # full / swa reference: gather the visible span through the page table
        span = tables["page_table"].shape[1] * cfg.nsa.block_size
        if cfg.attention == "swa":
            w = cfg.swa_window
            span = min(span, w)
            rows = pos[:, None] - (span - 1) + jnp.arange(span)[None, :]
        else:
            rows = jnp.broadcast_to(jnp.arange(span)[None, :], (b, span))
        rows_c = jnp.clip(rows, 0, None)
        k_view = jax.vmap(gather_rows, in_axes=(None, 0, 0))(
            layer_cache["k_pages"], tables["page_table"], rows_c)
        v_view = jax.vmap(gather_rows, in_axes=(None, 0, 0))(
            layer_cache["v_pages"], tables["page_table"], rows_c)
        mask = (rows >= 0) & (rows <= pos[:, None])
        if cfg.attention == "swa":
            mask &= rows > (pos[:, None] - cfg.swa_window)
        from repro.core.reference import _gqa_out, _gqa_scores, _safe_softmax
        def fn(q1, kc, vc, m1):
            probs, _ = _safe_softmax(_gqa_scores(q1, kc), m1[None, None, :])
            return _gqa_out(probs, vc).astype(q1.dtype)
        o = jax.vmap(fn)(q[:, 0:1], k_view, v_view, mask)[:, 0]
    o = o.reshape(b, 1, cfg.n_heads, -1)
    return _out_proj(p, o, cfg)[:, 0], layer_cache


def paged_attention_prefill_chunks(p, x_c, layer_cache, tables, t0, length,
                                   cfg):
    """Chunked prefill of a BATCH of slots into paged storage — one dispatch.

    x_c: (B, C, D) per-slot chunks of hidden states at absolute positions
    [t0_b, t0_b + C); tables: {"page_table": (B, max_pages), "cmp_table":
    (B, max_cmp_pages)}; t0/length: (B,) per-slot chunk offset and true
    prompt length.  Slots whose chunk lies entirely beyond their prompt (or
    padding slots with an all-dump-page table) write only to the dump page
    and contribute masked (zero) outputs, so a fixed-shape jit streams any
    mix of prompt lengths.  Attends chunk queries against the whole paged
    prefix (causally masked).
    """
    b, c, _ = x_c.shape
    pos_c = t0[:, None] + jnp.arange(c)                            # (B, C)
    q, k, v = _qkv(p, x_c, cfg, pos_c)                             # (B,C,h,d)…
    layer_cache = dict(layer_cache)
    layer_cache["k_pages"] = scatter_rows(
        layer_cache["k_pages"], tables["page_table"], pos_c, k,
        valid=pos_c < length[:, None], min_pos=tables.get("write_floor"))
    layer_cache["v_pages"] = scatter_rows(
        layer_cache["v_pages"], tables["page_table"], pos_c, v,
        valid=pos_c < length[:, None], min_pos=tables.get("write_floor"))

    s_max = tables["page_table"].shape[1] * cfg.nsa.block_size
    view_rows = jnp.arange(s_max)
    k_view = jax.vmap(gather_rows, in_axes=(None, 0, None))(
        layer_cache["k_pages"], tables["page_table"], view_rows)   # (B,S,hk,d)
    v_view = jax.vmap(gather_rows, in_axes=(None, 0, None))(
        layer_cache["v_pages"], tables["page_table"], view_rows)
    q_mask = pos_c < length[:, None]                               # padding

    if cfg.attention == "nsa":
        nsa = cfg.nsa
        l, st = nsa.cmp_block_size, nsa.cmp_stride
        # emit every cmp token whose window completes inside this chunk:
        # ends e(j) = j*st + l - 1 in [t0, t0+C)  ->  at most C//st + 1 tokens
        max_emit = c // st + 1
        j0 = jnp.maximum(-((l - 1 - t0) // st), 0)     # ceil((t0-l+1)/st)
        js = j0[:, None] + jnp.arange(max_emit)                    # (B, E)
        ends = js * st + l - 1
        ok = ((ends >= t0[:, None]) & (ends < t0[:, None] + c)
              & (ends < length[:, None]))
        wrows = (js * st)[:, :, None] + jnp.arange(l)[None, None, :]  # (B,E,l)
        gather_w = jax.vmap(jax.vmap(gather_rows, in_axes=(None, None, 0)),
                            in_axes=(None, 0, 0))
        win_k = gather_w(layer_cache["k_pages"], tables["page_table"], wrows)
        win_v = gather_w(layer_cache["v_pages"], tables["page_table"], wrows)
        ck, cv = _emit_cmp_token(p, cfg, win_k.reshape((b * max_emit,) + win_k.shape[2:]),
                                 win_v.reshape((b * max_emit,) + win_v.shape[2:]))
        ck = ck.reshape((b, max_emit) + ck.shape[1:])              # (B,E,hk,d)
        cv = cv.reshape((b, max_emit) + cv.shape[1:])
        layer_cache["cmp_k_pages"] = scatter_rows(
            layer_cache["cmp_k_pages"], tables["cmp_table"], js, ck, valid=ok,
            min_pos=tables.get("cmp_write_floor"))
        layer_cache["cmp_v_pages"] = scatter_rows(
            layer_cache["cmp_v_pages"], tables["cmp_table"], js, cv, valid=ok,
            min_pos=tables.get("cmp_write_floor"))

        n_cmp_max = tables["cmp_table"].shape[1] * nsa.block_size
        cmp_rows = jnp.arange(n_cmp_max)
        cmp_k = jax.vmap(gather_rows, in_axes=(None, 0, None))(
            layer_cache["cmp_k_pages"], tables["cmp_table"], cmp_rows)
        cmp_v = jax.vmap(gather_rows, in_axes=(None, 0, None))(
            layer_cache["cmp_v_pages"], tables["cmp_table"], cmp_rows)
        gates = gating.apply_gates(p["nsa"], x_c)                  # (B,C,h,3)
        sel_map = jnp.asarray(compression.cmp_to_sel_map(
            n_cmp_max, nsa.num_kv_blocks(s_max), nsa))
        sel_fn = uattn.sparse_selected_fn(nsa)   # honors policy union/gather
        o, _ = jax.vmap(
            lambda kv1, vv1, ck1, cv1, q1, g1, p1: sparse._nsa_chunk(
                p["nsa"], nsa, kv1, vv1, ck1, cv1, sel_map, (q1, g1, p1),
                selected_fn=sel_fn))(
                    k_view, v_view, cmp_k, cmp_v, q, gates, pos_c)
    else:
        key_pos = jnp.arange(s_max)
        mask = key_pos[None, None, :] <= pos_c[:, :, None]         # (B,C,S)
        if cfg.attention == "swa":
            mask &= key_pos[None, None, :] > (pos_c[:, :, None] - cfg.swa_window)
        from repro.core.reference import _gqa_out, _gqa_scores, _safe_softmax
        def one(q1, kv1, vv1, m1):
            probs, _ = _safe_softmax(_gqa_scores(q1, kv1), m1[:, None, :])
            return _gqa_out(probs, vv1).astype(q1.dtype)
        o = jax.vmap(one)(q, k_view, v_view, mask)
    o = jnp.where(q_mask[:, :, None, None],
                  o.reshape(b, c, cfg.n_heads, -1), 0)
    return _out_proj(p, o, cfg), layer_cache


def paged_attention_prefill_chunk(p, x_c, layer_cache, tables, t0, length, cfg):
    """Single-slot chunked prefill (compat wrapper over the batched path).

    x_c: (C, D); tables: {"page_table": (max_pages,), "cmp_table":
    (max_cmp_pages,)}; t0/length: scalars.
    """
    o, layer_cache = paged_attention_prefill_chunks(
        p, x_c[None], layer_cache,
        {k: v[None] for k, v in tables.items()},
        jnp.asarray(t0)[None], jnp.asarray(length)[None], cfg)
    return o[0], layer_cache
