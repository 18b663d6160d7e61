"""Decoder-only transformer stack: dense, MoE, SSM and hybrid families.

Layers are scanned (stacked params, ``lax.scan``) with rematerialization so
the compiled HLO stays small and activation memory is one layer deep.  The
zamba2-style hybrid scans *groups* of (period × mamba + shared-attention)
blocks, reusing one set of shared-attention weights across groups.

The LM loss is computed in sequence chunks so the (B, S, vocab) logits tensor
is never materialised (vocab is TP-sharded).

All attention math below the layer stack dispatches through the
``repro.attention`` backend registry (``attention_layer`` passes
``cfg.attn_impl`` / ``cfg.nsa.policy`` through); this module never names an
implementation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention_layer as attn
from repro.models import mamba2, moe
from repro.models.layers import (apply_mlp, cross_entropy, dense_init,
                                 init_mlp, rms_norm, softcap)
from repro.parallel.axes import shard
from repro.telemetry import named_scope

AUX_LOSS_WEIGHT = 0.01


# ------------------------------------------------------------------- blocks
def init_block(key, cfg):
    ks = jax.random.split(key, 4)
    dtype = jnp.dtype(cfg.dtype)
    p = {
        "ln1": jnp.zeros((cfg.d_model,), dtype),
        "ln2": jnp.zeros((cfg.d_model,), dtype),
        "attn": attn.init_attention(ks[0], cfg),
    }
    if cfg.moe is not None:
        p["moe"] = moe.init_moe(ks[1], cfg)
    else:
        p["mlp"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.mlp, dtype)
    return p


def apply_block(p, x, cfg):
    """x: (B,S,D) -> (x', aux_loss)."""
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    h = attn.attention_forward(p["attn"], h, cfg)
    x = x + h
    x = shard(x, "batch", "seq_sp", "embed")
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.moe is not None:
        h, aux = moe.apply_moe(p["moe"], h, cfg)
    else:
        h, aux = apply_mlp(p["mlp"], h, cfg.mlp), 0.0
    x = x + h
    return shard(x, "batch", "seq_sp", "embed"), aux


def init_mamba_block(key, cfg):
    return {
        "ln": jnp.zeros((cfg.d_model,), jnp.dtype(cfg.dtype)),
        "mixer": mamba2.init_mamba(key, cfg),
    }


def apply_mamba_block(p, x, cfg):
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    h, _ = mamba2.mamba_forward(p["mixer"], h, cfg)
    return shard(x + h, "batch", "seq_sp", "embed")


# ------------------------------------------------------------------- stacks
def _stack_init(init_fn, key, n):
    return jax.vmap(init_fn)(jax.random.split(key, n))


def init_lm(key, cfg):
    ks = jax.random.split(key, 5)
    dtype = jnp.dtype(cfg.dtype)
    p = {
        "embed": dense_init(ks[0], (cfg.padded_vocab(), cfg.d_model), dtype, scale=0.02),
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(ks[1], (cfg.d_model, cfg.padded_vocab()), dtype)
    if cfg.family == "hybrid":
        period = cfg.shared_attn_period
        n_groups, rem = divmod(cfg.n_layers, period)
        p["groups"] = _stack_init(
            lambda k: _stack_init(lambda k2: init_mamba_block(k2, cfg), k, period),
            ks[2], n_groups)
        if rem:
            p["tail"] = _stack_init(lambda k: init_mamba_block(k, cfg), ks[3], rem)
        p["shared_attn"] = init_block(ks[4], cfg)
    elif cfg.family == "ssm":
        p["layers"] = _stack_init(lambda k: init_mamba_block(k, cfg), ks[2],
                                  cfg.n_layers)
    else:
        p["layers"] = _stack_init(lambda k: init_block(k, cfg), ks[2],
                                  cfg.n_layers)
    if cfg.family == "vlm":
        p["img_proj"] = dense_init(ks[3], (cfg.d_model, cfg.d_model), dtype)
    return p


def _scan_blocks(p_stack, x, body):
    def step(carry, p_layer):
        x, aux = carry
        x, a = body(p_layer, x)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(step, (x, 0.0), p_stack)
    return x, aux


def backbone(params, x, cfg):
    """Hidden-states backbone over embedded inputs x: (B,S,D)."""
    if cfg.family == "hybrid":
        shared = params["shared_attn"]

        def group_body(p_group, x):
            body = jax.checkpoint(lambda p, h: (apply_mamba_block(p, h, cfg), 0.0)) \
                if cfg.remat else (lambda p, h: (apply_mamba_block(p, h, cfg), 0.0))
            x, _ = _scan_blocks(p_group, x, body)
            x, _ = apply_block(shared, x, cfg)
            return x, 0.0

        gb = jax.checkpoint(group_body) if cfg.remat else group_body
        x, aux = _scan_blocks(params["groups"], x, gb)
        if "tail" in params:
            body = lambda p, h: (apply_mamba_block(p, h, cfg), 0.0)
            x, _ = _scan_blocks(params["tail"], x,
                                jax.checkpoint(body) if cfg.remat else body)
        return x, aux
    if cfg.family == "ssm":
        body = lambda p, h: (apply_mamba_block(p, h, cfg), 0.0)
    else:
        body = lambda p, h: apply_block(p, h, cfg)
    if cfg.remat:
        body = jax.checkpoint(body)
    if cfg.scan_layers:
        return _scan_blocks(params["layers"], x, body)
    x_, aux = x, 0.0
    for i in range(cfg.n_layers):
        p_i = jax.tree.map(lambda a: a[i], params["layers"])
        x_, a = body(p_i, x_)
        aux += a
    return x_, aux


@named_scope("embed")
def _embed(params, tokens):
    """Embedding rows of ``tokens`` (any shape)."""
    return params["embed"][tokens]


def embed_tokens(params, tokens, cfg):
    x = _embed(params, tokens)                  # gather (B,S,D)
    return shard(x, "batch", "seq_sp", "embed")


@named_scope("lm_head")
def _head(params, x, cfg):
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ w
    logits = shard(logits, "batch", "seq", "vocab")
    logits = softcap(logits, cfg.logit_softcap)
    if cfg.padded_vocab() != cfg.vocab:   # mask vocab-padding classes
        logits = jnp.where(jnp.arange(logits.shape[-1]) < cfg.vocab,
                           logits, -1e30)
    return logits


def lm_loss(params, batch, cfg, *, loss_chunk: int = 1024):
    """batch: {"tokens": (B,S) int32, "labels": (B,S) int32 (-100 masked)}.

    Vision batches add "img_embeds": (B, n_img, D) — prepended as a prefix.
    """
    tokens, labels = batch["tokens"], batch["labels"]
    x = embed_tokens(params, tokens, cfg)
    n_img = 0
    if cfg.family == "vlm" and "img_embeds" in batch:
        img = batch["img_embeds"].astype(x.dtype) @ params["img_proj"]
        x = jnp.concatenate([img, x], axis=1)
        n_img = img.shape[1]
    x, aux = backbone(params, x, cfg)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if n_img:
        x = x[:, n_img:]

    b, s, d = x.shape
    c = min(loss_chunk, s)
    pad = (c - s % c) % c
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-100)

    @named_scope("lm_head")
    def chunk_loss(args):
        xc, lc = args
        logits = _head(params, xc, cfg)
        loss_sum, cnt = cross_entropy(logits, lc)
        return loss_sum * cnt, cnt

    xs = (x.reshape(b, -1, c, d).transpose(1, 0, 2, 3),
          labels.reshape(b, -1, c).transpose(1, 0, 2))
    sums, cnts = jax.lax.map(chunk_loss, xs)
    loss = sums.sum() / jnp.maximum(cnts.sum(), 1)
    return loss + AUX_LOSS_WEIGHT * aux, {"ce": loss, "aux": aux,
                                          "tokens": cnts.sum()}


def lm_logits(params, batch, cfg):
    """Full-sequence logits (prefill path / small-scale eval)."""
    x = embed_tokens(params, batch["tokens"], cfg)
    if cfg.family == "vlm" and "img_embeds" in batch:
        img = batch["img_embeds"].astype(x.dtype) @ params["img_proj"]
        x = jnp.concatenate([img, x], axis=1)
    x, _ = backbone(params, x, cfg)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return _head(params, x, cfg)


# ------------------------------------------------------------------- prefill
def _prefill_attn_block(p, x, cache, cfg):
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    h, cache = attn.attention_prefill(p["attn"], h, cfg, cache)
    x = x + h
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.moe is not None:
        h, _ = moe.apply_moe(p["moe"], h, cfg)
    else:
        h = apply_mlp(p["mlp"], h, cfg.mlp)
    return x + h, cache


def _prefill_mamba_block(p, x, cache, cfg):
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    h, (conv, ssm) = mamba2.mamba_forward(p["mixer"], h, cfg)
    return x + h, {"conv": conv.astype(cache["conv"].dtype), "ssm": ssm}


def lm_prefill(params, cache, batch, cfg):
    """Populate decode caches from a prompt. Returns (last-position logits, cache)."""
    x = embed_tokens(params, batch["tokens"], cfg)
    if cfg.family == "vlm" and "img_embeds" in batch:
        img = batch["img_embeds"].astype(x.dtype) @ params["img_proj"]
        x = jnp.concatenate([img, x], axis=1)

    if cfg.family == "hybrid":
        shared = params["shared_attn"]

        def group(x, args):
            p_g, c_g, c_attn = args

            def inner(x, a2):
                p_l, c_l = a2
                return _prefill_mamba_block(p_l, x, c_l, cfg)

            x, c_g = jax.lax.scan(inner, x, (p_g, c_g))
            x, c_attn = _prefill_attn_block(shared, x, c_attn, cfg)
            return x, (c_g, c_attn)

        x, (cg, ca) = jax.lax.scan(group, x, (params["groups"], cache["groups"],
                                              cache["shared_attn"]))
        cache = dict(cache, groups=cg, shared_attn=ca)
        if "tail" in params:
            x, ct = jax.lax.scan(
                lambda x, a2: _prefill_mamba_block(a2[0], x, a2[1], cfg),
                x, (params["tail"], cache["tail"]))
            cache["tail"] = ct
    elif cfg.family == "ssm":
        x, cl = jax.lax.scan(
            lambda x, a2: _prefill_mamba_block(a2[0], x, a2[1], cfg),
            x, (params["layers"], cache["layers"]))
        cache = dict(cache, layers=cl)
    else:
        x, cl = jax.lax.scan(
            lambda x, a2: _prefill_attn_block(a2[0], x, a2[1], cfg),
            x, (params["layers"], cache["layers"]))
        cache = dict(cache, layers=cl)

    x = rms_norm(x[:, -1], params["final_norm"], cfg.norm_eps)
    return _head(params, x[:, None], cfg)[:, 0], cache


# -------------------------------------------------------------------- decode
def _stack_cache(cache, *ns):
    """Prepend stacking dims (caches are zero-initialised, so just re-zero)."""
    return jax.tree.map(lambda a: jnp.zeros(tuple(ns) + a.shape, a.dtype), cache)


def init_lm_cache(cfg, batch: int, max_len: int):
    if cfg.family == "hybrid":
        period = cfg.shared_attn_period
        n_groups, rem = divmod(cfg.n_layers, period)
        cache = {
            "groups": _stack_cache(mamba2.init_mamba_cache(cfg, batch),
                                   n_groups, period),
            "shared_attn": _stack_cache(attn.init_attn_cache(cfg, batch, max_len),
                                        n_groups),
        }
        if rem:
            cache["tail"] = _stack_cache(mamba2.init_mamba_cache(cfg, batch), rem)
        return cache
    if cfg.family == "ssm":
        return {"layers": _stack_cache(mamba2.init_mamba_cache(cfg, batch),
                                       cfg.n_layers)}
    return {"layers": _stack_cache(attn.init_attn_cache(cfg, batch, max_len),
                                   cfg.n_layers)}


def init_lm_paged_cache(cfg, num_pages: int, num_cmp_pages: int):
    """Paged decode cache (attention families only — ssm/hybrid/encdec carry
    recurrent or cross-attention state that is not paged KV)."""
    if cfg.family in ("ssm", "hybrid", "encdec"):
        raise NotImplementedError(f"no paged cache for family '{cfg.family}'")
    return {"layers": _stack_cache(
        attn.init_paged_attn_cache(cfg, num_pages, num_cmp_pages),
        cfg.n_layers)}


@named_scope("tick.decode")
def lm_paged_decode_step(params, cache, tokens, pos, tables, cfg, *,
                         reduce_fn=None):
    """Batched decode on paged storage.

    tokens: (B,) int32; pos: (B,) per-slot absolute positions; tables: the
    shared {"page_table", "cmp_table"} arrays.  Returns (logits (B,V), cache).
    The paged-decode backend (Pallas kernel vs gather reference) is resolved
    per ``cfg.nsa.policy.paged_backend`` inside ``repro.attention``.

    ``reduce_fn`` (tensor-parallel serving): applied to each attention
    output before the residual add.  Under ``shard_map`` with the heads
    split over a mesh axis, the out-projection produces a partial sum —
    pass ``lambda t: jax.lax.psum(t, "model")`` to complete it.
    """
    rf = reduce_fn if reduce_fn is not None else (lambda t: t)
    x = _embed(params, tokens)

    def body(x, args):
        p_l, c_l = args
        h = rms_norm(x, p_l["ln1"], cfg.norm_eps)
        h, c_l = attn.paged_attention_decode(p_l["attn"], h, c_l, tables, pos, cfg)
        x = x + rf(h)
        h = rms_norm(x, p_l["ln2"], cfg.norm_eps)
        if cfg.moe is not None:
            h2, _ = moe.apply_moe(p_l["moe"], h[:, None, :], cfg)
            h = h2[:, 0]
        else:
            h = apply_mlp(p_l["mlp"], h, cfg.mlp)
        return x + h, c_l

    x, cl = jax.lax.scan(body, x, (params["layers"], cache["layers"]))
    cache = dict(cache, layers=cl)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return _head(params, x[:, None], cfg)[:, 0], cache


@named_scope("tick.prefill")
def lm_paged_prefill_chunks(params, cache, tokens_c, t0, length, tables, cfg,
                            *, reduce_fn=None):
    """Prefill one chunk for a BATCH of slots into paged storage.

    tokens_c: (B, C) int32, slot b's tokens at absolute positions
    [t0_b, t0_b + C) (tail beyond ``length_b`` is padding); t0/length: (B,);
    tables: {"page_table": (B, max_pages), "cmp_table": (B, max_cmp_pages)}.
    Returns (logits (B, C, V), cache) — the engine reads each slot's logit
    at its prompt's last position from the chunk that covers it.  Padding
    slots (length 0, all-dump-page tables) are inert.

    ``reduce_fn``: see ``lm_paged_decode_step`` (tensor-parallel psum over
    the partial attention out-projection).
    """
    rf = reduce_fn if reduce_fn is not None else (lambda t: t)
    x = _embed(params, tokens_c)                           # (B, C, D)

    def body(x, args):
        p_l, c_l = args
        h = rms_norm(x, p_l["ln1"], cfg.norm_eps)
        h, c_l = attn.paged_attention_prefill_chunks(
            p_l["attn"], h, c_l, tables, t0, length, cfg)
        x = x + rf(h)
        h = rms_norm(x, p_l["ln2"], cfg.norm_eps)
        if cfg.moe is not None:
            h, _ = moe.apply_moe(p_l["moe"], h, cfg)
        else:
            h = apply_mlp(p_l["mlp"], h, cfg.mlp)
        return x + h, c_l

    x, cl = jax.lax.scan(body, x, (params["layers"], cache["layers"]))
    cache = dict(cache, layers=cl)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return _head(params, x, cfg), cache


@named_scope("tick.prefill")
def lm_paged_mixed_step(params, cache, pf_tokens, pf_t0, pf_len,
                        dec_tokens, dec_pos, dec_active, tables, cfg, *,
                        reduce_fn=None):
    """ONE fused dispatch per engine tick: a bounded prefill chunk for
    admitting slots AND one decode token for active slots (vLLM-style
    continuous batching — decode never stalls behind a long co-admitted
    prompt's chunk loop).

    pf_tokens: (B, C) int32 chunk rows at absolute positions
    [pf_t0_b, pf_t0_b + C); pf_len: (B,) true prompt lengths (``pf_len == 0``
    rows are fully inert — slots not prefilling this tick).
    dec_tokens/dec_pos: (B,) decode operands; dec_active: (B,) bool — rows
    with ``False`` (slots mid-prefill or free) ride along with all writes
    routed to the dump page.  A slot is never both (disjoint masks), so the
    two sub-steps share ``tables`` and the per-layer page pools safely.

    ``reduce_fn``: see ``lm_paged_decode_step`` (tensor-parallel psum over
    the partial attention out-projections of BOTH sub-steps).

    Returns (pf_logits (B, C, V), dec_logits (B, V), cache).

    The step runs under the ``tick.prefill`` scope and its decode sub-step
    under ``tick.decode`` inside it: the layer loop that hands each layer's
    page pools to both sub-steps counts as the prefill's, which is what a
    mixed tick adds over a decode-only one.
    """
    rf = reduce_fn if reduce_fn is not None else (lambda t: t)
    x_pf = _embed(params, pf_tokens)                        # (B, C, D)
    with named_scope("tick.decode"):
        x_dec = _embed(params, dec_tokens)                  # (B, D)

    def body(carry, args):
        x_pf, x_dec = carry
        p_l, c_l = args
        # prefill sub-step (chunk rows; inert where pf_len == 0)
        h = rms_norm(x_pf, p_l["ln1"], cfg.norm_eps)
        h, c_l = attn.paged_attention_prefill_chunks(
            p_l["attn"], h, c_l, tables, pf_t0, pf_len, cfg)
        x_pf = x_pf + rf(h)
        h = rms_norm(x_pf, p_l["ln2"], cfg.norm_eps)
        if cfg.moe is not None:
            h, _ = moe.apply_moe(p_l["moe"], h, cfg)
        else:
            h = apply_mlp(p_l["mlp"], h, cfg.mlp)
        x_pf = x_pf + h
        # decode sub-step (one token per active slot)
        with named_scope("tick.decode"):
            h = rms_norm(x_dec, p_l["ln1"], cfg.norm_eps)
            h, c_l = attn.paged_attention_decode(
                p_l["attn"], h, c_l, tables, dec_pos, cfg, active=dec_active)
            x_dec = x_dec + rf(h)
            h = rms_norm(x_dec, p_l["ln2"], cfg.norm_eps)
            if cfg.moe is not None:
                h2, _ = moe.apply_moe(p_l["moe"], h[:, None, :], cfg)
                h = h2[:, 0]
            else:
                h = apply_mlp(p_l["mlp"], h, cfg.mlp)
            x_dec = x_dec + h
        return (x_pf, x_dec), c_l

    (x_pf, x_dec), cl = jax.lax.scan(body, (x_pf, x_dec),
                                     (params["layers"], cache["layers"]))
    cache = dict(cache, layers=cl)
    x_pf = rms_norm(x_pf, params["final_norm"], cfg.norm_eps)
    pf_logits = _head(params, x_pf, cfg)
    with named_scope("tick.decode"):
        x_dec = rms_norm(x_dec, params["final_norm"], cfg.norm_eps)
        dec_logits = _head(params, x_dec[:, None], cfg)[:, 0]
    return pf_logits, dec_logits, cache


def lm_paged_prefill_chunk(params, cache, tokens_c, t0, length, tables, cfg):
    """Single-slot chunked prefill (compat wrapper over the batched path).

    tokens_c: (C,) int32; t0/length: scalars; tables: this slot's
    {"page_table", "cmp_table"} rows.  Returns (logits (C, V), cache).
    """
    logits, cache = lm_paged_prefill_chunks(
        params, cache, tokens_c[None], jnp.asarray(t0)[None],
        jnp.asarray(length)[None],
        {k: v[None] for k, v in tables.items()}, cfg)
    return logits[0], cache


def _decode_attn_block(p, x_t, cache, pos, cfg):
    h = rms_norm(x_t, p["ln1"], cfg.norm_eps)
    h, cache = attn.attention_decode(p["attn"], h, cache, pos, cfg)
    x_t = x_t + h
    h = rms_norm(x_t, p["ln2"], cfg.norm_eps)
    if cfg.moe is not None:
        h2, _ = moe.apply_moe(p["moe"], h[:, None, :], cfg)
        h = h2[:, 0]
    else:
        h = apply_mlp(p["mlp"], h, cfg.mlp)
    return x_t + h, cache


def _decode_mamba_block(p, x_t, cache, cfg):
    h = rms_norm(x_t, p["ln"], cfg.norm_eps)
    h, conv, ssm = mamba2.mamba_decode_step(p["mixer"], h, cache["conv"],
                                            cache["ssm"], cfg)
    return x_t + h, {"conv": conv, "ssm": ssm}


def lm_decode_step(params, cache, tokens, pos, cfg):
    """tokens: (B,) int32; pos: scalar or (B,) per-slot absolute positions
    (continuous batching decodes every slot at its own depth).
    Returns (logits (B,V), cache)."""
    x = params["embed"][tokens]

    if cfg.family == "hybrid":
        shared = params["shared_attn"]

        def group_step(x, args):
            p_g, c_g, c_attn = args

            def inner(x, args2):
                p_l, c_l = args2
                x, c_new = _decode_mamba_block(p_l, x, c_l, cfg)
                return x, c_new

            x, c_g = jax.lax.scan(inner, x, (p_g, c_g))
            x, c_attn = _decode_attn_block(shared, x, c_attn, pos, cfg)
            return x, (c_g, c_attn)

        def outer(x, args):
            x, cs = group_step(x, args)
            return x, cs

        x, (cg, ca) = jax.lax.scan(outer, x,
                                   (params["groups"], cache["groups"],
                                    cache["shared_attn"]))
        cache = dict(cache, groups=cg, shared_attn=ca)
        if "tail" in params:
            def inner(x, args2):
                p_l, c_l = args2
                x, c_new = _decode_mamba_block(p_l, x, c_l, cfg)
                return x, c_new
            x, ct = jax.lax.scan(inner, x, (params["tail"], cache["tail"]))
            cache["tail"] = ct
    elif cfg.family == "ssm":
        def body(x, args):
            p_l, c_l = args
            x, c_new = _decode_mamba_block(p_l, x, c_l, cfg)
            return x, c_new
        x, cl = jax.lax.scan(body, x, (params["layers"], cache["layers"]))
        cache = dict(cache, layers=cl)
    else:
        def body(x, args):
            p_l, c_l = args
            x, c_new = _decode_attn_block(p_l, x, c_l, pos, cfg)
            return x, c_new
        x, cl = jax.lax.scan(body, x, (params["layers"], cache["layers"]))
        cache = dict(cache, layers=cl)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return _head(params, x[:, None], cfg)[:, 0], cache
