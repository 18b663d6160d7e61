"""Plain reference of the NSA decoder LM: float32, dense masks, no kernels,
no cache, no batching.  It imports nothing of the program and reads only
the weights that ``bench.weights`` makes from the seed.

One layer: x += W_o NSA(q, k, v) on rms_norm(x); x += SwiGLU(rms_norm(x)).
NSA (Native Sparse Attention, arXiv:2502.11089) mixes three causal branches
with per-head sigmoid gates computed from the normed input:

- compressed: attention over summary tokens, token j pooling raw positions
  [j*s, j*s + l) (mean of K + position term, then a d x d map), visible to
  query t once j*s + l - 1 <= t;
- selected: attention over the T blocks of B_K positions with the highest
  importance (compressed probabilities mapped onto blocks by overlap,
  summed over the query heads of a KV group), the first block and the two
  trailing blocks always included;
- sliding: attention over positions (t - W, t].

Rotary embedding uses the half-split convention.  The LM head reads only
the vocabulary's real columns.

``Num`` sets the arithmetic: float32 at ``highest`` precision, or every
matmul operand rounded to float8 (e4m3, scaled per tensor by its largest
magnitude) -- the control that a lower precision must fail.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
NEG = -1e30


class Num:
    def __init__(self, fp8: bool = False):
        self.fp8 = fp8

    def q(self, x):
        x = x.astype(F32)
        if not self.fp8:
            return x
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s

    def ein(self, spec, a, b):
        return jnp.einsum(spec, self.q(a), self.q(b), precision=HI)

    def __hash__(self):
        return hash(self.fp8)

    def __eq__(self, other):
        return isinstance(other, Num) and other.fp8 == self.fp8


def rms_norm(x, scale, eps):
    x = x.astype(F32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * (1.0 + scale.astype(F32)))


def rope(x, pos, theta):
    """x: (N, heads, d) at positions pos: (N,)."""
    d = x.shape[-1]
    freqs = jnp.asarray(1.0 / (theta ** (np.arange(0, d, 2) / d)), F32)
    ang = pos[:, None].astype(F32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def masked_softmax(s, mask):
    """Softmax over the last axis among ``mask``; all-masked rows give 0."""
    s = jnp.where(mask, s, NEG)
    m = jnp.maximum(jnp.max(s, -1, keepdims=True), NEG / 2)
    e = jnp.exp(s - m) * mask
    return e / jnp.maximum(jnp.sum(e, -1, keepdims=True), 1e-30)


def n_cmp_tokens(n, a):
    l, s = a["cmp_block_size"], a["cmp_stride"]
    return 1 if n < l else (n - l) // s + 1


def block_overlap(n_cmp, n_blocks, a):
    """(n_cmp, n_blocks): share of summary token j's window in block i."""
    l, s, bk = a["cmp_block_size"], a["cmp_stride"], a["block_size"]
    j = np.arange(n_cmp)[:, None]
    i = np.arange(n_blocks)[None, :]
    lo = np.maximum(j * s, i * bk)
    hi = np.minimum(j * s + l, (i + 1) * bk)
    return jnp.asarray(np.maximum(hi - lo, 0) / l, F32)


def compress(x, pe, w, a, num):
    """x: (N, h_k, d) -> summary tokens (n_cmp, h_k, d)."""
    n = x.shape[0]
    l, s = a["cmp_block_size"], a["cmp_stride"]
    idx = np.minimum(np.arange(n_cmp_tokens(n, a))[:, None] * s
                     + np.arange(l)[None, :], n - 1)
    pooled = (x[idx] + pe.astype(F32)[None, :, None, :]).mean(1)
    return num.ein("jkd,de->jke", pooled, w)


def nsa_chunk(a, num, k, v, kc, vc, overlap, chunk):
    """One block of query rows.  chunk = (q (c, h, d), gates (c, h, 3),
    pos (c,)); k, v: (N, h_k, d); kc, vc: summary tokens."""
    q, gates, pos = chunk
    c, h, d = q.shape
    n, hk = k.shape[0], k.shape[1]
    g = h // hk
    bk, n_blocks = a["block_size"], overlap.shape[1]
    qg = q.reshape(c, hk, g, d) / np.sqrt(d)

    ends = np.arange(kc.shape[0]) * a["cmp_stride"] + a["cmp_block_size"] - 1
    vis = pos[:, None] >= jnp.asarray(ends)[None, :]
    p_c = masked_softmax(num.ein("ckgd,jkd->ckgj", qg, kc),
                         vis[:, None, None, :])
    o_c = num.ein("ckgj,jkd->ckgd", p_c, vc)

    imp = num.ein("ckgj,jb->ckb", p_c, overlap)
    blk = jnp.arange(n_blocks)
    cur = (pos // bk)[:, None]
    causal = blk[None, :] <= cur
    forced = causal & ((blk[None, :] < a["num_init_blocks"])
                       | (blk[None, :] > cur - a["num_local_blocks"]))
    score = jnp.where(causal[:, None, :],
                      imp + jnp.where(forced[:, None, :], 1e30, 0.0), NEG)
    top_s, top_i = jax.lax.top_k(score, min(a["num_selected"], n_blocks))
    chosen = ((top_i[..., None] == blk) & (top_s > NEG / 2)[..., None]).any(2)

    kpos = jnp.arange(n)
    past = kpos[None, :] <= pos[:, None]                         # (c, N)
    m_sel = chosen[:, :, kpos // bk] & past[:, None, :]          # (c, hk, N)
    m_win = past & (kpos[None, :] > pos[:, None] - a["window_size"])
    s = num.ein("ckgd,nkd->ckgn", qg, k)
    o_s = num.ein("ckgn,nkd->ckgd", masked_softmax(s, m_sel[:, :, None, :]), v)
    o_w = num.ein("ckgn,nkd->ckgd",
                  masked_softmax(s, m_win[:, None, None, :]), v)
    gt = gates.reshape(c, hk, g, 3)
    out = gt[..., 0:1] * o_c + gt[..., 1:2] * o_s + gt[..., 2:3] * o_w
    return out.reshape(c, h * d)


def layer(a, num, chunk_rows, remat, x, p):
    """One decoder layer over the whole sequence x: (N, d_model)."""
    n = x.shape[0]
    h, hk, d = a["n_heads"], a["n_kv_heads"], a["head_dim"]
    pos = jnp.arange(n)
    at = p["attn"]
    hn = rms_norm(x, p["ln1"], a["norm_eps"])
    bias = lambda name: at[name].astype(F32) if name in at else 0.0
    q = (num.ein("nm,me->ne", hn, at["w_q"]) + bias("b_q")).reshape(n, h, d)
    k = (num.ein("nm,me->ne", hn, at["w_k"]) + bias("b_k")).reshape(n, hk, d)
    v = (num.ein("nm,me->ne", hn, at["w_v"]) + bias("b_v")).reshape(n, hk, d)
    q, k = rope(q, pos, a["rope_theta"]), rope(k, pos, a["rope_theta"])
    nsa = at["nsa"]
    gates = jax.nn.sigmoid(num.ein("nm,mhb->nhb", hn, nsa["w_gate"]))
    kc = compress(k, nsa["pe_k"], nsa["w_k"], a, num)
    vc = compress(v, nsa["pe_v"], nsa["w_v"], a, num)
    overlap = block_overlap(kc.shape[0], -(-n // a["block_size"]), a)

    c = min(chunk_rows, n)
    body = functools.partial(nsa_chunk, a, num, k, v, kc, vc, overlap)
    if remat:
        body = jax.checkpoint(body)
    o = jax.lax.map(body, (q.reshape(n // c, c, h, d),
                           gates.reshape(n // c, c, h, 3),
                           pos.reshape(n // c, c)))
    x = x + num.ein("ne,em->nm", o.reshape(n, h * d), at["w_o"])
    hn = rms_norm(x, p["ln2"], a["norm_eps"])
    mlp = p["mlp"]
    u = (jax.nn.silu(num.ein("nm,mf->nf", hn, mlp["w_gate"]))
         * num.ein("nm,mf->nf", hn, mlp["w_in"]))
    return x + num.ein("nf,fm->nm", u, mlp["w_out"])


def hidden(params, tokens, a, num, *, chunk_rows=256, remat=False):
    """Final normed hidden states (N, d_model); N a multiple of chunk_rows."""
    x = params["embed"][tokens].astype(F32)
    body = functools.partial(layer, a, num, chunk_rows, remat)
    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(lambda x, p: (body(x, p), None), x, params["layers"])
    return rms_norm(x, params["final_norm"], a["norm_eps"])


def logits(params, x, a, num):
    return num.ein("nm,mv->nv", x, params["lm_head"][:, :a["vocab"]])


@functools.partial(jax.jit, static_argnums=(3, 4))
def logits_at(params, tokens, rows, a_items, num):
    """Logits (R, vocab) at positions ``rows`` of sequence ``tokens``."""
    a = dict(a_items)
    x = hidden(params, tokens, a, num)
    return logits(params, x[rows], a, num)


def loss(params, batch, a, num, *, chunk_rows=256, head_rows=1024):
    """Mean next-token cross entropy over the batch's tokens whose label
    is not negative."""

    @jax.checkpoint
    def part(args):
        xr, lr = args
        z = logits(params, xr, a, num)
        lse = jax.nn.logsumexp(z, -1)
        ll = jnp.take_along_axis(z, jnp.maximum(lr, 0)[:, None], -1)[:, 0]
        return jnp.sum(jnp.where(lr >= 0, lse - ll, 0.0))

    def row(args):
        tokens, labels = args
        x = hidden(params, tokens, a, num, chunk_rows=chunk_rows, remat=True)
        n = x.shape[0]
        h = min(head_rows, n)
        return jnp.sum(jax.lax.map(part, (x.reshape(n // h, h, -1),
                                          labels.reshape(n // h, h))))

    total = jnp.sum(jax.lax.map(row, (batch["tokens"], batch["labels"])))
    return total / jnp.sum(batch["labels"] >= 0)
