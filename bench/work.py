"""Required work of the model and of its NSA kernels, counted from the
algorithm and its shapes.

Nothing here reads an implementation: padding, recomputation, rows that a
kernel carries for the MXU's sake, and blocks fetched twice are not work.
A kernel that replaces another is therefore read against the same counts.

``arch`` is the dict that ``bench.spec.arch_of`` makes from a configuration
file: widths (``d_model``, ``n_heads``, ``n_kv_heads``, ``head_dim``,
``d_ff``, ``vocab``, ``n_layers``, ``qkv_bias``) and the NSA settings
(``block_size`` B_K, ``num_selected`` T, ``cmp_block_size`` l,
``cmp_stride`` s, ``window_size`` W).
"""
from __future__ import annotations

BF16 = 2


def n_cmp_visible(t: int, a: dict) -> int:
    """Compressed tokens whose window [j*s, j*s + l) lies at or before t."""
    l, s = a["cmp_block_size"], a["cmp_stride"]
    return 0 if t + 1 < l else (t + 1 - l) // s + 1


def selected_keys(t: int, a: dict) -> int:
    """Keys the selected branch attends at position t: T blocks, clamped to
    the blocks that exist, with the current block cut at t (causal)."""
    bk = a["block_size"]
    t_eff = min(a["num_selected"], t // bk + 1)
    return (t_eff - 1) * bk + (t % bk + 1)


def window_keys(t: int, a: dict) -> int:
    return min(a["window_size"], t + 1)


def _sum_over(fn, positions, a):
    return sum(fn(t, a) for t in positions)


def attn_flops_fwd(positions, a: dict) -> float:
    """Forward FLOPs of NSA's three branches for queries at ``positions``
    (one layer): QK^T and PV, 2*d each per (query head, key)."""
    per_key = 4 * a["n_heads"] * a["head_dim"]
    keys = sum(n_cmp_visible(t, a) + selected_keys(t, a) + window_keys(t, a)
               for t in positions)
    return float(per_key * keys)


def cmp_flops(n_new_cmp: int, a: dict) -> float:
    """Making ``n_new_cmp`` compressed tokens: the d x d maps of K and V,
    per KV head (the pooling adds are not counted)."""
    return float(n_new_cmp * 2 * 2 * a["n_kv_heads"] * a["head_dim"] ** 2)


def matmul_params(a: dict, *, head: bool = True) -> int:
    """Weights one token passes through in matmuls (the embedding is a
    gather, not a matmul; the LM head is optional because prefill needs it
    only at a prompt's last row)."""
    d, h, hk, hd = a["d_model"], a["n_heads"], a["n_kv_heads"], a["head_dim"]
    attn = d * h * hd * 2 + d * hk * hd * 2 + d * h * 3     # q, o, k, v, gates
    mlp = 3 * d * a["d_ff"]
    return a["n_layers"] * (attn + mlp) + (d * a["vocab"] if head else 0)


def new_cmp_tokens(t0: int, t1: int, a: dict) -> int:
    """Compressed tokens completed by positions [t0, t1)."""
    return n_cmp_visible(t1 - 1, a) - (n_cmp_visible(t0 - 1, a) if t0 else 0)


def serve_flops(prefill_spans, decode_positions, a: dict) -> float:
    """Required forward FLOPs of one engine tick's live rows.

    prefill_spans: [(t0, t1, is_last_chunk)] prompt positions prefilled;
    decode_positions: positions of the tokens decoded.  The LM head counts
    for decoded tokens and for each prompt's last row only.
    """
    body = 2 * matmul_params(a, head=False)
    head = 2 * a["d_model"] * a["vocab"]
    total = 0.0
    for t0, t1, last in prefill_spans:
        rows = range(t0, t1)
        total += body * len(rows) + (head if last else 0)
        total += a["n_layers"] * (attn_flops_fwd(rows, a)
                                  + cmp_flops(new_cmp_tokens(t0, t1, a), a))
    for t in decode_positions:
        total += body + head
        total += a["n_layers"] * (attn_flops_fwd([t], a)
                                  + cmp_flops(new_cmp_tokens(t, t + 1, a), a))
    return total


def train_flops(n_tokens: int, a: dict) -> float:
    """Required FLOPs of one training step on one sequence of ``n_tokens``:
    6 x matmul params per token, plus attention at 3 x its forward
    (backward = 2 x forward) and the compressed-token maps at 3 x."""
    rows = range(n_tokens)
    fwd_attn = attn_flops_fwd(rows, a) + cmp_flops(
        new_cmp_tokens(0, n_tokens, a), a)
    return 6.0 * matmul_params(a) * n_tokens + 3.0 * a["n_layers"] * fwd_attn


# ------------------------------------------------------------- kernels
def paged_decode_work(positions, a: dict) -> tuple[float, float]:
    """(FLOPs, bytes) of the paged-decode kernel's branches, selected and
    sliding, for one layer and one decode row at each of ``positions``.

    Bytes: each branch's K and V rows per KV head, the query read and the
    two branch outputs written, in bf16.  The kernel does not compute the
    compressed branch, so it is not counted here."""
    h, hk, d = a["n_heads"], a["n_kv_heads"], a["head_dim"]
    flops = byts = 0.0
    for t in positions:
        keys = selected_keys(t, a) + window_keys(t, a)
        flops += 4 * h * d * keys
        byts += BF16 * (2 * hk * d * keys + 3 * h * d)
    return flops, byts


def fsa_fwd_work(n: int, a: dict) -> tuple[float, float]:
    """(FLOPs, bytes) of the selected branch's forward over one sequence of
    ``n`` queries for one layer: QK^T and PV over each query's selected
    keys; Q, K and V read once, O and the log-sum-exp written once."""
    h, hk, d = a["n_heads"], a["n_kv_heads"], a["head_dim"]
    keys = _sum_over(selected_keys, range(n), a)
    flops = 4.0 * h * d * keys
    byts = BF16 * (2 * n * h * d + 2 * n * hk * d) + 4 * n * h
    return flops, byts


def fsa_bwd_work(n: int, a: dict) -> tuple[float, float]:
    """(FLOPs, bytes) of the selected branch's backward: five matmuls per
    (query head, key) -- S = QK^T recomputed, dP = dO V^T, dV = P^T dO,
    dQ = dS K, dK = dS^T Q; Q, K, V, dO read and dQ, dK, dV written once,
    with the log-sum-exp and delta rows."""
    h, hk, d = a["n_heads"], a["n_kv_heads"], a["head_dim"]
    keys = _sum_over(selected_keys, range(n), a)
    flops = 10.0 * h * d * keys
    byts = BF16 * (4 * n * h * d + 4 * n * hk * d) + 2 * 4 * n * h
    return flops, byts


def roofline_share(flops: float, byts: float, seconds: float,
                   peaks: dict) -> tuple[float, str]:
    """Least time the chip could take, as a share of ``seconds`` (percent),
    and which bound sets it."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = byts / peaks["hbm_bytes_per_s"]
    return 100.0 * max(t_c, t_m) / seconds, ("compute" if t_c >= t_m
                                             else "memory")
