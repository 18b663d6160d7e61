"""Weights made from the seed, on the device, in one jitted call, in the
type they are served in (bfloat16).

The tree has the layout the program's transformer takes (``embed``,
``final_norm``, ``lm_head`` and the layer stack under ``layers``), so the
same arrays feed the system under test and ``bench.reference``.  The scales
follow the usual initialisation: matrices N(0, 1/fan_in), the embedding
N(0, 0.02^2), biases and the compression position terms N(0, 0.02^2), norm
scales 0 (the program multiplies by 1 + scale).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int, stream: int):
    """A key for any whole ``seed`` up to 2**62 (wider than 32 bits)."""
    key = jax.random.fold_in(jax.random.key(stream), seed % 2**31)
    return jax.random.fold_in(key, seed // 2**31)


def shapes(a: dict) -> dict:
    """{path: (shape, scale)} of every leaf; scale None means zeros."""
    d, h, hk, hd = a["d_model"], a["n_heads"], a["n_kv_heads"], a["head_dim"]
    L, ff, vp, l = a["n_layers"], a["d_ff"], a["padded_vocab"], a["cmp_block_size"]
    s = lambda fan: fan ** -0.5
    leaves = {
        "embed": ((vp, d), 0.02),
        "final_norm": ((d,), None),
        "lm_head": ((d, vp), s(d)),
        "layers/ln1": ((L, d), None),
        "layers/ln2": ((L, d), None),
        "layers/attn/w_q": ((L, d, h * hd), s(d)),
        "layers/attn/w_k": ((L, d, hk * hd), s(d)),
        "layers/attn/w_v": ((L, d, hk * hd), s(d)),
        "layers/attn/w_o": ((L, h * hd, d), s(h * hd)),
        "layers/attn/nsa/pe_k": ((L, l, hd), 0.02),
        "layers/attn/nsa/pe_v": ((L, l, hd), 0.02),
        "layers/attn/nsa/w_k": ((L, hd, hd), s(hd)),
        "layers/attn/nsa/w_v": ((L, hd, hd), s(hd)),
        "layers/attn/nsa/w_gate": ((L, d, h, 3), s(d)),
        "layers/mlp/w_in": ((L, d, ff), s(d)),
        "layers/mlp/w_gate": ((L, d, ff), s(d)),
        "layers/mlp/w_out": ((L, ff, d), s(ff)),
    }
    if a["qkv_bias"]:
        leaves.update({"layers/attn/b_q": ((L, h * hd), 0.02),
                       "layers/attn/b_k": ((L, hk * hd), 0.02),
                       "layers/attn/b_v": ((L, hk * hd), 0.02)})
    return leaves


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, val in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = val
    return tree


def make(a: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """All weights for ``seed``, made on the default device in one call."""
    spec = shapes(a)

    @jax.jit
    def build(key):
        out = {}
        for i, (path, (shape, scale)) in enumerate(sorted(spec.items())):
            if scale is None:
                out[path] = jnp.zeros(shape, dtype)
            else:
                k = jax.random.fold_in(key, i)
                out[path] = (jax.random.normal(k, shape, dtype)
                             * jnp.asarray(scale, dtype))
        return _nest(out)

    return build(seed_key(seed, 1))
