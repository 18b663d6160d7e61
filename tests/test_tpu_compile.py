"""Compile-only guard: the main-path Pallas kernels at real widths, compiled
for a described TPU v5e chip (none attached).

Interpret mode accepts block shapes and memory use that the TPU compiler
refuses, so every kernel the serving and training paths launch is compiled
here at N=4096 in bf16, at two sets of attention widths:

* h2o-danube-3-4b: h_K=8, g=4, head_dim 120 (not a multiple of 128);
* codeqwen1.5-7b:  h_K=32, g=1, head_dim 128 (the paper's best case).

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and test workers import every
test file.  All these tests stay in this one file for the same reason.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as flash
from repro.kernels import fsa_selected, fsa_selected_bwd
from repro.kernels import paged_decode as paged

N, T, B_Q, B_K, WINDOW = 4096, 16, 128, 64, 512
WIDTHS = {"danube": (8, 4, 120), "codeqwen": (32, 1, 128)}   # h_K, g, d


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:        # no TPU compiler here: nothing to guard
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a persistent-cache entry written by a chip-less compile cannot be read
    # back, so keep the cache out of these compiles
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    """Compile ``fn`` for the described chip; returns the compiled HLO."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _selected_shapes(h_k, g, d):
    rows, nq, nb = N * g, N // B_Q, N // B_K
    bf, i32, f32 = jnp.bfloat16, jnp.int32, jnp.float32
    return {
        "q": ((h_k, rows, d), bf), "kv": ((h_k, N, d), bf),
        "sel": ((h_k, rows, T), i32),
        "kv_ids": ((h_k, nq, min(nb, B_Q * T)), i32), "kv_cnt": ((h_k, nq), i32),
        "q_ids": ((h_k, nb, nq), i32), "q_cnt": ((h_k, nb), i32),
        "panel": ((h_k, rows, 128), f32),
    }


@pytest.mark.parametrize("widths", sorted(WIDTHS))
@pytest.mark.parametrize("return_lse", [False, True])
def test_fsa_selected_compiles(one_chip, widths, return_lse):
    h_k, g, d = WIDTHS[widths]
    s = _selected_shapes(h_k, g, d)
    fn = lambda q, k, v, sel, ids, cnt: fsa_selected.fsa_selected(
        q, k, v, sel, ids, cnt, g=g, block_q=B_Q, block_k=B_K,
        return_lse=return_lse)
    _compile(one_chip, fn, s["q"], s["kv"], s["kv"], s["sel"], s["kv_ids"],
             s["kv_cnt"])


@pytest.mark.parametrize("widths", sorted(WIDTHS))
def test_fsa_selected_dq_compiles(one_chip, widths):
    h_k, g, d = WIDTHS[widths]
    s = _selected_shapes(h_k, g, d)
    fn = lambda q, k, v, sel, do, lse, delta, ids, cnt: (
        fsa_selected_bwd.fsa_selected_dq(q, k, v, sel, do, lse, delta, ids,
                                         cnt, g=g, block_q=B_Q, block_k=B_K))
    _compile(one_chip, fn, s["q"], s["kv"], s["kv"], s["sel"], s["q"],
             s["panel"], s["panel"], s["kv_ids"], s["kv_cnt"])


@pytest.mark.parametrize("widths", sorted(WIDTHS))
def test_fsa_selected_dkv_compiles(one_chip, widths):
    h_k, g, d = WIDTHS[widths]
    s = _selected_shapes(h_k, g, d)
    fn = lambda q, k, v, sel, do, lse, delta, ids, cnt: (
        fsa_selected_bwd.fsa_selected_dkv(q, k, v, sel, do, lse, delta, ids,
                                          cnt, g=g, block_q=B_Q, block_k=B_K))
    _compile(one_chip, fn, s["q"], s["kv"], s["kv"], s["sel"], s["q"],
             s["panel"], s["panel"], s["q_ids"], s["q_cnt"])


@pytest.mark.parametrize("widths", sorted(WIDTHS))
@pytest.mark.parametrize("window", [None, WINDOW])
def test_flash_attention_compiles(one_chip, widths, window):
    h_k, g, d = WIDTHS[widths]
    s = _selected_shapes(h_k, g, d)
    fn = lambda q, k, v: flash.flash_attention(q, k, v, g=g, causal=True,
                                               window=window, return_lse=True)
    _compile(one_chip, fn, s["q"], s["kv"], s["kv"])


@pytest.mark.parametrize("widths", sorted(WIDTHS))
@pytest.mark.parametrize("kernel", ["dq", "dkv"])
def test_flash_attention_bwd_compiles(one_chip, widths, kernel):
    h_k, g, d = WIDTHS[widths]
    s = _selected_shapes(h_k, g, d)
    bwd = {"dq": flash.flash_attention_dq, "dkv": flash.flash_attention_dkv}
    fn = lambda q, k, v, do, lse, delta: bwd[kernel](
        q, k, v, do, lse, delta, g=g, causal=True, window=WINDOW)
    _compile(one_chip, fn, s["q"], s["kv"], s["kv"], s["q"], s["panel"],
             s["panel"])


@pytest.mark.parametrize("widths", sorted(WIDTHS))
def test_paged_decode_compiles(one_chip, widths):
    """The serving decode kernel over (N_pages, h_K, P, d) pools: 4 slots
    of 4096 tokens each, as ``Engine(n_slots=4, max_len=4096)`` holds."""
    h_k, g, d = WIDTHS[widths]
    slots, pages = 4, 4 * (N // B_K) + 1
    block_s = min(slots, -(-8 // g))
    steps = block_s * (T + paged.num_window_pages(WINDOW, B_K))
    fn = lambda q, k, v, pg, bl, pos: paged.paged_decode(
        q, k, v, pg, bl, pos, g=g, block_s=block_s, num_sel=T, window=WINDOW)
    bf, i32 = jnp.bfloat16, jnp.int32
    _compile(one_chip, fn, ((h_k, slots * g, d), bf),
             ((pages, h_k, B_K, d), bf), ((pages, h_k, B_K, d), bf),
             ((h_k, slots // block_s, steps), i32),
             ((h_k, slots // block_s, steps), i32), ((slots,), i32))
