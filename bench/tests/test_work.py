"""bench.work against counts made by hand at a tiny configuration
(B_K 16, T 4, l 8, s 4, W 32; 4 query heads over 2 KV heads of 16)."""
from __future__ import annotations

import pytest

from bench import spec, work
from bench.tests import tiny


@pytest.fixture(scope="module")
def a():
    return spec.arch_of(tiny.config("h2o-danube-3-4b"))


@pytest.mark.parametrize("t,want", [(6, 0), (7, 1), (11, 2), (100, 24)])
def test_compressed_tokens_visible(a, t, want):
    assert work.n_cmp_visible(t, a) == want


@pytest.mark.parametrize("t,want", [
    (0, 1),            # one block exists: T clamped to 1, cut at t
    (15, 16),          # still one block, whole
    (17, 18),          # two blocks: 16 + 2 of the current one
    (100, 53),         # T = 4: three whole blocks + 5 of the current
])
def test_selected_keys_clamp_T_causally(a, t, want):
    assert work.selected_keys(t, a) == want


def test_window_keys(a):
    assert work.window_keys(10, a) == 11
    assert work.window_keys(100, a) == 32


def test_attention_flops(a):
    # 4 heads x 16 dims x 4 (QK and PV) x (24 + 53 + 32) keys
    assert work.attn_flops_fwd([100], a) == 256 * 109


def test_matmul_params(a):
    attn = 64 * 4 * 16 * 2 + 64 * 2 * 16 * 2 + 64 * 4 * 3
    mlp = 3 * 64 * 128
    assert work.matmul_params(a) == 2 * (attn + mlp) + 64 * 256
    assert work.matmul_params(a, head=False) == 2 * (attn + mlp)


def test_paged_decode_work(a):
    f, b = work.paged_decode_work([100], a)
    assert f == 4 * 4 * 16 * (53 + 32)
    assert b == 2 * (2 * 2 * 16 * 85 + 3 * 4 * 16)


def test_fsa_work(a):
    keys = sum(range(1, 17)) + (17 + 18 + 19 + 20)     # positions 0..19
    f, b = work.fsa_fwd_work(20, a)
    assert f == 4 * 4 * 16 * keys
    assert b == 2 * (2 * 20 * 4 * 16 + 2 * 20 * 2 * 16) + 4 * 20 * 4
    fb, bb = work.fsa_bwd_work(20, a)
    assert fb == 2.5 * f
    assert bb == 2 * (4 * 20 * 4 * 16 + 4 * 20 * 2 * 16) + 8 * 20 * 4


def test_serve_flops_counts_head_only_where_a_token_comes(a):
    body = 2 * work.matmul_params(a, head=False)
    head = 2 * 64 * 256
    n = a["n_layers"]
    mid = work.serve_flops([(0, 16, False)], [], a)
    assert mid == 16 * body + n * (work.attn_flops_fwd(range(16), a)
                                   + work.cmp_flops(3, a))
    last = work.serve_flops([(0, 16, True)], [], a)
    assert last - mid == head
    # position 43 completes summary token 9 (43 = 9 * 4 + 8 - 1); 40 none
    dec = work.serve_flops([], [43], a)
    assert dec == body + head + n * (work.attn_flops_fwd([43], a)
                                     + work.cmp_flops(1, a))
    assert work.serve_flops([], [40], a) == body + head + n * \
        work.attn_flops_fwd([40], a)


def test_train_flops(a):
    n = 64
    attn = work.attn_flops_fwd(range(n), a) + work.cmp_flops(
        work.n_cmp_visible(n - 1, a), a)
    assert work.train_flops(n, a) == 6 * work.matmul_params(a) * n + \
        3 * a["n_layers"] * attn


def test_roofline_share():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_share(100, 5, 2.0, peaks) == (50.0, "compute")
    assert work.roofline_share(10, 40, 8.0, peaks) == (50.0, "memory")
