"""Cross-backend equivalence suite, driven FROM the registry.

Every backend registered in ``repro.attention`` is compared against the
``reference`` backend for every mode it declares (train/prefill, decode,
paged-decode) — a backend added tomorrow is covered here with zero test
changes.  Also: the ``resolve`` contract (capability filtering, structured
errors naming alternatives, min-seq dense fallback, policy routing) and the
deprecation shims mapping the old config spellings.
"""
import dataclasses
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.attention import (AttentionRequest, BackendResolutionError,
                             KernelPolicy, NSAConfig, capable_backends,
                             get_backend, list_backends, nsa_attention,
                             resolve)
from repro.core import apply_gates, compression, init_nsa_params
from repro.kernels import ref as kref

jax.config.update("jax_platform_name", "cpu")

CFG = NSAConfig(block_size=16, num_selected=4, cmp_block_size=8, cmp_stride=4,
                window_size=32, q_block_size=32, min_seq_for_sparse=1)
N, H_K, D, DM = 96, 2, 16, 32


def _nsa_state(g, seed=0):
    h = g * H_K
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    p = init_nsa_params(ks[0], DM, h, D, CFG)
    gates = apply_gates(p, jax.random.normal(ks[1], (N, DM)))
    q = jax.random.normal(ks[2], (N, h, D))
    k = jax.random.normal(ks[3], (N, H_K, D))
    v = jax.random.normal(ks[4], (N, H_K, D))
    return p, gates, q, k, v


def _paged_state(seed=0, slots=3, g=2, max_pages=4, n_pages=24):
    p_sz = CFG.block_size
    h = H_K * g
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    perm = np.random.default_rng(seed).permutation(np.arange(1, n_pages))
    n_cmp = CFG.num_cmp_blocks(max_pages * p_sz)
    return {
        "q": jax.random.normal(ks[0], (slots, h, D)),
        "gates": jax.nn.softmax(jax.random.normal(ks[1], (slots, h, 3)), -1),
        "k_pages": jax.random.normal(ks[2], (n_pages, H_K, p_sz, D)),
        "v_pages": jax.random.normal(ks[3], (n_pages, H_K, p_sz, D)),
        "cmp_k": jax.random.normal(ks[4], (slots, n_cmp, H_K, D)),
        "cmp_v": jax.random.normal(ks[5], (slots, n_cmp, H_K, D)),
        "tables": jnp.asarray(perm[:slots * max_pages].reshape(slots,
                                                               max_pages),
                              jnp.int32),
        "pos": jnp.asarray(np.random.default_rng(seed + 1).integers(
            0, max_pages * p_sz, size=(slots,)), jnp.int32),
    }


# ----------------------------------------------------- registry-driven sweep
def _declared(mode, algorithm="nsa"):
    return sorted(n for n, c in list_backends().items()
                  if mode in c.modes and algorithm in c.algorithms)


@pytest.mark.parametrize("name", _declared("prefill"))
def test_backend_matches_reference_prefill(name):
    caps = list_backends()[name]
    g = max(2, caps.min_g)
    p, gates, q, k, v = _nsa_state(g)
    ref = nsa_attention(p, gates, q, k, v, cfg=CFG, mode="prefill",
                        backend="reference")
    out = nsa_attention(p, gates, q, k, v, cfg=CFG, mode="prefill",
                        backend=name)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5, err_msg=name)


@pytest.mark.parametrize("algorithm", ["full", "sliding"])
@pytest.mark.parametrize("name", sorted(
    set(_declared("prefill", "full")) | set(_declared("prefill", "sliding"))))
def test_backend_matches_oracle_full_sliding(name, algorithm):
    caps = list_backends()[name]
    if algorithm not in caps.algorithms:
        pytest.skip(f"{name} does not declare algorithm {algorithm}")
    _, _, q, k, v = _nsa_state(2)
    window = 24 if algorithm == "sliding" else None
    out = nsa_attention(None, None, q, k, v, cfg=CFG, mode="prefill",
                        backend=name, algorithm=algorithm, window=window)
    oracle = kref.flash_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               atol=3e-5, rtol=3e-5, err_msg=name)


@pytest.mark.parametrize("name", _declared("decode"))
def test_backend_matches_reference_decode(name):
    p, gates, q, k, v = _nsa_state(2, seed=1)
    ck, cv = compression.compress_kv(p, k, v, CFG)
    for t in (37, N - 1):
        cache = {"cmp_k": ck, "cmp_v": cv, "pos": jnp.asarray(t)}
        ref = nsa_attention(p, gates[t], q[t], k, v, cache, cfg=CFG,
                            mode="decode", backend="reference")
        out = nsa_attention(p, gates[t], q[t], k, v, cache, cfg=CFG,
                            mode="decode", backend=name)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5, rtol=3e-5,
                                   err_msg=f"{name} pos={t}")


@pytest.mark.parametrize("name", _declared("paged_decode"))
def test_backend_matches_reference_paged_decode(name):
    st = _paged_state(seed=2)
    cache = {"page_tables": st["tables"], "cmp_k": st["cmp_k"],
             "cmp_v": st["cmp_v"], "pos": st["pos"]}
    args = (None, st["gates"], st["q"], st["k_pages"], st["v_pages"], cache)
    ref = nsa_attention(*args, cfg=CFG, mode="paged_decode",
                        backend="reference")
    out = nsa_attention(*args, cfg=CFG, mode="paged_decode", backend=name)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5, err_msg=name)


def test_every_backend_is_covered_by_some_sweep():
    """No registered backend escapes the equivalence sweeps above."""
    covered = set(_declared("prefill")) | set(_declared("decode")) \
        | set(_declared("paged_decode")) \
        | set(_declared("prefill", "full")) \
        | set(_declared("prefill", "sliding")) | {"reference"}
    assert covered == set(list_backends()), (
        f"backends outside the sweep: {set(list_backends()) - covered}")


# --------------------------------------------------------------- resolve()
def test_resolve_auto_defaults():
    cfg = CFG
    assert resolve(cfg, AttentionRequest(mode="train", seq_len=N,
                                         g=2)).name == "sparse_union"
    assert resolve(cfg, AttentionRequest(mode="decode",
                                         g=2)).name == "sparse_gather"
    assert resolve(cfg, AttentionRequest(mode="paged_decode", g=2,
                                         paged=True)).name == "paged_kernel"
    # TPU platform prefers the Pallas FSA kernel for train/prefill
    assert resolve(cfg, AttentionRequest(mode="train", seq_len=N, g=2,
                                         platform="tpu")).name == "fsa"


def test_resolve_prefers_fused_backward_for_training():
    """A train-mode request under jax.grad lands on a fused-backward
    backend (the Pallas backward kernels), not the XLA-twin paths — while
    inference-shaped requests (needs_grad=False, as in
    test_resolve_auto_defaults) keep the historic defaults."""
    req = AttentionRequest(mode="train", seq_len=N, g=2, needs_grad=True)
    assert resolve(CFG, req).name == "fsa"
    assert list_backends()["fsa"].fused_backward
    for algorithm, expect in (("full", "flash_full"),
                              ("sliding", "flash_sliding")):
        req = AttentionRequest(mode="train", algorithm=algorithm, seq_len=N,
                               g=2, needs_grad=True)
        assert resolve(CFG, req).name == expect
        assert list_backends()[expect].fused_backward
    # the bonus is train-only: prefill+needs_grad keeps the inference pick
    req = AttentionRequest(mode="prefill", seq_len=N, g=2, needs_grad=True)
    assert resolve(CFG, req).name == "sparse_union"


def test_resolve_min_seq_dense_fallback():
    cfg = dataclasses.replace(CFG, min_seq_for_sparse=256)
    assert resolve(cfg, AttentionRequest(mode="train", seq_len=64,
                                         g=2)).name == "reference"
    # explicit backends fall back too (old nsa_attention(impl=) semantics)
    assert resolve(cfg, AttentionRequest(mode="train", seq_len=64, g=2),
                   backend="sparse_union").name == "reference"


def test_resolve_policy_routing():
    cfg = dataclasses.replace(
        CFG, policy=KernelPolicy(backend="fsa_faithful",
                                 paged_backend="paged_gather",
                                 q_block_size=32))
    assert resolve(cfg, AttentionRequest(mode="train", seq_len=N,
                                         g=2)).name == "fsa_faithful"
    assert resolve(cfg, AttentionRequest(mode="paged_decode", g=2,
                                         paged=True)).name == "paged_gather"


def test_policy_nsa_backend_does_not_capture_full_sliding():
    """A policy naming an NSA selected-branch kernel must not hijack (and
    break) the full/sliding/cross-attention paths — the old cfg.kernel never
    affected them either."""
    cfg = dataclasses.replace(CFG, policy=KernelPolicy(backend="fsa",
                                                       q_block_size=32))
    assert resolve(cfg, AttentionRequest(mode="prefill", algorithm="full",
                                         seq_len=N, g=2)).name == "reference"
    _, _, q, k, v = _nsa_state(2, seed=5)
    out = nsa_attention(None, None, q, k, v, cfg=cfg, mode="prefill",
                        algorithm="sliding", window=24)
    oracle = kref.flash_ref(q, k, v, causal=True, window=24)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               atol=3e-5, rtol=3e-5)


def test_resolve_structured_error_names_alternatives():
    req = AttentionRequest(mode="train", seq_len=N, g=2)
    with pytest.raises(BackendResolutionError) as e:
        resolve(CFG, req, backend="nsa")        # nsa declares min_g=8
    err = e.value
    assert err.requested == "nsa" and "min_g" in err.reason
    assert "sparse_union" in err.alternatives and "fsa" in err.alternatives
    assert "sparse_union" in str(err)
    # ...and g=8 makes it capable again
    assert resolve(CFG, AttentionRequest(mode="train", seq_len=N, g=8),
                   backend="nsa").name == "nsa"


def test_resolve_rejects_nondifferentiable_for_grad():
    req = AttentionRequest(mode="paged_decode", g=2, paged=True,
                           needs_grad=True)
    with pytest.raises(BackendResolutionError, match="not differentiable"):
        resolve(CFG, req, backend="paged_kernel")


def test_decode_modes_are_nsa_only():
    """full/sliding have no cache-decode path: the request is rejected up
    front with a structured error, never a shape crash inside a backend."""
    for mode in ("decode", "paged_decode"):
        with pytest.raises(BackendResolutionError, match="NSA-only"):
            resolve(CFG, AttentionRequest(mode=mode, algorithm="full", g=2,
                                          paged=(mode == "paged_decode")))


def test_policy_routes_paged_prefill_selected_branch():
    """sparse_selected_fn surfaces the policy's union/gather choice for code
    that runs the sparse chunk machinery directly (paged chunked prefill)."""
    from repro.attention import backends as ab
    from repro.core import sparse as core_sparse
    assert ab.sparse_selected_fn(CFG) is core_sparse.selected_union_attention
    cfg = dataclasses.replace(CFG,
                              policy=KernelPolicy(backend="sparse_gather"))
    assert ab.sparse_selected_fn(cfg) is core_sparse.selected_gather_attention


def test_unknown_backend_errors():
    with pytest.raises(KeyError, match="unknown attention backend"):
        get_backend("does_not_exist")


def test_capable_backends_filters():
    names = capable_backends(AttentionRequest(mode="paged_decode", g=2,
                                              paged=True))
    assert set(names) == {"paged_kernel", "paged_gather", "reference"}


def test_nsa_config_policy_passthrough_knobs():
    """Tuning-knob kwargs land on the policy; algorithm fields are intact."""
    cfg = NSAConfig(block_size=16, q_block_size=32, interpret=True)
    assert cfg.block_size == 16
    assert cfg.q_block_size == 32 and cfg.policy.q_block_size == 32
    assert cfg.interpret is True


def test_nsa_config_rejects_removed_spellings():
    """The PR-5 deprecation shims (kernel=/selected_impl=/paged_kernel=)
    are gone: the old kwargs now fail loudly instead of warning."""
    with pytest.raises(TypeError):
        NSAConfig(kernel="fsa")
    with pytest.raises(TypeError):
        NSAConfig(selected_impl="gather")
    with pytest.raises(TypeError):
        NSAConfig(paged_kernel=True)


def test_policy_is_algorithm_invariant():
    """Swapping the policy never changes the math (same output)."""
    p, gates, q, k, v = _nsa_state(2, seed=3)
    outs = []
    for pol in (KernelPolicy(backend="sparse_union"),
                KernelPolicy(backend="fsa", q_block_size=32)):
        cfg = dataclasses.replace(CFG, policy=pol)
        outs.append(nsa_attention(p, gates, q, k, v, cfg=cfg, mode="prefill"))
    np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(outs[1]),
                               atol=3e-5, rtol=3e-5)


def test_legacy_impl_aliases_resolve():
    from repro.attention import normalize_backend_name
    assert normalize_backend_name("sparse", CFG) == "sparse_union"
    assert normalize_backend_name("kernel", CFG) == "fsa"
    cfg = dataclasses.replace(CFG, policy=KernelPolicy(backend="nsa"))
    assert normalize_backend_name("kernel", cfg) == "nsa"
    cfg = dataclasses.replace(CFG,
                              policy=KernelPolicy(backend="sparse_gather"))
    assert normalize_backend_name("sparse", cfg) == "sparse_gather"


def test_no_warnings_on_new_spellings():
    """Plain construction and the unified entry never warn."""
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        cfg = NSAConfig(block_size=16, num_selected=4, cmp_block_size=8,
                        cmp_stride=4, window_size=32, q_block_size=32,
                        interpret=True, min_seq_for_sparse=1,
                        policy=KernelPolicy(backend="sparse_union"))
        p, gates, q, k, v = _nsa_state(2, seed=4)
        nsa_attention(p, gates, q, k, v, cfg=cfg, mode="prefill")
