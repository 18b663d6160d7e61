"""Prefix caching: ref-counted page pool, radix-trie match/insert/evict,
copy-on-write at the compressed boundary page, write-floor routing, and
engine-level correctness — slots aliasing shared physical prefix pages must
decode exactly their dense-reference tokens, before and after the co-shared
slot is released (the page-table permutation-invariance guarantee extended
to shared tables)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced
from repro.core.paging import scatter_rows
from repro.models import build
from repro.serving import (Engine, PagePool, PagedNSACache, PrefixCache,
                           Request)

jax.config.update("jax_platform_name", "cpu")

MAX_LEN = 96
CHUNK = 32
P = 16                                   # reduced-config nsa.block_size


def _cfg(**over):
    return reduced(get_config("codeqwen1.5-7b"), **over)


def _dense_greedy(cfg, params, prompt, max_new, max_len=MAX_LEN):
    model = build(cfg)
    cache = model.init_cache(1, max_len)
    batch = {"tokens": jnp.asarray(prompt)[None],
             "labels": jnp.full((1, len(prompt)), -100)}
    logits, cache = jax.jit(model.prefill)(params, cache, batch)
    toks = [int(jnp.argmax(logits[0, :cfg.vocab]))]
    step = jax.jit(model.decode_step)
    for i in range(max_new - 1):
        logits, cache = step(params, cache, jnp.asarray([toks[-1]]),
                             jnp.asarray([len(prompt) + i]))
        toks.append(int(jnp.argmax(logits[0, :cfg.vocab])))
    return toks


def _prompt(seed, n, vocab):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 0,
                                         vocab))


# ------------------------------------------------------------- refcounts
def test_page_pool_refcounts():
    pool = PagePool(num_pages=8, page_size=16)
    lease = pool.try_alloc(2)
    pages = lease.take()
    assert [pool.refcount(p) for p in pages] == [1, 1]
    pool.share(pages)
    assert [pool.refcount(p) for p in pages] == [2, 2]
    pool.release(pages)                  # one ref down: still allocated
    assert pool.available == 5 and pool.refcount(pages[0]) == 1
    pool.release(pages)                  # last ref: pages return to the pool
    assert pool.available == 7 and pool.refcount(pages[0]) == 0
    with pytest.raises(ValueError):
        pool.release(pages)              # no live refs left
    with pytest.raises(ValueError):
        pool.share([pages[0]])           # sharing a freed page
    with pytest.raises(ValueError):
        pool.share([7])                  # never-allocated page


# ------------------------------------------------------------ radix trie
def _host_prefilled_cache(cfg, prompt, slot=0):
    """A PagedNSACache with ``slot`` allocated and marked fully prefilled
    (host bookkeeping only — trie tests don't touch page contents)."""
    cache = PagedNSACache(cfg, n_slots=2, max_len=MAX_LEN)
    prefix = PrefixCache(cache)
    cache.prefix = prefix
    cap = max(-(-len(prompt) // CHUNK) * CHUNK, len(prompt) + 4)
    assert cache.alloc_slot(slot, cap)
    cache.lengths[slot] = len(prompt)
    return cache, prefix


def test_trie_match_caps_and_aliases():
    """match() returns the longest cached block prefix, capped so at least
    one prompt token is always prefilled, with the donor's physical pages."""
    cfg = _cfg()
    prompt = _prompt(0, 80, cfg.vocab)
    cache, prefix = _host_prefilled_cache(cfg, prompt)
    assert prefix.insert(prompt, 0) == 80 // P           # 5 blocks indexed
    assert prefix.blocks_cached == 5

    m = prefix.match(prompt)             # identical prompt: cap applies
    assert m.tokens == ((80 - 1) // P) * P == 64         # 4, not 5 blocks
    assert m.raw_pages == cache.tables[0].pages[:4]      # physical aliases
    assert all(cache.pool.refcount(p) == 3 for p in m.raw_pages)
    m.cancel()                           # slot ref + trie ref remain
    assert all(cache.pool.refcount(p) == 2 for p in m.raw_pages)

    longer = np.concatenate([prompt, _prompt(1, 16, cfg.vocab)])
    m2 = prefix.match(longer)            # full 5 cached blocks now usable
    assert m2.tokens == 80
    m2.cancel()
    assert prefix.match(_prompt(2, 40, cfg.vocab)) is None   # diverges at 0
    assert prefix.match(prompt[:P]) is None                  # cap -> 0 blocks


def test_trie_shared_pages_survive_slot_release_until_evicted():
    cfg = _cfg()
    prompt = _prompt(3, 48, cfg.vocab)
    cache, prefix = _host_prefilled_cache(cfg, prompt)
    prefix.insert(prompt, 0)
    cached_raw = [n.raw_page for n in prefix._walk(prompt, 3)]
    cache.free_slot(0)
    # trie refs keep the cached blocks alive past the slot's release
    assert cache.pool.used == len(cached_raw) == 3
    assert prefix.evict_lru(prefix.blocks_cached) == 3
    assert cache.pool.used == 0 and cache.cmp_pool.used == 0
    assert prefix.blocks_cached == 0


def test_trie_lru_eviction_order():
    """evict_lru drops the least-recently-MATCHED leaf first."""
    cfg = _cfg()
    a = _prompt(4, 48, cfg.vocab)
    b = _prompt(5, 48, cfg.vocab)
    cache, prefix = _host_prefilled_cache(cfg, a)
    prefix.insert(a, 0)
    cap = max(-(-len(b) // CHUNK) * CHUNK, len(b) + 4)
    assert cache.alloc_slot(1, cap)
    cache.lengths[1] = len(b)
    prefix.insert(b, 1)
    a_leaf = prefix._walk(a, 3)[-1]
    prefix.match(np.concatenate([a, a[:8]])).cancel()     # touch chain a
    assert prefix.evict_lru(1) == 1                       # b's leaf goes
    assert prefix._walk(b, 3) != [] and len(prefix._walk(b, 3)) == 2
    assert prefix._walk(a, 3)[-1] is a_leaf               # a intact


# ------------------------------------------------------- write routing
def test_scatter_rows_min_pos_routes_to_dump_page():
    pool = jnp.zeros((4, 1, 4, 2))              # (pages, h_K, P, d)
    table = jnp.asarray([[1, 2], [3, 1]], jnp.int32)
    positions = jnp.asarray([[0, 5], [0, 5]], jnp.int32)
    values = jnp.ones((2, 2, 1, 2))
    out = scatter_rows(pool, table, positions, values,
                       min_pos=jnp.asarray([4, 0], jnp.int32))
    # slot 0's pos 0 is below its floor -> dumped; everything else lands
    assert float(out[1, :, 0].sum()) == 0       # page 1 row 0 (slot 0 pos 0)
    assert float(out[2, :, 1].sum()) == 2       # slot 0 pos 5 (above floor)
    assert float(out[3, :, 0].sum()) == 2       # slot 1 pos 0 (floor 0)
    assert float(out[1, :, 1].sum()) == 2       # slot 1 pos 5


# ----------------------------------------------------- engine-level CoW
def test_shared_tables_decode_identical_to_private_before_and_after_release():
    """Two slots aliasing the same physical prefix pages must decode exactly
    the tokens of fully private copies (= the dense reference), and shared
    page CONTENTS must stay byte-identical through the sharers' prefill and
    decode — including after one sharing slot is released mid-run."""
    cfg = _cfg()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    shared = _prompt(10, 48, cfg.vocab)
    pa = np.concatenate([shared, _prompt(11, 9, cfg.vocab)])
    pb = np.concatenate([shared, _prompt(12, 7, cfg.vocab)])
    pc = np.concatenate([shared, _prompt(13, 5, cfg.vocab)])

    eng = Engine(cfg, n_slots=2, max_len=MAX_LEN, prefill_chunk=CHUNK,
                 params=params, prefix_cache=True)
    donor = eng.submit(pa, max_new=2)
    while donor.state != "done":                     # warm the trie
        eng.step()
    trie_raw = [n.raw_page for n in eng._prefix._walk(shared, 3)]
    assert len(trie_raw) == 3 and eng.cache.pool.used >= 3

    rb = eng.submit(pb, max_new=8)
    rc = eng.submit(pc, max_new=2)
    eng.step()                                       # both admitted, matched
    assert rb.cached_tokens == 48 and rc.cached_tokens == 48
    sb, sc = rb.slot, rc.slot
    assert eng.cache.tables[sb].pages[:3] == trie_raw    # physical aliasing
    assert eng.cache.tables[sc].pages[:3] == trie_raw
    assert eng.cache.tables[sb].shared == 3
    layer0 = lambda: jax.tree.map(lambda a: np.asarray(a[0]),
                                  eng.cache.data["layers"])
    before = layer0()["k_pages"][trie_raw].copy()

    while rc.state != "done":                        # rc releases first
        eng.step()
    assert rb.state == "active"                      # rb still decoding
    np.testing.assert_array_equal(before, layer0()["k_pages"][trie_raw])
    eng.run()
    np.testing.assert_array_equal(before, layer0()["k_pages"][trie_raw])

    for req, prompt in ((donor, pa), (rb, pb), (rc, pc)):
        ref = _dense_greedy(cfg, params, prompt, req.max_new)
        assert list(req.out) == ref, f"rid {req.rid} diverged"
    s = eng.summary()
    assert s["prefix_hit_rate"] > 0 and s["prefix_blocks_reused"] == 6


def test_cow_boundary_cmp_page_is_private():
    """Full compressed pages are aliased; the partially-filled boundary
    compressed page is copy-on-write — the matcher gets its own physical
    page (its prefill appends rows there) and still matches dense."""
    cfg = _cfg()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(1))
    shared = _prompt(20, 80, cfg.vocab)              # ncmp(80)=19: 1 full page
    pa = np.concatenate([shared, _prompt(21, 5, cfg.vocab)])
    pb = np.concatenate([shared, _prompt(22, 3, cfg.vocab)])

    eng = Engine(cfg, n_slots=2, max_len=MAX_LEN, prefill_chunk=CHUNK,
                 params=params, prefix_cache=True)
    donor = eng.submit(pa, max_new=2)
    while donor.state != "done":
        eng.step()
    chain = eng._prefix._walk(shared, 5)
    assert len(chain) == 5
    full_cmp = [pg for n in chain for pg in n.cmp_full_new]
    boundary = chain[-1].cmp_boundary
    assert len(full_cmp) == 1 and boundary is not None

    rb = eng.submit(pb, max_new=2)
    eng.step()
    assert rb.cached_tokens == 80
    ct = eng.cache.cmp_tables[rb.slot]
    assert ct.pages[0] == full_cmp[0] and ct.shared == 1    # aliased
    assert ct.pages[1] != boundary                   # CoW: private copy
    eng.run()
    assert list(rb.out) == _dense_greedy(cfg, params, pb, 2)


def test_eviction_under_pressure_admits_unrelated_request():
    """When the pools can't cover an admission, LRU cached prefixes are
    evicted (trie refs dropped) instead of failing the admission."""
    cfg = _cfg()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(2))
    cache_probe = PagedNSACache(cfg, n_slots=1, max_len=MAX_LEN)
    num_pages = cache_probe.max_pages + 1            # exactly one slot's worth
    pa = _prompt(30, 80, cfg.vocab)
    pb = _prompt(31, 80, cfg.vocab)                  # unrelated prompt

    eng = Engine(cfg, n_slots=1, max_len=MAX_LEN, prefill_chunk=CHUNK,
                 params=params, num_pages=num_pages, prefix_cache=True)
    ra = eng.submit(pa, max_new=2)
    eng.run()
    assert eng._prefix.blocks_cached == 5
    assert eng.cache.pool.used == 5                  # trie refs only
    rb = eng.submit(pb, max_new=2)                   # needs the whole pool
    eng.run()
    assert rb.state == "done"
    assert eng._prefix.blocks_cached == 5            # pb's blocks replaced pa's
    assert eng._prefix._walk(pa, 5) == []            # pa's chain evicted
    assert list(ra.out) == _dense_greedy(cfg, params, pa, 2)
    assert list(rb.out) == _dense_greedy(cfg, params, pb, 2)


def test_prefix_cache_exact_parity_and_page_savings():
    """Prefix cache on vs off over the same prompts: identical tokens, hit
    counters advance, and fewer distinct raw pages are touched."""
    cfg = _cfg()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(3))
    shared = _prompt(40, 48, cfg.vocab)
    prompts = [np.concatenate([shared, _prompt(41 + i, 6 + i, cfg.vocab)])
               for i in range(4)]

    outs, peaks = {}, {}
    for on in (False, True):
        eng = Engine(cfg, n_slots=2, max_len=MAX_LEN, prefill_chunk=CHUNK,
                     params=params, prefix_cache=on)
        reqs = [eng.submit(p, max_new=3) for p in prompts]
        s = eng.run()
        outs[on] = [list(r.out) for r in reqs]
        peaks[on] = s["peak_page_util"]
        if on:
            assert s["prefix_hit_rate"] > 0
            assert s["prefix_blocks_reused"] >= 3
            assert s["prefix_blocks_cached"] > 0
            assert eng.cache.pool.used > 0           # trie refs post-drain
            eng._prefix.clear()
            assert eng.cache.pool.used == 0
        else:
            assert s["prefix_hit_rate"] == 0
            assert eng.cache.pool.used == 0
    assert outs[True] == outs[False]
    assert peaks[True] <= peaks[False]


def test_cache_reset_clears_prefix_cache():
    cfg = _cfg()
    prompt = _prompt(50, 48, cfg.vocab)
    cache, prefix = _host_prefilled_cache(cfg, prompt)
    prefix.insert(prompt, 0)
    cache.reset()
    assert prefix.blocks_cached == 0
    assert cache.pool.used == 0 and cache.cmp_pool.used == 0
