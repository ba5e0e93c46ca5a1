"""Paged tiered KV cache + refcounted prefix sharing (ISSUE 6).

Three layers, matching the feature's own:

  * **paging parity** (``kernel_parity`` marked — first step of the CI
    kernels lane): the page-table-indirected cold tier must be
    numerically invisible. Flash decode (plain + fused-RoPE) and flash
    prefill over a ``PagedKVCache`` — identity AND shuffled page tables
    — match the contiguous ``TieredKVCache`` paths; the XLA reference
    functions dispatch paged caches through the same ``as_tiered``
    gather; ``paged_admit``/``save_hot`` round-trip hot snapshots and
    copy-on-write boundary pages bit-exactly.
  * **host control plane**: ``PagePool`` refcounts never go negative and
    a page returns to the free list exactly when its last reader drops
    it; ``PrefixCache`` match/insert/evict honour the leaf-only-LRU and
    never evict a page a live slot still maps.
  * **serving end-to-end** (CPU, XLA gather paths): shared-prefix
    workloads produce bit-exact greedy tokens vs unshared/contiguous
    baselines, store the shared prefix physically once (refcount ledger
    asserted through a recording pool), report
    ``prefix_tokens_reused`` that reconciles with the DR-ledger
    external-read delta, and keep the chunked-admission compile count at
    ONE with paging enabled. The serving-path bugfix sweep rides along:
    decode interleaves with long-prompt chunk streaming, ``generate``
    pads with a sentinel instead of the stop token, and empty prompts
    are rejected at validation.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import kv_cache as kvc
from repro.kernels import flash_decode as fd
from repro.kernels import flash_prefill as fp
from repro.kernels import ops
from repro.models import pack as pack_lib
from repro.models import transformer as T
from repro.serving import engine as engine_mod
from repro.serving.engine import PAD_TOKEN, Engine
from repro.serving.paging import PagePool, PagePoolError, PrefixCache
from repro.serving.scheduler import Request

TOL = dict(rtol=2e-5, atol=2e-5)
THETA = 1e4


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _build_pair(b, hot, cold, g, d, lens, ps=8, dtype=jnp.float32, seed=0,
                n_pages=None):
    """Contiguous + paged caches filled with IDENTICAL per-slot content
    via active-masked decode appends (mixed lengths), in the stored (g·d)
    layout."""
    cont = kvc.init_cache(b, hot, cold, (g * d,), dtype)
    paged = kvc.init_paged_cache(
        b, hot, cold, (g * d,), dtype, page_size=ps, n_pages=n_pages
    )
    key = jax.random.PRNGKey(seed)
    for t in range(max(lens)):
        key, k1, k2 = jax.random.split(key, 3)
        kn = jax.random.normal(k1, (b, g, d), jnp.float32).astype(dtype)
        vn = jax.random.normal(k2, (b, g, d), jnp.float32).astype(dtype)
        act = jnp.asarray([t < n for n in lens])
        cont = kvc.append_decode(cont, kn, vn, active=act)
        paged = kvc.append_decode(paged, kn, vn, active=act)
    return cont, paged


def _shuffle_pages(cache: kvc.PagedKVCache, seed=0) -> kvc.PagedKVCache:
    """Re-address the pool through a random page permutation — same
    logical content, maximally non-identity page table."""
    perm = np.asarray(
        jax.random.permutation(jax.random.PRNGKey(seed), cache.n_pages)
    )
    inv = np.argsort(perm)  # new_pool[perm[p]] = old_pool[p]
    return cache._replace(
        pool_k=jnp.asarray(np.asarray(cache.pool_k)[inv]),
        pool_v=jnp.asarray(np.asarray(cache.pool_v)[inv]),
        page_table=jnp.asarray(perm, jnp.int32)[cache.page_table],
    )


def _prompt(seed, n, vocab):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, vocab), np.int32
    )


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("falcon3-1b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


class RecordingPool(PagePool):
    """PagePool that tracks the peak reader count per page and the total
    number of page allocations — the test-side refcount ledger."""

    def __init__(self, n_pages):
        super().__init__(n_pages)
        self.peak = np.zeros(n_pages, np.int32)
        self.total_allocs = 0

    def alloc(self, n):
        pages = super().alloc(n)
        if pages is not None:
            self.total_allocs += len(pages)
            for p in pages:
                self.peak[p] = max(self.peak[p], 1)
        return pages

    def incref(self, pages):
        super().incref(pages)
        for p in pages:
            self.peak[p] = max(self.peak[p], self.refs[p])


# ---------------------------------------------------------------------------
# paging parity: kernels + reference paths (CI kernels lane, first step)
# ---------------------------------------------------------------------------

pytestmark_parity = pytest.mark.kernel_parity


@pytest.mark.kernel_parity
def test_paged_append_and_as_tiered_match_contiguous():
    b, hot, cold, g, d = 3, 4, 24, 2, 8
    lens = [2, 9, 23]
    cont, paged = _build_pair(b, hot, cold, g, d, lens)
    np.testing.assert_array_equal(
        np.asarray(cont.lengths), np.asarray(paged.lengths)
    )
    tv = kvc.as_tiered(paged)
    np.testing.assert_array_equal(np.asarray(cont.hot_k), np.asarray(tv.hot_k))
    for s, n in enumerate(lens):
        nc = max(n - hot, 0)
        np.testing.assert_array_equal(
            np.asarray(cont.cold_k[s, :nc]), np.asarray(tv.cold_k[s, :nc])
        )
        np.testing.assert_array_equal(
            np.asarray(cont.cold_v[s, :nc]), np.asarray(tv.cold_v[s, :nc])
        )


@pytest.mark.kernel_parity
def test_paged_bulk_append_valid_matches_contiguous():
    b, hot, cold, g, d, C = 2, 4, 16, 2, 8, 6
    cont, paged = _build_pair(b, hot, cold, g, d, [3, 11])
    key = jax.random.PRNGKey(7)
    kn = jax.random.normal(key, (b, C, g, d), jnp.float32)
    vn = jax.random.normal(jax.random.fold_in(key, 1), (b, C, g, d))
    valid = jnp.asarray([4, 6], jnp.int32)
    cont2 = kvc.append(cont, kn, vn, valid=valid)
    paged2 = kvc.append(paged, kn, vn, valid=valid)
    tv = kvc.as_tiered(paged2)
    np.testing.assert_array_equal(
        np.asarray(cont2.lengths), np.asarray(tv.lengths)
    )
    for s in range(b):
        n = int(cont2.lengths[s])
        nc = max(n - hot, 0)
        np.testing.assert_array_equal(
            np.asarray(cont2.hot_k[s]), np.asarray(tv.hot_k[s])
        )
        np.testing.assert_array_equal(
            np.asarray(cont2.cold_k[s, :nc]), np.asarray(tv.cold_k[s, :nc])
        )


@pytest.mark.kernel_parity
@pytest.mark.parametrize("shuffled", [False, True])
def test_flash_decode_paged_parity(shuffled):
    b, hot, cold, g, d, rep = 3, 4, 24, 2, 16, 2
    lens = [2, 9, 23]
    cont, paged = _build_pair(b, hot, cold, g, d, lens, n_pages=12)
    if shuffled:
        paged = _shuffle_pages(paged, seed=3)
    q = jax.random.normal(jax.random.PRNGKey(5), (b, g * rep, d), jnp.float32)
    o_ref = fd.flash_decode_attention(
        q, cont, impl="pallas", interpret=True, block_s=8
    )
    o_pg = fd.flash_decode_attention(
        q, paged, impl="pallas", interpret=True, block_s=8
    )
    np.testing.assert_allclose(np.asarray(o_pg), np.asarray(o_ref), **TOL)
    # XLA reference dispatches the paged cache through the same gather
    o_xla = fd.flash_decode_attention(q, paged, impl="xla")
    np.testing.assert_allclose(np.asarray(o_xla), np.asarray(o_ref), **TOL)


@pytest.mark.kernel_parity
def test_flash_decode_fused_paged_parity():
    """Fused-RoPE decode (pre-append cache, 3 scalar-prefetch operands on
    the paged path) against the contiguous XLA composition."""
    b, hot, cold, g, d, rep = 3, 4, 24, 2, 16, 2
    lens = [1, 7, 20]
    cont, paged = _build_pair(b, hot, cold, g, d, lens, n_pages=12)
    paged = _shuffle_pages(paged, seed=11)
    key = jax.random.PRNGKey(9)
    q = jax.random.normal(key, (b, g * rep, d), jnp.float32)
    kn = jax.random.normal(jax.random.fold_in(key, 1), (b, g, d))
    vn = jax.random.normal(jax.random.fold_in(key, 2), (b, g, d))
    active = jnp.asarray([True, False, True])
    o_ref, krot_ref = fd.flash_decode_attention(
        q, cont, impl="xla", k_new=kn, v_new=vn, active=active,
        rope_theta=THETA,
    )
    o_pg, krot_pg = fd.flash_decode_attention(
        q, paged, impl="pallas", interpret=True, block_s=8,
        k_new=kn, v_new=vn, active=active, rope_theta=THETA,
    )
    np.testing.assert_allclose(np.asarray(o_pg), np.asarray(o_ref), **TOL)
    np.testing.assert_allclose(
        np.asarray(krot_pg), np.asarray(krot_ref), rtol=1e-6, atol=1e-6
    )


@pytest.mark.kernel_parity
@pytest.mark.parametrize("shuffled", [False, True])
def test_flash_prefill_paged_parity(shuffled):
    """Chunked-prefill continuation over a paged cache: o / k_cast /
    v_cast match the contiguous kernel; appending the emitted KV back
    through the paged bulk append reproduces the contiguous cache."""
    b, hot, cold, g, d, rep, C = 3, 4, 24, 2, 16, 2, 6
    lens = [0, 5, 14]
    cont, paged = _build_pair(b, hot, cold, g, d, lens, n_pages=11)
    if shuffled:
        paged = _shuffle_pages(paged, seed=4)
    key = jax.random.PRNGKey(13)
    q = jax.random.normal(key, (b, C, g * rep, d), jnp.float32)
    kn = jax.random.normal(jax.random.fold_in(key, 1), (b, C, g, d))
    vn = jax.random.normal(jax.random.fold_in(key, 2), (b, C, g, d))
    valid = jnp.asarray([6, 3, 5], jnp.int32)
    ref = fp.flash_prefill_attention(
        q, kn, vn, cont, valid, rope_theta=THETA, impl="pallas",
        interpret=True, block_q=4, block_s=8,
    )
    got = fp.flash_prefill_attention(
        q, kn, vn, paged, valid, rope_theta=THETA, impl="pallas",
        interpret=True, block_q=4, block_s=8,
    )
    for r, g_ in zip(ref, got):
        np.testing.assert_allclose(np.asarray(g_), np.asarray(r), **TOL)
    cont2 = kvc.append(cont, ref[1], ref[2], valid=valid)
    paged2 = kvc.append(paged, got[1], got[2], valid=valid)
    tv = kvc.as_tiered(paged2)
    for s in range(b):
        n = int(cont2.lengths[s])
        assert n == int(tv.lengths[s])
        nc = max(n - hot, 0)
        np.testing.assert_allclose(
            np.asarray(cont2.cold_k[s, :nc]), np.asarray(tv.cold_k[s, :nc]),
            rtol=1e-6, atol=1e-6,
        )


@pytest.mark.kernel_parity
def test_xla_chunk_attention_paged_dispatch():
    b, hot, cold, g, d, rep, C = 2, 4, 16, 2, 8, 2, 5
    cont, paged = _build_pair(b, hot, cold, g, d, [6, 13])
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (b, C, g * rep, d), jnp.float32)
    kn = jax.random.normal(jax.random.fold_in(key, 1), (b, C, g, d))
    vn = jax.random.normal(jax.random.fold_in(key, 2), (b, C, g, d))
    valid = jnp.asarray([5, 2], jnp.int32)
    ref = kvc.tiered_chunk_attention(q, kn, vn, cont, valid)
    got = kvc.tiered_chunk_attention(q, kn, vn, paged, valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-6,
                               atol=1e-6)


@pytest.mark.kernel_parity
def test_save_hot_paged_admit_roundtrip_with_cow():
    """Snapshot slot 1's hot tier, then (re)admit slot 0 with the
    snapshot + slot 1's first cold page shared + a COW boundary copy:
    slot 0's logical rows [0, M) must equal slot 1's bit-exactly, and
    appending into slot 0's COW page must not disturb slot 1's copy."""
    b, hot, cold, g, d, ps = 2, 4, 16, 2, 8, 8
    _, paged = _build_pair(b, hot, cold, g, d, [0, 14], ps=ps, n_pages=8)
    # slot 1 owns pool pages (per the identity table) 2, 3; snapshot its
    # hot tier into spare page 6
    paged = kvc.save_hot(paged, jnp.int32(1), jnp.asarray([6], jnp.int32))
    M = 13  # hot 4 + full page 8 + 1 boundary row
    reset = jnp.asarray([True, False])
    new_table = jnp.asarray([[2, 5], [2, 3]], jnp.int32)  # share page 2
    state = kvc.paged_admit(
        paged, reset,
        jnp.asarray([M, 0], jnp.int32), new_table,
        jnp.asarray([[6], [-1]], jnp.int32),  # hot restore from page 6
        jnp.asarray([3, -1], jnp.int32),  # COW: copy slot 1's page 3 ...
        jnp.asarray([5, -1], jnp.int32),  # ... into fresh page 5
    )
    assert int(state.lengths[0]) == M
    tv = kvc.as_tiered(state)
    np.testing.assert_array_equal(
        np.asarray(tv.hot_k[0]), np.asarray(tv.hot_k[1])
    )
    np.testing.assert_array_equal(
        np.asarray(tv.cold_k[0, : M - hot]),
        np.asarray(tv.cold_k[1, : M - hot]),
    )
    # slot 0 appends past the boundary into its COW copy; slot 1's page
    # must be untouched (copy-on-write, not aliasing)
    before = np.asarray(state.pool_k[3]).copy()
    kn = jnp.ones((b, g, d), jnp.float32)
    state = kvc.append_decode(
        state, kn, kn, active=jnp.asarray([True, False])
    )
    np.testing.assert_array_equal(np.asarray(state.pool_k[3]), before)
    row = (M - hot) % ps  # boundary row just written in slot 0's page 5
    np.testing.assert_array_equal(
        np.asarray(state.pool_k[5, row]), np.ones((g * d,), np.float32)
    )


@pytest.mark.kernel_parity
def test_default_page_size_is_decode_s_block():
    for rep, d, cap in [(4, 128, 544), (2, 64, 96), (8, 128, 4096)]:
        expect = ops.select_blocks(rep, d, cap, "pack2", kind="decode_attn")[2]
        assert ops.default_page_size(rep, d, cap) == expect


# ---------------------------------------------------------------------------
# host control plane: PagePool / PrefixCache invariants
# ---------------------------------------------------------------------------


def test_pagepool_refcount_lifecycle():
    pool = PagePool(4)
    a = pool.alloc(2)
    assert len(a) == 2 and pool.available() == 2 and pool.used() == 2
    pool.incref(a)  # second reader
    pool.decref(a)  # first reader leaves: pages still live
    assert pool.available() == 2
    assert all(pool.refs[p] == 1 for p in a)
    pool.decref(a)  # last reader leaves: freed exactly now
    assert pool.available() == 4
    assert all(pool.refs[p] == 0 for p in a)
    # over-alloc refuses rather than corrupting
    assert pool.alloc(5) is None
    # refcounts never go negative: double-free raises the typed error
    # (with page context), and keeps doing so under `python -O`
    b = pool.alloc(1)
    pool.decref(b)
    with pytest.raises(PagePoolError, match="decref on free page"):
        pool.decref(b)
    with pytest.raises(PagePoolError, match="incref on free page"):
        pool.incref(b)  # incref on a free page is a bug too
    try:
        pool.decref(b)
    except PagePoolError as e:
        assert e.page == b[0] and e.refcount == 0


def test_prefix_cache_match_insert_roundtrip():
    hc, ps = 4, 4
    pool = PagePool(16)
    tree = PrefixCache(pool, hot_cap=hc, page_size=ps)
    toks = np.arange(100, 115, dtype=np.int32)  # 15 tokens: hot 4 + 2 runs + 3
    slot_pages = pool.alloc(3)  # the serving slot's cold pages
    saved = []
    assert tree.match(toks).length == 0  # empty tree: miss
    assert tree.insert(toks, slot_pages, saved.extend)
    assert len(saved) == 1  # one hot-snapshot page (hc <= ps)
    # full re-match caps at len - 1 (the last token must be prefilled)
    m = tree.match(toks)
    assert m.length == hc + 2 * ps  # 12: hot + both full runs; tail stays
    assert m.shared_pages == (slot_pages[0], slot_pages[1])
    assert m.cow_src == -1
    # an extended prompt matches everything the tree holds
    ext = np.concatenate([toks[:12], np.asarray([7, 8, 9, 10], np.int32)])
    m2 = tree.match(ext)
    assert m2.length == 12 and m2.shared_pages == m.shared_pages
    # divergence inside the second run: COW on the partial boundary
    div = toks.copy()
    div[10] = 999
    m3 = tree.match(div)
    assert m3.length == hc + ps + 2  # hot + run 1 + 2 boundary rows
    assert m3.shared_pages == (slot_pages[0],)
    assert m3.cow_src == slot_pages[1] and m3.cow_len == 2
    # different hot prefix: miss (hot nodes are keyed by the full hc run)
    other = toks.copy()
    other[1] = 999
    assert tree.match(other).length == 0
    # adopted pages gained the tree as a second reader
    assert all(pool.refs[p] == 2 for p in slot_pages[:2])
    assert pool.refs[slot_pages[2]] == 1  # partial tail stays slot-private


def test_prefix_cache_insert_dedup_keeps_one_copy():
    hc, ps = 2, 2
    pool = PagePool(12)
    tree = PrefixCache(pool, hot_cap=hc, page_size=ps)
    toks = np.asarray([1, 2, 3, 4, 5, 6], np.int32)
    first = pool.alloc(2)
    assert tree.insert(toks, first, lambda ids: None)
    # a second slot that served the same prompt re-inserts: the tree
    # keeps its existing nodes and adopts nothing new
    second = pool.alloc(2)
    assert tree.insert(toks, second, lambda ids: None)
    assert all(pool.refs[p] == 2 for p in first)
    assert all(pool.refs[p] == 1 for p in second)  # slot-private only


def test_prefix_cache_eviction_is_leaf_only_lru_and_respects_readers():
    hc, ps = 2, 2
    pool = PagePool(8)
    tree = PrefixCache(pool, hot_cap=hc, page_size=ps)
    a = np.asarray([1, 2, 3, 4, 5, 6], np.int32)  # hot + 2 runs
    pa = pool.alloc(2)
    assert tree.insert(a, pa, lambda ids: None)
    # pool now: 2 slot pages (ref 2 via tree) + 1 hot page = free 5
    b = np.asarray([9, 9, 7, 7], np.int32)  # different hot prefix + 1 run
    pb = pool.alloc(1)
    assert tree.insert(b, pb, lambda ids: None)
    assert pool.available() == 3
    # a live slot still reads pa/pb (ref 2): eviction may only reclaim
    # the two hot-snapshot pages (ref 1, childless once leaves peel)
    assert not tree.evict_for(8)  # impossible: live readers pin 3 pages
    # drop slot a's refs: its chain (2 pages) becomes evictable leaf-first
    pool.decref(pa)
    tree.match(b)  # touch b: a's chain is now strictly older (LRU)
    assert tree.evict_for(6)
    assert pool.available() >= 6
    # b's pages survived — a slot still reads pb
    assert pool.refs[pb[0]] == 2


# ---------------------------------------------------------------------------
# serving end-to-end: shared prefixes, COW, ledger reconciliation, bugfixes
# ---------------------------------------------------------------------------


def _mk_reqs(reqs):
    return [Request(r.rid, r.tokens, r.max_new_tokens) for r in reqs]


def test_paged_serving_shared_prefix_end_to_end(setup, monkeypatch):
    """The acceptance scenario: N requests sharing one prompt prefix.
    Greedy tokens bit-exact vs the unshared-paged AND contiguous-chunked
    baselines; the prefix is stored physically once (refcount ledger);
    ``prefix_tokens_reused`` reconciles with the DR external-read delta;
    chunked admission still compiles exactly once with paging enabled."""
    cfg, params = setup
    monkeypatch.setattr(engine_mod, "PagePool", RecordingPool)
    hot, ml, ps = 4, 64, 8
    shared = _prompt(1, 21, cfg.vocab_size)
    reqs = [
        Request(i, np.concatenate([shared, _prompt(10 + i, 5, cfg.vocab_size)]), 6)
        for i in range(3)
    ]
    reqs.append(Request(3, _prompt(99, 7, cfg.vocab_size), 5))  # unrelated
    eng = Engine(cfg, params, hot_cap=hot, max_len=ml, prefill_chunk=4,
                 paged=True, page_size=ps, slots=1)
    fin = {f.rid: f for f in eng.serve(_mk_reqs(reqs), slots=1, sync_every=3)}
    assert set(fin) == {0, 1, 2, 3}
    # satellite: ONE chunk-dispatch compile and ONE admit compile with
    # paging enabled, regardless of the length/match mix
    assert eng._chunk_step_fn._cache_size() == 1
    assert eng._paged_admit_fn._cache_size() == 1
    # rid 0 populated the tree; 1 and 2 reused hot 4 + 2 full pages = 20
    # tokens of the 21-token shared prefix; rid 3 shares nothing
    assert fin[0].prefix_tokens_reused == 0
    assert fin[1].prefix_tokens_reused == 20
    assert fin[2].prefix_tokens_reused == 20
    assert fin[3].prefix_tokens_reused == 0
    pool, tree = eng._last_pool, eng._last_ptree
    # ONE physical copy: the shared cold pages were simultaneously read
    # by the tree and a live slot (peak refcount 2), never duplicated —
    # rid 1/2 allocated only their novel-suffix + budget pages
    tree_pages = set(tree.tree_pages())
    assert any(pool.peak[p] >= 2 for p in tree_pages)
    # every slot retired: tree is the only reader left, and every
    # non-tree page is back on the free list (freed exactly when its
    # last reader left — the never-negative half is asserted in decref)
    for p in range(pool.n_pages):
        if p in tree_pages:
            assert pool.refs[p] == 1
        else:
            assert pool.refs[p] == 0
    assert pool.available() == pool.n_pages - len(tree_pages)
    # tokens bit-exact vs paged-without-sharing and contiguous-chunked
    eng_n = Engine(cfg, params, hot_cap=hot, max_len=ml, prefill_chunk=4,
                   paged=True, page_size=ps, slots=1, prefix_sharing=False)
    fin_n = {f.rid: f for f in eng_n.serve(_mk_reqs(reqs), slots=1,
                                           sync_every=3)}
    eng_c = Engine(cfg, params, hot_cap=hot, max_len=ml, prefill_chunk=4,
                   slots=1)
    fin_c = {f.rid: f for f in eng_c.serve(_mk_reqs(reqs), slots=1,
                                           sync_every=3)}
    tb = eng._kv_token_bytes()
    for r in reqs:
        np.testing.assert_array_equal(fin[r.rid].tokens, fin_n[r.rid].tokens)
        np.testing.assert_array_equal(fin[r.rid].tokens, fin_c[r.rid].tokens)
        assert fin_n[r.rid].prefix_tokens_reused == 0
        # the external reads the shared run skipped reconcile exactly
        # with the reuse count through the closed-form resumed ledger
        M = fin[r.rid].prefix_tokens_reused
        full = kvc.prompt_traffic_tokens(r.prompt_len, hot)
        res = kvc.prompt_traffic_tokens_resumed(r.prompt_len, M, hot)
        for k in kvc.TRAFFIC_KEYS:
            assert (fin_n[r.rid].traffic[k] - fin[r.rid].traffic[k]
                    == (full[k] - res[k]) * tb), (r.rid, k)


def test_paged_serving_cow_divergent_prompts(setup):
    """Two prompts diverging inside a cold page: the second adopts the
    boundary page copy-on-write and still decodes bit-exactly."""
    cfg, params = setup
    hot, ml, ps = 4, 64, 8
    base = _prompt(2, 26, cfg.vocab_size)  # hot 4 + 2 full pages + tail
    div = base.copy()
    div[15] = (int(div[15]) + 1) % cfg.vocab_size  # diverge inside run 2
    reqs = [Request(0, base, 6), Request(1, div, 6)]
    eng = Engine(cfg, params, hot_cap=hot, max_len=ml, prefill_chunk=4,
                 paged=True, page_size=ps, slots=1)
    fin = {f.rid: f for f in eng.serve(_mk_reqs(reqs), slots=1)}
    # matched: hot 4 + full run [4:12) + 3 boundary rows of run [12:20)
    assert fin[0].prefix_tokens_reused == 0
    assert fin[1].prefix_tokens_reused == hot + ps + 3
    for r in reqs:
        solo = eng.serve([Request(9, r.tokens, r.max_new_tokens)], slots=1)[0]
        np.testing.assert_array_equal(fin[r.rid].tokens, solo.tokens)


def test_paged_matches_grouped_admission_tokens(setup):
    """Paged chunked serving == the legacy grouped-admission engine."""
    cfg, params = setup
    reqs = [
        Request(0, _prompt(40, 5, cfg.vocab_size), 9),
        Request(1, _prompt(41, 12, cfg.vocab_size), 3),
        Request(2, _prompt(42, 1, cfg.vocab_size), 5),
    ]
    eng_p = Engine(cfg, params, hot_cap=4, max_len=64, prefill_chunk=4,
                   paged=True, page_size=8)
    fin_p = {f.rid: f for f in eng_p.serve(_mk_reqs(reqs), slots=2,
                                           sync_every=3)}
    eng_g = Engine(cfg, params, hot_cap=4, max_len=64)
    fin_g = {f.rid: f for f in eng_g.serve(_mk_reqs(reqs), slots=2,
                                           sync_every=3)}
    for r in reqs:
        np.testing.assert_array_equal(fin_p[r.rid].tokens, fin_g[r.rid].tokens)
        assert len(fin_p[r.rid].tokens) == r.max_new_tokens


def test_paged_engine_validates_construction(setup):
    cfg, params = setup
    with pytest.raises(ValueError, match="chunked prefill"):
        Engine(cfg, params, hot_cap=4, max_len=64, paged=True)
    with pytest.raises(ValueError, match="cold tier"):
        Engine(cfg, params, hot_cap=64, max_len=64, prefill_chunk=4,
               paged=True)


# ---------------------------------------------------------------------------
# serving-path bugfix sweep (satellites)
# ---------------------------------------------------------------------------


def test_decode_interleaves_with_long_prompt_streaming(setup):
    """Regression (chunked admission stall): while a long prompt streams
    in, already-active slots must keep emitting — chunk waves and decode
    dispatches interleave instead of the old drain-everything loop."""
    cfg, params = setup
    eng = Engine(cfg, params, hot_cap=4, max_len=64, prefill_chunk=2)
    events = []
    real_chunk = eng._get_chunk_step()
    real_step = eng._get_step(eng.max_len, None)
    eng._chunk_step_fn = lambda *a, **k: (
        events.append("chunk"), real_chunk(*a, **k))[1]
    eng._step_fns[(eng.max_len, None)] = lambda *a, **k: (
        events.append("decode"), real_step(*a, **k))[1]
    reqs = [
        Request(0, _prompt(50, 3, cfg.vocab_size), 12),  # short, decodes early
        Request(1, _prompt(51, 24, cfg.vocab_size), 2),  # 12 chunk waves
    ]
    fin = {f.rid: f for f in eng.serve(_mk_reqs(reqs), slots=2, sync_every=2)}
    assert len(fin[0].tokens) == 12 and len(fin[1].tokens) == 2
    # decode dispatches happen BEFORE the long prompt finishes streaming
    assert "decode" in events
    first_decode = events.index("decode")
    last_chunk = len(events) - 1 - events[::-1].index("chunk")
    assert first_decode < last_chunk, events
    # and the interleaved run is still bit-exact vs solo serves
    for r in reqs:
        solo = eng.serve([Request(9, r.tokens, r.max_new_tokens)], slots=1)[0]
        np.testing.assert_array_equal(fin[r.rid].tokens, solo.tokens)


def test_generate_pads_with_sentinel_not_stop_token(setup):
    """Regression: rows that stop early are padded with PAD_TOKEN, never
    the stop token itself — a stop token the model actually emitted
    remains distinguishable from padding, and per-row step counts are
    exposed."""
    cfg, params = setup
    eng = Engine(cfg, params, hot_cap=4, max_len=64)
    prompts = jnp.stack([
        jnp.asarray(_prompt(60, 6, cfg.vocab_size)),
        jnp.asarray(_prompt(61, 6, cfg.vocab_size)),
    ])
    probe = eng.generate(prompts, max_new_tokens=12)
    # stop at row 0's third greedy token: row 0 retires after 2 emits
    stop = int(probe.tokens[0, 2])
    res = eng.generate(prompts, max_new_tokens=12, stop_token=stop)
    toks = np.asarray(res.tokens)
    assert res.steps_per_row is not None
    n0 = res.steps_per_row[0]
    assert n0 <= 2
    # emitted region survives the round trip; padding is the sentinel
    np.testing.assert_array_equal(toks[0, :n0], np.asarray(probe.tokens)[0, :n0])
    assert (toks[0, n0:] == PAD_TOKEN).all()
    assert stop not in toks[0, n0:]
    # an un-stopped row is full length and unpadded
    if res.steps_per_row[1] == 12:
        assert (toks[1] != PAD_TOKEN).all()
    assert res.steps == max(res.steps_per_row)


def test_empty_prompt_rejected_at_validation(setup):
    cfg, params = setup
    empty = Request(0, np.zeros((0,), np.int32), 4)
    for kw in (dict(), dict(prefill_chunk=4),
               dict(prefill_chunk=4, paged=True, page_size=8)):
        eng = Engine(cfg, params, hot_cap=4, max_len=64, **kw)
        with pytest.raises(ValueError, match="empty prompt"):
            eng.serve([Request(0, empty.tokens, 4)], slots=1)


# ---------------------------------------------------------------------------
# decode step over the layer-stacked cache: the kernel reads layer l in
# place, the append writes one row per slot into the carried stack
# ---------------------------------------------------------------------------


def _decode_run(cfg, params, cache, tokens, active):
    step = jax.jit(lambda c, t: T.decode_step(params, cfg, t, c, mode="packed",
                                              active=active))
    logits = []
    for t in tokens:
        lg, cache = step(cache, t)
        logits.append(np.asarray(lg))
    return np.stack(logits), cache


@pytest.mark.kernel_parity
@pytest.mark.parametrize("paged", [True, False], ids=["paged", "contiguous"])
def test_decode_step_stacked_cache_parity(paged):
    """40 decode steps of a 3-layer dense model: the Pallas impl
    (interpret mode; flash-decode reads each layer of the stacked cache
    in place) against the XLA impl (rotate, append, read a copy of the
    layer). Slot 0 starts empty and crosses the hot -> cold boundary and
    a page boundary; slots 1 and 2 read one shared prefix page and append
    into private pages; slot 3 is inactive throughout. Logits agree to
    the fused-decode parity tolerance; the final caches hold the same
    rows and lengths, and the shared page and the inactive slot are
    untouched."""
    import dataclasses

    base = get_smoke_config("falcon3-1b")
    cfgs = {impl: dataclasses.replace(
        base, n_layers=3, bitnet=dataclasses.replace(base.bitnet, impl=impl))
        for impl in ("pallas", "xla")}
    params = pack_lib.pack_params(
        T.init_params(jax.random.PRNGKey(0), cfgs["xla"]), cfgs["xla"])
    b, hot, ps, max_len, n_steps = 4, 4, 8, 64, 40
    gd = base.n_kv_heads * base.resolved_head_dim
    cache = T.init_decode_cache(cfgs["xla"], b, max_len, hot, jnp.float32,
                                paged=paged, page_size=ps, n_pages=33)["attn"]
    key = jax.random.PRNGKey(1)
    n_layers = cache.lengths.shape[0]
    prefix = hot + ps  # slots 1 and 2: hot tier + one full shared page
    lengths = jnp.asarray([0, prefix, prefix, 5], jnp.int32)
    rows = jax.random.normal(key, (n_layers, hot + ps, gd), jnp.float32)
    hot_k = cache.hot_k.at[:, 1:3].set(rows[:, None, :hot])
    if paged:
        shared = 32  # past every slot's private pages (4 x 8)
        table = cache.page_table.at[:, 2, 0].set(shared)  # slot 2 -> slot 1's
        table = table.at[:, 1, 0].set(shared)
        pool_k = cache.pool_k.at[:, shared].set(rows[:, hot:])
        cache = cache._replace(hot_k=hot_k, hot_v=hot_k, pool_k=pool_k,
                               pool_v=pool_k, page_table=table)
    else:
        cold_k = cache.cold_k.at[:, 1:3, :ps].set(rows[:, None, hot:])
        cache = cache._replace(hot_k=hot_k, hot_v=hot_k, cold_k=cold_k,
                               cold_v=cold_k)
    cache = {"attn": cache._replace(
        lengths=jnp.broadcast_to(lengths, cache.lengths.shape))}
    active = jnp.asarray([True, True, True, False])
    tokens = jax.random.randint(jax.random.PRNGKey(2), (n_steps, b), 0,
                                base.vocab_size)
    out = {impl: _decode_run(cfgs[impl], params, cache, tokens, active)
           for impl in ("pallas", "xla")}
    (lp, cp), (lx, cx) = out["pallas"], out["xla"]
    np.testing.assert_allclose(lp[:, :3], lx[:, :3], rtol=5e-5, atol=5e-5)
    cp, cx = cp["attn"], cx["attn"]
    want = np.broadcast_to(np.asarray(lengths) + n_steps * np.asarray(active),
                           (n_layers, b))
    np.testing.assert_array_equal(np.asarray(cp.lengths), want)
    np.testing.assert_array_equal(np.asarray(cx.lengths), want)
    for name in cp._fields:
        np.testing.assert_allclose(np.asarray(getattr(cp, name)),
                                   np.asarray(getattr(cx, name)),
                                   rtol=1e-5, atol=1e-5, err_msg=name)
    before = cache["attn"]
    if paged:
        np.testing.assert_array_equal(np.asarray(cp.pool_k[:, shared]),
                                      np.asarray(before.pool_k[:, shared]))
    np.testing.assert_array_equal(np.asarray(cp.hot_k[:, 3]),
                                  np.asarray(before.hot_k[:, 3]))


@pytest.mark.kernel_parity
@pytest.mark.parametrize("paged", [True, False], ids=["paged", "contiguous"])
def test_chunk_step_stacked_cache_parity(paged):
    """Six chunked-prefill steps of a 3-layer dense model: the Pallas
    impl (interpret mode; flash-prefill reads each layer of the stacked
    cache in place, the append scatters the chunk into it) against the
    XLA impl. Slot 0 prefills from empty across the hot -> cold boundary
    and two page boundaries, slot 1 continues a prompt mid-page with
    short chunks, slot 2 idles (n_valid 0). Logits of the prefilling
    slots agree to the fused-decode parity tolerance; the final caches
    hold the same rows and lengths, and the idle slot is untouched."""
    import dataclasses

    base = get_smoke_config("falcon3-1b")
    cfgs = {impl: dataclasses.replace(
        base, n_layers=3, bitnet=dataclasses.replace(base.bitnet, impl=impl))
        for impl in ("pallas", "xla")}
    params = pack_lib.pack_params(
        T.init_params(jax.random.PRNGKey(0), cfgs["xla"]), cfgs["xla"])
    b, hot, ps, max_len, c = 3, 4, 8, 64, 6
    cache = T.init_decode_cache(cfgs["xla"], b, max_len, hot, jnp.float32,
                                paged=paged, page_size=ps, n_pages=24)
    n_valid = [jnp.asarray(v, jnp.int32) for v in
               ([6, 3, 0], [6, 5, 0], [6, 0, 0], [2, 6, 0], [6, 1, 0],
                [5, 6, 0])]
    tokens = jax.random.randint(jax.random.PRNGKey(2), (len(n_valid), b, c),
                                0, base.vocab_size)
    out = {}
    for impl in ("pallas", "xla"):
        step = jax.jit(lambda cc, t, n, cfg=cfgs[impl]: T.prefill_chunk_step(
            params, cfg, t, cc, n, mode="packed"))
        cc, logits = cache, []
        for t, n in zip(tokens, n_valid):
            lg, cc = step(cc, t, n)
            logits.append(np.asarray(lg))
        out[impl] = (np.stack(logits), cc["attn"])
    (lp, cp), (lx, cx) = out["pallas"], out["xla"]
    np.testing.assert_allclose(lp[:, :2], lx[:, :2], rtol=5e-5, atol=5e-5)
    want = sum(np.asarray(n) for n in n_valid)
    n_layers = cp.lengths.shape[0]
    np.testing.assert_array_equal(np.asarray(cp.lengths),
                                  np.broadcast_to(want, (n_layers, b)))
    np.testing.assert_array_equal(np.asarray(cx.lengths),
                                  np.broadcast_to(want, (n_layers, b)))
    for name in cp._fields:
        np.testing.assert_allclose(np.asarray(getattr(cp, name)),
                                   np.asarray(getattr(cx, name)),
                                   rtol=1e-5, atol=1e-5, err_msg=name)
    np.testing.assert_array_equal(np.asarray(cp.hot_k[:, 2]),
                                  np.asarray(cache["attn"].hot_k[:, 2]))
