"""Unit tests for the tiered KV cache (``serving/kv_tiers.py`` +
``PagedKVCache`` tier plumbing) — no model, no engine.

Covers the page state machine (live -> parked -> host -> persisted, with
revive and prefetch back), the reclaim cascade over prefix-index
descendants, content-key stability across spill/reload and process
restarts, byte-exactness of a spilled/reloaded page, and the quantized
pool's admission-capacity win at equal device bytes.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.storage import ArtifactStore
from repro.serving import KVTierManager, PagedKVCache
from repro.serving.kv_tiers import chain_key


def _cache(tiers=None, **kw):
    args = dict(num_layers=2, num_kv_heads=2, head_dim=4, dtype=jnp.float32,
                max_slots=3, max_context=64, page_size=8, tiers=tiers)
    args.update(kw)
    return PagedKVCache(**args)


def _fill(cache, pages, seed=0):
    """Write recognizable per-page values into every pool array."""
    rng = np.random.default_rng(seed)
    for key, arr in cache.pages.items():
        host = np.array(arr)
        for p in pages:
            host[:, p] = rng.normal(size=host[:, p].shape).astype(host.dtype)
        cache.pages[key] = jnp.asarray(host)


# ---------------------------------------------------------------------------
# chain keys
# ---------------------------------------------------------------------------


def test_chain_key_names_whole_prefix():
    a = chain_key(b"", range(8))
    b = chain_key(b"", range(8))
    assert a == b and len(a) == 32
    assert chain_key(a, range(8, 16)) != chain_key(b"", range(8, 16))
    assert chain_key(b"", [1, 2]) != chain_key(b"", [2, 1])


# ---------------------------------------------------------------------------
# park / revive / reclaim
# ---------------------------------------------------------------------------


def test_release_parks_prefix_pages_and_rerun_revives():
    tiers = KVTierManager()
    cache = _cache(tiers)
    toks = list(range(100, 125))  # 25 tokens: 3 full pages + tail
    slot, _ = cache.admit(len(toks), toks)
    cache.register_prefix(slot, toks, len(toks))
    pages = list(cache._slot_pages[slot])
    avail = cache.pool.available

    cache.release(slot)
    # 3 indexed pages parked (refcount 0, off the free list), tail freed
    assert set(tiers.parked) == set(pages[:3])
    assert cache.pool.available == avail + 1
    assert all(cache.pool.refcounts[p] == 0 for p in pages[:3])

    # rerun of the same prompt revives the parked pages in place
    slot2, cached = cache.admit(len(toks), toks)
    assert cached == 24
    assert cache._slot_pages[slot2][:3] == pages[:3]
    assert not tiers.parked
    assert tiers.counters["device_hits"] == 3
    assert all(cache.pool.refcounts[p] == 1 for p in pages[:3])


def test_reclaim_under_pressure_cascades_descendants():
    """Allocation pressure reclaims parked pages LRU-first, and reclaiming
    a chain parent always takes its index descendants with it — a surviving
    child entry would dangle behind a recycled parent page id."""
    tiers = KVTierManager()
    # 7 usable pages
    cache = _cache(tiers, num_pages=8, max_slots=2)
    toks = list(range(200, 225))  # 3 full pages + tail
    slot, _ = cache.admit(len(toks), toks)
    cache.register_prefix(slot, toks, len(toks))
    chain = list(cache._slot_pages[slot][:3])
    cache.release(slot)
    assert len(tiers.parked) == 3 and cache.pool.available == 4

    # a 6-page admission cannot be served from the free list alone: the
    # LRU parked page is the chain ROOT, so the whole chain is reclaimed
    slot2, cached = cache.admit(41, list(range(300, 341)))
    assert cached == 0
    assert not tiers.parked
    assert tiers.counters["reclaimed_pages"] == 3
    assert not cache._prefix_index  # no dangling child entries
    assert all(p not in cache._page_ck for p in chain)
    cache.release(slot2)


def test_admission_protects_its_own_matched_prefix():
    """can_admit must never reclaim the parked pages the admission itself
    just matched (reclaim racing its own hit)."""
    tiers = KVTierManager()
    cache = _cache(tiers, num_pages=8, max_slots=2)
    toks = list(range(10, 35))  # 3 full pages + tail
    slot, _ = cache.admit(len(toks), toks)
    cache.register_prefix(slot, toks, len(toks))
    chain = list(cache._slot_pages[slot][:3])
    cache.release(slot)

    # same prompt, longer context: needs 3 matched + 3 fresh = free list
    # holds 4, so no reclaim needed; matched pages must survive and revive
    assert cache.can_admit(41, toks + list(range(500, 517)))
    slot2, cached = cache.admit(41, toks + list(range(500, 517)))
    assert cached == 24 and cache._slot_pages[slot2][:3] == chain
    cache.release(slot2)


# ---------------------------------------------------------------------------
# host spill + prefetch
# ---------------------------------------------------------------------------


def test_spill_to_host_and_prefetch_restores_bytes():
    """A parked page reclaimed into the host tier and prefetched back on a
    prefix hit restores the exact device bytes (all pool arrays)."""
    tiers = KVTierManager(host_pages=8)
    cache = _cache(tiers, num_pages=8, max_slots=2)
    toks = list(range(50, 75))
    slot, _ = cache.admit(len(toks), toks)
    cache.register_prefix(slot, toks, len(toks))
    chain = list(cache._slot_pages[slot][:3])
    _fill(cache, chain, seed=3)
    want = {p: cache._read_page(p) for p in chain}
    cache.release(slot)

    # pressure: spills the chain to host RAM, frees the device pages
    slot2, _ = cache.admit(41, list(range(300, 341)))
    assert tiers.counters["spilled_pages"] == 3
    assert tiers.host_count == 3
    cache.release(slot2)

    # rerun: can_admit prefetches the chain back (pending), a step later
    # the pages are matchable and the admission maps them
    assert not cache.can_admit(len(toks), toks)  # prefetch dispatched, wait
    assert tiers.counters["host_hits"] == 3
    assert tiers.counters["prefetched_pages"] == 3
    assert len(tiers.pending) == 3
    assert cache.match_prefix(toks)[1] == 0  # pending pages stay invisible
    cache.tick_tiers()
    assert cache.can_admit(len(toks), toks)
    slot3, cached = cache.admit(len(toks), toks)
    assert cached == 24
    for i, p in enumerate(cache._slot_pages[slot3][:3]):
        got = cache._read_page(p)
        for key in want[chain[i]]:
            np.testing.assert_array_equal(got[key], want[chain[i]][key])
    # a host hit promotes: the entries left the host LRU
    assert tiers.host_count == 0


def test_host_tier_lru_eviction_caps_entries():
    tiers = KVTierManager(host_pages=2)
    arrays = lambda i: {"k": np.full((2, 8), i, np.float32)}
    for i in range(4):
        tiers.spill(bytes([i]) * 32, arrays(i))
    assert tiers.host_count == 2
    assert set(tiers.host) == {bytes([2]) * 32, bytes([3]) * 32}  # LRU evicted
    assert tiers.counters["spilled_pages"] == 4


def test_flush_tiers_parks_nothing_spills_everything():
    tiers = KVTierManager(host_pages=8)
    cache = _cache(tiers)
    toks = list(range(80, 105))
    slot, _ = cache.admit(len(toks), toks)
    cache.register_prefix(slot, toks, len(toks))
    cache.release(slot)
    assert len(tiers.parked) == 3
    freed = cache.flush_tiers()
    assert freed == 3 and not tiers.parked
    assert tiers.host_count == 3
    assert cache.pool.available == cache.num_pages - 1


# ---------------------------------------------------------------------------
# persisted tier (ArtifactStore write-through, restart re-attach)
# ---------------------------------------------------------------------------


def test_persisted_prefix_survives_restart(tmp_path):
    """Spill with a store attached writes through to the ArtifactStore; a
    FRESH cache + tier manager over the same store directory resolves the
    prefix by content key and restores identical bytes."""
    store = ArtifactStore(tmp_path / "kv")
    tiers = KVTierManager(store=store)
    cache = _cache(tiers)
    toks = list(range(60, 85))
    slot, _ = cache.admit(len(toks), toks)
    cache.register_prefix(slot, toks, len(toks))
    chain = list(cache._slot_pages[slot][:3])
    _fill(cache, chain, seed=7)
    want = [cache._read_page(p) for p in chain]
    cache.release(slot)
    assert cache.flush_tiers() == 3
    assert tiers.persisted_count == 3

    # "restart": new process = new store handle, new manager, empty cache
    tiers2 = KVTierManager(store=ArtifactStore(tmp_path / "kv"))
    assert tiers2.persisted_count == 3  # index re-loaded from disk
    cache2 = _cache(tiers2)
    assert not cache2.can_admit(len(toks), toks)  # prefetch from the store
    assert tiers2.counters["persist_hits"] == 3
    cache2.tick_tiers()
    slot2, cached = cache2.admit(len(toks), toks)
    assert cached == 24
    for i, p in enumerate(cache2._slot_pages[slot2][:3]):
        got = cache2._read_page(p)
        for key in want[i]:
            np.testing.assert_array_equal(got[key], want[i][key])


def test_prefetch_never_starves_its_admission(tmp_path):
    """Prefetch stops while the free pool can still cover the rest of the
    prompt — reloading a long spilled prefix must not consume the pages the
    admission itself needs."""
    store = ArtifactStore(tmp_path / "kv")
    tiers = KVTierManager(store=store)
    cache = _cache(tiers, num_pages=8, max_slots=2)  # 7 usable pages
    toks = list(range(150, 190))  # 40 tokens: exactly 5 full pages
    slot, _ = cache.admit(len(toks), toks)
    cache.register_prefix(slot, toks, len(toks))
    cache.release(slot)
    assert cache.flush_tiers() == 5

    cache.can_admit(len(toks), toks)
    # 5 pages needed in total; the budget rule is the invariant, not the
    # count: after prefetch the pool must still cover the unprefetched
    # remainder of the prompt
    total = 5
    prefetched = tiers.counters["prefetched_pages"]
    assert cache.pool.available >= total - prefetched
    cache.tick_tiers()
    slot2, cached = cache.admit(len(toks), toks)
    assert cached == prefetched * cache.page_size
    cache.release(slot2)


# ---------------------------------------------------------------------------
# quantized pages: capacity at equal pool bytes
# ---------------------------------------------------------------------------


def test_int8_pages_double_admission_at_equal_pool_bytes():
    """Acceptance: at (approximately) equal device pool bytes, an int8 pool
    admits >= 2x the concurrent sequences of an fp32 pool."""
    def build(quant, budget_bytes):
        probe = PagedKVCache(
            num_layers=2, num_kv_heads=2, head_dim=8, dtype=jnp.float32,
            max_slots=64, max_context=64, page_size=8, num_pages=2,
            quant=quant,
        )
        num_pages = max(2, budget_bytes // probe.page_nbytes + 1)
        return PagedKVCache(
            num_layers=2, num_kv_heads=2, head_dim=8, dtype=jnp.float32,
            max_slots=64, max_context=64, page_size=8, num_pages=num_pages,
            quant=quant,
        )

    budget = 1 << 18  # 256 KiB of pool
    admitted = {}
    for quant in ("none", "int8"):
        cache = build(quant, budget)
        n = 0
        while cache.free_slot_count and cache.can_admit(32):
            cache.admit(32)  # 4 pages each
            n += 1
        admitted[quant] = n
    assert admitted["int8"] >= 2 * admitted["none"], admitted


def test_quantized_pool_array_shapes_and_page_bytes():
    fp = _cache()
    q = _cache(quant="int8")
    assert set(q.pages) == {"kv", "kv_scale"}
    assert q.pages["kv"].dtype == jnp.int8
    # one f32 scale per (layer, page, position, kv head) for K and for V
    assert q.pages["kv_scale"].shape == q.pages["kv"].shape[:-1] + (
        q.pages["kv"].shape[-1] // q.head_dim,)
    # int8 + f32 scales must beat fp32 by >= 2x per page for head_dim >= 8
    assert fp.page_nbytes >= 2 * q.page_nbytes


def test_quantized_write_prefill_roundtrip_within_bound():
    """Dense prefill scattered into an int8 pool dequantizes back within
    the documented per-element bound (absmax/127/2 per (pos, head) row)."""
    from repro.serving.kv_cache import write_prefill_pages

    rng = np.random.default_rng(11)
    cache = _cache(quant="int8")
    plen = 20
    slot, _ = cache.admit(plen)
    k = rng.normal(size=(2, plen, 2, 4)).astype(np.float32)
    v = rng.normal(size=(2, plen, 2, 4)).astype(np.float32)
    cache.swap_pages(write_prefill_pages(
        dict(cache.pages), jnp.asarray(k), jnp.asarray(v),
        cache.device_row(slot), jnp.asarray(plen, jnp.int32),
    ))
    got_k, got_v = cache.gather_dense(slot)
    for got, ref_arr in ((got_k, k), (got_v, v)):
        bound = np.abs(ref_arr).max(axis=-1, keepdims=True) / 127.0 / 2 + 1e-6
        assert (np.abs(got - ref_arr) <= bound).all()


def test_parked_page_survives_quantized_spill_reload_exactly():
    """int8 pool: spill + prefetch restores the quantized bytes AND scales
    bit-exactly (no requantization drift across tier moves)."""
    tiers = KVTierManager(host_pages=8)
    cache = _cache(tiers, quant="int8", num_pages=8, max_slots=2)
    toks = list(range(70, 95))
    slot, _ = cache.admit(len(toks), toks)
    cache.register_prefix(slot, toks, len(toks))
    chain = list(cache._slot_pages[slot][:3])
    _fill(cache, chain, seed=13)
    want = {p: cache._read_page(p) for p in chain}
    cache.release(slot)
    slot2, _ = cache.admit(41, list(range(300, 341)))  # forces spill
    cache.release(slot2)
    assert not cache.can_admit(len(toks), toks)
    cache.tick_tiers()
    slot3, cached = cache.admit(len(toks), toks)
    assert cached == 24
    for i, p in enumerate(cache._slot_pages[slot3][:3]):
        got = cache._read_page(p)
        for key, arr in want[chain[i]].items():
            np.testing.assert_array_equal(got[key], arr)


# ---------------------------------------------------------------------------
# tier manager edge cases
# ---------------------------------------------------------------------------


def test_pop_lru_skips_protected_and_pending():
    t = KVTierManager()
    for p in (3, 5, 7):
        t.park(p, bytes([p]) * 32)
    t.pending.add(3)
    assert t.pop_lru({5}) == (7, bytes([7]) * 32)
    assert t.pop_lru({5}) is None  # 3 pending, 5 protected
    t.tick()
    assert t.pop_lru({5}) == (3, bytes([3]) * 32)


def test_no_spill_targets_means_reclaim_drops_bytes():
    """Device-park-only config (host_pages=0, no store): reclaim frees the
    page without reading it back — wants_spill gates the device read."""
    tiers = KVTierManager()
    assert not tiers.wants_spill
    cache = _cache(tiers)
    toks = list(range(40, 65))
    slot, _ = cache.admit(len(toks), toks)
    cache.register_prefix(slot, toks, len(toks))
    cache.release(slot)
    assert cache.flush_tiers() == 3
    assert tiers.counters["spilled_pages"] == 0
    assert tiers.host_count == 0 and tiers.persisted_count == 0
    # the prefix is simply gone: next query is a clean miss
    assert cache.match_prefix(toks)[1] == 0
