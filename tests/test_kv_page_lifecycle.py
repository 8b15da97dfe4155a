"""KV page lifecycle: PagePool refcount / copy-on-write / truncate / free
interactions, and the PagedKVCache device side of CoW (llm/kv_cache.py).

These are the invariants the radix prefix cache (llm/prefix_cache.py) leans
on: a page is recycled exactly when its LAST reference (slot or cache)
drops, a slot never writes into a page someone else still references, and
rollback (truncate) never strands or double-frees shared pages.
"""

import numpy as np
import pytest
from conftest import fill_pages

from clearml_serving_tpu.llm.kv_cache import PagedKVCache, PagePool
from clearml_serving_tpu.llm.kv_sanitizer import (
    KVSanitizer,
    KVSanitizerError,
    enabled as sanitizer_enabled,
)
from clearml_serving_tpu.llm.prefix_cache import RadixPrefixCache


@pytest.fixture(autouse=True)
def armed_sanitizer(monkeypatch):
    """Paged-engine construction in this suite (and any engine built through
    it) runs with the runtime sanitizer armed."""
    monkeypatch.setenv("TPUSERVE_SANITIZE", "1")
    assert sanitizer_enabled()


def _pool(num_pages=16, page_size=4, max_slots=4):
    return PagePool(num_pages=num_pages, page_size=page_size, max_slots=max_slots)


# -- refcount basics ----------------------------------------------------------


def test_allocate_free_roundtrip():
    pool = _pool()
    pages = pool.allocate(0, 10)  # 3 pages
    assert len(pages) == 3
    assert all(pool.page_refcount(p) == 1 for p in pages)
    assert pool.free_pages == 15 - 3
    pool.free(0)
    assert pool.free_pages == 15
    assert all(pool.page_refcount(p) == 0 for p in pages)


def test_truncate_returns_only_unshared_surplus():
    pool = _pool()
    pool.allocate(0, 16)  # 4 pages
    pages = pool.slot_pages(0)
    pool.ref_pages(pages[3:])  # cache holds the last page
    pool.truncate(0, 5)        # keep 2 pages, surplus = pages[2:]
    assert pool.slot_pages(0) == pages[:2]
    assert pool.page_refcount(pages[2]) == 0   # unshared -> freed
    assert pool.page_refcount(pages[3]) == 1   # cache ref keeps it
    assert pool.slot_length(0) == 5
    # the shared surplus page is NOT in the free list
    assert pool.free_pages == 15 - 2 - 1


def test_truncate_past_length_raises():
    pool = _pool()
    pool.allocate(0, 4)
    with pytest.raises(ValueError):
        pool.truncate(0, 5)


def test_extend_after_truncate_reuses_tail_page():
    pool = _pool()
    pool.allocate(0, 8)
    pool.truncate(0, 5)
    new = pool.extend(0, 1)  # token 5 fits the kept tail page
    assert new == []
    new = pool.extend(0, 3)  # tokens 6,7,8 -> one new page
    assert len(new) == 1


def test_ref_unref_errors():
    pool = _pool()
    with pytest.raises(RuntimeError):
        pool.ref_pages([3])  # never allocated
    pages = pool.allocate(0, 4)
    pool.ref_pages(pages)
    assert pool.unref_pages(pages) == 0  # slot still holds them
    pool.free(0)
    assert pool.page_refcount(pages[0]) == 0


# -- sharing / map_shared -----------------------------------------------------


def test_map_shared_zero_copy_mapping():
    pool = _pool()
    pool.allocate(0, 8)
    shared = pool.slot_pages(0)
    pool.ref_pages(shared)   # cache stores them
    pool.free(0)             # original slot finishes
    assert all(pool.page_refcount(p) == 1 for p in shared)
    pool.map_shared(1, shared, 8)
    assert pool.slot_pages(1) == shared
    assert all(pool.page_refcount(p) == 2 for p in shared)
    assert pool.slot_length(1) == 8
    # both release: pages recycle exactly once
    pool.free(1)
    assert pool.unref_pages(shared) == len(shared)
    assert pool.free_pages == 15


def test_map_shared_requires_alignment_and_empty_slot():
    pool = _pool()
    pool.allocate(0, 8)
    shared = pool.slot_pages(0)
    with pytest.raises(ValueError):
        pool.map_shared(1, shared, 7)  # not page-aligned
    pool.allocate(1, 2)
    with pytest.raises(RuntimeError):
        pool.map_shared(1, shared, 8)  # slot not empty


# -- copy-on-write ------------------------------------------------------------


def test_extend_into_shared_tail_page_cows():
    pool = _pool()
    pool.allocate(0, 6)  # 2 pages; tail page half full
    pages = pool.slot_pages(0)
    pool.ref_pages([pages[1]])  # someone else references the tail page
    new = pool.extend(0, 1)     # write position 6 is INSIDE the shared page
    assert pool.cow_events == 1
    swapped = pool.slot_pages(0)
    assert swapped[0] == pages[0]
    assert swapped[1] != pages[1]          # private replacement
    assert pool.page_refcount(pages[1]) == 1   # only the external ref left
    assert pool.page_refcount(swapped[1]) == 1
    assert pool.drain_pending_cow() == [(pages[1], swapped[1])]
    assert new == []  # token 6 fit the (replacement) tail page


def test_extend_page_aligned_never_cows():
    pool = _pool()
    pool.allocate(0, 8)  # exactly 2 full pages
    pages = pool.slot_pages(0)
    pool.ref_pages(pages)  # everything shared
    new = pool.extend(0, 1)  # next write starts a FRESH page
    assert pool.cow_events == 0
    assert len(new) == 1


def test_cow_exhaustion_raises_memory_error():
    pool = PagePool(num_pages=3, page_size=4, max_slots=2)  # 2 usable
    pool.allocate(0, 6)  # both pages
    pool.ref_pages([pool.slot_pages(0)[1]])
    with pytest.raises(MemoryError):
        pool.extend(0, 1)  # CoW needs a free page; none left


def test_paged_kv_cache_cow_copies_device_page():
    """The device side: after a CoW swap, apply_pending_cow duplicates the
    page contents so the slot's history is intact in its private copy."""
    cache = PagedKVCache(
        n_layers=1, n_kv_heads=1, head_dim=2,
        num_pages=8, page_size=4, max_slots=2, dtype="float32",
    )
    pool = cache.pool
    # write a 6-token prompt (2 pages, tail half full)
    k = np.arange(6 * 2, dtype=np.float32).reshape(1, 6, 1, 2)
    fill_pages(cache, 0, k, k * 10.0)
    pages = pool.slot_pages(0)
    pool.ref_pages([pages[1]])            # share the tail page
    pool.extend(0, 1)
    assert pool.cow_events == 1
    copied = cache.apply_pending_cow()
    assert copied == 1
    new_tail = pool.slot_pages(0)[1]
    np.testing.assert_array_equal(
        np.asarray(cache.k[0, 0, new_tail]), np.asarray(cache.k[0, 0, pages[1]])
    )
    np.testing.assert_array_equal(
        np.asarray(cache.v[0, 0, new_tail]), np.asarray(cache.v[0, 0, pages[1]])
    )


# -- int8 pools: a page and its scale rows share one lifecycle ----------------


def _int8_cache(**kw):
    kw.setdefault("n_layers", 1)
    kw.setdefault("n_kv_heads", 1)
    kw.setdefault("head_dim", 2)
    kw.setdefault("num_pages", 8)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_slots", 2)
    return PagedKVCache(dtype="float32", kv_quant="int8", **kw)


def test_int8_cow_copies_scale_rows_with_the_page():
    """Copy-on-write on int8 pools must duplicate the page's scale rows in
    the same batch as its data — a private copy dequantizing with the old
    shared page's scales would corrupt every token in it."""
    cache = _int8_cache()
    pool = cache.pool
    k = np.clip(np.arange(6 * 2, dtype=np.float32), 0, 126).reshape(1, 6, 1, 2)
    k_q = k.astype(np.int8)
    k_s = (0.25 + np.arange(6, dtype=np.float32)).reshape(1, 6, 1)
    fill_pages(cache, 0, k_q, k_q, k_s, k_s * 2.0)
    pages = pool.slot_pages(0)
    pool.ref_pages([pages[1]])            # share the tail page
    pool.extend(0, 1)
    assert pool.cow_events == 1
    assert cache.apply_pending_cow() == 1
    new_tail = pool.slot_pages(0)[1]
    np.testing.assert_array_equal(
        np.asarray(cache.k[0, 0, new_tail]), np.asarray(cache.k[0, 0, pages[1]])
    )
    np.testing.assert_array_equal(
        np.asarray(cache.k_scale[0, 0, new_tail]),
        np.asarray(cache.k_scale[0, 0, pages[1]]),
    )
    np.testing.assert_array_equal(
        np.asarray(cache.v_scale[0, 0, new_tail]),
        np.asarray(cache.v_scale[0, 0, pages[1]]),
    )


def test_int8_sanitizer_checks_scale_shape_and_names_scale_rows():
    """Invariant 6: a scale pool whose page axis drifted from the allocator
    fails the audit; drain leaks name the stranded scale rows."""
    cache = _int8_cache()
    pool = cache.pool
    san = KVSanitizer(pool, paged_cache=cache)
    san.check("step")  # consistent: passes
    # leak: a slot abandons pages -> drain audit names pages AND scale rows
    pool.allocate(0, 8)
    with pytest.raises(KVSanitizerError) as err:
        san.check("drain", drained=True)
    assert "scale rows" in str(err.value)
    pool.free(0)
    # shape drift: scale pool no longer addresses the allocator's pages
    import jax.numpy as jnp

    cache.k_scale = jnp.zeros((1, 1, 4, 4), jnp.float32)
    with pytest.raises(KVSanitizerError) as err:
        san.check("step")
    assert "lifecycle" in str(err.value)


# -- transient pins (prefix-cache lookup accounting) --------------------------


def test_pin_unpin_roundtrip_and_accounting():
    pool = _pool()
    pages = pool.allocate(0, 8)
    pool.pin_pages(pages)  # in-flight admission holds them
    assert all(pool.page_refcount(p) == 2 for p in pages)
    pool.free(0)  # slot exits first
    assert all(pool.page_refcount(p) == 1 for p in pages)  # pin keeps them
    assert pool.unpin_pages(pages) == len(pages)
    assert pool.free_pages == 15


def test_unpin_without_pin_raises():
    pool = _pool()
    pages = pool.allocate(0, 4)
    with pytest.raises(RuntimeError):
        pool.unpin_pages(pages)
    pool.free(0)


# -- runtime KV sanitizer (llm/kv_sanitizer.py) -------------------------------


def test_sanitizer_clean_pool_passes_all_checks():
    pool = _pool()
    san = KVSanitizer(pool)
    pool.allocate(0, 10)
    pool.allocate(1, 5)
    san.check("step")
    pool.free(0)
    pool.free(1)
    san.check("drain", drained=True)
    assert san.stats() == {"checks": 2, "failures": 0}


def test_sanitizer_names_unaccounted_reference():
    pool = _pool()
    san = KVSanitizer(pool)
    pages = pool.allocate(0, 4)
    with pool._lock:
        pool._refs[pages[0]] += 1  # simulate a lost unref (leak)
    with pytest.raises(KVSanitizerError) as ei:
        san.check("step")
    assert ei.value.pages == [pages[0]]
    assert "refcount conservation" in str(ei.value)
    assert "page {}".format(pages[0]) in str(ei.value)


def test_sanitizer_catches_free_list_corruption():
    pool = _pool()
    san = KVSanitizer(pool)
    pages = pool.allocate(0, 4)
    with pool._lock:
        pool._free.append(pages[0])  # referenced page back on the free list
    with pytest.raises(KVSanitizerError) as ei:
        san.check("step")
    assert "free list" in str(ei.value)


def test_sanitizer_catches_slot_table_shape_drift():
    pool = _pool()
    san = KVSanitizer(pool)
    pool.allocate(0, 5)  # 2 pages
    with pool._lock:
        pool._slot_len[0] = 9  # claims 3 pages' worth of tokens
    with pytest.raises(KVSanitizerError) as ei:
        san.check("step")
    assert "slot 0" in str(ei.value)


def test_sanitizer_drain_flags_abandoned_slot_pages():
    pool = _pool()
    san = KVSanitizer(pool)
    pages = pool.allocate(0, 8)
    san.check("step")  # mid-run: a populated slot is normal
    with pytest.raises(KVSanitizerError) as ei:
        san.check("drain", drained=True)
    assert ei.value.where == "drain"
    assert sorted(ei.value.pages) == sorted(pages)
    assert "leaked pages at drain" in str(ei.value)


def test_sanitizer_accounts_radix_cache_and_pins():
    """Full holder set: slot + radix-cache nodes + a lookup pin, all
    attributed; then each holder exits and the drain audit passes."""
    pool = _pool(num_pages=32, page_size=4)
    cache = RadixPrefixCache(
        max_nodes=16, block=4, pool=pool, page_bytes=64,
    )
    san = KVSanitizer(pool, cache)
    ids = list(range(1, 14))  # 13 tokens -> 12-token (3-block) prefix
    pool.allocate(0, len(ids))
    cache.store_pages(ids, 0, pool.slot_pages(0))
    san.check("step")
    hit = cache.lookup_pages(ids, 0)
    assert hit is not None and len(hit["pages"]) == 3
    san.check("step")           # pin attributed
    cache.release(hit)          # admission mapped (or failed): pin drops
    san.check("step")
    pool.free(0)                # slot exits; cache still holds the prefix
    san.check("drain", drained=True)
    assert san.stats()["failures"] == 0
