"""Paged KV cache: refcounted page pool + per-sequence page tables.

vLLM's PagedAttention memory model rebuilt for TPU/HBM (SURVEY.md §2.9 row 2):
the cache is a fixed pool of fixed-size pages per layer; sequences own page
lists, so HBM holds only the tokens that exist and slots never reserve
max_seq_len. Allocation is host-side (cheap integer bookkeeping); the device
side sees dense pools + int32 page tables, which feed
ops/paged_attention.paged_attention.

Pages are REFCOUNTED so they can be shared between live slots and the radix
prefix cache (llm/prefix_cache.py): the cache stores a prompt prefix by
taking a reference on the admitting slot's pages, and a later admission
sharing that prefix maps the same pages into its own page table — zero HBM
copies either way. A page returns to the free list only when its last
reference (slot or cache) drops. A slot that must WRITE into a shared page
(its tail page is referenced elsewhere) gets a private replacement first —
copy-on-write: the pool swaps the page id host-side and records a
(src, dst) pair; PagedKVCache.apply_pending_cow() performs the device copy
before the next write lands.

Device layout per layer:   k_pool/v_pool [Hkv, num_pages, page_size, D]
(head-major — the layout ops/paged_attention.py's kernel tiles over); the
cache holds the layers stacked, ``k`` / ``v`` ``[L, Hkv, num_pages, P, D]``.
The model step takes the donated stacks whole, updates them in place (the
touched pages are patched at [layer, head, page, offset]) and hands them
back: the kernels read a layer of the stack by its index, so a launch never
copies a pool
(models/llama.py "paged KV serving path"). Everything here — allocation, CoW
copies, prefix cache, tiering, shipping — reads and writes the same stacks.
Host bookkeeping:          free-page stack + per-slot page lists + refcounts

int8 paged KV (``kv_quant="int8"``, docs/paged_kv_quant.md): the K/V pools
store int8 and each side gains a SCALE pool ``[L, Hkv, num_pages, P]`` f32
holding the per-(token, head) symmetric dequant scales (the same
quantization as models/llama._kv_store on the dense path). A page id
indexes BOTH its data plane and its scale row — one lifecycle: every write
(prompt scatter, per-token append), every copy-on-write duplication, and
every free/share/refcount operation covers the scale row by construction,
because the scale pools are addressed by the same page ids the PagePool
hands out. Pool HBM per token-head drops from 2·D bytes (bf16) to D + 4
(int8 + f32 scale) — 1.94x at D=128 — which doubles the page budget the
radix prefix cache can hold.

Host-RAM tier (docs/kv_tiering.md): ``enable_host_tier`` preallocates a
:class:`HostKVTier` — page-major host buffers addressed by HOST-tier page
ids, a separate id space from the device pool's. The radix prefix cache
(llm/prefix_cache.py) demotes cold cached pages into the tier instead of
dropping them (``demote_pages``: device→host readback of int8 pages AND
their scale rows, 2x cheaper than bf16 to hold and transfer) and re-onlines
them on a hit (``promote_pages``: async host→device DMA enqueued under the
dispatch lock, so every later consumer program is ordered after the copy by
data dependency on the pool handles — the "tier fence";
llm/schedule_explorer.py's ``tier_promotion`` scenario models losing it).
Promotion completion is observed at the engine's retire boundaries
(``reap_promotions``), which is where the DMA-overlap metric comes from.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from . import lifecycle_ledger as _ledger

from .shapes import pad_pages, pow2_bucket


class PagePool:
    """Host-side refcounted page allocator for a fixed pool.

    Page 0 is RESERVED as the null page: unused page-table entries point at it
    and inactive batch slots write their garbage KV there — it is never
    allocated to a sequence and never refcounted.

    A single re-entrant lock guards all bookkeeping: the engine loop thread,
    decode worker threads, and admission workers (prefix-cache pins) all
    mutate refcounts concurrently."""

    # lock-discipline registry (tpuserve-analyze TPU301): every mutation of
    # these attributes must sit inside `with self._lock:`; helpers called
    # with the lock already held annotate their def line
    __guarded_by__ = {
        "_lock": ("_free", "_slot_pages", "_slot_len", "_refs",
                  "_pending_cow", "_pins", "_used_peak"),
    }

    # ownership-discipline registry (tpuserve-analyze TPU7xx,
    # docs/static_analysis.md): every declared acquire must reach a
    # matching release / drop-to-recompute handler on ALL paths (exception
    # edges included). Mirrored in analyze/rules_lifecycle.py
    # LIFECYCLE_REGISTRY (consistency-tested); "static": False entries are
    # cross-function protocols the runtime ownership ledger
    # (llm/lifecycle_ledger.py) audits instead.
    __acquires__ = {
        "allocate": {"resource": "pages.slot",
                     "releases": ("free", "truncate"),
                     "drops": ("_free_slot_pages",),
                     "receivers": ("pool", "_pool", "page_pool", "pages")},
        "extend": {"resource": "pages.slot",
                   "releases": ("free", "truncate"),
                   "drops": ("_free_slot_pages",),
                   "receivers": ("pool", "_pool", "page_pool")},
        "map_shared": {"resource": "pages.slot", "releases": ("free",),
                       "drops": ("_free_slot_pages",),
                       "receivers": ("pool", "_pool", "page_pool")},
        "allocate_cache_pages": {"resource": "pages.ref",
                                 "releases": ("unref_pages",),
                                 "mint": True},
        "ref_pages": {"resource": "pages.ref", "releases": ("unref_pages",),
                      "static": False},
        "pin_pages": {"resource": "pages.pin",
                      "releases": ("unpin_pages",)},
    }

    def __init__(self, num_pages: int, page_size: int, max_slots: int):
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.max_slots = int(max_slots)
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self._slot_pages: List[List[int]] = [[] for _ in range(max_slots)]
        self._slot_len: List[int] = [0] * max_slots
        self._refs: List[int] = [0] * num_pages
        self._lock = threading.RLock()
        # copy-on-write bookkeeping: host-side id swaps whose device copy is
        # still pending (drained by PagedKVCache.apply_pending_cow)
        self._pending_cow: List[Tuple[int, int]] = []
        self.cow_events = 0
        # transient out-of-structure references (prefix-cache lookup pins):
        # page -> count of refs held by in-flight admissions. Tracked apart
        # from _refs so the KV sanitizer (llm/kv_sanitizer.py) can prove
        # refcount CONSERVATION: refs == slot-table + cache-node + pin refs.
        self._pins: Dict[int, int] = {}
        # high-water mark of pages off the free list since construction
        self._used_peak = 0

    @property
    def free_pages(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def used_pages_peak(self) -> int:
        """High-water mark of pages off the free list: held by live
        requests AND by the prefix cache (the null page is neither free nor
        used)."""
        with self._lock:
            return self._used_peak

    def pages_needed(self, tokens: int) -> int:
        return -(-tokens // self.page_size)

    def can_allocate(self, tokens: int) -> bool:
        with self._lock:
            return self.pages_needed(tokens) <= len(self._free)

    def _pop_free(self) -> int:  # tpuserve: ignore[TPU301] lock held by caller
        page = self._free.pop()
        self._refs[page] = 1
        self._used_peak = max(
            self._used_peak, self.num_pages - 1 - len(self._free)
        )
        return page

    def _unref(self, page: int) -> bool:  # tpuserve: ignore[TPU301] lock held by caller
        """Drop one reference; True when the page returned to the free list."""
        self._refs[page] -= 1
        if self._refs[page] == 0:
            self._free.append(page)
            return True
        if self._refs[page] < 0:
            raise RuntimeError("page {} refcount went negative".format(page))
        return False

    def allocate(self, slot: int, tokens: int) -> List[int]:
        """Give `slot` enough pages for `tokens` total; returns new page ids."""
        with self._lock:
            have = len(self._slot_pages[slot])
            need = self.pages_needed(tokens) - have
            if need > len(self._free):
                raise MemoryError(
                    "page pool exhausted: need {} pages, {} free".format(
                        need, len(self._free)
                    )
                )
            new = [self._pop_free() for _ in range(max(0, need))]
            self._slot_pages[slot].extend(new)
            self._slot_len[slot] = tokens
            if new and _ledger.armed():
                _ledger.acquire("pages.slot", key=slot, n=len(new),
                                domain=self)
            return new

    def extend(self, slot: int, extra_tokens: int = 1) -> List[int]:
        """Grow a sequence; returns ALL newly allocated page ids (possibly
        several when `extra_tokens` spans page boundaries; empty if none).

        Copy-on-write: if the slot's write position falls inside a page that
        is ALSO referenced elsewhere (prefix cache or another slot), the page
        is replaced with a private copy first — writing in place would
        corrupt every other reader. The device copy is deferred to
        PagedKVCache.apply_pending_cow()."""
        with self._lock:
            length = self._slot_len[slot]
            if extra_tokens > 0 and length % self.page_size:
                idx = length // self.page_size
                page = self._slot_pages[slot][idx]
                if self._refs[page] > 1:
                    if not self._free:
                        raise MemoryError(
                            "page pool exhausted (copy-on-write of shared "
                            "page {})".format(page)
                        )
                    fresh = self._pop_free()
                    self._slot_pages[slot][idx] = fresh
                    self._refs[page] -= 1  # > 1, so never frees here
                    self._pending_cow.append((page, fresh))
                    self.cow_events += 1
            return self.allocate(slot, length + extra_tokens)

    def free(self, slot: int) -> None:
        """Release the slot's references; pages still referenced by the
        prefix cache (or another slot) stay allocated."""
        with self._lock:
            for page in reversed(self._slot_pages[slot]):
                self._unref(page)
            self._slot_pages[slot] = []
            self._slot_len[slot] = 0
            if _ledger.armed():
                _ledger.release("pages.slot", key=slot, domain=self,
                                all_of_key=True)

    def truncate(self, slot: int, tokens: int) -> None:
        """Shrink a sequence to `tokens`, dropping this slot's references to
        the surplus pages (speculative chunks over-allocate for the
        worst-case accepted length, then roll back to what was actually
        emitted). Surplus pages shared with the cache stay allocated."""
        with self._lock:
            if tokens > self._slot_len[slot]:
                raise ValueError(
                    "truncate({}) past current length {}".format(
                        tokens, self._slot_len[slot]
                    )
                )
            keep = self.pages_needed(tokens)
            surplus = self._slot_pages[slot][keep:]
            self._slot_pages[slot] = self._slot_pages[slot][:keep]
            for page in reversed(surplus):
                self._unref(page)
            self._slot_len[slot] = tokens
            if surplus and _ledger.armed():
                _ledger.release("pages.slot", key=slot, n=len(surplus),
                                domain=self)

    # -- sharing (prefix cache) --------------------------------------------

    def ref_pages(self, pages: List[int]) -> None:
        """Take one reference on each page (cache store / lookup pin).
        Validates the whole batch before mutating anything: a mid-loop
        raise must not leave earlier pages referenced (the failure fires
        exactly when accounting is already suspect — don't compound it)."""
        with self._lock:
            for page in pages:
                if self._refs[page] <= 0:
                    raise RuntimeError(
                        "ref_pages on unallocated page {}".format(page)
                    )
            for page in pages:
                self._refs[page] += 1
            if pages and _ledger.armed():
                _ledger.acquire("pages.ref", n=len(pages), domain=self)

    def unref_pages(self, pages: List[int]) -> int:
        """Drop one reference per page; returns how many were freed."""
        freed = 0
        with self._lock:
            for page in pages:
                if self._unref(page):
                    freed += 1
            if pages and _ledger.armed():
                _ledger.release("pages.ref", n=len(pages), domain=self)
        return freed

    def pin_pages(self, pages: List[int]) -> None:
        """Take one TRANSIENT reference per page (prefix-cache lookup pin,
        held by an in-flight admission). Same refcount semantics as
        ref_pages, but accounted separately so the sanitizer can attribute
        every reference to a holder."""
        with self._lock:
            # validate-then-mutate: no partial pins on error
            for page in pages:
                if self._refs[page] <= 0:
                    raise RuntimeError(
                        "pin_pages on unallocated page {}".format(page)
                    )
            for page in pages:
                self._refs[page] += 1
                self._pins[page] = self._pins.get(page, 0) + 1
            if pages and _ledger.armed():
                # keyed by the exact page run: concurrent admissions' pins
                # must not discharge each other's entries
                _ledger.acquire("pages.pin", key=tuple(pages),
                                n=len(pages), domain=self)

    def unpin_pages(self, pages: List[int]) -> int:
        """Drop one transient reference per page; returns pages freed."""
        freed = 0
        with self._lock:
            # validate-then-mutate: no partial unpins on error
            counted: Dict[int, int] = {}
            for page in pages:
                counted[page] = counted.get(page, 0) + 1
                if self._pins.get(page, 0) < counted[page]:
                    raise RuntimeError(
                        "unpin_pages on unpinned page {}".format(page)
                    )
            for page in pages:
                count = self._pins[page]
                if count == 1:
                    self._pins.pop(page)
                else:
                    self._pins[page] = count - 1
                if self._unref(page):
                    freed += 1
            if pages and _ledger.armed():
                _ledger.release("pages.pin", key=tuple(pages),
                                n=len(pages), domain=self)
        return freed

    def snapshot(self) -> Dict[str, object]:
        """Consistent copy of all bookkeeping (one lock hold) for the KV
        sanitizer: refcounts, free list, slot tables/lengths, transient
        pins, and pending copy-on-write pairs."""
        with self._lock:
            return {
                "refs": list(self._refs),
                "free": list(self._free),
                "slot_pages": [list(p) for p in self._slot_pages],
                "slot_len": list(self._slot_len),
                "pins": dict(self._pins),
                "pending_cow": list(self._pending_cow),
            }

    def map_shared(self, slot: int, pages: List[int], tokens: int) -> None:
        """Map already-allocated (shared) pages as the slot's first pages —
        the zero-copy half of a prefix-cache hit. The slot takes its own
        reference on each page; ``tokens`` must cover the pages exactly
        (page-aligned prefix)."""
        with self._lock:
            if self._slot_pages[slot]:
                raise RuntimeError(
                    "map_shared into non-empty slot {}".format(slot)
                )
            if tokens != len(pages) * self.page_size:
                raise ValueError(
                    "shared prefix of {} tokens does not fill {} pages".format(
                        tokens, len(pages)
                    )
                )
            for page in pages:
                if self._refs[page] <= 0:
                    raise RuntimeError(
                        "map_shared of unallocated page {}".format(page)
                    )
            for page in pages:
                self._refs[page] += 1
            self._slot_pages[slot] = list(pages)
            self._slot_len[slot] = tokens
            if pages and _ledger.armed():
                _ledger.acquire("pages.slot", key=slot, n=len(pages),
                                domain=self)

    def allocate_cache_pages(self, n: int) -> List[int]:
        """Pop ``n`` free pages with one reference each, to be owned by the
        radix prefix cache (promotion targets for host-tier re-onlining,
        docs/kv_tiering.md). The caller MUST attach them to cache nodes (or
        unref them on failure) inside the same tree-lock window it called
        from — the KV sanitizer's conservation audit snapshots under that
        lock, so no intermediate owner-less state is ever observable."""
        with self._lock:
            if n > len(self._free):
                raise MemoryError(
                    "page pool exhausted: promotion needs {} pages, {} "
                    "free".format(n, len(self._free))
                )
            fresh = [self._pop_free() for _ in range(n)]
            if fresh and _ledger.armed():
                _ledger.acquire("pages.ref", n=len(fresh), domain=self)
            return fresh

    def drain_pending_cow(self) -> List[Tuple[int, int]]:
        with self._lock:
            out, self._pending_cow = self._pending_cow, []
            return out

    def page_refcount(self, page: int) -> int:
        with self._lock:
            return self._refs[page]

    def slot_pages(self, slot: int) -> List[int]:
        with self._lock:
            return list(self._slot_pages[slot])

    @property
    def shared_pages(self) -> int:
        """Pages with more than one reference (slot+cache or slot+slot)."""
        with self._lock:
            return sum(1 for r in self._refs[1:] if r > 1)

    def slot_length(self, slot: int) -> int:
        return self._slot_len[slot]

    def token_coords(self, slot: int, start: int, count: int):
        """(page_id, offset) for token positions [start, start+count) of a
        slot. The single source of the page//offset math for engine, cache,
        and tests."""
        with self._lock:
            pages = list(self._slot_pages[slot])
        out = []
        for pos in range(start, start + count):
            out.append((pages[pos // self.page_size], pos % self.page_size))
        return out

    def page_table(self, pages_per_seq: int) -> np.ndarray:
        """Dense [max_slots, pages_per_seq] table (unused entries point at
        page 0 — they are masked by lengths on the device side). Raises if any
        slot owns more pages than the table can express — silently truncating
        would drop the newest tokens from attention."""
        with self._lock:
            table = np.zeros((self.max_slots, pages_per_seq), np.int32)
            for slot, pages in enumerate(self._slot_pages):
                if len(pages) > pages_per_seq:
                    raise ValueError(
                        "slot {} holds {} pages > table width {}".format(
                            slot, len(pages), pages_per_seq
                        )
                    )
                table[slot, : len(pages)] = pages
            return table

    def lengths(self) -> np.ndarray:
        with self._lock:
            return np.asarray(self._slot_len, np.int32)


def available_host_memory_bytes(path: str = "/proc/meminfo") -> int:
    """``MemAvailable`` from /proc/meminfo, in bytes — the input to the
    host-tier auto-sizer (aux ``engine.prefix_cache_host_mb: "auto"``,
    docs/kv_tiering.md). Raises :class:`errors.HostTierAutoSizeError`
    (named, construction-time) on platforms without the file or without
    the field: silently guessing a size would hide that the knob did
    nothing."""
    from ..errors import HostTierAutoSizeError

    try:
        with open(path) as fh:
            for line in fh:
                if line.startswith("MemAvailable:"):
                    return int(line.split()[1]) * 1024
    except OSError as ex:
        raise HostTierAutoSizeError(
            "prefix_cache_host_mb='auto' needs {} (Linux); probe failed on "
            "this platform: {}".format(path, ex)
        )
    raise HostTierAutoSizeError(
        "prefix_cache_host_mb='auto': {} has no MemAvailable field on this "
        "platform; set an explicit engine.prefix_cache_host_mb".format(path)
    )


def cohosted_worker_processes() -> int:
    """How many engine worker processes share this host's RAM — the
    divisor for ``prefix_cache_host_mb: "auto"``. Each process sizes its
    tier independently from the same ``MemAvailable`` reading, so without
    the divide a 2-worker fleet claims half of host memory TWICE
    (over-commit the OOM killer settles later, not the sizer). The
    process-fleet builder (serving/process_replica.py) exports the fleet
    width as ``TPUSERVE_COHOSTED_PROCS`` into every worker; unset or
    malformed reads as 1 (the single-process in-heap backend)."""
    raw = os.environ.get("TPUSERVE_COHOSTED_PROCS", "")
    try:
        n = int(raw)
    except ValueError:
        return 1
    return max(1, n)


class HostKVTier:
    """Preallocated host-RAM page tier behind the HBM pools
    (docs/kv_tiering.md).

    Layout is PAGE-MAJOR — ``hk``/``hv`` [Nh, L, Hkv, P, D] (+ [Nh, L, Hkv,
    P] f32 scale rows on int8 pools) — so one host page's bytes are
    contiguous: a demotion writes one slab, a promotion stages one slab, and
    the host→device upload presents the runtime a single contiguous source
    per page run instead of a strided gather. Buffers are allocated ONCE at
    construction (numpy keeps them resident; on TPU runtimes jax's transfer
    path stages through its own pinned buffers, and preallocating here
    avoids allocator churn on the demote/promote paths).

    Host page ids are a SEPARATE id space from the device pool's: a cached
    node references either a device page id or a host-tier page id, never
    both (the KV sanitizer's two-tier invariant). Ownership is single-holder
    by construction — only the radix prefix cache allocates host pages, one
    node per id — so the tier needs an allocator, not refcounts."""

    # lock-discipline registry (tpuserve-analyze TPU301): id bookkeeping is
    # mutated only under self._lock. The data slabs themselves need no lock:
    # a freshly allocated id is exclusive to its allocator until freed, and
    # promotion stages a COPY of the rows before the id returns to the free
    # list (the PR-4 aliasing rule).
    __guarded_by__ = {"_lock": ("_free", "_used")}

    # ownership-discipline registry (tpuserve-analyze TPU7xx): host ids
    # pair allocate/free; the radix cache owns them at steady state
    __acquires__ = {
        "allocate": {"resource": "host.pages", "releases": ("free",),
                     "receivers": ("host_tier", "_host", "tier", "host")},
    }

    def __init__(self, num_pages: int, page_size: int, n_layers: int,
                 n_kv_heads: int, head_dim: int, dtype, quantized: bool):
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        if self.num_pages <= 0:
            raise ValueError("host tier needs at least one page")
        shape = (self.num_pages, n_layers, n_kv_heads, page_size, head_dim)
        self.hk = np.zeros(shape, np.dtype(dtype))
        self.hv = np.zeros(shape, np.dtype(dtype))
        if quantized:
            self.hk_scale = np.zeros(shape[:-1], np.float32)
            self.hv_scale = np.zeros(shape[:-1], np.float32)
        else:
            self.hk_scale = None
            self.hv_scale = None
        self._free: List[int] = list(range(self.num_pages - 1, -1, -1))
        self._used: set = set()
        self._lock = threading.Lock()

    @property
    def quantized(self) -> bool:
        return self.hk_scale is not None

    @property
    def page_bytes(self) -> int:
        """True host bytes per page: K+V slabs plus scale rows."""
        per = int(self.hk[0].nbytes) + int(self.hv[0].nbytes)
        if self.hk_scale is not None:
            per += int(self.hk_scale[0].nbytes) + int(self.hv_scale[0].nbytes)
        return per

    @property
    def free_pages(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def used_pages(self) -> int:
        with self._lock:
            return len(self._used)

    def allocate(self, n: int) -> List[int]:
        with self._lock:
            if n > len(self._free):
                raise MemoryError(
                    "host KV tier exhausted: need {} pages, {} free".format(
                        n, len(self._free)
                    )
                )
            ids = [self._free.pop() for _ in range(n)]
            self._used.update(ids)
            if ids and _ledger.armed():
                _ledger.acquire("host.pages", n=len(ids), domain=self)
            return ids

    def free(self, ids: List[int]) -> None:
        with self._lock:
            for hid in ids:
                if hid not in self._used:
                    raise RuntimeError(
                        "free of unallocated host page {}".format(hid)
                    )
                self._used.discard(hid)
                self._free.append(hid)
            if ids and _ledger.armed():
                _ledger.release("host.pages", n=len(ids), domain=self)

    def snapshot(self) -> Dict[str, object]:
        """Consistent copy of the id bookkeeping for the KV sanitizer."""
        with self._lock:
            return {
                "free": list(self._free),
                "used": set(self._used),
                "num_pages": self.num_pages,
            }


class PagedKVCache:
    """Device pools for all layers + the shared host-side PagePool.

    Pools are ONE stacked array per side — ``k``/``v`` [L, Hkv, N, P, D] — and
    every write goes through a jitted, buffer-donating scatter: the pool is
    updated in place in HBM, never copied (an eager ``.at[].set`` would copy
    the whole multi-GB pool per token).

    ``dispatch_lock`` serializes DISPATCH of device programs that touch the
    pools: the decode/spec chunks donate k/v while admission workers
    concurrently enqueue prefix-KV gathers and commit writes — without the
    lock a gather could grab a pool reference that a racing donating dispatch
    has already invalidated. Execution still overlaps; only the (cheap,
    host-side) enqueue is serialized.

    Donation ordering under the pipelined decode loop
    (docs/pipelined_decode.md): chained chunk dispatches rebind ``k``/``v``
    to the PENDING outputs of the in-flight chunk, and every later program
    (the next chunk, CoW copies, commit scatters, prefix gathers) consumes
    those handles — device-side ordering holds by data dependency, never by
    host-side waiting. Page FREES are the one thing data flow cannot order:
    the engine defers a freed slot's ``pool.free`` to the retirement of the
    newest chunk still writing it (the quarantine barrier), so a page is
    never re-allocated under an in-flight write. The barrier protocol is
    modelled and explored across seeded interleavings by
    llm/schedule_explorer.py's ``quarantine_barrier`` scenario
    (``--mutate drop_quarantine`` demonstrates the corruption a missing
    barrier causes); the thread-ownership side is machine-checked by
    tpuserve-analyze TPU501 via the engine's ``__affine_to__``."""

    # pool-handle rebinds happen only under the dispatch lock (a donating
    # dispatch invalidates the old handle; tpuserve-analyze TPU301). The
    # in-flight promotion records ride the same lock: they are appended at
    # copy-enqueue time (dispatch path) and drained at retire boundaries.
    __guarded_by__ = {
        "dispatch_lock": ("k", "v", "k_scale", "v_scale", "_promotions"),
    }

    def __init__(
        self,
        n_layers: int,
        n_kv_heads: int,
        head_dim: int,
        *,
        num_pages: int,
        page_size: int = 16,
        max_slots: int = 8,
        dtype="bfloat16",
        kv_quant: str = "",
        layout=None,
        counters: int = 0,
        row_state=None,
    ):
        import jax
        import jax.numpy as jnp

        if kv_quant not in ("", "int8"):
            raise ValueError(
                "kv_quant must be '' or 'int8' (got {!r})".format(kv_quant)
            )
        if layout is not None and kv_quant:
            raise ValueError(
                "kv_quant cannot serve a {} page layout: its planes are the "
                "model's own".format(layout.kind)
            )
        self.kv_quant = kv_quant
        self.layout = layout
        self.pool = PagePool(num_pages, page_size, max_slots)
        self.n_layers = n_layers
        shape = (n_layers, n_kv_heads, num_pages, page_size, head_dim)
        pool_dtype = jnp.int8 if kv_quant else jnp.dtype(dtype)
        if layout is not None:
            # a model's own LAYOUT (docs/latent_cache.md): ``k`` and ``v``
            # are pytrees of planes [layers of a kind, 1, N, P, row width],
            # every plane under the ONE page id the PagePool hands out, so
            # the page table, the radix prefix cache, copy-on-write and
            # preemption go on working on page ids; a leaf that is no plane
            # (rank under 4: the launch's counters) rides along untouched
            self.k, self.v = layout.init_pools(num_pages, page_size)
        else:
            self.k = jnp.zeros(shape, pool_dtype)
            self.v = jnp.zeros(shape, pool_dtype)
        # a model that counts its passes' work on the device (``counters``
        # int32 values; models/afmoe.py) gets them carried beside the
        # standard V pool: see :attr:`v_carry`
        self.counters = (
            jnp.zeros((int(counters),), jnp.int32) if counters else None)
        # a model whose rows keep a recurrent state BESIDE their pages
        # (docs/hybrid_cache.md): the ``StateCache`` whose planes ride the
        # launch's carry the same way, slot = batch row
        if row_state is not None and (counters or layout is not None):
            raise ValueError(
                "a row state rides beside the STANDARD pools alone: not "
                "beside a model's own page layout or its counters")
        self.row_state = row_state
        # int8: per-(token, head) f32 dequant scales, page-id addressed so
        # a page and its scale row share one lifecycle (module docstring)
        if kv_quant:
            self.k_scale = jnp.zeros(shape[:-1], jnp.float32)
            self.v_scale = jnp.zeros(shape[:-1], jnp.float32)
        else:
            self.k_scale = None
            self.v_scale = None
        self.dispatch_lock = threading.Lock()
        # host-RAM tier (docs/kv_tiering.md): None until enable_host_tier;
        # the radix prefix cache demotes into / promotes out of it
        self.host_tier: Optional[HostKVTier] = None
        self._promotions: List[dict] = []   # in-flight promotion DMAs
        # tier counters (pages moved; GIL-atomic int bumps): observability
        # for engine_kv_demotions_total / engine_kv_promotions_total
        self.demoted_pages = 0
        self.promoted_pages = 0
        self.promo_reaped = 0       # promotion DMAs observed complete
        self.promo_wait_ms = 0.0    # exposed (un-hidden) wait at the reap
        self.promo_total_ms = 0.0   # issue -> observed-complete wall time

        def _write_pages(pool, chunks, pages):
            # chunks [NP, L, Hkv, P, D] (or [NP, L, Hkv, P] for scale pools),
            # pages [NP] -> scatter all pages in ONE dispatch (a per-page
            # Python loop would put O(prompt/page_size) host->device
            # roundtrips on the TTFT-critical prefill path)
            chunks = jnp.moveaxis(chunks, 0, 2)          # [L, Hkv, NP, P(, D)]
            return pool.at[:, :, pages].set(chunks)

        def _copy_page(pool, src, dst):
            # copy-on-write: duplicate one page inside the pool (src read,
            # dst written, one fused donated program — no host round trip)
            page = jax.lax.dynamic_slice(
                pool, (0, 0, src, 0, 0),
                (pool.shape[0], pool.shape[1], 1, pool.shape[3], pool.shape[4]),
            )
            return jax.lax.dynamic_update_slice(pool, page, (0, 0, dst, 0, 0))

        def _copy_pages(pool, srcs, dsts):
            # batched CoW: all pending (src, dst) pairs in ONE donated
            # gather/scatter — the pipelined decode loop applies CoW on the
            # dispatch path, so per-pair dispatches would put 4 host->device
            # program launches per shared-tail slot between chunks. Pair
            # lists pad to (0, 0): writing the reserved null page onto
            # itself is a no-op by construction.
            # a K/V pool is one plane; a layout's pool is a pytree of them
            return jax.tree.map(
                lambda plane: plane.at[:, :, dsts].set(plane[:, :, srcs])
                if plane.ndim >= 4 else plane, pool,
            )

        self._write_pages = jax.jit(_write_pages, donate_argnums=(0,))
        self._copy_page = jax.jit(_copy_page, donate_argnums=(0,))
        self._copy_pages = jax.jit(_copy_pages, donate_argnums=(0,))

    def layer(self, li: int):
        """Per-layer head-major views for ops.paged_attention."""
        return self.k[li], self.v[li]

    @property
    def v_carry(self):
        """What a launch takes and gives back as ``v_pools``: the V pool,
        ``(pool, counters)`` for a model that keeps counters on the device,
        or ``(pool, planes)`` for one whose rows keep a state beside their
        pages (``row_state``). The pool itself stays ``self.v``, a plain
        stack, for everything else that moves pages."""
        if self.row_state is not None:
            return (self.v, self.row_state.planes)
        return self.v if self.counters is None else (self.v, self.counters)

    @v_carry.setter
    def v_carry(self, carry):  # tpuserve: ignore[TPU301] lock held by caller
        if self.row_state is not None:
            self.v, self.row_state.planes = carry  # tpuserve: ignore[TPU301] this cache's lock guards the planes beside it
        elif self.counters is None:
            self.v = carry
        else:
            self.v, self.counters = carry

    @property
    def has_scales(self) -> bool:
        return self.k_scale is not None

    def pool_bytes(self) -> Dict[str, int]:
        """Device HBM held by the pools, split by kind (observability:
        statistics/metrics.py exports these as engine_kv_pool_bytes)."""
        scale = 0
        if self.k_scale is not None:
            scale = int(self.k_scale.nbytes) + int(self.v_scale.nbytes)
        import jax

        planes = [x for x in jax.tree.leaves((self.k, self.v)) if x.ndim >= 4]
        return {"kv": sum(int(x.nbytes) for x in planes), "scale": scale}

    @property
    def pool_dtype(self) -> str:
        import jax

        return str(jax.tree.leaves(self.k)[0].dtype)

    def max_pages_per_seq(self, max_seq_len: int) -> int:
        return self.pool.pages_needed(max_seq_len)

    def apply_pending_cow(self) -> int:
        """Perform the device copies for any host-side copy-on-write page
        swaps (PagePool.extend). MUST run after extending slots and before
        the writes of the extension land — with pipelined decode this sits
        on the dispatch path between chained chunks, and ordering holds by
        data dependency: the copy consumes the in-flight chunk's output
        pool handle, so it reads post-chunk page contents. Returns the
        number of pages copied.

        All pending pairs are applied in ONE donated program per pool side
        (pair count padded to a power-of-two bucket with null-page no-ops,
        so traces stay bounded)."""
        import jax.numpy as jnp

        pairs = self.pool.drain_pending_cow()
        if not pairs:
            return 0
        bucket = pow2_bucket(len(pairs))
        padded = pairs + [(0, 0)] * (bucket - len(pairs))
        srcs = jnp.asarray([s for s, _ in padded], jnp.int32)
        dsts = jnp.asarray([d for _, d in padded], jnp.int32)
        with self.dispatch_lock:
            self.k = self._copy_pages(self.k, srcs, dsts)
            self.v = self._copy_pages(self.v, srcs, dsts)
            if self.k_scale is not None:
                # scale rows share the page lifecycle: a CoW'd page carries
                # its dequant scales to the private copy in the same batch
                self.k_scale = self._copy_pages(self.k_scale, srcs, dsts)
                self.v_scale = self._copy_pages(self.v_scale, srcs, dsts)
        return len(pairs)

    # -- host-RAM tier (docs/kv_tiering.md) --------------------------------

    def enable_host_tier(self, num_pages: int) -> "HostKVTier":
        """Preallocate a host-RAM page tier matching this pool's geometry.
        Returns the tier (also kept as ``self.host_tier``)."""
        self._require_kv_pages("the host tier (HostKVTier)")
        _l, hkv, _n, p, d = self.k.shape
        self.host_tier = HostKVTier(
            num_pages, p, self.n_layers, hkv, d,
            dtype=self.k.dtype, quantized=bool(self.kv_quant),
        )
        return self.host_tier

    def export_pages(self, pages: List[int]) -> Dict[str, np.ndarray]:
        """Synchronous device→host readback of ``pages`` (and, on int8
        pools, their scale rows) into PAGE-MAJOR numpy slabs — ``hk``/``hv``
        ``[n, L, Hkv, P, D]`` (+ ``hk_scale``/``hv_scale`` ``[n, L, Hkv,
        P]``): the host-tier demote layout, which is also the KV-transport
        shipment payload (llm/kv_transport.py, docs/disaggregation.md).

        The gather consumes the CURRENT pool handles under the dispatch
        lock, so it is ordered after every enqueued write by data
        dependency; the readback itself is synchronous (the host copy is
        complete before the caller releases or re-uses the device pages —
        a later re-allocation can never overwrite bytes the caller still
        needs). The victim list pads to a power of two with null-page
        entries (llm/shapes.py) so the gather compiles once per power of
        two, not once per count (tpuserve-analyze TPU601)."""
        self._require_kv_pages('page export (KV shipment, demotion)')
        import jax.numpy as jnp

        n = len(pages)
        idx = jnp.asarray(pad_pages(pages), jnp.int32)
        with self.dispatch_lock:
            k_slab = self.k[:, :, idx]          # [L, Hkv, n_pad, P, D]
            v_slab = self.v[:, :, idx]
            if self.kv_quant:
                ks_slab = self.k_scale[:, :, idx]   # [L, Hkv, n_pad, P]
                vs_slab = self.v_scale[:, :, idx]
        # device->host readback OUTSIDE the dispatch lock: the gather
        # outputs are immutable device arrays; only the (cheap) enqueue
        # needed serializing against donating dispatches. Rows past the
        # real count gathered the null page and are dropped here.
        out = {
            "hk": np.moveaxis(np.asarray(k_slab), 2, 0)[:n],
            "hv": np.moveaxis(np.asarray(v_slab), 2, 0)[:n],
        }
        if self.kv_quant:
            out["hk_scale"] = np.moveaxis(np.asarray(ks_slab), 2, 0)[:n]
            out["hv_scale"] = np.moveaxis(np.asarray(vs_slab), 2, 0)[:n]
        return out

    def demote_pages(self, pages: List[int]) -> List[int]:
        """Copy device pages (and, on int8 pools, their scale rows) into
        freshly allocated host-tier pages; returns the host-tier page ids.

        The gather/readback contract is :meth:`export_pages` (same slabs,
        same fence). Raises MemoryError when the tier is full; the caller
        (radix cache eviction) then drops the run for real."""
        tier = self.host_tier
        if tier is None:
            raise RuntimeError("demote_pages without an enabled host tier")
        host_ids = tier.allocate(len(pages))
        try:
            slabs = self.export_pages(pages)
            tier.hk[host_ids] = slabs["hk"]
            tier.hv[host_ids] = slabs["hv"]
            if self.kv_quant:
                tier.hk_scale[host_ids] = slabs["hk_scale"]
                tier.hv_scale[host_ids] = slabs["hv_scale"]
        except BaseException:
            tier.free(host_ids)
            raise
        self.demoted_pages += len(pages)
        return host_ids

    def promote_pages(self, host_ids: List[int], pages: List[int]) -> None:
        """Re-online host-tier pages into freshly allocated device pages
        (``pages``, from PagePool.allocate_cache_pages) via an ASYNC
        host→device DMA: the donated page scatter is only ENQUEUED here —
        dispatch returns in microseconds and the copy itself proceeds in
        the background, hidden behind whatever the engine enqueues next
        (the prefix hit's tail-chunk prefill). Ordering for every later
        consumer holds by data dependency on the rebound pool handles (the
        tier fence). Frees the host ids: the rows are STAGED into fresh
        arrays first, so the upload never aliases tier memory a later
        demotion may overwrite (the PR-4 zero-copy race class)."""
        tier = self.host_tier
        if tier is None:
            raise RuntimeError("promote_pages without an enabled host tier")
        if len(host_ids) != len(pages):
            raise ValueError(
                "promotion of {} host pages into {} device pages".format(
                    len(host_ids), len(pages)
                )
            )
        # stage into POWER-OF-TWO-bucketed private slabs (llm/shapes.py):
        # fancy indexing COPIES the real rows, rows beyond the count stay
        # zero and scatter into the dead null page 0 — so the upload and
        # the donated page scatter compile once per power of two, not once
        # per promotion size (tpuserve-analyze TPU601), and never alias
        # tier memory a later demotion may overwrite (the PR-4 race class)
        n = len(pages)
        padded = pad_pages(pages)
        k_rows = np.zeros((len(padded),) + tier.hk.shape[1:], tier.hk.dtype)
        v_rows = np.zeros_like(k_rows)
        k_rows[:n] = tier.hk[host_ids]        # [n_pad, L, Hkv, P, D]
        v_rows[:n] = tier.hv[host_ids]
        if self.kv_quant:
            ks_rows = np.zeros(
                (len(padded),) + tier.hk_scale.shape[1:], tier.hk_scale.dtype
            )
            vs_rows = np.zeros_like(ks_rows)
            ks_rows[:n] = tier.hk_scale[host_ids]
            vs_rows[:n] = tier.hv_scale[host_ids]
        tier.free(host_ids)
        self._upload_pages(
            k_rows, v_rows,
            ks_rows if self.kv_quant else None,
            vs_rows if self.kv_quant else None,
            padded, len(pages),
        )
        self.promoted_pages += len(pages)

    def _upload_pages(self, k_rows, v_rows, ks_rows, vs_rows,
                      padded: List[int], n: int) -> None:
        """Enqueue the async host→device page scatter shared by the tier
        promotion and the KV-transport import (docs/kv_tiering.md,
        docs/disaggregation.md): the donated write is only ENQUEUED under
        the dispatch lock — dispatch returns in microseconds, the copy
        proceeds in the background, and ordering for every later consumer
        holds by data dependency on the rebound pool handles (the tier
        fence). Rows must be PRIVATE staged copies padded to ``padded``'s
        power-of-two length (rows past ``n`` scatter into dead page 0)."""
        import jax.numpy as jnp

        page_ids = jnp.asarray(padded, jnp.int32)
        t_issue = time.perf_counter()
        with self.dispatch_lock:
            # the fence holds the UPLOADED chunk arrays (not the pool
            # handles — a later donating dispatch deletes those): their
            # readiness marks the host→device transfer complete, and the
            # scatter that consumes them is ordered for every later reader
            # by data dependency on the rebound pools
            k_dev = jnp.asarray(k_rows)
            v_dev = jnp.asarray(v_rows)
            self.k = self._write_pages(self.k, k_dev, page_ids)
            self.v = self._write_pages(self.v, v_dev, page_ids)
            fence = [k_dev, v_dev]
            if self.kv_quant:
                ks_dev = jnp.asarray(ks_rows)
                vs_dev = jnp.asarray(vs_rows)
                self.k_scale = self._write_pages(self.k_scale, ks_dev, page_ids)
                self.v_scale = self._write_pages(self.v_scale, vs_dev, page_ids)
                fence += [ks_dev, vs_dev]
            self._promotions.append({
                "pages": n,
                "t_issue": t_issue,
                "fence": fence,
            })
            if _ledger.armed():
                _ledger.acquire("kv.promotion", domain=self)

    def import_pages(self, hk, hv, pages: List[int],
                     hk_scale=None, hv_scale=None) -> None:
        """Re-online SHIPPED page slabs (llm/kv_transport.py KVShipment
        rows, ``[n, L, Hkv, P, D]`` page-major + scale rows on int8 pools)
        into freshly allocated device pages via the same async
        enqueue-before-publish fence as a host-tier promotion
        (docs/disaggregation.md). The rows are staged into PRIVATE
        power-of-two-padded buffers first — the upload never aliases the
        transport slab, which the sender's mailbox may recycle (the PR-4
        zero-copy race class) — and completion is observed at the engine's
        retire boundaries (``reap_promotions``)."""
        self._require_kv_pages('page import (KV shipment, promotion)')
        if len(pages) != int(hk.shape[0]):
            raise ValueError(
                "import of {} slab rows into {} device pages".format(
                    hk.shape[0], len(pages)
                )
            )
        self._require_scales(hk_scale, hv_scale)
        n = len(pages)
        padded = pad_pages(pages)
        k_rows = np.zeros((len(padded),) + tuple(hk.shape[1:]), self.k.dtype)
        v_rows = np.zeros_like(k_rows)
        k_rows[:n] = hk
        v_rows[:n] = hv
        ks_rows = vs_rows = None
        if self.kv_quant:
            ks_rows = np.zeros(
                (len(padded),) + tuple(hk_scale.shape[1:]), np.float32
            )
            vs_rows = np.zeros_like(ks_rows)
            ks_rows[:n] = hk_scale
            vs_rows[:n] = hv_scale
        self._upload_pages(k_rows, v_rows, ks_rows, vs_rows, padded, n)

    def reap_promotions(self, force: bool = False) -> int:
        """Account promotion DMAs that completed (engine retire-stage
        event): a record whose fence arrays are ready cost the serving loop
        nothing — the copy hid behind the in-flight prefill/decode work.
        ``force`` blocks on stragglers (drain/stop paths and the A/B bench's
        end-of-run accounting). Returns how many records were reaped."""
        import jax

        with self.dispatch_lock:
            if not self._promotions:
                return 0
            if force:
                records, self._promotions = self._promotions, []
            else:
                records = [
                    r for r in self._promotions
                    if all(
                        getattr(f, "is_ready", lambda: True)()
                        for f in r["fence"]
                    )
                ]
                for r in records:
                    self._promotions.remove(r)
            if records and _ledger.armed():
                _ledger.release("kv.promotion", n=len(records), domain=self)
        reaped = 0
        for rec in records:
            t_reap = time.perf_counter()
            try:
                for f in rec["fence"]:
                    jax.block_until_ready(f)
            except Exception:
                # a poisoned fence surfaces at its consumer; the record is
                # still retired so the list cannot grow without bound
                pass
            t_done = time.perf_counter()
            self.promo_wait_ms += (t_done - t_reap) * 1e3
            self.promo_total_ms += (t_done - rec["t_issue"]) * 1e3
            self.promo_reaped += 1
            reaped += 1
        return reaped

    def tier_stats(self) -> Optional[Dict[str, object]]:
        """Host-tier movement/occupancy counters for lifecycle_stats()
        (None when no tier is enabled). ``overlap_ratio`` = share of the
        promotion DMA wall time hidden behind other device work, observed
        at the reap points."""
        tier = self.host_tier
        if tier is None:
            return None
        total = self.promo_total_ms
        hidden = max(0.0, total - self.promo_wait_ms)
        return {
            "host_pages_used": tier.used_pages,
            "host_pages_capacity": tier.num_pages,
            "host_page_bytes": tier.page_bytes,
            "demoted_pages_total": self.demoted_pages,
            "promoted_pages_total": self.promoted_pages,
            "promotions_reaped": self.promo_reaped,
            "promo_wait_ms": round(self.promo_wait_ms, 3),
            "promo_total_ms": round(self.promo_total_ms, 3),
            "overlap_ratio": (
                round(hidden / total, 4) if total > 0 else None
            ),
        }

    def _require_kv_pages(self, what: str) -> None:
        if self.layout is not None:
            raise ValueError(
                "{} moves K/V pages [L, Hkv, N, P, D]; the {} page layout "
                "keeps planes of its own and is not taught to it yet "
                "(docs/latent_cache.md)".format(what, self.layout.kind)
            )

    def _require_scales(self, k_scales, v_scales) -> None:
        """Fail fast when the caller's scale operands disagree with the
        pool layout: an int8 pool written without scales would silently
        dequantize with stale rows; scales against a bf16 pool mean the
        caller quantized for the wrong backend."""
        if self.kv_quant and (k_scales is None or v_scales is None):
            raise ValueError(
                "int8 KV pools need k_scales/v_scales alongside every write"
            )
        if not self.kv_quant and (k_scales is not None or v_scales is not None):
            raise ValueError("scale operands given but the pools are not int8")


class StateCache:
    """The second kind of sequence state (docs/state_cache.md): one SLOT of
    fixed size per sequence, whatever its length, where ``PagedKVCache``
    holds pages that grow. For models whose layers keep a recurrent state:
    ``planes`` is a dict of NAMED device arrays, each ``[L, slots (+ a
    model's spare), ...]``, whatever the model's ``init_state`` declares,
    carried through the model's layer loop like the paged K/V stacks. Power
    retention (``attention="power_retention"``: ops/power_retention.py)
    declares ``s`` [L, slots, Hkv, D, rows] and ``z`` [L, slots, Hkv, zrows,
    D]; a Mamba-2 mixer (models/falcon_h1.py, ops/mamba2.py) ``h`` and the
    convolution's window ``conv``.

    It stands ALONE (``engine.cache=state``: no keys and values anywhere) or
    BESIDE a ``PagedKVCache`` (docs/hybrid_cache.md: a model that attends
    over pages AND keeps a row state; the planes then ride the paged launch's
    carry, ``PagedKVCache.v_carry``, and are rebound under THAT cache's
    dispatch lock).

    The contract the engine keeps with it:

    - ``allocate(slot)`` when a request is given the batch row ``slot`` (slot
      index = batch row: admission is bounded by free rows, so the pool has
      ``max_batch`` slots and never runs out under a row), ``free(slot)``
      when the row's request ends, fails or is preempted;
    - a slot is ZEROED when it changes hands, lazily: the first launch that
      carries tokens of the new owner starts at position 0 (``length(slot)
      == 0``) and the kernels then count whatever the last owner left as
      zero (alone: the launch plan's ``reset`` flags name the slot; beside
      pages: the model reads it off the launch's own positions). ``resets``
      counts those launches;
    - ``advance(slot, n)`` after a launch took ``n`` tokens of the row into
      the state. A state cannot be rolled back: ``rewind(slot)`` forgets the
      whole sequence (the next launch recomputes it from position 0), which
      is also how a preempted request comes back. Beside pages the page
      pool's slot length is the row's length, and ``length`` here only says
      whether the slot has been counted as zero for its owner yet.

    ``dispatch_lock`` and the rebinding of the planes under it follow
    ``PagedKVCache``: the step donates the pools and returns them."""

    __guarded_by__ = {"dispatch_lock": ("planes", "s", "z")}

    def __init__(self, init_state, n_slots: int):
        self.n_slots = int(n_slots)
        self._init_state = init_state
        self.planes = self._fresh()
        self.dispatch_lock = threading.Lock()
        self._lengths = np.zeros(self.n_slots, np.int64)
        self._in_use = np.zeros(self.n_slots, bool)
        self.in_use_peak = 0
        self.resets = 0
        self.rewinds = 0

    def _fresh(self) -> Dict[str, object]:
        """The model's planes, zero; a bare ``(s, z)`` pair (power
        retention's ``init_state``) is named."""
        planes = self._init_state(self.n_slots)
        return planes if isinstance(planes, dict) else dict(zip("sz", planes))

    def reinit_if_lost(self) -> bool:
        """After a failed launch that consumed (donated) the planes: fresh
        zero planes. Every live sequence's state went with them; their
        requests were failed by the step-failure path."""
        if not any(getattr(a, "is_deleted", lambda: False)()
                   for a in self.planes.values()):
            return False
        self.planes = self._fresh()  # tpuserve: ignore[TPU301] recovery: no launch in flight
        return True

    # power retention's two planes by name: its step takes and returns them
    # as two operands (llm/engine.py ``_ragged_state_step``)
    @property
    def s(self):
        return self.planes["s"]

    @s.setter
    def s(self, value):  # tpuserve: ignore[TPU301] lock held by caller
        self.planes["s"] = value

    @property
    def z(self):
        return self.planes["z"]

    @z.setter
    def z(self, value):  # tpuserve: ignore[TPU301] lock held by caller
        self.planes["z"] = value

    # -- slots ---------------------------------------------------------------

    def allocate(self, slot: int) -> None:
        if self._in_use[slot]:
            raise RuntimeError("state slot {} is already owned".format(slot))
        self._in_use[slot] = True
        self._lengths[slot] = 0
        self.in_use_peak = max(self.in_use_peak, int(self._in_use.sum()))

    def free(self, slot: int) -> None:
        """Idempotent: teardown paths free a row without knowing whether its
        request ever reached a launch."""
        self._in_use[slot] = False
        self._lengths[slot] = 0

    def owned(self, slot: int) -> bool:
        return bool(self._in_use[slot])

    def length(self, slot: int) -> int:
        """Tokens of the slot's sequence that the state has taken in."""
        return int(self._lengths[slot])

    def advance(self, slot: int, tokens: int) -> None:
        if self._lengths[slot] == 0 and tokens > 0:
            self.resets += 1     # that launch counted the slot as zero
        self._lengths[slot] += int(tokens)

    def rewind(self, slot: int) -> None:
        self._lengths[slot] = 0
        self.rewinds += 1

    # -- accounting ----------------------------------------------------------

    @property
    def in_use(self) -> int:
        return int(self._in_use.sum())

    @property
    def bytes_per_slot(self) -> int:
        return int(sum(a.nbytes // a.shape[1] for a in self.planes.values()))

    def pool_bytes(self) -> int:
        return int(sum(a.nbytes for a in self.planes.values()))

    def snapshot(self) -> Dict[str, object]:
        first = next(iter(self.planes.values()))
        out = {
            "slots": self.n_slots,
            "in_use": self.in_use,
            "in_use_peak": int(self.in_use_peak),
            "bytes_per_slot": self.bytes_per_slot,
            "bytes": self.pool_bytes(),
            "resets": int(self.resets),
            "rewinds": int(self.rewinds),
            "dtype": str(first.dtype),
            "planes": {k: list(a.shape) for k, a in self.planes.items()},
        }
        # the names PR 26's readers and dashboards know
        out.update({k + "_shape": list(a.shape)
                    for k, a in self.planes.items() if k in ("s", "z")})
        return out
