"""Content-addressed prefix cache over the paged pool: parity + lifecycle.

The prefix-cache contract (serve/prefix.py + the allocator transitions in
serve/paged.py):

* **bitwise hit parity** — a request admitted over cached prefix pages
  produces tokens bitwise identical to the cold path (and to solo
  generation) in digital greedy mode, on both fused-decode kernel
  families (gpt2-large tiny = MHA, command-r-35b tiny = RoPE + GQA):
  a shared page holds exactly the KV the request would have computed
  (same tokens, same absolute positions, per-tensor quantizer scales)
  and the paged kernels are page-permutation invariant;
* **refcounted sharing** — promotion moves a slot's first private page
  into the shared set (ref 1, refs-then-owned row order), hits acquire
  (ref += 1), retire/quarantine release, and only ref==0 pages are
  evictable, LRU-first, pinned hits excluded;
* **quarantine** — a faulted slot leaks its *private* pages only; its
  shared references are released and the cached pages stay servable.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ExecConfig
from repro.serve import ContinuousBatcher, GenerationEngine, Request
from repro.serve.paged import PageAllocator
from repro.serve.prefix import PrefixCache, _ROOT, page_digest

from conftest import tiny_config
from test_serve_paged import (_check_invariants, _engine, _faulty_engine,
                              _prompt, _solo, MAX_LEN, N_PAGES, N_SLOTS, PS)


# ---------------------------------------------------------------- hashing

def test_page_digest_chains_and_separates():
    d1 = page_digest(b"", list(range(8)))
    d2 = page_digest(b"", list(range(8)))
    assert d1 == d2 and len(d1) == 16
    # content-sensitive ...
    assert d1 != page_digest(b"", list(range(1, 9)))
    # ... and CHAIN-sensitive: the same tokens after a different history
    # must key a different page (same page content at a different
    # absolute position holds different KV)
    assert page_digest(d1, [7]) != page_digest(d2 + b"x", [7])
    # no width ambiguity: [1, 23] vs [12, 3]
    assert page_digest(b"", [1, 23]) != page_digest(b"", [12, 3])


# ------------------------------------------------- cache unit (no model)

def _pool(n_pages=9, ps=4):
    a = PageAllocator(n_pages)
    return a, PrefixCache(a, ps)


def _feed(a, pc, slot, tokens, ps):
    """Stream a prompt's full pages through promote, like the batcher
    (the chain starts at the cache's root, exactly as ``match`` walks it)."""
    n_full = len(tokens) // ps
    pages = a.alloc(slot, n_full)
    digest = _ROOT
    for i, page in enumerate(pages):
        ok, digest = pc.promote(slot, page, digest, tokens[i * ps:(i + 1) * ps])
        assert ok
    return pages, digest


def test_match_walks_chain_and_caps_last_token():
    a, pc = _pool()
    toks = list(range(12))  # 3 full pages at ps=4
    pages, _ = _feed(a, pc, 0, toks, 4)
    a.free_slot(0)
    # full-prefix lookup: the cap keeps the LAST token uncached — its
    # logits seed generation, so at 12 tokens only (12-1)//4 = 2 pages hit
    hits, digest, covered = pc.match(toks)
    assert [p for _, p in hits] == pages[:2] and covered == 8
    # 13+ tokens may hit all 3
    hits13, _, covered13 = pc.match(toks + [99])
    assert [p for _, p in hits13] == pages and covered13 == 12
    # divergence stops the walk at the first mismatched page
    fork = toks[:4] + [77] + toks[5:]
    hits_f, _, covered_f = pc.match(fork)
    assert [p for _, p in hits_f] == pages[:1] and covered_f == 4
    # match is pure: counters and LRU untouched until commit
    assert pc.lookups == 0 and pc.hit_pages == 0
    pc.commit(hits, 3)
    assert pc.lookups == 1 and pc.hit_pages == 2 and pc.miss_pages == 1
    assert pc.hit_requests == 1


def test_promote_enforces_row_order_and_stops_on_duplicate():
    a, pc = _pool()
    toks = list(range(8))
    _feed(a, pc, 0, toks, 4)
    # slot 1 streamed the same prefix concurrently: its first page's
    # digest is already cached -> promote refuses (False) with NO side
    # effects; the caller must stop walking (promo_dead)
    pages1 = a.alloc(1, 2)
    ok, _ = pc.promote(1, pages1[0], _ROOT, toks[:4])
    assert not ok
    assert a.owned(1) == pages1  # still private, row order intact
    # promotion must walk in order: page[1] before page[0] raises
    with pytest.raises(ValueError, match="first private page"):
        pc.promote(1, pages1[1], b"", toks[:4])
    a.assert_invariants()


def test_lru_eviction_is_ref0_only_and_pin_aware():
    a, pc = _pool(n_pages=9, ps=4)
    t1, t2 = list(range(0, 8)), list(range(100, 108))
    p1, _ = _feed(a, pc, 0, t1, 4)   # older entries
    p2, _ = _feed(a, pc, 1, t2, 4)   # newer entries
    # slot 0 retires -> t1's pages at ref 0; slot 1 keeps t2 pinned
    a.free_slot(0)
    assert pc.n_evictable() == 2
    # LRU order: t1's chain evicts before t2's would
    assert pc.evict(1) == 1
    assert a.is_shared(p1[1]) and not a.is_shared(p1[0])  # oldest first
    # pinning excludes a page even at ref 0
    a.free_slot(1)
    assert pc.evict(10, pinned=frozenset([p2[0]])) == 2  # p1[1] + p2[1]
    assert a.is_shared(p2[0]) and pc.evictions == 3
    # referenced pages are never victims: re-acquire and try to evict
    a.acquire(2, p2[0])
    assert pc.evict(10) == 0
    with pytest.raises(ValueError, match="not an evictable"):
        a.evict_shared(p2[0])
    a.assert_invariants()


def test_allocator_shared_transitions():
    a = PageAllocator(6)
    pages = a.alloc(0, 3)
    with pytest.raises(ValueError, match="not shared"):
        a.acquire(1, pages[0])
    a.promote(0, pages[0])
    assert a.shared_ref(pages[0]) == 1 and a.refs(0) == [pages[0]]
    assert a.owned(0) == pages[1:]
    a.acquire(1, pages[0])
    assert a.shared_ref(pages[0]) == 2
    # quarantine: slot 0's PRIVATE pages leak, its shared ref releases
    a.leak_slot(0)
    assert a.n_leaked == 2 and a.shared_ref(pages[0]) == 1
    a.free_slot(1)
    assert a.shared_ref(pages[0]) == 0  # evictable, still cached
    a.assert_invariants()
    # n == 0 is a valid reservation (a would-be full-hit admission)
    assert a.alloc(2, 0) == []
    a.acquire(2, pages[0])
    a.free_slot(2)
    # a slot holding only shared refs still blocks re-admission: alloc
    # refuses until the refs are released
    a.acquire(3, pages[0])
    with pytest.raises(ValueError, match="already holds"):
        a.alloc(3, 1)
    a.release_refs(3)
    assert a.alloc(3, 1) is not None
    a.assert_invariants()


# ------------------------------------------- end-to-end (tiny models)

def _shared_prefix_trace(cb):
    """Submit 4 requests sharing a 2-page prefix with distinct
    page-misaligned tail lengths (truncations of one pool prompt);
    returns [(rid, L, cseed, shared)] for the solo oracle."""
    meta = []
    for rid, L in enumerate((2 * PS + 3, 2 * PS + 1, 3 * PS, 2 * PS + 5)):
        cb.submit(Request(rid, _prompt(L, 0, shared=True), n_new=3))
        meta.append((rid, L, 0, True))
    return meta


@pytest.mark.parametrize("name", ["gpt2-large", "command-r-35b"])
def test_hit_path_bitwise_equals_cold_and_solo(name):
    """The acceptance criterion: prefix-hit requests' tokens are bitwise
    identical to the cold path across MHA and GQA — checked against BOTH
    a prefix-off run of the same trace and the memoized solo oracle."""
    eng = _engine(name)
    runs = {}
    for on in (False, True):
        cb = ContinuousBatcher(eng, n_slots=2, page_size=PS,
                               n_pages=N_PAGES + 4, prefix_cache=on)
        meta = _shared_prefix_trace(cb)
        while cb.queue or any(s is not None for s in cb.slots):
            cb.step()
            _check_invariants(cb)
        for rid, L, cseed, shared in meta:
            assert cb.done[rid].error is None
            got = [int(t) for t in cb.done[rid].result]
            assert got == _solo(name, L, cseed, 3, shared), (name, on, rid)
        runs[on] = cb
    hot = runs[True]
    assert hot.prefix.hit_pages > 0  # the trace really did share pages
    assert runs[False].prefix is None
    # hits skipped chunk work: strictly fewer chunk calls than cold
    assert hot.chunk_calls < runs[False].chunk_calls
    # and the step-clock sees it: later requests' TTFT improves
    assert (hot.metrics.ttft.summary()["mean"]
            < runs[False].metrics.ttft.summary()["mean"])


def test_identical_prompt_readmission_hits_and_matches():
    """Serving the same prompt twice in sequence: the second admission
    maps (P-1)//PS pages from cache, streams only the final partial page,
    and still matches the solo oracle exactly."""
    name = "gpt2-large"
    eng = _engine(name)
    cb = ContinuousBatcher(eng, n_slots=1, page_size=PS, n_pages=N_PAGES)
    L = 2 * PS + 1
    for rid in range(2):
        cb.submit(Request(rid, _prompt(L, 1, shared=True), n_new=2))
    cb.run_all()
    for rid in range(2):
        got = [int(t) for t in cb.done[rid].result]
        assert got == _solo(name, L, 1, 2, True)
    s = cb.prefix.stats()
    assert s["prefix_hit_pages"] == 2   # (17-1)//8 on the second admission
    assert s["prefix_hit_requests"] == 1 and s["prefix_promotions"] == 2


def test_eviction_under_pressure_end_to_end():
    """A pool too small to keep the cache resident: admission evicts
    ref==0 LRU pages to make room, and everything still matches solo."""
    name = "gpt2-large"
    eng = _engine(name)
    # 2 slots x up-to-4-page requests against 6 allocatable pages
    cb = ContinuousBatcher(eng, n_slots=2, page_size=PS, n_pages=7)
    reqs = []
    for rid in range(5):
        cseed, shared = (0, True) if rid % 2 == 0 else (rid, False)
        L = 2 * PS + (1 + rid) % 3
        cb.submit(Request(rid, _prompt(L, cseed, shared), n_new=2))
        reqs.append((rid, L, cseed, shared))
    steps = 0
    while cb.queue or any(s is not None for s in cb.slots):
        cb.step()
        steps += 1
        assert steps < 500
        _check_invariants(cb)
    assert cb.prefix.evictions > 0  # pressure really forced evictions
    for rid, L, cseed, shared in reqs:
        assert cb.done[rid].error is None, cb.done[rid].error
        got = [int(t) for t in cb.done[rid].result]
        assert got == _solo(name, L, cseed, 2, shared)


def test_quarantine_releases_shared_keeps_cache_servable():
    """A decode-faulted slot leaks only its private pages: its shared
    references release (back to ref 0), the cached pages stay resident,
    and a later identical request hits them and completes cleanly on the
    surviving slot."""
    from repro.hw.noise import fault_rows, site_key

    eng = _faulty_engine(0.5, seed=1)
    cb = ContinuousBatcher(eng, n_slots=2, page_size=PS,
                           n_pages=1 + 2 * (MAX_LEN // PS))
    nz = eng.plan.exec_cfg.noise
    fmap = np.asarray(fault_rows(nz, site_key(nz, "decode_fault", (2,)), 2))
    assert list(fmap) == [False, True]  # slot 1 faults at decode

    L = 2 * PS + 1  # 2 full (promotable) prompt pages + 1 streamed token
    for rid in range(4):
        cb.submit(Request(rid, _prompt(L, 2, shared=True), n_new=3))
    while cb.queue or any(s is not None for s in cb.slots):
        cb.step()
        _check_invariants(cb)
    assert cb.dead_slots == {1}
    # slot 1 lost the promotion race to slot 0 (promo_dead), so ALL 3 of
    # its pages were still private when it faulted — leaked, while the 2
    # shared prefix pages slot 0 promoted survive in the cache at ref 0
    assert cb.allocator.n_leaked == 3
    assert cb.allocator.n_shared == 2
    hits, _, _ = cb.prefix.match(_prompt(L, 2, shared=True))
    assert len(hits) == 2
    assert all(cb.allocator.shared_ref(p) == 0 for _, p in hits)
    failed = [r for r in cb.done.values() if r.error is not None]
    assert len(failed) == 1
    # the post-fault admissions HIT the cache the healthy slot built
    # (2 pages each); on the NOISY engine the clean solo oracle doesn't
    # apply, but the hit path must still be transparent: every healthy
    # request ran the same prompt on the same surviving row, so cold
    # (rid 0) and hit (rids 2, 3) outputs must be identical
    assert cb.prefix.hit_pages == 4
    healthy = [list(map(int, r.result)) for r in cb.done.values()
               if r.error is None]
    assert len(healthy) == 3
    assert all(got == healthy[0] for got in healthy)
