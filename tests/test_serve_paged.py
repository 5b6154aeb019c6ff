"""Property-based lifecycle fuzz for block-paged continuous serving.

The paged-mode contract (serve/continuous.py + serve/paged.py):

* **bitwise solo parity** — in digital greedy mode every request's tokens
  are identical to serving it alone, however its prompt was chunked into
  pages and however its neighbours churned (admission order, page
  shuffling, retire-mid-chunk, backpressure stalls change *nothing*);
* **page-economy invariants** — after every step, each physical page is
  in exactly one of {free list, one slot's private list, the prefix
  cache's shared set, leaked} — the refcount-aware pool partition
  ``free + leaked + Σ private + shared = n_pages − 1`` — the trash page 0
  is in none, live block-table rows mirror shared references then private
  ownership exactly, and once the trace drains everything except the
  resident ref==0 cache pages is back on the free list;
* **quarantine accounting** — a faulted slot's private pages leak (never
  re-issued), its shared references are merely released, and the slot
  never hosts another request (satellite: the dead-slot re-admission
  regression).

The fuzz runs ≥ 200 generated traces (110 per config: gpt2-large is MHA,
command-r-35b is RoPE + GQA — the two fused-decode kernel families) with
prompt lengths hitting the paging corner cases: 1 token, page_size ± 1,
exact page multiples, and 3x the prefill chunk (longer than any pinned
admission width the contiguous path would have locked). Half the
generated prompts are truncations of a small pool of shared long prompts,
so traces mix shared-prefix requests organically and the prefix cache
(on by default in paged mode since PR 8) sees hits, promotions, and
evictions under the same bitwise-parity oracle as cold requests. The page
pool is deliberately undersized (8 allocatable pages for 3 slots x
up to 4-page requests) so admission backpressure and retire-reissue churn
occur organically inside the traces. `tests/_hypothesis_compat.py` keeps
the sweep deterministic when hypothesis isn't installed.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ExecConfig
from repro.serve import ContinuousBatcher, GenerationEngine, Request
from repro.serve.paged import PageAllocator

from conftest import tiny_config
from _hypothesis_compat import given, settings, strategies as st

PS = 8            # page size AND prefill chunk: 8 divides nothing tested
N_SLOTS = 3
N_PAGES = 9       # 8 allocatable: 3 slots x 4-page requests MUST contend
MAX_LEN = 64
LENGTHS = (1, PS - 1, PS, PS + 1, 2 * PS, 3 * PS)  # paging corner cases

_ENGINES: dict = {}
_SOLO: dict = {}


def _engine(name):
    """One engine (and so one compiled executable set) per config for the
    whole module — the fuzz's device cost is per-step, not per-trace."""
    if name not in _ENGINES:
        cfg = tiny_config(get_config(name))
        ec = ExecConfig(mode="digital", fused_attention=True)
        eng = GenerationEngine(cfg, None, ec, max_len=MAX_LEN)
        eng.params = eng.model.init(jax.random.PRNGKey(0))
        _ENGINES[name] = eng
    return _ENGINES[name]


def _prompt(L, cseed, shared=False):
    """Deterministic prompt content per (length, content-seed): a small
    pool keeps the memoized solo oracle's hit rate high across traces.
    ``shared`` prompts are truncations of ONE 3-page pool prompt per
    cseed, so requests of different lengths share page-aligned prefixes
    — the traffic shape the prefix cache exists for."""
    if shared:
        return _prompt(3 * PS, cseed)[:L]
    rng = np.random.default_rng(100_000 * L + cseed)
    return rng.integers(0, 255, size=L, dtype=np.int64).tolist()


def _solo(name, L, cseed, n_new, shared=False):
    """Memoized solo-generation oracle (the parity reference)."""
    key = (name, L, cseed, n_new, shared)
    if key not in _SOLO:
        eng = _engine(name)
        prompt = np.asarray(_prompt(L, cseed, shared), np.int32)
        _SOLO[key] = [int(t) for t in eng.generate(prompt[None, :], n_new)[0]]
    return _SOLO[key]


def _check_invariants(cb):
    """The page-economy assertions run after EVERY step of every trace."""
    a = cb.allocator
    a.assert_invariants()  # exact partition, refcounts, no double-holds
    # the refcount-aware pool partition, spelled out (satellite 3):
    assert (a.n_free + a.n_leaked + a.pages_in_use + a.n_shared
            == cb.n_pages - 1)
    for slot, s in enumerate(cb.slots):
        owned = a.owned(slot)
        refs = a.refs(slot)
        row = cb.block_table[slot]
        if s is not None:
            # a live row maps its shared references (prefix-cache hits +
            # its own promotions) then its private pages, in order, then 0s
            mapped = refs + owned
            assert list(row[: len(mapped)]) == mapped
            assert not row[len(mapped):].any()
        else:
            assert not owned and not refs and not row.any()
    for slot in cb.dead_slots:
        # quarantined slots never host a request or map a page again
        assert cb.slots[slot] is None
        assert not cb.block_table[slot].any()


def _fuzz_trace(name, trace_seed):
    rng = np.random.default_rng(trace_seed)
    eng = _engine(name)
    cb = ContinuousBatcher(eng, n_slots=N_SLOTS, page_size=PS,
                           n_pages=N_PAGES)
    assert cb.paged  # decoder-only all-attn models serve paged by default
    reqs = []
    for rid in range(int(rng.integers(2, 6))):
        L = int(LENGTHS[rng.integers(0, len(LENGTHS))])
        cseed = int(rng.integers(0, 3))
        n_new = int(rng.integers(1, 5))
        # half the prompts truncate a shared pool prompt: same-cseed
        # requests then share page-aligned prefixes and the trace
        # exercises prefix-cache hits/promotions against the same oracle
        shared = bool(rng.integers(0, 2))
        reqs.append((Request(rid, _prompt(L, cseed, shared), n_new=n_new),
                     L, cseed, shared))
    for r, _, _, _ in reqs:
        cb.submit(r)
    steps, max_in_use = 0, 0
    while cb.queue or any(s is not None for s in cb.slots):
        cb.step()
        steps += 1
        assert steps < 500, "trace failed to drain"
        _check_invariants(cb)
        max_in_use = max(max_in_use, cb.allocator.pages_in_use)
    # drained: nothing leaked (no faults here), nothing still owned by a
    # retired slot, and every page is back on the free list EXCEPT the
    # ref==0 prefix-cache pages — resident shared pages ARE the cache
    assert cb.allocator.pages_in_use == 0
    assert cb.allocator.n_leaked == 0
    assert cb.allocator.n_free + cb.allocator.n_shared == N_PAGES - 1
    assert cb.allocator.n_shared == len(cb.prefix)
    assert max_in_use <= N_PAGES - 1
    for r, L, cseed, shared in reqs:
        done = cb.done[r.rid]
        assert done.error is None, done.error
        got = [int(t) for t in done.result]
        want = _solo(name, L, cseed, r.n_new, shared)
        assert got == want, (
            f"rid={r.rid} P={L} n_new={r.n_new} shared={shared} diverged "
            f"from solo: {got} != {want}")


@settings(max_examples=110, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_paged_lifecycle_fuzz_mha(trace_seed):
    """110 random traces on the MHA config (gpt2-large tiny): bitwise
    solo parity + page-economy invariants after every step."""
    _fuzz_trace("gpt2-large", trace_seed)


@settings(max_examples=110, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_paged_lifecycle_fuzz_gqa(trace_seed):
    """Same 110-trace property on RoPE + grouped-query KV
    (command-r-35b tiny): the GQA-native paged decode kernel family."""
    _fuzz_trace("command-r-35b", trace_seed)


# ---------------------------------------------------------------------------
# directed lifecycle tests: the acceptance scenarios, pinned explicitly
# ---------------------------------------------------------------------------

def test_long_prompt_streams_through_chunks():
    """A prompt 3x the prefill chunk — longer than any contiguous
    admission width could pin without resizing the pool — serves
    end-to-end through chunked prefill-into-slot, bitwise equal to solo."""
    eng = _engine("gpt2-large")
    cb = ContinuousBatcher(eng, n_slots=N_SLOTS, page_size=PS,
                           n_pages=N_PAGES, prefill_chunk=PS)
    L = 3 * PS
    cb.submit(Request(0, _prompt(L, 0), n_new=4))
    done = cb.run_all()
    assert [int(t) for t in done[0].result] == _solo("gpt2-large", L, 0, 4)
    # 24 prompt tokens at chunk width 8 is exactly 3 chunk calls
    assert cb.chunk_calls == 3
    assert cb.model_calls == cb.chunk_calls + cb.decode_steps


def test_pool_exhaustion_backpressures_in_fifo_order():
    """Two 3-page requests against a 4-page pool: the second stays queued
    (admission returns None, no side effects) until the first retires and
    frees its pages — and completion order stays FIFO."""
    eng = _engine("gpt2-large")
    cb = ContinuousBatcher(eng, n_slots=N_SLOTS, page_size=PS, n_pages=5)
    for rid in range(2):
        cb.submit(Request(rid, _prompt(2 * PS, rid), n_new=3))
    saw_queued_while_running = False
    order = []
    while cb.queue or any(s is not None for s in cb.slots):
        retired = cb.step()
        order.extend(retired)
        _check_invariants(cb)
        assert cb.allocator.pages_in_use <= 4
        if cb.queue and any(s is not None for s in cb.slots):
            saw_queued_while_running = True
    assert saw_queued_while_running  # the pool really was too small
    assert order == [0, 1]
    for rid in range(2):
        assert cb.done[rid].error is None
        assert [int(t) for t in cb.done[rid].result] == _solo(
            "gpt2-large", 2 * PS, rid, 3)


def test_submit_rejects_requests_beyond_capacity():
    eng = _engine("gpt2-large")
    cb = ContinuousBatcher(eng, n_slots=2, page_size=PS, n_pages=3)
    with pytest.raises(ValueError, match="exceeds the block table"):
        cb.submit(Request(0, _prompt(PS, 0) * 8, n_new=1))  # P = max_len
    with pytest.raises(ValueError, match="pages"):
        cb.submit(Request(1, _prompt(3 * PS, 0), n_new=1))  # 3 pages > 2
    with pytest.raises(ValueError, match="empty prompt"):
        cb.submit(Request(2, [], n_new=1))
    assert not cb.queue


def test_paged_mode_gating():
    """prefill_len pins the contiguous path; paged=True refuses it, and
    models whose caches have no paged form refuse paged=True with the
    layout named."""
    eng = _engine("gpt2-large")
    with pytest.raises(ValueError, match="pass prefill_chunk"):
        ContinuousBatcher(eng, paged=True, prefill_len=16)
    # explicit prefill_len silently selects contiguous (back-compat)
    assert not ContinuousBatcher(eng, prefill_len=16).paged
    assert ContinuousBatcher(eng, paged=False).paged is False
    for name, frag in (("jamba-v0.1-52b", "paged cache form"),
                       ("gemma3-4b", "paged cache form"),
                       ("whisper-tiny", "encoder-decoder")):
        why = ContinuousBatcher.pageable_reason(
            dataclasses.replace(eng, cfg=get_config(name)))
        assert why is not None and frag in why


# ---------------------------------------------------------------------------
# quarantine accounting (satellite): dead slots keep their pages leaked
# ---------------------------------------------------------------------------

def _faulty_engine(fault_rate, seed=0):
    """Digital engine with decode attention routed through the noisy
    staged backend at the given fault rate (tests/test_serve_continuous.py
    documents the idiom); paged serving reaches it through the
    gather-degrade path, so faults land per slot row exactly as on the
    contiguous pool."""
    from repro.hw.noise import NoiseConfig
    nz = dataclasses.replace(NoiseConfig.preset("worst_case", seed=seed),
                             fault_rate=fault_rate)
    ec = ExecConfig(mode="digital", noise=nz).with_ops(
        attention_decode="raceit_noisy_staged")
    cfg = tiny_config(get_config("gpt2-large"))
    eng = GenerationEngine(cfg, None, ec, max_len=MAX_LEN)
    eng.params = eng.model.init(jax.random.PRNGKey(0))
    return eng


def test_quarantined_slot_leaks_pages_and_never_readmits():
    """The quarantine-accounting regression: after a decode fault kills a
    slot, (a) its pages leave the economy for good — never re-issued to a
    later admission — and (b) every later request is served by the
    surviving slots only; the dead slot's block-table row stays zero."""
    from repro.hw.noise import fault_rows, site_key

    eng = _faulty_engine(0.5, seed=1)
    # prefix cache off: this is the PR 7 regression pinned on *private*
    # page counts — promotion would move the full prompt page to shared
    # (released, not leaked, on quarantine) and change the arithmetic;
    # the shared-page quarantine contract lives in test_serve_prefix.py
    cb = ContinuousBatcher(eng, n_slots=2, page_size=PS,
                           n_pages=1 + 2 * (MAX_LEN // PS),
                           prefix_cache=False)
    # pin the scenario: at seed 1 the (2,)-row fault map kills slot 1
    nz = eng.plan.exec_cfg.noise
    fmap = np.asarray(fault_rows(nz, site_key(nz, "decode_fault", (2,)), 2))
    assert list(fmap) == [False, True]

    for rid in range(4):
        cb.submit(Request(rid, _prompt(PS + 1, rid % 3), n_new=3))
    leaked_after_fault = None
    while cb.queue or any(s is not None for s in cb.slots):
        cb.step()
        _check_invariants(cb)  # leaked pages counted, never double-held
        if cb.dead_slots:
            if leaked_after_fault is None:
                leaked_after_fault = cb.allocator.n_leaked
            # the leak never shrinks and the dead slot never comes back
            assert cb.allocator.n_leaked == leaked_after_fault
            assert cb.dead_slots == {1}
    assert leaked_after_fault == 2  # ceil((9 + 3 - 1) / 8) pages, leaked
    # exactly one request died (structured error), the rest completed on
    # the surviving slot with clean results
    failed = [r for r in cb.done.values() if r.error is not None]
    assert len(failed) == 1
    assert failed[0].error.stage in ("decode", "prefill")
    for r in cb.done.values():
        if r.error is None:
            assert len(r.result) == 3
    # end state: everything not leaked is back on the free list
    assert cb.allocator.pages_in_use == 0
    assert cb.allocator.n_free == cb.n_pages - 1 - leaked_after_fault


def test_all_slots_dead_drains_queue_and_deadlock_names_leak():
    """Every slot faulting must not hang run_all (stage='admit' errors),
    and the deadlock error names the leaked-page count — the operator's
    signal that the pool shrank for good."""
    eng = _faulty_engine(1.0, seed=1)
    cb = ContinuousBatcher(eng, n_slots=1, page_size=PS, n_pages=3)
    for rid in range(2):
        cb.submit(Request(rid, _prompt(PS - 1, rid), n_new=4))
    done = cb.run_all()  # must terminate
    assert sorted(done) == [0, 1]
    assert all(done[r].error is not None for r in done)
    assert done[1].error.stage == "admit"
    assert cb.dead_slots == {0}
    _check_invariants(cb)


def test_allocator_unit_invariants():
    """PageAllocator alone: alloc is all-or-nothing, double-admit raises,
    leak+free partition the pool exactly."""
    a = PageAllocator(6)  # pages 1..5
    assert a.alloc(0, 6) is None and a.n_free == 5  # no side effects
    p0 = a.alloc(0, 2)
    p1 = a.alloc(1, 2)
    assert len(p0) == 2 and len(p1) == 2 and not set(p0) & set(p1)
    with pytest.raises(ValueError, match="already holds"):
        a.alloc(0, 1)
    a.assert_invariants()
    a.leak_slot(0)
    a.free_slot(1)
    a.assert_invariants()
    assert a.n_leaked == 2 and a.n_free == 3 and a.pages_in_use == 0
    # leaked pages are gone: even an ask for "everything" can't get them
    assert a.alloc(2, 4) is None
    got = a.alloc(2, 3)
    assert got is not None and not set(got) & set(p0)
    a.assert_invariants()
    with pytest.raises(ValueError, match="at least"):
        PageAllocator(1)
