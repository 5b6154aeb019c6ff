"""Slot-level continuous batching: solo parity, single-compile, occupancy.

The `ContinuousBatcher` contract (serve/continuous.py): requests retire
and admit mid-stream over a fixed slot pool, every slot decodes at its own
cache fill level (per-row ``kv_len`` down to the kernels), and in digital
greedy mode a request's tokens are **bitwise identical** to serving it
alone — however its neighbours churn. The pool's shapes are pinned, so the
whole run compiles exactly one decode executable and one admission-prefill
executable (the bucketed scheduler's per-shape re-jit, satellite 6).
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ExecConfig
from repro.models import Model
from repro.serve import (BatchScheduler, ContinuousBatcher, GenerationEngine,
                         Request)

from conftest import tiny_config


def _engine(key, name="gpt2-large", exec_cfg=ExecConfig(), **kw):
    cfg = tiny_config(get_config(name))
    model = Model(cfg, exec_cfg)
    params = model.init(key)
    return GenerationEngine(cfg, params, exec_cfg=exec_cfg, max_len=64, **kw)


def _mixed_trace(rng, n=5):
    lens = (7, 3, 5, 2, 6, 4, 8)[:n]
    nnew = (4, 2, 6, 1, 3, 5, 2)[:n]
    return [Request(i, rng.integers(0, 255, ln).astype(np.int32), n_new=nn)
            for i, (ln, nn) in enumerate(zip(lens, nnew))]


# ---------------------------------------------------------------------------
# bitwise solo parity under churn (the CI continuous-batching smoke)
# ---------------------------------------------------------------------------

def test_continuous_matches_solo_digital(key):
    """More requests than slots, mixed lengths AND mixed n_new: every
    request's tokens are bitwise-identical to its solo run — retirement
    and admission mid-stream change nothing (digital greedy)."""
    eng = _engine(key)
    rng = np.random.default_rng(0)
    reqs = _mixed_trace(rng)
    solo = [eng.generate(r.prompt[None, :], r.n_new)[0] for r in reqs]
    cb = ContinuousBatcher(eng, n_slots=2)
    for r in reqs:
        cb.submit(Request(r.rid, r.prompt, n_new=r.n_new))
    done = cb.run_all()
    assert sorted(done) == [r.rid for r in reqs]
    for r, want in zip(reqs, solo):
        np.testing.assert_array_equal(done[r.rid].result, want,
                                      err_msg=f"request {r.rid} diverged")


def test_continuous_parity_rope_gqa_digital(key):
    """Same contract on a RoPE + grouped-query config (per-slot positions
    must reach RoPE, not just the masks)."""
    eng = _engine(key, name="command-r-35b")
    assert eng.cfg.n_kv_heads < eng.cfg.n_heads
    rng = np.random.default_rng(1)
    reqs = _mixed_trace(rng, n=4)
    solo = [eng.generate(r.prompt[None, :], r.n_new)[0] for r in reqs]
    cb = ContinuousBatcher(eng, n_slots=2)
    for r in reqs:
        cb.submit(Request(r.rid, r.prompt, n_new=r.n_new))
    done = cb.run_all()
    for r, want in zip(reqs, solo):
        np.testing.assert_array_equal(done[r.rid].result, want)


def test_continuous_single_compiled_step(key):
    """The whole mixed-length run reuses ONE decode executable and ONE
    prefill executable — the slot pool pins both shapes (satellite 6: the
    bucketed path re-jits per bucket shape). Paged mode (the default)
    streams admissions through the pinned (n_slots, prefill_chunk) chunk
    executable; the contiguous pin is the (1, prefill_len) solo prefill."""
    eng = _engine(key)
    rng = np.random.default_rng(2)
    cb = ContinuousBatcher(eng, n_slots=2)
    assert cb.paged  # decoder-only all-attn model: paged by default
    for r in _mixed_trace(rng):
        cb.submit(r)
    cb.run_all()
    assert eng._decode._cache_size() == 1
    assert eng._prefill_chunk._cache_size() == 1

    eng2 = _engine(key)
    cb2 = ContinuousBatcher(eng2, n_slots=2, paged=False)
    for r in _mixed_trace(rng):
        cb2.submit(r)
    cb2.run_all()
    assert eng2._decode._cache_size() == 1
    assert eng2._prefill._cache_size() == 1


def test_continuous_raceit_serving_smoke(key):
    """End-to-end on the raceit serving default: the plan resolves the
    per-row GQA decode backend and mixed traffic produces well-formed
    tokens (bitwise solo parity is the digital-mode guarantee; raceit
    couples slots only through whole-tensor activation scales)."""
    eng = _engine(key, name="command-r-35b", exec_cfg=ExecConfig.serving())
    assert eng.plan.backend("attention_decode") == "raceit_gqa_paged"
    rng = np.random.default_rng(3)
    cb = ContinuousBatcher(eng, n_slots=2)
    for r in _mixed_trace(rng, n=3):
        cb.submit(r)
    done = cb.run_all()
    for r in done.values():
        assert r.result.shape == (r.n_new,)
        assert (r.result >= 0).all() and (r.result < eng.cfg.vocab_size).all()


# ---------------------------------------------------------------------------
# slot lifecycle mechanics
# ---------------------------------------------------------------------------

def test_empty_slots_are_harmless(key):
    """More slots than requests: dead rows (kv_len 0) ride every decode
    step without perturbing the live request."""
    eng = _engine(key)
    rng = np.random.default_rng(4)
    p = rng.integers(0, 255, 5).astype(np.int32)
    solo = eng.generate(p[None, :], 4)[0]
    cb = ContinuousBatcher(eng, n_slots=4)
    cb.submit(Request(0, p, n_new=4))
    done = cb.run_all()
    np.testing.assert_array_equal(done[0].result, solo)


def test_n_new_one_retires_at_admission(key):
    """A 1-token request is satisfied by its prefill logits alone and must
    free its slot without consuming a decode step."""
    eng = _engine(key)
    rng = np.random.default_rng(5)
    cb = ContinuousBatcher(eng, n_slots=2)
    for i in range(3):
        cb.submit(Request(i, rng.integers(0, 255, 4).astype(np.int32),
                          n_new=1))
    done = cb.run_all()
    assert sorted(done) == [0, 1, 2]
    assert cb.decode_steps == 0 and cb.prefills == 3


def test_prompt_longer_than_pinned_width_rejected(key):
    eng = _engine(key)
    cb = ContinuousBatcher(eng, n_slots=2, prefill_len=4)
    with pytest.raises(ValueError):
        cb.submit(Request(0, np.arange(9, dtype=np.int32), n_new=2))
    with pytest.raises(ValueError):  # pinned width + n_new must fit max_len
        cb.submit(Request(1, np.arange(3, dtype=np.int32), n_new=61))


def test_jointly_infeasible_queue_fails_fast_with_state_intact(key):
    """Individually-acceptable requests can be jointly infeasible once the
    pool width locks to the longest queued prompt; that must surface at
    lock time (nothing admitted, queue intact) — not as a crash after
    other requests are already in flight."""
    eng = _engine(key)  # max_len = 64; paged=False: the shared-width lock
    cb = ContinuousBatcher(eng, n_slots=2, paged=False)  # is contiguous-only
    cb.submit(Request(0, np.arange(4, dtype=np.int32), n_new=60))  # 4+60 ok
    cb.submit(Request(1, np.arange(8, dtype=np.int32), n_new=1))   # width 8
    with pytest.raises(ValueError, match="jointly infeasible"):
        cb.run_all()
    assert len(cb.queue) == 2 and all(s is None for s in cb.slots)


# ---------------------------------------------------------------------------
# occupancy: the tokens-per-model-call win the bench row gates
# ---------------------------------------------------------------------------

def test_continuous_beats_bucketed_occupancy(key):
    """On a mixed-n_new trace the bucketed scheduler idles early-finished
    slots until the bucket drains; the slot pool retires/admits
    mid-stream. Deterministic counter contract: >= 1.3x decode tokens per
    decode step — the same metric the serve/continuous_occupancy bench
    row pins in CI (prefill is accounted separately: admission prefills
    are per-request, bucket prefills bucket-wide)."""
    eng = _engine(key)
    rng = np.random.default_rng(6)
    mk = lambda: [Request(i, rng.integers(0, 255, ln).astype(np.int32),
                          n_new=nn)
                  for i, (ln, nn) in enumerate(
                      zip((7, 3, 5, 2, 6, 4, 5, 3), (8, 1, 2, 6, 1, 2, 8, 1)))]
    sched = BatchScheduler(eng, bucket_size=4)
    for r in mk():
        sched.submit(r)
    sched.run_all()
    cb = ContinuousBatcher(eng, n_slots=4)
    for r in mk():
        cb.submit(r)
    cb.run_all()
    assert sched.tokens_out == cb.tokens_out
    bucketed = sched.decode_tokens / sched.decode_steps
    continuous = cb.decode_tokens / cb.decode_steps
    assert continuous >= 1.3 * bucketed, (continuous, bucketed)


# ---------------------------------------------------------------------------
# fail-safe serving: structured errors + slot quarantine under device faults
# ---------------------------------------------------------------------------

def test_empty_prompt_rejected(key):
    """The first token is sampled from the prompt's last position, so an
    empty prompt has nothing to prefill — reject at submit, like the
    too-long case, instead of crashing inside the admission gather."""
    eng = _engine(key)
    cb = ContinuousBatcher(eng, n_slots=2)
    with pytest.raises(ValueError, match="empty prompt"):
        cb.submit(Request(0, np.zeros(0, dtype=np.int32), n_new=2))
    assert not cb.queue


def test_duplicate_rid_rejected_at_submit(key):
    """rids key the result map and (paged) page ownership: a silent
    re-submit would overwrite the first request's ``done`` entry and
    cross-wire allocator slots, so the batcher raises at submit —
    malformed traffic, not operational backpressure. The collision is
    caught whether the first holder is queued, running, or already done."""
    eng = _engine(key)
    cb = ContinuousBatcher(eng, n_slots=2)
    rng = np.random.default_rng(0)
    cb.submit(Request(3, rng.integers(0, 255, 4).astype(np.int32), n_new=2))
    with pytest.raises(ValueError, match="duplicate rid 3"):
        cb.submit(Request(3, rng.integers(0, 255, 5).astype(np.int32),
                          n_new=1))  # collides while QUEUED
    cb.run_all()
    assert cb.done[3].error is None
    with pytest.raises(ValueError, match="duplicate rid 3"):
        cb.submit(Request(3, rng.integers(0, 255, 4).astype(np.int32),
                          n_new=2))  # collides while DONE
    assert cb.done[3].result is not None  # the original survived intact
    # distinct rids keep flowing
    cb.submit(Request(4, rng.integers(0, 255, 4).astype(np.int32), n_new=1))
    cb.run_all()
    assert cb.done[4].error is None


def _faulty_engine(key, fault_rate, seed=0):
    """Digital engine with ONLY the decode attention routed through the
    noisy staged backend, all sigmas at worst_case but fault_rate as
    given: the no-fault run stays deterministic noisy, and the fault map
    is the sole difference between reference and fault runs."""
    import dataclasses

    from repro.hw.noise import NoiseConfig
    nz = dataclasses.replace(NoiseConfig.preset("worst_case", seed=seed),
                             fault_rate=fault_rate)
    ec = ExecConfig(mode="digital", noise=nz).with_ops(
        attention_decode="raceit_noisy_staged")
    return _engine(key, exec_cfg=ec)


def test_decode_fault_retires_only_affected_slot(key):
    """A stuck-row fault mid-decode must (a) end the affected request with
    a structured RequestError instead of emitting NaN-driven garbage
    tokens, (b) quarantine that slot (the fault map is static per
    executable — re-admitting would re-fault), and (c) leave the
    surviving slot's tokens BITWISE identical to a no-fault run of the
    same noisy config (the staged decode path is row-independent)."""
    from repro.hw.noise import fault_rows, site_key

    def run(fault_rate):
        eng = _faulty_engine(key, fault_rate, seed=1)
        cb = ContinuousBatcher(eng, n_slots=2, prefill_len=6)
        rng = np.random.default_rng(7)
        for rid in range(2):
            cb.submit(Request(rid, rng.integers(0, 255, 6).astype(np.int32),
                              n_new=5))
        cb.run_all()
        return cb

    flt = run(0.5)
    # pin the scenario: at seed 1 the (seed, "decode_fault", n_slots=2)
    # map faults exactly slot 1 — recomputed here from first principles so
    # the test documents, not just assumes, which row dies
    nz = flt.engine.plan.exec_cfg.noise
    fmap = np.asarray(fault_rows(nz, site_key(nz, "decode_fault", (2,)), 2))
    assert list(fmap) == [False, True]

    err = flt.done[1].error
    assert flt.done[1].result is None
    assert err is not None and err.rid == 1
    assert err.stage == "decode" and err.step >= 1
    assert flt.dead_slots == {1}

    ref = run(0.0)
    assert flt.done[0].error is None and ref.done[0].error is None
    np.testing.assert_array_equal(flt.done[0].result, ref.done[0].result)


def test_all_slots_quarantined_drains_queue(key):
    """Every slot faulting must not hang run_all: once the pool is fully
    quarantined the queue drains with stage='admit' errors."""
    eng = _faulty_engine(key, fault_rate=1.0, seed=1)
    cb = ContinuousBatcher(eng, n_slots=1, prefill_len=5)
    rng = np.random.default_rng(8)
    for rid in range(3):
        cb.submit(Request(rid, rng.integers(0, 255, 5).astype(np.int32),
                          n_new=4))
    done = cb.run_all()  # must terminate
    assert sorted(done) == [0, 1, 2]
    assert all(done[r].error is not None and done[r].result is None
               for r in done)
    assert done[0].error.stage == "decode"
    assert {done[1].error.stage, done[2].error.stage} == {"admit"}
    assert cb.dead_slots == {0}
