"""The output check fails what it must: the control (the reference at int4
in the program's place), run through the same comparison, and a run whose
served tokens are altered where they are produced. Where the mix shares
prefixes, the sample holds a request served from the prefix cache. Tiny
configuration on the CPU; the chip readings that set the real limits are
in PERF.md."""
import shutil
import time
from pathlib import Path

import jax
import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
CELL = {"name": "tiny.tiny-chat", "config": "tiny", "traffic": "tiny-chat",
        "chips": 1}
SEED = 2 ** 31 + 6


@pytest.fixture(scope="module")
def bench_dir(tmp_path_factory):
    bd = tmp_path_factory.mktemp("files") / "bench"
    shutil.copytree(ROOT / "bench", bd,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(DATA / "tiny.json", bd / "configs" / "tiny.json")
    for mix in ("tiny-chat", "tiny-sessions", "tiny-backlog"):
        shutil.copy(DATA / f"{mix}.json", bd / "traffic" / f"{mix}.json")
    return bd


def _run(bench_dir, cell=CELL, seconds=4.0):
    return harness.run_cell(cell, SEED, seconds, time.perf_counter(),
                            jax.devices()[0], bench_dir=bench_dir)


def test_program_passes_and_control_fails(bench_dir):
    run, checks = _run(bench_dir)
    assert harness.result_line(run, {}, checks)["correct"] is True, checks
    control = harness.check_outputs(run.cfg_file, SEED, run.window,
                                    run.sample, control_bits=4)
    assert harness.result_line(run, {}, control)["correct"] is False
    for name in ("mean_logit_gap", "worst_request_mean_gap"):
        assert checks[name]["value"] <= checks[name]["limit"]
    assert (control["worst_request_mean_gap"]["value"]
            >= control["mean_logit_gap"]["value"])
    # the program's numbers are those of the tokens it served
    items = [(r.spec.prompt, r.served) for r in run.sample]
    gaps, _ = harness.reference_gaps(run.cfg_file, SEED, items)
    assert harness.gap_numbers(gaps)["max_logit_gap"] == pytest.approx(
        checks["max_logit_gap"]["value"])


def test_altered_tokens_fail(bench_dir, monkeypatch):
    from repro.serve.engine import GenerationEngine
    sample = GenerationEngine._sample

    def altered(self, logits, rng):
        tok = sample(self, logits, rng)
        return (tok + 1) % logits.shape[-1]

    monkeypatch.setattr(GenerationEngine, "_sample", altered)
    run, checks = _run(bench_dir)
    line = harness.result_line(run, {}, checks)
    assert line["correct"] is False
    assert checks["mean_logit_gap"]["value"] > \
        run.cfg_file["check"]["mean_logit_gap"]
    assert checks["sampled_requests"]["value"] >= 1


def test_shared_prefix_sample_holds_a_cache_hit(bench_dir):
    cell = {"name": "tiny.tiny-sessions", "config": "tiny",
            "traffic": "tiny-sessions", "chips": 1}
    run, checks = _run(bench_dir, cell, seconds=6.0)
    assert run.extra["shares_prefix"]
    assert checks["sampled_prefix_hit_pages"]["limit"] == 1
    assert checks["sampled_prefix_hit_pages"]["value"] >= 1, checks
    assert any(r.hit_pages for r in run.sample[:2])
    assert harness.result_line(run, {}, checks)["correct"] is True, checks


def _stale_cache(decode, model):
    """The decode step returns the cache it was given, unchanged."""
    undonated = jax.jit(model.decode_step, static_argnames=("page_size",))

    def step(params, tok, cache, *args, **kw):
        logits, _ = undonated(params, tok, cache, *args, **kw)
        return logits, cache
    return step


def _half_batch(decode, model):
    """Half of the slots are left out: their rows get the other half's
    logits."""
    def step(*args, **kw):
        logits, cache = decode(*args, **kw)
        h = logits.shape[0] // 2
        return logits.at[h:].set(logits[: logits.shape[0] - h]), cache
    return step


BACKLOG = {"name": "tiny.tiny-backlog", "config": "tiny",
           "traffic": "tiny-backlog", "chips": 1}


def test_sound_backlog_passes(bench_dir):
    """The run the faults below break passes unbroken, every slot busy."""
    run, checks = _run(bench_dir, BACKLOG, seconds=6.0)
    assert harness.result_line(run, {}, checks)["correct"] is True, checks
    assert checks["sampled_requests"]["value"] >= 2


@pytest.mark.parametrize("fault", [_stale_cache, _half_batch],
                         ids=["stale_cache", "half_batch"])
def test_broken_decode_fails(bench_dir, monkeypatch, fault):
    build = harness.build_engine

    def broken(cfg_file, params):
        eng = build(cfg_file, params)
        eng._decode = fault(eng._decode, eng.model)
        return eng

    monkeypatch.setattr(harness, "build_engine", broken)
    run, checks = _run(bench_dir, BACKLOG, seconds=6.0)
    assert checks["sampled_requests"]["value"] >= 1
    assert harness.result_line(run, {}, checks)["correct"] is False, checks
