"""The benchmark harness on the CPU, at a tiny size.

The command itself refuses a CPU; these tests call the serving loop in
process. Pallas kernels run in interpret mode here.
"""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import harness, roofline
from bench.traffic import Traffic, _lengths

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
BIG_SEED = 2 ** 33 + 17   # wider than 32 bits, as run seeds may be


def _bench_copy(tmp_path: Path) -> Path:
    """A copy of bench/ plus new files for one more configuration, traffic
    mix and metric; the harness must run them without an edit."""
    bd = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bd,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(DATA / "tiny.json", bd / "configs" / "tiny.json")
    shutil.copy(DATA / "tiny-chat.json", bd / "traffic" / "tiny-chat.json")
    (bd / "metrics" / "tiny_done_requests.py").write_text(
        "def read(run):\n"
        "    return sum(1 for r in run.window.records.values()\n"
        "               if r.done_t is not None)\n")
    return bd


def _bm(cell: str) -> dict:
    return {"end_to_end": [
                {"name": "output_tokens_per_s", "unit": "tokens/s"},
                {"name": "setup_s", "unit": "s"},
                {"name": "tiny_done_requests", "unit": "requests",
                 "workloads": ["other.cell"]}],
            "per_layer": [
                {"name": "decode_batch_mean", "unit": "tokens",
                 "moves": "output_tokens_per_s", "workloads": [cell]},
                {"name": "tiny_done_requests", "unit": "requests",
                 "moves": "output_tokens_per_s"},
                {"name": "prefix_hit_pct", "unit": "%",
                 "moves": "tiny_done_requests"}]}


@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    bd = _bench_copy(tmp_path_factory.mktemp("files"))
    cell = {"name": "tiny.tiny-chat", "config": "tiny",
            "traffic": "tiny-chat", "chips": 1}
    run, checks = harness.run_cell(cell, BIG_SEED, 4.0, time.perf_counter(),
                                   jax.devices()[0], bench_dir=bd)
    return bd, cell, run, checks


def test_new_files_run_unedited(tiny_run):
    bd, cell, run, checks = tiny_run
    bm = _bm(cell["name"])
    e2e = harness.read_metrics(run, harness.cell_metrics(bm, cell["name"],
                                                         False), bd)
    assert set(e2e) == {"output_tokens_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in e2e.values())
    layer = harness.read_metrics(run, harness.cell_metrics(bm, cell["name"],
                                                           True), bd)
    # prefix_hit_pct moves a metric this cell does not report
    assert set(layer) == {"decode_batch_mean", "tiny_done_requests"}
    assert layer["tiny_done_requests"]["value"] >= 1
    line = harness.result_line(run, e2e, checks)
    assert line["correct"] is True, line["checks"]
    assert list(line)[-1] == "checks"
    assert line["checks"]["max_logit_gap"]["value"] <= 1.0
    assert json.loads(json.dumps(line)) == line


def test_timed_path_tokens_are_checked(tiny_run):
    """A sampled request's served tokens are the program's own, and each
    finished request served as many tokens as it asked for."""
    _, _, run, checks = tiny_run
    sample = harness.pick_sample(run.window, BIG_SEED)
    assert sample and all(r.served is not None for r in sample)
    assert all(len(r.served) == r.spec.n_new for r in sample)
    finished = [r for r in run.window.records.values()
                if r.done_t is not None and r.error is None]
    size = lambda r: len(r.spec.prompt) + r.spec.n_new
    assert size(sample[0]) == max(size(r) for r in finished)
    assert checks["wrong_length_results"]["value"] == 0
    assert checks["sampled_tokens"]["value"] == sum(len(r.served)
                                                    for r in sample)


def test_traffic_is_seeded_and_reproducible():
    mix = json.loads((DATA / "tiny-chat.json").read_text())
    a = Traffic(mix, BIG_SEED, 10.0, 1000, 4).specs
    b = Traffic(mix, BIG_SEED, 10.0, 1000, 4).specs
    c = Traffic(mix, BIG_SEED + 1, 10.0, 1000, 4).specs
    key = lambda s: (s.rid, s.due, s.n_new, tuple(s.prompt))
    assert [key(s) for s in a] == [key(s) for s in b]
    assert [key(s) for s in a] != [key(s) for s in c]
    # another seed: the same sizes and gaps, in another order
    sizes = lambda ss: sorted((len(s.prompt), s.n_new) for s in ss)
    assert sizes(a) == sizes(c)
    # the gap before the first request is not among the diffs
    gaps = lambda ss: set(np.round(np.diff([s.due for s in ss]), 9))
    assert len(gaps(a) & gaps(c)) >= len(a) - 2


def _closed_loop(t: Traffic, n: int) -> list:
    """The first ``n`` requests of a closed loop that retires every
    request as soon as it is sent."""
    specs = t.refill(0, [])
    while len(specs) < n:
        specs += t.refill(0, [specs[-1].rid])
    return specs


@pytest.mark.parametrize("name", ["chat", "offline", "shared-prefix"])
def test_traffic_mixes_stay_in_bounds(name):
    mix = harness.load_traffic(name)
    t = Traffic(mix, BIG_SEED, 30.0, 50257, 16)
    specs = t.specs if t.open_loop else _closed_loop(t, 600)
    assert specs
    p, o = mix["prompt"], mix["output"]
    for s in specs:
        assert o["min"] <= s.n_new <= o["max"]
        assert s.prompt.dtype == np.int32 and s.prompt.max() < 50257
        if t.shares_prefix:
            d = mix["document"]
            assert (d["min"] + p["min"] <= len(s.prompt)
                    <= d["max"] + p["max"])
        else:
            assert p["min"] <= len(s.prompt) <= p["max"]
    if t.open_loop:
        dues = [s.due for s in specs]
        assert dues == sorted(dues)
        assert -mix["warmup_s"] <= dues[0] and dues[-1] < 30.0
    else:
        assert [s.rid for s in specs] == list(range(len(specs)))


def _share_document(asks, prompt_max) -> bool:
    n = min(len(a.prompt) for a in asks) - prompt_max
    return all(np.array_equal(a.prompt[:n], asks[0].prompt[:n])
               for a in asks)


def test_closed_sessions_ask_after_each_answer():
    mix = {"arrivals": {"kind": "session_backlog", "sessions": 3,
                        "asks": [2, 4]},
           "document": {"dist": "uniform", "min": 100, "max": 300},
           "prompt": {"dist": "uniform", "min": 4, "max": 20},
           "output": {"dist": "uniform", "min": 2, "max": 9},
           "warmup_s": 2.0, "shape_seed": 8}
    t = Traffic(mix, BIG_SEED, 20.0, 50257, 4)
    first = t.refill(5, [])
    assert len(first) == 3 and len({s.session for s in first}) == 3
    # nothing retired, nothing new: one ask outstanding per session
    assert t.refill(0, []) == [] and t.refill(0, [10 ** 9]) == []
    specs = list(first)
    live = {s.session: s for s in first}
    for _ in range(60):
        done = live.pop(min(live))
        (nxt,) = t.refill(0, [done.rid])
        assert nxt.rid == specs[-1].rid + 1
        live[nxt.session] = nxt
        specs.append(nxt)
    by = {}
    for s in specs:
        by.setdefault(s.session, []).append(s)
    assert len(by) > 15
    full = [a for sid, a in by.items() if sid not in live]
    assert full and all(2 <= len(a) <= 4 and _share_document(a, 20)
                        for a in full)
    # another seed: the same session sizes, in another order
    t2 = Traffic(mix, BIG_SEED + 1, 20.0, 50257, 4)
    sizes = lambda tr: sorted((d, tuple(a)) for d, a in
                              (next(tr._backlog) for _ in range(8)))
    t3 = Traffic(mix, BIG_SEED, 20.0, 50257, 4)
    assert sizes(t2) == sizes(t3)


def test_lengths_clip():
    rng = np.random.default_rng(0)
    x = _lengths(rng, {"dist": "lognormal", "median": 100, "sigma": 3.0,
                       "min": 10, "max": 200}, 5000)
    assert x.min() == 10 and x.max() == 200


class _SlowBatcher:
    """Stands in for the batcher: every step takes ``step_s`` and finishes
    the head of the queue with one token."""

    def __init__(self, step_s):
        from collections import deque
        self.step_s, self.queue, self.slots, self.done = \
            step_s, deque(), [None], {}
        self.n, self.prefill_chunk, self.engine = 1, 64, None

    def submit(self, req):
        self.queue.append(req)

    def step(self):
        time.sleep(self.step_s)
        if not self.queue:
            return []
        req = self.queue.popleft()
        req.result = np.zeros(1, np.int32)
        self.done[req.rid] = req
        return [req.rid]

    def summary(self):
        return {}


def test_open_loop_due_times_ignore_service():
    """A slow server does not delay when requests fall due: their due times
    are the schedule's, and their wait counts in time to first token."""
    mix = {"arrivals": {"kind": "poisson", "rate_per_s": 20.0},
           "prompt": {"dist": "uniform", "min": 4, "max": 8},
           "output": {"dist": "uniform", "min": 1, "max": 1},
           "warmup_s": 0.0, "shape_seed": 3}
    wins = {}
    for step_s in (0.001, 0.2):
        t = Traffic(mix, BIG_SEED, 1.5, 100, 1)
        wins[step_s] = harness.serve_window(_SlowBatcher(step_s), t, 1.5)
    fast, slow = wins[0.001], wins[0.2]
    dues = lambda w: {r.due for r in w.records.values()}
    assert dues(slow) <= dues(fast)
    assert len(dues(fast)) >= 25
    assert (harness.percentile(harness.ttft_values(slow), 95)
            > 2 * harness.percentile(harness.ttft_values(fast), 95))


def test_cell_metric_selection():
    bm = harness.benchmark(ROOT)
    for cell in bm["workloads"]:
        e2e = {m["name"] for m in harness.cell_metrics(bm, cell["name"],
                                                       False)}
        layer = harness.cell_metrics(bm, cell["name"], True)
        assert "setup_s" in e2e and len(e2e) >= 2 and layer
        assert all(m["moves"] in e2e for m in layer)


def test_every_metric_has_a_reader():
    bm = harness.benchmark(ROOT)
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert callable(harness.load_reader(m["name"]))
    for c in bm["configs"]:
        assert (ROOT / c["file"]).is_file()
    for w in bm["workloads"]:
        harness.load_config(w["config"])
        harness.load_traffic(w["traffic"])


def _run_command(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = harness.benchmark(ROOT)["workloads"][0]["name"]
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell,
         "--seed", str(BIG_SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_without_a_result():
    out = _run_command(ROOT)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not any(line.startswith("{") for line in out.stdout.splitlines())


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_command(tmp_path)
    assert out.returncode != 0
    assert not any(line.startswith("{") for line in out.stdout.splitlines())


def test_peaks_refuse_unknown_chips():
    assert roofline.peaks("TPU v5 lite")["int8_ops_per_s"] == 393e12
    with pytest.raises(ValueError):
        roofline.peaks("TPU v9 imaginary")


def test_attempts_count_the_served(tiny_run):
    """Every request that got tokens in the window counts as attempted,
    also one queued before it, as a closed loop's requests are."""
    _, _, run, checks = tiny_run
    win = run.window
    served = [r for r in win.records.values() if r.n_in > 0]
    line = harness.result_line(run, {}, checks)
    assert line["attempted"] >= len(served) >= 1
    assert line["failed"] == 0
