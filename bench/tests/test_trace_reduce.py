"""The trace reduction: interval arithmetic on made-up events, and the
whole reduction on a small trace recorded on a TPU v5e."""
import gzip
import shutil
from pathlib import Path

import pytest

from bench import trace_reduce as tr
from bench.trace_reduce import Event

DATA = Path(__file__).resolve().parent / "data"


def test_union_and_clip():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert tr.clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]


def _ev(name, s, e):
    return Event(name, s, e)


def test_busy_idle_modules_kernels_and_gaps():
    ms = 1_000_000
    data = {"devices": {"/device:TPU:0": {
        "modules": [_ev("jit_decode_step(7)", 10 * ms, 20 * ms),
                    _ev("jit_prefill_chunk(8)", 30 * ms, 50 * ms)],
        "ops": [_ev("%fusion.1 = s32[8] fusion()", 10 * ms, 12 * ms),
                _ev("%acam_attention_codes.1 = (s32[480,8,128]) custom-call()",
                    12 * ms, 18 * ms),
                _ev("fusion.2", 18 * ms, 20 * ms),
                _ev("%acam_attention_codes.3 = (s32[480,8,128]) custom-call()",
                    30 * ms, 45 * ms),
                _ev("copy.4", 45 * ms, 50 * ms)]}},
        "host": [_ev("bench.window", 0, 100 * ms),
                 _ev("batcher.step", 5 * ms, 55 * ms),
                 _ev("engine.decode_step", 8 * ms, 10 * ms),
                 _ev("bench.idle", 60 * ms, 100 * ms)]}
    r = tr.reduce_events(data)
    assert r.window_s == pytest.approx(0.1)
    assert r.busy_s == pytest.approx(0.030)
    assert r.idle_share == pytest.approx(0.7)
    assert r.module_s["decode"] == [pytest.approx(0.010)]
    assert r.module_s["chunk"] == [pytest.approx(0.020)]
    assert r.kernel_s["decode"] == [pytest.approx(0.006)]
    assert r.kernel_s["chunk"] == [pytest.approx(0.015)]
    gaps = dict((k, v) for k, v in r.breakdown()["idle_gaps"])
    assert gaps["engine.decode_step"] == pytest.approx(0.002)  # 8-10 ms
    assert gaps["batcher.step"] == pytest.approx(0.003 + 0.010 + 0.005)
    assert gaps["bench.idle"] == pytest.approx(0.040)
    ops = dict(r.breakdown()["device_ops"])
    assert ops["acam_attention_codes s32[480,8,128]"] == pytest.approx(0.021)
    assert ops["fusion s32[8]"] == pytest.approx(0.002)


def test_reduction_of_a_chip_trace(tmp_path):
    """A trace of gpt2-large.chat recorded on one TPU v5e."""
    src = DATA / "chip_trace.xplane.pb.gz"
    path = tmp_path / "t.xplane.pb"
    with gzip.open(src) as f, open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    r = tr.reduce_events(tr.load(str(path)))
    assert 0.0 < r.busy_s <= r.window_s
    assert r.module_s["decode"] and r.module_s["chunk"]
    for kind in ("decode", "chunk"):
        assert len(r.kernel_s[kind]) == len(r.module_s[kind])
        assert all(0.0 < k < m for k, m in zip(r.kernel_s[kind],
                                               r.module_s[kind]))
    b = r.breakdown()
    assert 0 < len(b["device_ops"]) <= tr.MAX_ENTRIES
    assert sum(s for _, s in b["idle_gaps"]) == pytest.approx(
        r.window_s - r.busy_s, rel=1e-6)
