"""The scope and phase readings: on made-up events, on the trace recorded
on a TPU v5e, and through the metric readers of a run."""
import gzip
import os
import re
import shutil
import time
import types
from pathlib import Path

import pytest

from bench import harness, scopes
from bench import trace_reduce as tr
from bench.scopes import Op, Span

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000
PREFIX = "jit(decode_step)/layer_scan/while/body/closed_call"


def _scoped_trace():
    """Two decode runs and one chunk run, each op under a scope path."""
    ops = [Op("%fusion.1 = s32[8] fusion()", 10 * MS, 12 * MS,
              f"{PREFIX}/mlp/dot_general:"),
           Op("%while.2 = (s32[]) while()", 12 * MS, 19 * MS,
              f"{PREFIX}/while:"),      # spans the ops it runs: left out
           Op("%scatter.3 = f32[8] scatter()", 12 * MS, 13 * MS,
              f"{PREFIX}/attn/kv_pool/scatter:"),
           Op("%fusion.4 = s8[8] fusion()", 13 * MS, 15 * MS,
              f"{PREFIX}/attn/jit(raceit_attention_decode_paged)/kv_pool/"
              f"round:"),
           Op("%acam_attention_codes.5 = (s32[8]) custom-call()", 15 * MS,
              18 * MS, f"{PREFIX}/attn/jit(raceit_attention_decode_paged)/"
              f"jit(acam_attention_codes)/pallas_call:"),
           Op("%copy.6 = f32[8] copy()", 18 * MS, 19 * MS, ""),
           Op("%dynamic-slice.7 = f32[8] dynamic-slice()", 19 * MS,
              20 * MS, "jit(decode_step)/layer_scan/while/dynamic_slice:"),
           Op("%fusion.8 = f32[8] fusion()", 30 * MS, 34 * MS,
              "jit(decode_step)/unembed/dot_general:"),
           Op("%fusion.9 = f32[8] fusion()", 40 * MS, 45 * MS,
              "jit(prefill_chunk)/layer_scan/while/body/mlp/dot_general:")]
    modules = [Op("jit_decode_step(1)", 10 * MS, 20 * MS),
               Op("jit_decode_step(1)", 30 * MS, 35 * MS),
               Op("jit_prefill_chunk(2)", 40 * MS, 45 * MS)]
    host = [Span("bench.window", 0, 100 * MS)]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": host}


def test_innermost_layer_scope_wins():
    assert scopes.innermost_scope(
        f"{PREFIX}/attn/kv_pool/scatter:") == "kv_pool"
    assert scopes.innermost_scope(
        f"{PREFIX}/attn/jit(quantize_tensor)/max:") == "attn"
    assert scopes.innermost_scope("jit(decode_step)/while/copy:") \
        == scopes.UNSCOPED
    assert scopes.innermost_scope("") == scopes.UNSCOPED


def test_scope_runs_sum_to_each_runs_op_time():
    data = _scoped_trace()
    runs = scopes.scope_runs(data)
    assert len(runs["decode"]) == 2 and len(runs["chunk"]) == 1
    first, second = runs["decode"]
    assert first == pytest.approx({"mlp": 0.002, "kv_pool": 0.003,
                                   "attn": 0.003, "unscoped": 0.001,
                                   "layer_scan": 0.001})
    assert second == pytest.approx({"unembed": 0.004})
    # per run, the scopes share out exactly the op time the run holds
    # (control flow excluded, as trace_reduce leaves it out of op time)
    ops = data["devices"]["/device:TPU:0"]["ops"]
    for run, (lo, hi) in zip(runs["decode"], ((10, 20), (30, 35))):
        own = sum(e.end - e.start for e in ops
                  if lo * MS <= e.start and e.end <= hi * MS
                  and not e.name.startswith("%while"))
        assert sum(run.values()) == pytest.approx(own * 1e-9)
    assert scopes.scope_ms(runs, "decode", "kv_pool") == pytest.approx(1.5)
    assert scopes.scope_ms(runs, "decode", "mlp") == pytest.approx(1.0)
    assert scopes.scope_ms(runs, "chunk", "mlp") == pytest.approx(5.0)


def test_a_program_that_names_nothing_reads_none():
    data = _scoped_trace()
    for e in data["devices"]["/device:TPU:0"]["ops"]:
        e.tf_op = ""
    runs = scopes.scope_runs(data)
    assert scopes.scope_ms(runs, "decode", "attn") is None
    data["host"] += [Span("batcher.step", 5 * MS, 25 * MS)]
    assert scopes.batcher_host_ms(data) is None
    assert scopes.chunk_fill_pct(data) is None
    assert scopes.compiles_in_window(data) is None


def _phases(data):
    """Two steps of the batcher's phases inside the harness's spans."""
    def step(t, compiles, chunk=None):
        out = [Span("batcher.step", t, t + 10 * MS),
               Span("batcher.admit", t, t + 1 * MS,
                    {"rids": "3 4", "compiles": compiles})]
        if chunk:
            out.append(Span("batcher.chunk", t + 1 * MS, t + 2 * MS,
                            {"fed": chunk, "slot_tokens": 1024}))
        out += [Span("batcher.decode", t + 2 * MS, t + 3 * MS),
                Span("batcher.sample", t + 3 * MS, t + 4 * MS),
                Span("batcher.wait", t + 4 * MS, t + 9 * MS),
                Span("batcher.commit", t + 9 * MS, t + 10 * MS,
                     {"compiles": compiles + 1})]
        return out
    data["host"] += step(20 * MS, 7, chunk=70) + step(40 * MS, 8, chunk=10)
    data["host"].sort(key=lambda e: e.start)
    return data


def test_phase_readings():
    data = _phases(_scoped_trace())
    assert scopes.batcher_host_ms(data) == pytest.approx(5.0)
    assert scopes.chunk_fill_pct(data) == pytest.approx(100 * 80 / 2048)
    assert scopes.compiles_in_window(data) == 2


def _chip_trace(tmp_path, name="chip_trace") -> str:
    path = tmp_path / "t.xplane.pb"
    with gzip.open(DATA / f"{name}.xplane.pb.gz") as f, \
            open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    return str(path)


def test_load_reads_op_scope_paths_of_a_chip_trace(tmp_path):
    """The trace the program recorded on a TPU v5e before it named its
    scopes: each op's tf_op path is read, the events are those
    `trace_reduce.load` reads, and no op has a layer scope."""
    path = _chip_trace(tmp_path)
    data = scopes.load(path)
    ops = data["devices"]["/device:TPU:0"]["ops"]
    kernel = next(e for e in ops
                  if e.name.startswith("%acam_attention_codes.9 "))
    assert kernel.tf_op == (
        "jit(prefill_chunk)/while/body/closed_call/jit(raceit_attention_"
        "decode_paged)/jit(acam_attention_codes)/pallas_call:")
    plain = tr.load(path)
    for key in ("ops", "modules"):
        mine = data["devices"]["/device:TPU:0"][key]
        theirs = plain["devices"]["/device:TPU:0"][key]
        assert [(e.name, e.start, e.end) for e in mine] == [
            (e.name, e.start, e.end) for e in theirs]
    assert [e.name for e in data["host"]] == [e.name for e in plain["host"]]
    runs = scopes.scope_runs(data)
    assert scopes.scope_ms(runs, "decode", "layer_scan") is None


# the stacked K or V pool of olmo-1b.offline: layers, pages, page rows,
# KV heads, head size
POOL_COPY = re.compile(r"%copy\.\d+ = f32\[16,321,64,16,128\]\{")


def test_readings_of_a_scoped_chip_trace(tmp_path):
    """Three seconds of olmo-1b.offline recorded on one TPU v5e with the
    program's scopes and phase spans: five decode runs, two chunk calls.
    The four layers carry 82% of a decode run's device time; what no
    scope names is the compiler's copies of the whole stacked K and V
    pools, which copy insertion adds without op metadata (15 ms a run),
    and nothing else of weight. (The paths in this recording also hold
    the ExecPlan slot names the program gave then; they are not
    layers.)"""
    data = scopes.load(_chip_trace(tmp_path, "chip_scoped_trace"))
    runs = scopes.scope_runs(data)
    assert len(runs["decode"]) == 5 and len(runs["chunk"]) == 2
    step_ms = 1e3 * sum(tr.reduce_events(data).module_s["decode"]) / 5
    four = sum(scopes.scope_ms(runs, "decode", s)
               for s in ("layer_scan", "kv_pool", "mlp", "attn"))
    assert 0.8 * step_ms <= four < step_ms
    dev = data["devices"]["/device:TPU:0"]
    decode = [m for m in dev["modules"] if m.name.startswith("jit_decode")]
    by_kind = {"unnamed": 0, "named": 0, "scoped": 0}
    pool_copies = []
    for e in dev["ops"]:
        if tr._op_label(e).split(" ")[0] in tr.CONTROL_FLOW:
            continue                            # spans the ops it runs
        if any(m.start <= e.start < m.end for m in decode):
            kind = ("scoped" if scopes.innermost_scope(e.tf_op)
                    != scopes.UNSCOPED else "named" if e.tf_op
                    else "unnamed")
            by_kind[kind] += e.end - e.start
            if kind == "unnamed" and POOL_COPY.match(e.name):
                pool_copies.append(e.end - e.start)
    total = sum(by_kind.values())
    assert by_kind["unnamed"] > 0.1 * total     # the pools' copies
    assert len(pool_copies) == 2 * 5            # K and V, each decode run
    # any other op without metadata, above 0.5% of the unnamed time, fails
    assert sum(pool_copies) >= 0.995 * by_kind["unnamed"]
    assert by_kind["named"] < 0.01 * total      # all else has a layer
    gaps = dict(tr.reduce_events(data).breakdown()["idle_gaps"])
    assert max(gaps, key=gaps.get) == "batcher.wait"
    assert scopes.batcher_host_ms(data) > 0
    assert scopes.chunk_fill_pct(data) == pytest.approx(100 * 65 / 2048)
    assert scopes.compiles_in_window(data) == 0


def _traced_run(tmp_path, monkeypatch, since_s=-60.0):
    """A ``--trace 1`` run as `bench/run.py` leaves it for the readers:
    its trace under a ``bench-trace-*`` directory of the temporary
    directory, its `trace_reduce` reading (by `trace_reduce.load`, the
    other decoder), its window opened ``since_s`` from now."""
    monkeypatch.setattr(scopes.tempfile, "tempdir", str(tmp_path))
    where = tmp_path / "bench-trace-a" / "plugins" / "profile" / "1"
    where.mkdir(parents=True)
    path = _chip_trace(where)
    return path, types.SimpleNamespace(
        trace=tr.reduce_events(tr.load(path)), device={"count": 1},
        window=types.SimpleNamespace(start=time.perf_counter() + since_s),
        extra={})


@pytest.mark.parametrize("case", ["own", "stale", "other"])
def test_trace_of_reads_only_the_runs_own_trace(tmp_path, monkeypatch,
                                                case):
    """The readers find the run's trace by name and time; a trace older
    than the window (one a killed run left), or one whose events do not
    reduce to the run's own reading (another run's), reads as None."""
    path, run = _traced_run(tmp_path, monkeypatch)
    if case == "stale":
        old = time.time() - 3600
        os.utime(path, (old, old))
    elif case == "other":
        run.trace = types.SimpleNamespace(window_s=run.trace.window_s,
                                          busy_s=run.trace.busy_s * 0.9)
    data = scopes.trace_of(run)
    assert (data is not None) == (case == "own")
    assert scopes.trace_of(run) is data      # decoded once, then kept


def _run(data):
    """A traced run whose scoped trace is already decoded."""
    return types.SimpleNamespace(trace=object(), device={"count": 1},
                                 extra={"scoped_trace": data})


@pytest.mark.parametrize("name, value", [
    ("decode_layer_scan_ms", 0.5), ("decode_kv_pool_ms", 1.5),
    ("decode_mlp_ms", 1.0), ("decode_attn_ms", 1.5),
    ("batcher_host_ms", 5.0), ("chunk_fill_pct", 100 * 80 / 2048),
    ("compiles_in_window", 2)])
def test_metric_readers(name, value):
    read = harness.load_reader(name)
    assert read(_run(_phases(_scoped_trace()))) == pytest.approx(value)
    assert read(types.SimpleNamespace(trace=None, extra={})) is None
