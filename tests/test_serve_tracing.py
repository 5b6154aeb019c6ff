"""The serving path names its own work for a profiler: layer scopes on the
device operations, phase spans and counters on the batcher's host steps.

Scopes are metadata only: they must change no compiled operation.
"""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ExecConfig
from repro.launch.compile_cache import compile_count
from repro.models import Model
from repro.models.model import quantize_model_params
from repro.serve import ContinuousBatcher, GenerationEngine, Request

from conftest import tiny_config

LAYER_SCOPES = ("layer_scan", "attn", "kv_pool", "mlp", "embed", "unembed")
PS = 64


def _engine(max_len=128):
    """A tiny OLMo-shaped engine serving raceit with resident int8
    weights, as the benchmark's cell does."""
    cfg = tiny_config(get_config("olmo-1b"))
    ec = ExecConfig.serving()
    params = quantize_model_params(Model(cfg, ec).init(
        jax.random.PRNGKey(0)))
    return GenerationEngine(cfg, params, exec_cfg=ec, max_len=max_len)


@pytest.fixture(scope="module")
def engine():
    return _engine()


def _decode_lowered(eng, n=2):
    """The paged decode step, lowered for an ``n``-slot pool."""
    max_pages = -(-eng.max_len // PS)
    cache = eng.model.init_slot_cache(n, eng.max_len, page_size=PS,
                                      n_pages=1 + n * max_pages)
    step = jax.jit(eng.model.decode_step, static_argnames=("page_size",))
    return step.lower(eng.params, jnp.zeros((n, 1), jnp.int32), cache,
                      None, None, jnp.ones(n, jnp.int32),
                      jnp.zeros((n, max_pages), jnp.int32), page_size=PS)


@pytest.fixture(scope="module")
def decode_hlo(engine):
    """The compiled decode step's HLO text: calls are inlined there, so
    each instruction's ``op_name`` is its whole scope path, as a profile's
    ``tf_op`` reports it."""
    jax.clear_caches()
    return _decode_lowered(engine).compile().as_text()


def _op_names(hlo: str, opcode=r"[\w-]+") -> list[str]:
    return [m.group(1) for m in re.finditer(
        rf'= \S+ (?:{opcode})\(.*op_name="([^"]*)"', hlo)]


def test_decode_step_carries_layer_scopes(decode_hlo):
    """Every matmul of the decode step reports under a layer scope, and
    the scan, attention, KV pool and MLP scopes are all there."""
    seen = {part for p in _op_names(decode_hlo) for part in p.split("/")}
    assert {"layer_scan", "attn", "kv_pool", "mlp"} <= seen
    dots = _op_names(decode_hlo, "dot")
    assert dots
    for p in dots:
        assert set(p.split("/")) & set(LAYER_SCOPES), p


def _instructions(hlo: str) -> str:
    """The module's computations with op metadata stripped, without the
    source-location tables printed ahead of them, and with names numbered
    by first use (a name's numeric suffix follows its op's debug name)."""
    body = hlo[re.search(r"^(%|ENTRY )", hlo, re.M).start():]
    body = re.sub(r', metadata=\{[^}]*\}', "", body)
    names: dict = {}
    return re.sub(r"%[\w.-]+",
                  lambda m: names.setdefault(m.group(0), f"%v{len(names)}"),
                  body)


def test_scopes_change_no_compiled_operation(engine, decode_hlo,
                                             monkeypatch):
    """The decode step compiled with the scopes and with ``named_scope``
    made a null context holds the same instructions once op metadata is
    stripped."""
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()
    plain = _decode_lowered(engine).compile().as_text()
    jax.clear_caches()
    assert "kv_pool" in decode_hlo and "kv_pool" not in plain
    assert _instructions(decode_hlo) == _instructions(plain)


def _traced_steps(tmp_path, cb):
    """Serve the batcher's queue dry, each step inside a ``batcher.step``
    span as the benchmark's loop does; returns the host spans, by name
    prefix ``batcher.``, with their arguments."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        while cb.queue or any(s is not None for s in cb.slots):
            with jax.profiler.TraceAnnotation("batcher.step"):
                cb.step()
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("batcher."):
                    spans.append((ev.name, int(ev.start_ns),
                                  int(ev.start_ns + ev.duration_ns),
                                  dict(ev.stats)))
    return sorted(spans, key=lambda s: s[1])


PHASES = re.compile(r"admit( chunk sample wait commit)?"
                    r"( decode sample wait commit)?")


def test_phase_spans_tile_each_step_in_order(engine, tmp_path):
    cb = ContinuousBatcher(engine, n_slots=2, page_size=PS)
    rng = np.random.default_rng(0)
    for rid, (n, new) in enumerate(((70, 3), (10, 4), (5, 2))):
        cb.submit(Request(rid, rng.integers(0, 255, n).astype(np.int32),
                          new))
    spans = _traced_steps(tmp_path, cb)
    steps = [s for s in spans if s[0] == "batcher.step"]
    phases = [s for s in spans if s[0] != "batcher.step"]
    assert len(steps) == cb.metrics.step
    covered = total = 0
    for _, lo, hi, _ in steps:
        inside = [p for p in phases if lo <= p[1] and p[2] <= hi]
        order = " ".join(p[0].split(".")[1] for p in inside)
        assert PHASES.fullmatch(order), order
        for a, b in zip(inside, inside[1:]):
            assert a[2] <= b[1]          # one after another, no overlap
        covered += sum(p[2] - p[1] for p in inside)
        total += hi - lo
    assert covered >= 0.8 * total
    chunks = [p[3] for p in phases if p[0] == "batcher.chunk"]
    s = cb.summary()
    assert sum(a["fed"] for a in chunks) == s["chunk_fed_tokens"]
    assert sum(a["slot_tokens"] for a in chunks) == s["chunk_slot_tokens"]
    marks = [p[3]["compiles"] for p in phases
             if p[0] in ("batcher.admit", "batcher.commit")]
    assert marks == sorted(marks)        # a count, never decreasing


def test_chunk_fill_counters_are_exact(engine):
    """Prompts of 70 and 10 tokens at chunk 64 on two slots: two chunk
    calls of 2 x 64 slot rows feed the 80 prompt tokens."""
    cb = ContinuousBatcher(engine, n_slots=2, page_size=PS)
    cb.submit(Request(0, np.arange(70, dtype=np.int32) % 255, 2))
    cb.submit(Request(1, np.arange(10, dtype=np.int32), 2))
    cb.run_all()
    s = cb.summary()
    assert s["chunk_calls"] == 2
    assert s["chunk_fed_tokens"] == 80
    assert s["chunk_slot_tokens"] == 2 * 2 * 64


def test_compile_count_counts_new_executables():
    f = jax.jit(lambda x: x * 2 + 1)
    a = np.ones(3, np.float32)
    before = compile_count()
    f(a)
    assert compile_count() == before + 1
    f(a + 1)                            # same shape: the executable exists
    assert compile_count() == before + 1
    f(np.ones(5, np.float32))           # a new shape is a new executable
    assert compile_count() == before + 2
