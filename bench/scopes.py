"""The program's own names in a profiler trace: the layer scope of each
device operation, and the batcher's step phases with their arguments.

`bench.trace_reduce` reads the trace with JAX's `ProfileData`, which does
not expose an operation's HLO metadata. Here the ``.xplane.pb`` is decoded
with the installed protobuf runtime and a descriptor of the public
``tsl/profiler/protobuf/xplane.proto`` (planes, lines, events, event and
stat metadata, stats), built below; each ``XLA Ops`` event then also has
its ``tf_op`` stat, the operation's scope path, e.g.
``jit(decode_step)/layer_scan/while/body/attn/kv_pool/scatter:``.

Names the program gives its work (``src/repro``), and what reads them:

- layer scopes (`jax.named_scope`): ``layer_scan`` (what the layer scan
  adds itself: slicing each layer's weights and cache in, stacking the
  updated cache out), ``attn`` (projections, Q quantization, attention),
  ``kv_pool`` (cache writes and the pool's quantization), ``mlp``,
  ``embed``, ``unembed``. An operation's layer is its innermost layer
  scope, else ``unscoped``; other parts of the path (``jit(...)``, JAX's
  own names) are not layers. Read by ``decode_<scope>_ms``.
- the batcher's phase spans inside each step: ``batcher.admit`` (arg
  ``compiles``), ``batcher.chunk`` (``fed``, ``slot_tokens``),
  ``batcher.decode``, ``batcher.sample``, ``batcher.wait``,
  ``batcher.commit`` (``compiles``). Read by ``batcher_host_ms``,
  ``chunk_fill_pct`` and ``compiles_in_window``.

A trace of a program that names nothing reads as all ``unscoped`` and no
phase: every reading here is then None.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import math
import os
import tempfile
import time
from collections import defaultdict
from typing import Optional

from . import trace_reduce
from .trace_reduce import Event

LAYER_SCOPES = ("layer_scan", "attn", "kv_pool", "mlp", "embed", "unembed")
UNSCOPED = "unscoped"
TRACE_DIR_PREFIX = "bench-trace-"   # bench/run.py's temporary trace dir


@dataclasses.dataclass
class Op(Event):
    tf_op: str = ""      # the op's scope path, "" where the trace has none


@dataclasses.dataclass
class Span(Event):
    args: dict = dataclasses.field(default_factory=dict)


# ---------------------------------------------------------------- decode
_I64, _U64, _F64, _STR, _BYTES = 3, 4, 1, 9, 12   # FieldDescriptorProto types
_XPLANE = {   # message -> [(field, number, type or message, repeated)]
    # the fields read here; parsing skips the others
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("name", 2, _STR, False), ("lines", 3, "XLine", True),
               ("event_metadata", 4, "EventMetadataEntry", True),
               ("stat_metadata", 5, "StatMetadataEntry", True)],
    "EventMetadataEntry": [("key", 1, _I64, False),
                           ("value", 2, "XEventMetadata", False)],
    "StatMetadataEntry": [("key", 1, _I64, False),
                          ("value", 2, "XStatMetadata", False)],
    "XLine": [("name", 2, _STR, False), ("timestamp_ns", 3, _I64, False),
              ("events", 4, "XEvent", True)],
    "XEvent": [("metadata_id", 1, _I64, False), ("offset_ps", 2, _I64, False),
               ("duration_ps", 3, _I64, False), ("stats", 4, "XStat", True)],
    "XStat": [("metadata_id", 1, _I64, False),
              ("double_value", 2, _F64, False),
              ("uint64_value", 3, _U64, False),
              ("int64_value", 4, _I64, False), ("str_value", 5, _STR, False),
              ("bytes_value", 6, _BYTES, False),
              ("ref_value", 7, _U64, False)],
    "XEventMetadata": [("name", 2, _STR, False), ("stats", 5, "XStat", True)],
    "XStatMetadata": [("name", 2, _STR, False)],
}


@functools.lru_cache(maxsize=1)
def _xspace_class():
    """The XSpace message class. Maps are declared as their wire form,
    repeated key/value entries; proto2, so that a stat's value field
    reports whether it is set."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto2")
    F = descriptor_pb2.FieldDescriptorProto
    for msg, fields in _XPLANE.items():
        m = fd.message_type.add(name=msg)
        for name, number, kind, repeated in fields:
            f = m.field.add(name=name, number=number,
                            label=(F.LABEL_REPEATED if repeated
                                   else F.LABEL_OPTIONAL))
            if isinstance(kind, str):
                f.type, f.type_name = F.TYPE_MESSAGE, f".bench_xplane.{kind}"
            else:
                f.type = kind
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def _stat_value(stat, stat_names: dict):
    for field in ("str_value", "int64_value", "uint64_value",
                  "double_value", "bytes_value"):
        if stat.HasField(field):
            return getattr(stat, field)
    if stat.HasField("ref_value"):   # a string kept once, as a stat name
        return stat_names.get(stat.ref_value, "")
    return None


def load(path: str, n_chips: int = 1) -> dict:
    """`trace_reduce.load`'s form, read with the op scopes and the span
    arguments: ``{'devices': {plane: {'ops': [Op], 'modules': [Op]}},
    'host': [Span]}``, events sorted by start."""
    space = _xspace_class()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    out = {"devices": {}, "host": []}
    for plane in space.planes:
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        idx = trace_reduce._device_index(plane.name)
        if idx is not None and idx < n_chips:
            meta = {e.key: e.value for e in plane.event_metadata}
            tf_op = {k: next((str(_stat_value(s, stat_names))
                              for s in m.stats
                              if stat_names.get(s.metadata_id) == "tf_op"),
                             "")
                     for k, m in meta.items()}
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key is None:
                    continue
                for ev in line.events:
                    s = line.timestamp_ns + ev.offset_ps // 1000
                    dev[key].append(Op(meta[ev.metadata_id].name, s,
                                       s + ev.duration_ps // 1000,
                                       tf_op[ev.metadata_id]))
            for evs in dev.values():
                evs.sort(key=lambda e: e.start)
            out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            names = {e.key: e.value.name for e in plane.event_metadata}
            for line in plane.lines:
                for ev in line.events:
                    name = names.get(ev.metadata_id, "")
                    if not name.startswith(trace_reduce.HOST_SPANS):
                        continue
                    s = line.timestamp_ns + ev.offset_ps // 1000
                    args = {stat_names.get(st.metadata_id, ""):
                            _stat_value(st, stat_names) for st in ev.stats}
                    out["host"].append(
                        Span(name, s, s + ev.duration_ps // 1000, args))
    out["host"].sort(key=lambda e: e.start)
    return out


# ---------------------------------------------------------------- scopes
def innermost_scope(tf_op: str) -> str:
    """The innermost layer scope on an op's path (``path:op_type``)."""
    path = tf_op.rpartition(":")[0] or tf_op
    for part in reversed(path.split("/")):
        if part in LAYER_SCOPES:
            return part
    return UNSCOPED


def window(data: dict) -> tuple[int, int]:
    """The traced window (ns): the harness's ``bench.window`` span, else
    everything the trace holds."""
    spans = [e for e in data["host"] if e.name == "bench.window"]
    if spans:
        return spans[0].start, spans[0].end
    evs = [e for d in data["devices"].values() for e in d["ops"]]
    return min(e.start for e in evs), max(e.end for e in evs)


def scope_runs(data: dict) -> dict:
    """{kind: [{scope: device seconds} per executable run]} over the
    window, kind as in `trace_reduce.EXECUTABLES`. An op belongs to the
    run it starts in (ends rounded to the nanosecond can pass the run's
    end by one). Control-flow ops span the ops they run and are left
    out, so a run's scopes sum to its op time."""
    lo, hi = window(data)
    runs = defaultdict(list)
    for dev in data["devices"].values():
        ops = [e for e in dev["ops"] if trace_reduce._op_label(e).split(
            " ")[0] not in trace_reduce.CONTROL_FLOW]
        starts = [e.start for e in ops]
        for m in dev["modules"]:
            if m.start < lo or m.end > hi:
                continue
            kind = next((k for k, exe in trace_reduce.EXECUTABLES.items()
                         if m.name.startswith(exe)), None)
            if kind is None:
                continue
            per = defaultdict(float)
            i = bisect.bisect_left(starts, m.start)
            j = bisect.bisect_left(starts, m.end)
            for e in ops[i:j]:
                per[innermost_scope(e.tf_op)] += (e.end - e.start) * 1e-9
            runs[kind].append(dict(per))
    return dict(runs)


def scope_ms(runs: dict, kind: str, scope: str) -> Optional[float]:
    """Mean device ms per run of ``kind`` whose innermost layer scope is
    ``scope``; None where no op of those runs carries a layer scope."""
    rs = runs.get(kind, [])
    if not any(set(r) - {UNSCOPED} for r in rs):
        return None
    return 1e3 * sum(r.get(scope, 0.0) for r in rs) / len(rs)


# ---------------------------------------------------------------- phases
def _in_window(data: dict, prefix: str) -> list[Span]:
    lo, hi = window(data)
    return [e for e in data["host"]
            if e.name.startswith(prefix) and e.start >= lo and e.end <= hi]


def batcher_host_ms(data: dict) -> Optional[float]:
    """Mean over the window's steps (the harness's ``batcher.step``) of
    the step's time less its ``batcher.wait`` spans: the host's own work
    in a step. None where the program records no wait."""
    waits = _in_window(data, "batcher.wait")
    steps = [e for e in _in_window(data, "batcher.step")
             if e.name == "batcher.step"]
    if not waits or not steps:
        return None
    starts = [w.start for w in waits]
    host = []
    for st in steps:
        i = bisect.bisect_left(starts, st.start)
        j = bisect.bisect_right(starts, st.end)
        host.append(st.end - st.start - sum(w.end - w.start
                                            for w in waits[i:j]))
    return 1e-6 * sum(host) / len(host)


def chunk_fill_pct(data: dict) -> Optional[float]:
    """Prompt tokens the window's chunk calls fed, over their slot rows
    (n_slots x chunk width per call), from the ``batcher.chunk`` args."""
    calls = [e.args for e in _in_window(data, "batcher.chunk")
             if "fed" in e.args and "slot_tokens" in e.args]
    rows = sum(a["slot_tokens"] for a in calls)
    return 100.0 * sum(a["fed"] for a in calls) / rows if rows else None


def compiles_in_window(data: dict) -> Optional[int]:
    """Executables the program lowered during the window's steps: the
    compile count after the last step's dispatches less the count at the
    first step's start."""
    marks = [e for e in _in_window(data, "batcher.")
             if e.name in ("batcher.admit", "batcher.commit")
             and "compiles" in e.args]
    if not marks or marks[0].name != "batcher.admit":
        return None
    return int(marks[-1].args["compiles"]) - int(marks[0].args["compiles"])


# ---------------------------------------------------------------- runs
def latest_trace(since: float) -> Optional[str]:
    """The newest trace written at or after ``since`` (wall clock, s)
    under the temporary directories `bench/run.py` makes for ``--trace
    1`` (it removes its own once read)."""
    files = [f for d in glob.glob(os.path.join(
        tempfile.gettempdir(), TRACE_DIR_PREFIX + "*"))
        for f in glob.glob(os.path.join(d, "plugins", "profile", "*",
                                        "*.xplane.pb"))
        if os.path.getmtime(f) >= since]
    return max(files, key=os.path.getmtime) if files else None


def same_reading(data: dict, reduced) -> bool:
    """Whether decoded events reduce to ``reduced``, the run's own
    `trace_reduce` reading: the same window and device busy time."""
    if not data["devices"]:
        return False
    mine = trace_reduce.reduce_events(data)
    return (math.isclose(mine.window_s, reduced.window_s, rel_tol=1e-9)
            and math.isclose(mine.busy_s, reduced.busy_s, rel_tol=1e-9))


def trace_of(run) -> Optional[dict]:
    """The scoped trace of a ``--trace 1`` run, decoded once and kept on
    the run for the other readers. None for an untraced run, and where
    the newest trace written since the window opened is not the one the
    run's `trace_reduce` reading came from (another run's, or none)."""
    if run.trace is None:
        return None
    if "scoped_trace" not in run.extra:
        # the window's start (perf_counter) on the files' wall clock
        since = time.time() - (time.perf_counter() - run.window.start)
        path = latest_trace(since)
        data = load(path, int(run.device.get("count", 1))) if path else None
        if data is not None and not same_reading(data, run.trace):
            data = None
        run.extra["scoped_trace"] = data
    return run.extra["scoped_trace"]


def decode_scope_ms(run, scope: str) -> Optional[float]:
    """`scope_ms` of the decode executable in a ``--trace 1`` run."""
    data = trace_of(run)
    if data is None:
        return None
    if "scope_runs" not in run.extra:
        run.extra["scope_runs"] = scope_runs(data)
    return scope_ms(run.extra["scope_runs"], "decode", scope)


def read(run, reading):
    """``reading(data)`` on the scoped trace of a ``--trace 1`` run."""
    data = trace_of(run)
    return None if data is None else reading(data)
