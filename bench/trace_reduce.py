"""Reduction of a profiler trace of the window to per-layer numbers.

Reads the ``.xplane.pb`` that `jax.profiler` wrote, with JAX's own
`ProfileData`. Device planes are ``/device:TPU:<n>``; on each, the line
``XLA Modules`` holds one event per executable run (named after the jitted
function, e.g. ``jit_decode_step(<hash>)``) and ``XLA Ops`` one event per
operation, named by its HLO text (``%fusion.12 = s32[...] fusion(...)``);
a ``while`` event spans the whole layer loop it runs.
Host spans are the TraceAnnotations the harness puts around its loop
(``bench.*``), the batcher's step (``batcher.step``) and the engine calls
(``engine.*``); device and host events share the profiler's clock.

What the reduction relies on by name, until the program names its own
spans: the executables ``jit_decode_step`` and ``jit_prefill_chunk``, and
the attention kernel, the custom call ``acam_attention_codes`` (named
after the function that issues the ``pallas_call``). ``PERF.md`` lists
them.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Optional

import numpy as np

EXECUTABLES = {"decode": "jit_decode_step", "chunk": "jit_prefill_chunk"}
ATTN_KERNEL = re.compile(r"^%?acam_attention_codes[.\d]* = ")
CONTROL_FLOW = ("while", "conditional", "call")  # span the ops they run
HOST_SPANS = ("bench.", "batcher.", "engine.")
MAX_ENTRIES = 10


@dataclasses.dataclass
class Event:
    name: str
    start: int   # ns
    end: int     # ns


def trace_file(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def _device_index(plane_name: str) -> Optional[int]:
    m = re.fullmatch(r"/device:TPU:(\d+)", plane_name)
    return int(m.group(1)) if m else None


def load(path: str, n_chips: int = 1) -> dict:
    """{'devices': {plane: {'ops': [...], 'modules': [...]}},
    'host': [...]} with events sorted by start; only the planes of the
    first ``n_chips`` TPU devices, the ones a cell uses."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in pd.planes:
        idx = _device_index(plane.name)
        if idx is not None and idx < n_chips:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key is None:
                    continue
                for ev in line.events:
                    s = int(ev.start_ns)
                    dev[key].append(Event(ev.name, s,
                                          s + int(ev.duration_ns)))
            for key in dev:
                dev[key].sort(key=lambda e: e.start)
            out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPANS):
                        s = int(ev.start_ns)
                        out["host"].append(
                            Event(ev.name, s, s + int(ev.duration_ns)))
    out["host"].sort(key=lambda e: e.start)
    return out


def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted (start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _op_label(ev: Event) -> str:
    """A stable name for an operation: its HLO op name without the numeric
    suffix, and the type of its result, e.g. ``fusion s32[7864320]``."""
    head, _, rest = ev.name.partition(" = ")
    name = re.sub(r"\.\d+$", "", head.lstrip("%"))
    result = rest.split("{")[0].split(" ")[0].strip("(),")
    return f"{name} {result}" if result else name


@dataclasses.dataclass
class Reduced:
    """The numbers the per-layer readers use, over the traced window."""

    window_s: float
    busy_s: float
    module_s: dict            # executable -> [device seconds per run]
    kernel_s: dict            # executable -> [attention kernel s per run]
    op_s: dict                # op label -> device seconds
    gaps: list                # (seconds, host span name) of idle gaps

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:MAX_ENTRIES]
        by_host = defaultdict(float)
        for sec, name in self.gaps:
            by_host[name] += sec
        gaps = sorted(by_host.items(), key=lambda kv: -kv[1])[:MAX_ENTRIES]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


OUTSIDE = "host: outside the traced loop"


def host_timeline(host: list[Event]) -> tuple[list[int], list[str]]:
    """The innermost open host span as a step function: segment i runs
    from ``bounds[i]`` to ``bounds[i + 1]`` with label ``labels[i]``.
    Spans of one thread nest, so the innermost is the latest opened."""
    points = sorted({t for ev in host for t in (ev.start, ev.end)})
    bounds, labels, stack, k = [], [], [], 0
    for t in points:
        stack = [ev for ev in stack if ev.end > t]
        while k < len(host) and host[k].start <= t:
            if host[k].end > t:
                stack.append(host[k])
            k += 1
        bounds.append(t)
        labels.append(stack[-1].name if stack else OUTSIDE)
    return bounds, labels


def attribute(gap: tuple[int, int], bounds: list[int],
              labels: list[str]) -> list[tuple[float, str]]:
    """Split an idle gap (ns) by what the host was doing in it."""
    t, e = gap
    out = []
    i = bisect.bisect_right(bounds, t) - 1   # bounds[i] <= t < bounds[i+1]
    while t < e:
        end = min(bounds[i + 1] if i + 1 < len(bounds) else e, e)
        out.append(((end - t) * 1e-9, labels[i] if i >= 0 else OUTSIDE))
        t, i = end, i + 1
    return out


def reduce_events(data: dict, lo: Optional[int] = None,
                  hi: Optional[int] = None) -> Reduced:
    """Reduce loaded events over [lo, hi] ns (default: the host window
    span ``bench.window``, else everything the trace holds)."""
    host = data["host"]
    devs = data["devices"]
    if not devs:
        raise ValueError("the trace holds no TPU device plane")
    if lo is None or hi is None:
        spans = [e for e in host if e.name == "bench.window"]
        if spans:
            lo, hi = spans[0].start, spans[0].end
        else:
            evs = [e for d in devs.values() for e in d["ops"]]
            lo, hi = min(e.start for e in evs), max(e.end for e in evs)
    window = (hi - lo) * 1e-9
    bounds, labels = host_timeline(host)
    busy, gaps = [], []
    module_s, kernel_s, op_s = defaultdict(list), defaultdict(list), \
        defaultdict(float)
    for dev in devs.values():
        ops = [e for e in dev["ops"] if e.end > lo and e.start < hi]
        merged = clip(union((e.start, e.end) for e in ops), lo, hi)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps += [(sec / len(devs), name) for sec, name
                         in attribute((s, e), bounds, labels)]
        mods = [m for m in dev["modules"] if m.start >= lo and m.end <= hi]
        kern = [e for e in ops if ATTN_KERNEL.search(e.name)]
        starts = [e.start for e in kern]
        for kind, exe in EXECUTABLES.items():
            runs = [m for m in mods if m.name.startswith(exe)]
            module_s[kind] += [(m.end - m.start) * 1e-9 for m in runs]
            for m in runs:
                i = bisect.bisect_left(starts, m.start)
                j = bisect.bisect_right(starts, m.end)
                kernel_s[kind].append(sum(
                    (e.end - e.start) * 1e-9 for e in kern[i:j]
                    if e.end <= m.end))
        for e in ops:
            label = _op_label(e)
            if label.split(" ")[0] in CONTROL_FLOW:
                continue
            s, t = max(e.start, lo), min(e.end, hi)
            op_s[label] += (t - s) * 1e-9 / len(devs)
    return Reduced(window_s=window, busy_s=float(np.mean(busy)),
                   module_s=dict(module_s), kernel_s=dict(kernel_s),
                   op_s=dict(op_s), gaps=gaps)


def reduce(trace_dir: str, n_chips: int = 1) -> Reduced:
    return reduce_events(load(trace_file(trace_dir), n_chips))
