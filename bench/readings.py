"""Arithmetic the metric readers share: each reader in ``bench/metrics``
is one small file that calls into here with the run (``ctx``) it is given.
A reading that has nothing to read returns None, and the harness leaves
the metric out of the result line."""
from __future__ import annotations

import statistics
from typing import Optional

from . import roofline
from .harness import Run, window_tokens


def output_tokens_per_s(run: Run) -> Optional[float]:
    n = window_tokens(run.window)
    return n / run.window.seconds if n else None


def counter_delta(run: Run, name: str) -> int:
    return (run.window.counters_after.get(name, 0)
            - run.window.counters_before.get(name, 0))


def prefix_hit_pct(run: Run) -> Optional[float]:
    hit = counter_delta(run, "prefix_hit_pages")
    total = hit + counter_delta(run, "prefix_miss_pages")
    return 100.0 * hit / total if total else None


def decode_batch_mean(run: Run) -> Optional[float]:
    steps = counter_delta(run, "decode_steps")
    return counter_delta(run, "decode_tokens") / steps if steps else None


def step_ms(run: Run, kind: str) -> Optional[float]:
    """Mean device time of one run of the chunk or decode executable."""
    if run.trace is None:
        return None
    runs = run.trace.module_s.get(kind, [])
    return 1e3 * statistics.mean(runs) if runs else None


def _calls(run: Run, kind: str) -> list:
    return [c for c in (run.window.calls or []) if c[0] == kind]


def attn_roofline_pct(run: Run, kind: str) -> Optional[float]:
    """The attention kernel's share of its roofline, in the decode or the
    chunk executable: the least time the chip needs for the attention the
    traced calls asked for (as the reference module counts it), over the
    kernel time the trace shows."""
    if run.trace is None:
        return None
    kernel = run.trace.kernel_s.get(kind, [])
    calls = _calls(run, kind)
    n = min(len(kernel), len(calls))
    spent = sum(kernel[len(kernel) - n:])
    if n == 0 or spent <= 0.0:
        return None
    a, kvb = run.cfg_file["reference"], run.extra["kv_bytes"]
    pk = roofline.peaks(run.device["kind"])
    need = 0.0
    for _, _, rows in calls[len(calls) - n:]:
        ops, nbytes = run.ref.attn_cost(a, kind, rows, kvb)
        need += roofline.bound_seconds(ops, nbytes, pk)[0]
    return 100.0 * need / spent


def step_mfu_pct(run: Run) -> Optional[float]:
    """Model operations of every chunk and decode call in the traced part
    (useful rows only, as the reference module counts them) over the wall
    time of the steps that made them, times the chip's int8 peak."""
    win = run.window
    if win.trace is None or not win.calls:
        return None
    lo, hi = win.trace
    wall = sum(e - s for s, e in win.steps if s >= lo and e <= hi)
    if wall <= 0.0:
        return None
    a = run.cfg_file["reference"]
    seen = {k: counter_delta(run, k) for k in win.counters_after}
    ops = 0
    for kind, t, rows in win.calls:
        if kind == "chunk":
            ops += run.ref.step_ops(a, rows, [], seen)
        else:
            ops += run.ref.step_ops(a, [], rows, seen)
    pk = roofline.peaks(run.device["kind"])
    return 100.0 * ops / (wall * pk["int8_ops_per_s"])


def device_idle_pct(run: Run) -> Optional[float]:
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
