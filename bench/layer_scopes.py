"""Device time by layer scope, with the scopes of latent attention
(``mla``) and of expert layers (``moe``) among the layers.

The program nests ``mla`` inside ``attn`` and ``moe`` inside ``mlp``.
`bench.scopes` attributes an operation to the innermost of its fixed
list of layer scopes, which does not name these two, so it counts their
operations under the enclosing ``attn`` and ``mlp``. This module reads
the same decoded trace (`scopes.trace_of`) and attributes each operation
to the innermost scope of that list plus ``mla`` and ``moe``, so that an
operation inside ``attn/mla/kv_pool`` stays ``kv_pool``. A trace of a
program that names neither scope reads None for both. Once
`bench.scopes.LAYER_SCOPES` names the two, this module has no work left.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Optional

from . import scopes, trace_reduce

LAYER_SCOPES = scopes.LAYER_SCOPES + ("mla", "moe")


def innermost_scope(tf_op: str) -> str:
    path = tf_op.rpartition(":")[0] or tf_op
    for part in reversed(path.split("/")):
        if part in LAYER_SCOPES:
            return part
    return scopes.UNSCOPED


def scope_runs(data: dict) -> dict:
    """`scopes.scope_runs` over this module's layer scopes."""
    lo, hi = scopes.window(data)
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


def decode_scope_ms(run, scope: str) -> Optional[float]:
    """Mean device ms per decode run of the ops whose innermost scope is
    ``scope``; None where no decode op carries it."""
    data = scopes.trace_of(run)
    if data is None:
        return None
    if "layer_scope_runs" not in run.extra:
        run.extra["layer_scope_runs"] = scope_runs(data)
    rs = run.extra["layer_scope_runs"].get("decode", [])
    if not any(scope in r for r in rs):
        return None
    return 1e3 * sum(r.get(scope, 0.0) for r in rs) / len(rs)
