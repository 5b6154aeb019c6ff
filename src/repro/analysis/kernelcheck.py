"""Kernel contract checker: static proofs over the real Pallas call sites.

The pass never re-implements a kernel. It monkeypatches `pl.pallas_call`
with a recorder, traces each public kernel wrapper under `jax.eval_shape`
(abstract values only — nothing executes, interpret padding rules are the
compile-path ones), and then drives the *captured* BlockSpec index-map
closures through the interval/symbolic domains of `analysis.intervals`:

P1 (KC101/KC109) — every block index each map can produce, over the full
    scalar-prefetch domain (kv_len in [0, Smax] scalar and per-group
    vector, block tables holding arbitrary page ids), lies inside the
    operand's block grid.
P2 (KC102/KC103) — dead-block clamping is a genuine fixed point: for every
    live frontier f (including zero-length rows), a k/v map evaluated at a
    dead step k > f yields *structurally the same* address as at step f —
    for every block-table permutation at once, via symbolic table cells —
    so the dead step never DMAs a fresh tile. k/v operands whose maps
    ignore the prefetched frontier on a multi-block dynamic grid are
    KC102; other k-dependent operands that refetch per dead step are the
    softer KC103.
P3 (KC104/KC105) — output maps never depend on prefetched scalars (writes
    are fence-routed by the serving layer, not the grid), and every
    block-table column a k/v map consults is at or below the live page
    frontier — composed with the serving-side invariants (KC107/KC108)
    this is the "live rows never read the trash page" proof.
P4 (KC106) — per-invocation VMEM footprint (double-buffered in/out blocks
    + VMEM scratch) against a declared budget per bench shape.

Concrete companions that anchor the serving half of the paged contract:
KC107 exhaustively checks the cache-write routing helpers in
`models.layers` (every write lands on the written token's own page or the
trash page — never another live page, including fill levels *past* table
capacity), and KC108 drives `serve.paged.PageAllocator` through
alloc/free/promote/evict cycles asserting the trash page is never issued.
"""
from __future__ import annotations

import dataclasses
import functools
import inspect
import math
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .findings import REPO_ROOT, Finding
from .intervals import Iv, JnpProxy, Sym, concretize

MIB = 2 ** 20
DEFAULT_VMEM_BUDGET = 16 * MIB     # one TPU core's VMEM


# ---------------------------------------------------------------------------
# capture
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CapturedCall:
    grid: tuple
    num_scalar_prefetch: int
    in_specs: list
    out_specs: list
    scratch: list
    out_shape: object
    operands: list          # ShapeDtypeStructs, prefetch operands included
    kernel_name: str = ""


class _Capture:
    def __init__(self):
        self.calls: list[CapturedCall] = []

    def fake_pallas_call(self, kernel, *, out_shape=None, grid_spec=None,
                         grid=None, in_specs=None, out_specs=None,
                         scratch_shapes=None, interpret=False, **kw):
        if grid_spec is not None:
            rec = CapturedCall(
                grid=tuple(grid_spec.grid),
                num_scalar_prefetch=int(grid_spec.num_scalar_prefetch or 0),
                in_specs=list(grid_spec.in_specs),
                out_specs=list(jax.tree_util.tree_leaves(grid_spec.out_specs)),
                scratch=list(grid_spec.scratch_shapes or []),
                out_shape=out_shape, operands=[])
        else:
            rec = CapturedCall(
                grid=tuple(grid) if grid is not None else (),
                num_scalar_prefetch=0,
                in_specs=list(in_specs or []),
                out_specs=list(jax.tree_util.tree_leaves(out_specs)),
                scratch=list(scratch_shapes or []),
                out_shape=out_shape, operands=[])
        rec.kernel_name = getattr(
            kernel, "func", kernel).__name__ if not isinstance(
            kernel, functools.partial) else kernel.func.__name__

        def runner(*operands):
            rec.operands = [jax.ShapeDtypeStruct(jnp.shape(a), a.dtype)
                            for a in operands]
            self.calls.append(rec)
            return jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), out_shape)

        return runner


def capture_call(fn: Callable, arg_structs: Sequence, statics: dict,
                 ) -> CapturedCall:
    """Trace `fn` (the unjitted wrapper) abstractly; return its pallas call."""
    cap = _Capture()
    real = pl.pallas_call
    pl.pallas_call = cap.fake_pallas_call
    try:
        jax.clear_caches()   # nested jits would otherwise replay cached traces
        jax.eval_shape(functools.partial(fn, **statics), *arg_structs)
    finally:
        pl.pallas_call = real
    if len(cap.calls) != 1:
        raise RuntimeError(f"expected exactly one pallas_call under "
                           f"{fn.__name__}, captured {len(cap.calls)}")
    return cap.calls[0]


# ---------------------------------------------------------------------------
# probe registry: representative bench shapes per kernel entry point
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PagedMeta:
    page_size: int
    max_pages: int
    n_pages: int
    groups_per_slot: int


@dataclasses.dataclass
class Probe:
    name: str
    family: str                  # "attention" | "softmax" | "lut" | "mvm"
    fn_name: str                 # dotted public entry point (for the report)
    build: Callable[[], tuple]   # () -> (unjitted fn, arg_structs, statics)
    smax: int = 0                # logical key extent (0 = no kv domain)
    kv_vector: bool = False      # per-group kv_len vector (else scalar)
    paged: Optional[PagedMeta] = None
    budget: int = DEFAULT_VMEM_BUDGET


def _st(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _probes() -> list[Probe]:
    from repro.core.crossbar import CrossbarConfig
    from repro.kernels import ops
    from repro.kernels.acam_lut import acam_lut_2d
    from repro.kernels.acam_softmax import acam_softmax_codes

    f32, i32, b8 = jnp.float32, jnp.int32, jnp.bool_

    def softmax():
        return (acam_softmax_codes.__wrapped__,
                (_st((256, 512), i32),),
                dict(mode="pot", interpret=False))

    def lut():
        return (acam_lut_2d.__wrapped__,
                (_st((256, 512), i32), _st((256,), i32)),
                dict(bias=128, interpret=False))

    def mvm():
        return (ops.acam_mvm.__wrapped__,
                (_st((256, 256), jnp.int8), _st((256, 256), jnp.int8)),
                dict(cfg=CrossbarConfig(), interpret=False))

    def prefill():
        q = _st((1, 8, 512, 64), f32)
        k = _st((1, 8, 512, 64), f32)
        return (ops.raceit_attention_fused.__wrapped__, (q, k, k),
                dict(softmax_mode="pot", causal=True, fold_scale=False,
                     interpret=False))

    def prefill_masked():
        q = _st((1, 8, 256, 64), f32)
        k = _st((1, 8, 1024, 64), f32)
        m = _st((1, 8, 256, 1024), b8)
        return (ops.raceit_attention_fused.__wrapped__, (q, k, k, m),
                dict(softmax_mode="pot", causal=False, fold_scale=False,
                     interpret=False))

    def dec(kv_shape):
        def build():
            q = _st((4, 2, 1, 64), f32)
            k = _st((4, 2, 2048, 64), f32)
            return (ops.raceit_attention_decode_fused.__wrapped__,
                    (q, k, k, _st(kv_shape, i32)),
                    dict(softmax_mode="pot", fold_scale=False,
                         interpret=False))
        return build

    def dec_onetile():
        q = _st((4, 2, 1, 64), f32)
        k = _st((4, 2, 256, 64), f32)
        return (ops.raceit_attention_decode_fused.__wrapped__,
                (q, k, k, _st((4,), i32)),
                dict(softmax_mode="pot", fold_scale=False, interpret=False))

    def dec_gqa():
        q = _st((2, 8, 1, 64), f32)
        k = _st((2, 2, 2048, 64), f32)
        return (ops.raceit_attention_decode_gqa.__wrapped__,
                (q, k, k, _st((2,), i32)),
                dict(softmax_mode="pot", fold_scale=False, interpret=False))

    def dec_paged(sq, masked):
        def build():
            q = _st((4, 2, sq, 64), f32)
            pool = _st((33, 256, 2, 64), f32)
            args = [q, pool, pool, _st((4,), i32), _st((4, 8), i32)]
            if masked:
                args.append(_st((4, sq, 2048), b8))
            return (ops.raceit_attention_decode_paged.__wrapped__,
                    tuple(args),
                    dict(softmax_mode="pot", fold_scale=False,
                         interpret=False))
        return build

    def dec_gqa_paged():
        q = _st((4, 4, 1, 64), f32)
        pool = _st((33, 256, 2, 64), f32)
        return (ops.raceit_attention_decode_gqa_paged.__wrapped__,
                (q, pool, pool, _st((4,), i32), _st((4, 8), i32)),
                dict(softmax_mode="pot", fold_scale=False, interpret=False))

    A = "attention"
    return [
        Probe("softmax_256x512", "softmax",
              "kernels.acam_softmax.acam_softmax_codes", softmax),
        Probe("lut_256x512", "lut", "kernels.acam_lut.acam_lut_2d", lut),
        Probe("mvm_256x256x256", "mvm", "kernels.ops.acam_mvm", mvm),
        Probe("prefill_8x512x512x64_causal", A,
              "kernels.ops.raceit_attention_fused", prefill),
        Probe("prefill_masked_8x256x1024x64", A,
              "kernels.ops.raceit_attention_fused", prefill_masked),
        Probe("decode_scalar_8x1x2048x64", A,
              "kernels.ops.raceit_attention_decode_fused", dec(()),
              smax=2048),
        Probe("decode_rows_8x1x2048x64", A,
              "kernels.ops.raceit_attention_decode_fused", dec((4,)),
              smax=2048, kv_vector=True),
        Probe("decode_onetile_8x1x256x64", A,
              "kernels.ops.raceit_attention_decode_fused", dec_onetile,
              smax=256, kv_vector=True),
        Probe("decode_gqa_2x8x2048x64_rep4", A,
              "kernels.ops.raceit_attention_decode_gqa", dec_gqa,
              smax=2048, kv_vector=True),
        Probe("decode_paged_4x2x2048x64_ps256", A,
              "kernels.ops.raceit_attention_decode_paged",
              dec_paged(1, False), smax=2048, kv_vector=True,
              paged=PagedMeta(256, 8, 33, 2)),
        Probe("chunk_paged_masked_4x2x256q_2048x64_ps256", A,
              "kernels.ops.raceit_attention_decode_paged",
              dec_paged(256, True), smax=2048, kv_vector=True,
              paged=PagedMeta(256, 8, 33, 2)),
        Probe("decode_gqa_paged_4x4kv2x2048x64_ps256", A,
              "kernels.ops.raceit_attention_decode_gqa_paged",
              dec_gqa_paged, smax=2048, kv_vector=True,
              paged=PagedMeta(256, 8, 33, 2)),
    ]


def _roles(probe: Probe, call: CapturedCall) -> tuple[list[str], list[str]]:
    """Operand role per in_spec (kernel-module layout is fixed by the
    builders; see acam_attention.acam_attention_codes) and per out_spec."""
    n = len(call.in_specs)
    if probe.family == "softmax":
        return ["x", "lut_exp", "lut_log", "lut_prob"][:n], ["out"]
    if probe.family == "lut":
        return ["x", "lut"][:n], ["out"]
    if probe.family == "mvm":
        return ["x", "w"][:n], ["out"]
    roles = ["scale", "qoff", "cmax_floor", "q", "k", "v"]
    if call.num_scalar_prefetch == 0:
        roles = ["kvlen", "kvmax"] + roles
    if n == len(roles) + 4:
        roles = roles + ["mask"]
    roles = roles + ["lut_exp", "lut_log", "lut_prob"]
    if len(roles) != n:
        raise RuntimeError(f"{probe.name}: cannot assign operand roles "
                           f"({n} in_specs, guessed {len(roles)})")
    return roles, ["out", "cmax"][:len(call.out_specs)]


# ---------------------------------------------------------------------------
# abstract prefetch refs
# ---------------------------------------------------------------------------

class _AbsVec:
    """Scalar-prefetch vector: any index returns `value`; reads recorded."""

    def __init__(self, length: int, value, oob: list):
        self.length, self.value, self.oob = length, value, oob
        self.reads = 0

    def __getitem__(self, i):
        self.reads += 1
        i = concretize(i)
        lo, hi = (i.lo, i.hi) if isinstance(i, Iv) else (i, i)
        if lo < 0 or hi >= self.length:
            self.oob.append(f"index [{lo},{hi}] into a length-{self.length} "
                            f"prefetch vector")
        return self.value


class _AbsTable:
    """Block table: interval mode returns any-page; sym mode returns an
    opaque per-cell variable. Every access is recorded for the frontier
    (KC105) and bounds (KC109) checks."""

    def __init__(self, rows: int, cols: int, n_pages: int, mode: str,
                 oob: list):
        self.rows, self.cols, self.n_pages = rows, cols, n_pages
        self.mode, self.oob = mode, oob
        self.accesses: list[tuple] = []

    def __getitem__(self, rc):
        r, c = (concretize(x) for x in rc)
        self.accesses.append((r, c))
        for v, n, what in ((r, self.rows, "row"), (c, self.cols, "column")):
            lo, hi = (v.lo, v.hi) if isinstance(v, Iv) else (v, v)
            if lo < 0 or hi >= n:
                self.oob.append(f"block-table {what} index [{lo},{hi}] "
                                f"outside [0,{n})")
        if self.mode == "interval":
            return Iv(0, self.n_pages - 1)
        if isinstance(r, Iv) or isinstance(c, Iv):
            raise RuntimeError("symbolic table access with non-concrete "
                               "indices")
        return Sym.var(("bt", r, c))


@dataclasses.dataclass
class _EvalResult:
    idx: tuple
    vec_reads: int
    table: Optional[_AbsTable]
    oob: list


def _eval_map(idx_map, grid_idx, kvl, kvm, bt) -> _EvalResult:
    """Run a real index-map closure on abstract args, jnp proxied."""
    idx_map = inspect.unwrap(idx_map)  # Pallas wraps maps in _IndexMapFunc
    args = list(grid_idx)
    extra = idx_map.__code__.co_argcount - len(args)
    args += [kvl, kvm, bt][:max(extra, 0)]
    g = idx_map.__globals__
    oob: list = []
    had, prev = "jnp" in g, g.get("jnp")
    if had:
        g["jnp"] = JnpProxy(prev)
    try:
        out = idx_map(*args)
    finally:
        if had:
            g["jnp"] = prev
    reads = (kvl.reads if isinstance(kvl, _AbsVec) else 0) + \
            (kvm.reads if isinstance(kvm, _AbsVec) else 0)
    for ref in (kvl, kvm):
        if isinstance(ref, _AbsVec):
            oob += ref.oob
    if isinstance(bt, _AbsTable):
        oob += bt.oob
    return _EvalResult(tuple(concretize(x) for x in out), reads,
                       bt if isinstance(bt, _AbsTable) else None, oob)


def _map_anchor(idx_map) -> tuple[str, int]:
    """(repo-relative path, line) of the *inner* map, unwrapping `_im`."""
    fn = inspect.unwrap(idx_map)
    for cell in (fn.__closure__ or ()):
        if callable(getattr(cell, "cell_contents", None)):
            inner = cell.cell_contents
            if getattr(inner, "__code__", None) is not None:
                fn = inner
                break
    code = fn.__code__
    path = code.co_filename
    try:
        import pathlib
        path = str(pathlib.Path(path).resolve().relative_to(REPO_ROOT))
    except ValueError:
        pass
    return path, code.co_firstlineno


def _grid_points(grid):
    return np.ndindex(*grid) if grid else iter([()])


# ---------------------------------------------------------------------------
# the per-call contract analysis
# ---------------------------------------------------------------------------

def analyze_call(probe: Probe, call: CapturedCall) -> tuple[list[Finding], dict]:
    findings: list[Finding] = []
    in_roles, out_roles = _roles(probe, call)
    nsp = call.num_scalar_prefetch
    ops_in = call.operands[nsp:]
    smax = probe.smax
    nk = call.grid[3] if len(call.grid) == 4 else 0
    paged = probe.paged

    def prefetch_refs(mode: str, kv_value, oob):
        if nsp == 0 and smax == 0:
            return None, None, None
        ng = call.grid[1] if len(call.grid) == 4 else 1
        kvl_len = call.operands[0].shape[0] if nsp else max(
            1, ops_in[0].shape[0] if in_roles[:1] == ["kvlen"] else 1)
        kvl = _AbsVec(kvl_len, kv_value, oob)
        kvm = _AbsVec(ng, kv_value, oob)
        bt = None
        if paged is not None:
            bt = _AbsTable(call.operands[2].shape[0],
                           call.operands[2].shape[1],
                           paged.n_pages, mode, oob)
        return kvl, kvm, bt

    # whole-array SMEM operands (scalars) have no index map to check
    specs = [(r, s, o) for r, s, o in
             [(r, s, ops_in[j]) for j, (r, s) in
              enumerate(zip(in_roles, call.in_specs))] +
             list(zip(out_roles, call.out_specs,
                      jax.tree_util.tree_leaves(call.out_shape)))
             if s.index_map is not None]

    # ---- P1: bounds over the whole grid x full prefetch domain -----------
    for role, spec, operand in specs:
        path, line = _map_anchor(spec.index_map)
        site = f"{probe.name}:{role}"
        block = spec.block_shape
        for gp in _grid_points(call.grid):
            oob: list = []
            kvl, kvm, bt = prefetch_refs("interval", Iv(0, max(smax, 0)), oob)
            res = _eval_map(spec.index_map, gp, kvl, kvm, bt)
            for msg in res.oob:
                findings.append(Finding("kernelcheck", "KC109", path, line,
                                        site, f"at grid {gp}: {msg}"))
            if len(res.idx) != len(block):
                findings.append(Finding(
                    "kernelcheck", "KC101", path, line, site,
                    f"map returned {len(res.idx)} indices for a "
                    f"{len(block)}-d block"))
                break
            for d, (ix, b, dim) in enumerate(
                    zip(res.idx, block, operand.shape)):
                n_blocks = -(-dim // b)
                lo, hi = (ix.lo, ix.hi) if isinstance(ix, Iv) else (ix, ix)
                if isinstance(ix, Sym):
                    findings.append(Finding(
                        "kernelcheck", "KC101", path, line, site,
                        f"dim {d}: symbolic index escaped interval "
                        f"analysis at grid {gp}"))
                elif lo < 0 or hi > n_blocks - 1:
                    findings.append(Finding(
                        "kernelcheck", "KC101", path, line, site,
                        f"dim {d}: block index range [{lo},{hi}] outside "
                        f"[0,{n_blocks - 1}] (operand dim {dim}, block {b}) "
                        f"at grid {gp}"))
    # ---- classify maps: does each read the prefetched frontier? ----------
    dyn = nsp >= 2 and nk > 1
    reads_kvm: dict[int, bool] = {}
    k_dependent: dict[int, bool] = {}
    if len(call.grid) == 4:
        base = (0, 0, 0, 0)
        bumped = (0, 0, 0, min(1, nk - 1))
        for j, (role, spec, _) in enumerate(specs):
            # classify in *symbolic* table mode: when block_k == page_size
            # the in-page dims are constant and an interval-mode table
            # collapses every k to the same any-page interval, hiding the
            # k-dependence that flows through the table lookup
            oob: list = []
            kvl, kvm, bt = prefetch_refs("sym",
                                         Iv(max(smax, 1), max(smax, 1)), oob)
            r0 = _eval_map(spec.index_map, base, kvl, kvm, bt)
            reads_kvm[j] = r0.vec_reads > 0 or (
                r0.table is not None and len(r0.table.accesses) > 0)
            oob2: list = []
            kvl, kvm, bt = prefetch_refs("sym",
                                         Iv(max(smax, 1), max(smax, 1)), oob2)
            r1 = _eval_map(spec.index_map, bumped, kvl, kvm, bt)
            k_dependent[j] = r0.idx != r1.idx

    # ---- P2: dead-block clamp is a fixed point (per live frontier) -------
    frontier_domains = 0
    if dyn:
        k_spec = next(s for r, s, _ in specs if r == "k")
        bk = k_spec.block_shape[1]
        spb = (paged.page_size // bk) if paged else None
        p_grid, ng, nq = call.grid[0], call.grid[1], call.grid[2]
        frontiers = [None]       # None = empty rows (kv_len == 0)
        frontiers += [f for f in range(nk) if f * bk + 1 <= smax]
        for j, (role, spec, operand) in enumerate(specs):
            path, line = _map_anchor(spec.index_map)
            site = f"{probe.name}:{role}"
            is_kv = role in ("k", "v")
            if role in out_roles:
                continue
            if not k_dependent.get(j, False):
                continue
            if not reads_kvm[j]:
                rule = "KC102" if is_kv else "KC103"
                sev = "error" if is_kv else "warn"
                findings.append(Finding(
                    "kernelcheck", rule, path, line, site,
                    f"k-dependent index map ignores the prefetched live "
                    f"frontier on a {nk}-block dynamic grid: every dead "
                    f"block DMAs a fresh tile", severity=sev))
                continue
            for f in frontiers:
                frontier_domains += 1
                if f is None:
                    kv_iv, live_k, page_frontier = Iv(0, 0), 0, 0
                else:
                    kv_iv = Iv(f * bk + 1, min((f + 1) * bk, smax))
                    live_k, page_frontier = f, (f // spb if spb else None)
                for p in range(p_grid):
                    for g in range(ng):
                        for i in range(nq):
                            oob: list = []
                            kvl, kvm, bt = prefetch_refs("sym", kv_iv, oob)
                            ref = _eval_map(spec.index_map,
                                            (p, g, i, live_k), kvl, kvm, bt)
                            _check_frontier(findings, ref, page_frontier,
                                            path, line, site, f)
                            for k in range(live_k + 1, nk):
                                oob2: list = []
                                kvl2, kvm2, bt2 = prefetch_refs(
                                    "sym", kv_iv, oob2)
                                dead = _eval_map(spec.index_map,
                                                 (p, g, i, k),
                                                 kvl2, kvm2, bt2)
                                _check_frontier(findings, dead,
                                                page_frontier, path, line,
                                                site, f)
                                if dead.idx != ref.idx:
                                    findings.append(Finding(
                                        "kernelcheck", "KC102", path, line,
                                        site,
                                        f"frontier {f} (kv_len in "
                                        f"[{kv_iv.lo},{kv_iv.hi}]): dead "
                                        f"step k={k} addresses "
                                        f"{dead.idx}, live frontier "
                                        f"addresses {ref.idx} — not a "
                                        f"fixed point"))
                                    break
                            else:
                                continue
                            break

    # ---- P3: out maps independent of prefetched scalars ------------------
    for role, spec, _ in ((r, s, o) for r, s, o in specs if r in out_roles):
        if len(call.grid) != 4 or (nsp == 0 and smax == 0):
            break
        path, line = _map_anchor(spec.index_map)
        oob: list = []
        kvl, kvm, bt = prefetch_refs("interval", Iv(0, max(smax, 1)), oob)
        res = _eval_map(spec.index_map, (0, 0, 0, 0), kvl, kvm, bt)
        tbl = res.table is not None and len(res.table.accesses) > 0
        if res.vec_reads or tbl:
            findings.append(Finding(
                "kernelcheck", "KC104", path, line, f"{probe.name}:{role}",
                "output BlockSpec index map reads prefetched scalars — "
                "write routing must not depend on runtime lengths"))

    # ---- P4: VMEM footprint ---------------------------------------------
    vmem = 0
    for (role, spec, operand) in specs:
        vmem += 2 * int(np.prod(spec.block_shape)) * np.dtype(
            operand.dtype).itemsize
    for s in call.scratch:
        space = str(getattr(s, "memory_space", "vmem")).lower()
        if "smem" not in space:
            vmem += int(np.prod(s.shape)) * np.dtype(s.dtype).itemsize
    if vmem > probe.budget:
        path, line = _map_anchor(specs[0][1].index_map)
        findings.append(Finding(
            "kernelcheck", "KC106", path, line, f"{probe.name}:vmem",
            f"estimated VMEM footprint {vmem / MIB:.2f} MiB exceeds the "
            f"{probe.budget / MIB:.0f} MiB budget"))

    stats = dict(grid_points=int(np.prod(call.grid)) if call.grid else 1,
                 spec_sites=len(specs), vmem_bytes=vmem,
                 frontier_domains=frontier_domains,
                 map_anchors=sorted({_map_anchor(s.index_map)
                                     for _, s, _ in specs}))
    return findings, stats


def _check_frontier(findings, res: _EvalResult, page_frontier, path, line,
                    site, f):
    """KC105: consulted block-table columns stay at/below the live page
    frontier for this kv_len domain (empty rows must consult column 0)."""
    if res.table is None or page_frontier is None:
        return
    for (_, c) in res.table.accesses:
        lo, hi = (c.lo, c.hi) if isinstance(c, Iv) else (c, c)
        if hi > page_frontier:
            findings.append(Finding(
                "kernelcheck", "KC105", path, line, site,
                f"frontier {f}: consults block-table column [{lo},{hi}] "
                f"past the last live page {page_frontier}"))


# ---------------------------------------------------------------------------
# concrete serving-side probes: write fencing + allocator
# ---------------------------------------------------------------------------

def check_write_fence(route_chunk: Optional[Callable] = None,
                      route_decode: Optional[Callable] = None,
                      ) -> list[Finding]:
    """KC107: every paged cache write lands on the written token's own page
    or the trash page — exhaustively, including fills past table capacity."""
    from repro.models import layers
    route_chunk = route_chunk or layers.paged_write_targets_chunk
    route_decode = route_decode or layers.paged_write_targets_decode
    findings: list[Finding] = []
    ps, mp, b_rows = 4, 2, 3
    cap = ps * mp
    bt = np.asarray([[3, 1], [5, 2], [4, 6]], np.int32)   # distinct, no 0

    def anchor(fn):
        code = fn.__code__
        try:
            import pathlib
            path = str(pathlib.Path(code.co_filename).resolve()
                       .relative_to(REPO_ROOT))
        except ValueError:
            path = code.co_filename
        return path, code.co_firstlineno

    # chunk path: all (lens, offs) with lens up to past-capacity overflow
    sq = 4
    path, line = anchor(route_chunk)
    for l0 in range(0, cap + 3):
        for o0 in range(0, l0 + 1):
            lens = np.asarray([l0, cap, 0], np.int32)
            offs = np.asarray([o0, 0, 0], np.int32)
            pages, slot = (np.asarray(a) for a in route_chunk(
                jnp.asarray(bt), jnp.asarray(lens), jnp.asarray(offs),
                sq, ps))
            for b in range(b_rows):
                for j in range(sq):
                    col = int(offs[b]) + j
                    live = col < min(int(lens[b]), cap)
                    want_page = int(bt[b, col // ps]) if live else 0
                    want_slot = col % ps if live else None
                    if int(pages[b, j]) != want_page or (
                            live and int(slot[b, j]) != want_slot):
                        findings.append(Finding(
                            "kernelcheck", "KC107", path, line,
                            f"write_fence:chunk",
                            f"lens={lens.tolist()} offs={offs.tolist()} "
                            f"row {b} token {j} (col {col}): wrote page "
                            f"{int(pages[b, j])} slot {int(slot[b, j])}, "
                            f"contract wants "
                            f"{'page %d slot %d' % (want_page, want_slot) if live else 'trash page 0'}"))
                        return findings   # first violation is enough
    # decode path: every fill level incl. 0 and past-capacity
    path, line = anchor(route_decode)
    for l0 in range(0, cap + 3):
        lens = np.asarray([l0, 1, cap + 2], np.int32)
        pages, slot = (np.asarray(a) for a in route_decode(
            jnp.asarray(bt), jnp.asarray(lens), ps))
        for b in range(b_rows):
            lb = int(lens[b])
            live = 0 < lb <= cap
            pos = lb - 1
            want_page = int(bt[b, pos // ps]) if live else 0
            if int(pages[b]) != want_page or (
                    live and int(slot[b]) != pos % ps):
                findings.append(Finding(
                    "kernelcheck", "KC107", path, line,
                    f"write_fence:decode",
                    f"lens={lens.tolist()} row {b}: wrote page "
                    f"{int(pages[b])} slot {int(slot[b])}, contract wants "
                    f"{'page %d slot %d' % (want_page, pos % ps) if live else 'trash page 0'}"))
                return findings
    return findings


def check_allocator() -> list[Finding]:
    """KC108: PageAllocator never issues physical page 0 through any
    alloc/free/promote/evict/leak cycle."""
    from repro.serve.paged import PageAllocator
    findings: list[Finding] = []
    import inspect
    import pathlib
    src = inspect.getsourcefile(PageAllocator)
    try:
        path = str(pathlib.Path(src).resolve().relative_to(REPO_ROOT))
    except ValueError:
        path = src
    line = inspect.getsourcelines(PageAllocator)[1]

    def issue(pages):
        if pages and 0 in pages:
            findings.append(Finding(
                "kernelcheck", "KC108", path, line, "allocator",
                f"alloc() handed out the trash page: {pages}"))

    a = PageAllocator(8)
    p0 = a.alloc(0, 7) or []
    issue(p0)                       # exhaustion: every page but 0 issued
    assert a.alloc(1, 1) is None or issue(a.alloc(1, 1))
    a.free_slot(0)
    p1 = a.alloc(1, 3) or []
    issue(p1)
    if p1:
        a.promote(1, p1[0])         # slot-owned -> shared
        a.acquire(2, p1[0])
        a.release_refs(2)
        a.free_slot(1)
        a.evict_shared(p1[0])       # shared -> free again
    p2 = a.alloc(3, 7) or []
    issue(p2)
    a.leak_slot(3)
    a.assert_invariants()
    return findings


# ---------------------------------------------------------------------------
# entry point + contract report
# ---------------------------------------------------------------------------

def run() -> tuple[list[Finding], dict, str]:
    """(findings, coverage, kernel-contracts markdown) over all probes."""
    findings: list[Finding] = []
    rows = []
    anchors: set = set()
    grid_points = spec_sites = frontier_domains = 0
    for probe in _probes():
        fn, args, statics = probe.build()
        call = capture_call(fn, args, statics)
        f, stats = analyze_call(probe, call)
        findings += f
        anchors.update(tuple(a) for a in stats["map_anchors"])
        grid_points += stats["grid_points"]
        spec_sites += stats["spec_sites"]
        frontier_domains += stats["frontier_domains"]
        rows.append((probe, call, stats,
                     sum(1 for x in f if x.severity == "error")))
    findings += check_write_fence()
    findings += check_allocator()
    modules = sorted({a[0] for a in anchors})
    coverage = dict(
        probes=len(rows),
        pallas_calls=len(rows),
        spec_sites=spec_sites,
        index_map_sites=len(anchors),
        kernel_modules=modules,
        grid_points=grid_points,
        frontier_domains=frontier_domains,
    )
    return findings, coverage, contracts_markdown(rows, coverage)


def contracts_markdown(rows, coverage) -> str:
    """Deterministic per-kernel contract report (docs/kernel_contracts.md)."""
    out = [
        "# Kernel contracts",
        "",
        "Generated by `python -m repro.analysis --write-contracts` — do not",
        "edit by hand; `tests/test_docs.py` checks this file matches the",
        "analyzer's current output. One section per analyzed bench shape:",
        "the captured grid, each operand's block and index-map class, and",
        "the proof obligations discharged (P1 bounds, P2 dead-block clamp",
        "fixed point, P3 prefetch-independent writes / live-frontier table",
        "columns, P4 VMEM footprint vs budget).",
        "",
    ]
    for probe, call, stats, n_err in rows:
        in_roles, out_roles = _roles(probe, call)
        out.append(f"## {probe.name}")
        out.append("")
        out.append(f"- entry: `{probe.fn_name}`")
        out.append(f"- grid: `{call.grid}` "
                   f"(scalar-prefetch operands: {call.num_scalar_prefetch})")
        kv = ("none (static extent)" if probe.smax == 0 else
              f"kv_len in [0, {probe.smax}] "
              f"({'per-group vector' if probe.kv_vector else 'scalar'})")
        out.append(f"- prefetch domain: {kv}")
        if probe.paged:
            p = probe.paged
            out.append(f"- paging: page_size={p.page_size}, "
                       f"max_pages={p.max_pages}, pool={p.n_pages} pages, "
                       f"block table permutation-free proof via symbolic "
                       f"cells")
        out.append(f"- VMEM estimate: {stats['vmem_bytes'] / MIB:.2f} MiB "
                   f"of {probe.budget / MIB:.0f} MiB budget "
                   f"(double-buffered blocks + scratch)")
        out.append(f"- verdict: "
                   f"{'PROVEN' if n_err == 0 else f'{n_err} violation(s)'}")
        out.append("")
        out.append("| operand | block | index map |")
        out.append("|---|---|---|")
        specs = list(zip(in_roles, call.in_specs)) + \
            list(zip(out_roles, call.out_specs))
        for role, spec in specs:
            if spec.index_map is None:
                out.append(f"| {role} | whole array (SMEM) | — |")
                continue
            path, line = _map_anchor(spec.index_map)
            out.append(f"| {role} | `{spec.block_shape}` | "
                       f"`{path}:{line}` |")
        out.append("")
    out.append("## Coverage")
    out.append("")
    for k in sorted(coverage):
        v = coverage[k]
        if isinstance(v, list):
            v = ", ".join(f"`{x}`" for x in v)
        out.append(f"- {k}: {v}")
    out.append("")
    return "\n".join(out)
