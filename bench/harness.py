"""The benchmark's harness: one cell is a configuration file and a traffic
mix, both found by name, served through the program's own engine.

    cell "gpt2-large.chat" = bench/configs/gpt2-large.json
                           x bench/traffic/chat.json
    metric "x"             = bench/metrics/x.py, a ``read(ctx)`` function
    "module": "m" in a configuration's reference block
                           = bench/refs/m.py (default: the dense
                             decoder's module, bench/model_ref)

Nothing here names a configuration, a mix, a metric or an architecture: a
new one is a new file. A configuration's reference module makes its
weights, runs its reference and counts its operations. The serving loop
is the benchmark's own: it submits each request at its due time (open
loop) or as the closed loop asks (a backlog kept full, or a session's
next ask when its answer returns), calls
`ContinuousBatcher.step()`, and stamps the tokens a step produced on the
host clock when the step returns (the step has read the sampled tokens
back, so their device work is done).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import importlib.util
import json
import statistics
import time
from pathlib import Path
from typing import Callable, Optional

import numpy as np

from . import model_ref
from .traffic import Spec, Traffic, seed_sequence

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
WARM_RID = 1 << 40           # request ids of the compile warm-up
SAMPLE_TOKENS = 600          # served tokens the output check reads, at least
SAMPLE_MAX_REQUESTS = 8


# ---------------------------------------------------------------- lookup
def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def find_cell(bm: dict, name: str) -> dict:
    for cell in bm["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json; known: "
                     f"{[c['name'] for c in bm['workloads']]}")


def load_config(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return load_json(bench_dir / "configs" / f"{name}.json")


def load_traffic(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return load_json(bench_dir / "traffic" / f"{name}.json")


def _load_module(prefix: str, path: Path):
    name = path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(f"{prefix}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, bench_dir: Path = BENCH_DIR) -> Callable:
    """The ``read(ctx)`` function of metric ``name``, from its own file."""
    return _load_module("bench_metric",
                        bench_dir / "metrics" / f"{name}.py").read


@functools.lru_cache(maxsize=None)
def _load_reference(path: Path):
    # once per file, so that its jitted functions keep their traces
    return _load_module("bench_ref", path)


def reference_module(cfg_file: dict, bench_dir: Path = BENCH_DIR):
    """The configuration's reference module, whose contract the docstring
    of `bench/model_ref` states: ``bench/refs/<module>.py`` where its
    reference block names one, else that dense default."""
    name = cfg_file["reference"].get("module")
    if name is None:
        return model_ref
    return _load_reference((bench_dir / "refs" / f"{name}.py").resolve())


def freeze(x):
    """JSON made hashable: a list becomes a tuple, a dict its sorted
    items, at every depth; a static argument of a jitted function."""
    if isinstance(x, dict):
        return tuple(sorted((k, freeze(v)) for k, v in x.items()))
    if isinstance(x, list):
        return tuple(freeze(v) for v in x)
    return x


def cell_metrics(bm: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end ones, or with
    ``trace`` its per-layer ones."""
    e2e = [m for m in bm["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bm["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def run_key_int(seed: int) -> int:
    """A 32-bit weight key from a run seed of any size."""
    return int(seed_sequence(seed, 0).generate_state(1)[0])


# ---------------------------------------------------------------- system
def make_params(cfg_file: dict, seed: int, bench_dir: Path = BENCH_DIR):
    """The served weights, made on the device by the reference module from
    the seed (and quantized by the program when the mode serves int8)."""
    import jax
    from repro.models.model import quantize_model_params
    quantize = (quantize_model_params
                if cfg_file["serving"]["mode"] == "raceit_q8"
                else (lambda tree: tree))
    return reference_module(cfg_file, bench_dir).served_params(
        jax.random.PRNGKey(run_key_int(seed)), cfg_file["reference"],
        quantize)


def program_config(cfg_file: dict):
    """The program's model configuration with the file's overrides; JSON
    lists become the tuples that layer patterns are."""
    from repro.configs import get_config
    prog = cfg_file["program"]
    return get_config(prog["name"]).replace(**{
        k: tuple(v) if isinstance(v, list) else v
        for k, v in prog["overrides"].items()})


def build_engine(cfg_file: dict, params):
    from repro.configs.base import ExecConfig
    from repro.serve import GenerationEngine
    srv = cfg_file["serving"]
    exec_cfg = ExecConfig.serving(
        mode="raceit" if srv["mode"].startswith("raceit") else "digital")
    return GenerationEngine(program_config(cfg_file), params,
                            exec_cfg=exec_cfg, max_len=srv["max_len"])


def new_batcher(cfg_file: dict, eng):
    from repro.serve import ContinuousBatcher
    srv = cfg_file["serving"]
    return ContinuousBatcher(eng, n_slots=srv["n_slots"], paged=True,
                             page_size=srv["page_size"],
                             n_pages=srv.get("n_pages"),
                             prefix_cache=srv["prefix_cache"])


def build_system(cfg_file: dict, seed: int, bench_dir: Path = BENCH_DIR):
    """The program objects the window drives: the seed's weights, the
    engine, and the paged continuous batcher."""
    eng = build_engine(cfg_file, make_params(cfg_file, seed, bench_dir))
    return eng, new_batcher(cfg_file, eng)


def warm_compile(batcher, vocab: int, seed: int) -> None:
    """Compile the two executables the window uses (the chunk call and the
    decode step) and the small programs around them, by serving one
    request per slot whose prompt spans two chunk calls."""
    from repro.serve import Request
    rng = np.random.default_rng(seed_sequence(seed, 3))
    for i in range(batcher.n):
        prompt = rng.integers(0, vocab, batcher.prefill_chunk + 1)
        batcher.submit(Request(WARM_RID + i, prompt.astype(np.int32), 2))
    batcher.run_all()


def counters(batcher) -> dict:
    """Every whole-number counter of the batcher's summary, for readers and
    reference modules; those the harness reads are 0 where it keeps none."""
    s = batcher.summary()
    out = {k: 0 for k in ("decode_steps", "decode_tokens", "prefix_hit_pages",
                          "prefix_miss_pages", "prefix_evictions")}
    out.update((k, v) for k, v in s.items() if type(v) is int)
    return out


# ---------------------------------------------------------------- loop
@dataclasses.dataclass
class Record:
    """What the load generator saw of one request, in seconds from the
    window's start."""

    spec: Spec
    submit_t: float
    admit_t: Optional[float] = None
    first_t: Optional[float] = None
    done_t: Optional[float] = None
    n_tok: int = 0
    n_in: int = 0                 # tokens stamped inside the window
    first_in: Optional[float] = None
    last_in: Optional[float] = None
    error: Optional[str] = None
    served: Optional[np.ndarray] = None  # the tokens the program returned
    hit_pages: int = 0            # prompt pages served from the prefix cache

    @property
    def due(self) -> float:
        return self.spec.due if self.spec.due is not None else self.submit_t


@dataclasses.dataclass
class Window:
    seconds: float
    start: float                  # host clock at the window's start
    records: dict
    steps: list                   # (start, end) of each step, s
    counters_before: dict
    counters_after: dict
    late_s: list                  # submit lateness of open-loop requests
    queued_at_close: int = 0
    trace: Optional[tuple] = None  # (start, end) of the traced part, s
    calls: Optional[list] = None  # engine calls seen in the traced part


class _CallLog:
    """Wraps the engine's two jitted entry points, in a traced run only:
    names each call on the host timeline and keeps the rows it served."""

    def __init__(self, eng, clock):
        import jax
        self.calls, self.clock, self.on = [], clock, False
        chunk, decode = eng._prefill_chunk, eng._decode

        def prefill_chunk(params, toks, cache, offs, feeds, bt, ps):
            with jax.profiler.TraceAnnotation("engine.prefill_chunk"):
                out = chunk(params, toks, cache, offs, feeds, bt, ps)
            if self.on:
                o, f = np.asarray(offs), np.asarray(feeds)
                self.calls.append(("chunk", self.clock(),
                                   [(int(a), int(b)) for a, b in zip(o, f)
                                    if b > 0]))
            return out

        def decode_step(params, tok, cache, *args, **kw):
            with jax.profiler.TraceAnnotation("engine.decode_step"):
                out = decode(params, tok, cache, *args, **kw)
            if self.on:
                lens = np.asarray(args[2])
                self.calls.append(("decode", self.clock(),
                                   [int(n) for n in lens if n > 0]))
            return out

        eng._prefill_chunk, eng._decode = prefill_chunk, decode_step


def track_prefix_hits(batcher) -> dict:
    """Prompt pages each admitted request found in the prefix cache, by
    request id. Admission pops the request from the queue and then commits
    its hits to the cache; both calls are wrapped to pair them."""
    hits: dict[int, int] = {}
    prefix = getattr(batcher, "prefix", None)
    if prefix is None:
        return hits
    pop, commit, last = batcher.queue.popleft, prefix.commit, [None]

    def popleft():
        req = pop()
        last[0] = req.rid
        return req

    def commit_hits(found, n_full_pages):
        hits[last[0]] = len(found)
        return commit(found, n_full_pages)

    batcher.queue.popleft, prefix.commit = popleft, commit_hits
    return hits


def serve_window(batcher, traffic: Traffic, seconds: float,
                 trace_dir: Optional[str] = None) -> Window:
    """Run the mix's warm-up, then the measured window; returns what the
    load generator recorded. With ``trace_dir`` the whole window is
    traced into it."""
    from repro.serve import Request
    clock = time.perf_counter
    recs: dict[int, Record] = {}
    steps, late = [], []
    t0 = clock() + traffic.warmup_s
    now = lambda: clock() - t0
    pending = list(traffic.specs) if traffic.open_loop else None
    hits = track_prefix_hits(batcher)
    retired: list[int] = []
    nxt = 0
    tracing = trace_dir is not None
    log = None
    if tracing:
        import jax
        log = _CallLog(batcher.engine, now)

    def span(name):
        """A host span in the trace; nothing outside a traced window."""
        if traced is None:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(name)

    traced = None
    before = None

    def submit(spec, t):
        recs[spec.rid] = Record(spec, t)
        batcher.submit(Request(spec.rid, spec.prompt, spec.n_new))

    def stamp(rec, n, t):
        new = n - rec.n_tok
        if new <= 0:
            return
        if rec.first_t is None:
            rec.first_t = t
        rec.n_tok = n
        if 0.0 <= t < seconds:
            rec.n_in += new
            rec.last_in = t
            if rec.first_in is None:
                rec.first_in = t

    while True:
        t = now()
        if t >= seconds:
            break
        if before is None and t >= 0.0:
            before = counters(batcher)
        if tracing and traced is None and t >= 0.0:
            import jax
            jax.profiler.start_trace(trace_dir)
            traced = [now(), None]
            window_span = span("bench.window")
            window_span.__enter__()
            log.on = True
        if pending is not None:
            while nxt < len(pending) and pending[nxt].due <= t:
                late.append(t - pending[nxt].due)
                submit(pending[nxt], t)
                nxt += 1
        else:
            for spec in traffic.refill(len(batcher.queue), retired):
                submit(spec, t)
            retired = []
        busy = bool(batcher.queue) or any(
            s is not None for s in batcher.slots)
        if not busy:
            wait = seconds - t
            if pending is not None and nxt < len(pending):
                wait = min(wait, pending[nxt].due - t)
            with span("bench.idle"):
                time.sleep(max(wait, 0.0))
            continue
        t_start = now()
        with span("batcher.step"):
            retired = batcher.step()
        t_end = now()
        steps.append((t_start, t_end))
        for st in batcher.slots:
            if st is None:
                continue
            rec = recs.get(st.req.rid)
            if rec is None:
                continue
            if rec.admit_t is None:
                rec.admit_t = t_start
                rec.hit_pages = hits.get(rec.spec.rid, 0)
            stamp(rec, len(st.tokens), t_end)
        for rid in retired:
            rec = recs.get(rid)
            if rec is None:
                continue
            req = batcher.done[rid]
            if rec.admit_t is None:
                rec.admit_t = t_start
                rec.hit_pages = hits.get(rec.spec.rid, 0)
            rec.done_t = t_end
            if req.error is not None:
                rec.error = f"{req.error.stage}: {req.error.reason}"
            else:
                stamp(rec, len(req.result), t_end)
    if before is None:
        before = counters(batcher)
    if tracing and traced is not None:
        import jax
        traced[1] = now()
        log.on = False
        window_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    return Window(seconds, t0, recs, steps, before, counters(batcher), late,
                  queued_at_close=len(batcher.queue),
                  trace=tuple(traced) if traced else None,
                  calls=log.calls if log else None)


# ---------------------------------------------------------------- e2e
def percentile(values, q: float) -> Optional[float]:
    """The q-th percentile (0-100), linear between order statistics."""
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def attempted_in_window(win: Window) -> list[Record]:
    """Requests the window asked of the system: those due in it, and those
    that held a slot in it (a closed loop's requests wait in the queue
    from before the window, so their due time says little)."""
    return [r for r in win.records.values()
            if 0.0 <= r.due < win.seconds
            or (r.admit_t is not None and r.admit_t < win.seconds
                and (r.done_t is None or r.done_t >= 0.0))]


def due_in_window(win: Window) -> list[Record]:
    return [r for r in win.records.values() if 0.0 <= r.due < win.seconds]


def ttft_values(win: Window) -> list[float]:
    """Time to first token from the due time, for every request due in the
    window; one with no first token at the close counts at its age."""
    out = []
    for r in due_in_window(win):
        if r.first_t is not None and r.first_t <= win.seconds:
            out.append(r.first_t - r.due)
        elif r.error is None:
            out.append(win.seconds - r.due)
    return out


def tpot_values(win: Window) -> list[float]:
    """Each request's mean gap between its tokens inside the window."""
    return [(r.last_in - r.first_in) / (r.n_in - 1)
            for r in win.records.values() if r.n_in >= 2]


def window_tokens(win: Window) -> int:
    return sum(r.n_in for r in win.records.values())


# ---------------------------------------------------------------- check
def pick_sample(win: Window, seed: int,
                shares_prefix: bool = False) -> list[Record]:
    """Requests the window finished, drawn from the seed, the longest
    among them first, until the sample holds ``SAMPLE_TOKENS`` served
    tokens (or ``SAMPLE_MAX_REQUESTS`` requests). Where the mix shares
    prefixes, a request that the prefix cache served pages to comes
    second, if the window finished one."""
    done = [r for r in win.records.values()
            if r.done_t is not None and r.error is None
            and r.done_t >= 0.0]
    if not done:
        return []
    size = lambda r: len(r.spec.prompt) + r.spec.n_new
    longest = max(done, key=lambda r: (size(r), -r.spec.rid))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(seed_sequence(seed, 4))
    order = [rest[i] for i in rng.permutation(len(rest))]
    if shares_prefix and longest.hit_pages == 0:
        hit = [r for r in order if r.hit_pages > 0]
        if hit:
            order.remove(hit[0])
            order.insert(0, hit[0])
    sample, tokens = [longest], longest.spec.n_new
    for r in order:
        if tokens >= SAMPLE_TOKENS or len(sample) >= SAMPLE_MAX_REQUESTS:
            break
        sample.append(r)
        tokens += r.spec.n_new
    return sample


def reference_gaps(cfg_file: dict, seed: int, items, control_bits=None,
                   bench_dir: Path = BENCH_DIR):
    """For each (prompt, served tokens): the gap by which each served
    token's reference logit lies below the reference's best, and with
    ``control_bits`` the same for the tokens that the lower-precision
    reference puts first at the same positions. Returns two lists of
    per-token arrays. Weights are made anew from the seed."""
    import jax
    import jax.numpy as jnp
    ref, arch = reference_module(cfg_file, bench_dir), cfg_file["reference"]
    w = ref.reference_weights(jax.random.PRNGKey(run_key_int(seed)), arch)
    S = cfg_file["serving"]["max_len"]
    static = freeze(arch)
    gaps, ctl = [], []
    for prompt, served in items:
        P, n = len(prompt), len(served)
        toks = np.zeros(S, np.int32)
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        toks[: len(seq)] = seq
        tgt = np.zeros(S, np.int32)
        tgt[P - 1: P - 1 + n] = served
        valid = np.zeros(S, bool)
        valid[P - 1: P - 1 + n] = True
        g, c = ref.gap_readings(w, jnp.asarray(toks), jnp.asarray(tgt),
                                jnp.asarray(valid), static,
                                control_bits=control_bits)
        gaps.append(np.asarray(g)[valid])
        if c is not None:
            ctl.append(np.asarray(c)[valid])
    del w
    return gaps, ctl


def gap_numbers(per_token: list) -> dict:
    """Over the sampled requests' tokens: the widest gap, the mean gap,
    and the mean gap of the request whose mean is widest (one wrong
    request among many shows there)."""
    if not per_token:
        return {"max_logit_gap": None, "mean_logit_gap": None,
                "worst_request_mean_gap": None}
    allg = np.concatenate(per_token)
    return {"max_logit_gap": float(allg.max()),
            "mean_logit_gap": float(allg.mean()),
            "worst_request_mean_gap": float(max(g.mean()
                                                for g in per_token))}


def check_outputs(cfg_file: dict, seed: int, win: Window, sample,
                  shares_prefix: bool = False,
                  control_bits: Optional[int] = None,
                  bench_dir: Path = BENCH_DIR) -> dict:
    """The numbers compared, each beside its limit. A limit of None marks
    a number that is reported and not compared. With ``control_bits`` the
    control stands in the program's place: the gaps read are those of the
    tokens that the reference at that precision puts first."""
    served_bad = sum(1 for r in win.records.values()
                     if r.done_t is not None and r.error is None
                     and r.n_tok != r.spec.n_new)
    failed = sum(1 for r in win.records.values() if r.error is not None)
    items = [(r.spec.prompt, r.served) for r in sample]
    gaps, ctl = (reference_gaps(cfg_file, seed, items, control_bits,
                                bench_dir) if items else ([], []))
    if control_bits is not None:
        gaps = ctl
    out = {"sampled_requests": {"value": len(sample), "limit": 1,
                                "ok": len(sample) >= 1},
           "sampled_tokens": {"value": sum(len(i[1]) for i in items)}}
    hit_pages = sum(r.hit_pages for r in sample)
    out["sampled_prefix_hit_pages"] = (
        {"value": hit_pages, "limit": 1, "ok": hit_pages >= 1}
        if shares_prefix else {"value": hit_pages})
    for name, value in gap_numbers(gaps).items():
        limit = cfg_file["check"].get(name)
        out[name] = {"value": value, "limit": limit,
                     "ok": limit is None or (value is not None
                                             and value <= limit)}
    out["wrong_length_results"] = {"value": served_bad, "limit": 0,
                                   "ok": served_bad == 0}
    out["failed_requests"] = {"value": failed, "limit": 0, "ok": failed == 0}
    return out


# ---------------------------------------------------------------- run
@dataclasses.dataclass
class Run:
    """Everything a metric reader may read (``ctx``)."""

    cell: dict
    cfg_file: dict
    mix: dict
    seed: int
    window: Window
    setup_s: float
    device: dict
    trace: object = None          # trace_reduce.Reduced, --trace 1 only
    extra: dict = dataclasses.field(default_factory=dict)
    sample: list = dataclasses.field(default_factory=list)  # checked
    ref: object = None            # the configuration's reference module


def run_cell(cell: dict, seed: int, seconds: float, t_process: float,
             device, trace_dir: Optional[str] = None,
             bench_dir: Path = BENCH_DIR) -> tuple[Run, dict]:
    """Build, warm, serve the window (traced into ``trace_dir`` if given),
    read memory, check the outputs. Returns the run (for the metric
    readers) and the compared numbers."""
    import jax
    cfg_file = load_config(cell["config"], bench_dir)
    mix = load_traffic(cell["traffic"], bench_dir)
    eng, batcher = build_system(cfg_file, seed, bench_dir)
    vocab = cfg_file["reference"]["vocab_size"]
    warm_compile(batcher, vocab, seed)
    traffic = Traffic(mix, seed, seconds, vocab, batcher.n)
    win = serve_window(batcher, traffic, seconds, trace_dir)
    setup_s = win.start - t_process
    for rec in win.records.values():
        req = batcher.done.get(rec.spec.rid)
        rec.served = (np.asarray(req.result) if req is not None
                      and req.result is not None else None)
    # bytes of one cached key or value element as the attention consumes
    # it: the raceit kernels read int8 codes, any other path the pool
    kv_bytes = (1 if cfg_file["serving"]["mode"].startswith("raceit") else
                max(a.dtype.itemsize for a in jax.tree.leaves(batcher.cache)))
    stats = device.memory_stats() or {}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": cell.get("chips", 1),
           "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    sample = pick_sample(win, seed, traffic.shares_prefix)
    # free the program's state before the reference runs, so that the
    # reference neither sets the peak nor runs short of memory
    del eng, batcher
    gc.collect()
    checks = check_outputs(cfg_file, seed, win, sample,
                           traffic.shares_prefix, bench_dir=bench_dir)
    return Run(cell, cfg_file, mix, seed, win, setup_s, dev,
               extra={"kv_bytes": kv_bytes,
                      "shares_prefix": traffic.shares_prefix},
               sample=sample,
               ref=reference_module(cfg_file, bench_dir)), checks


def read_metrics(run: Run, metrics: list[dict],
                 bench_dir: Path = BENCH_DIR) -> dict:
    out = {}
    for m in metrics:
        v = load_reader(m["name"], bench_dir)(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def result_line(run: Run, metrics: dict, checks: dict,
                breakdown: Optional[dict] = None) -> dict:
    win = run.window
    asked = attempted_in_window(win)
    attempted = len(asked)
    failed = sum(1 for r in asked if r.error is not None)
    correct = all(c.get("ok", True) for c in checks.values())
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": run.device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    ev = (win.counters_after["prefix_evictions"]
          - win.counters_before["prefix_evictions"])
    line["load"] = {"generator_late_p99_s": percentile(win.late_s, 99),
                    "queued_at_close": win.queued_at_close,
                    "prefix_evictions": ev,
                    "steps": len(win.steps),
                    "median_step_s": (statistics.median(
                        e - s for s, e in win.steps) if win.steps else None)}
    line["checks"] = {k: {kk: vv for kk, vv in c.items() if kk != "ok"}
                      for k, c in checks.items()}
    return line
