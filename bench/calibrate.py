"""Readings that set the benchmark's fixed numbers, run once on the chip.

    python3 bench/calibrate.py seeds --workload W --seeds 1,2,3 --seconds 40
    python3 bench/calibrate.py sweep --workload W --rates 2,4,6 --seconds 20

``seeds`` runs the cell once per seed in one process, exactly as
``bench/run.py`` does (build, warm-up, the window, the output check), and
prints per seed the numbers the check compares for the program and for
its control: the same comparison, with the tokens that the reference
computed with int4 weight and activation matmuls puts first standing in
for the served tokens. The largest program reading and the smallest
control reading of each number bound its limit in the configuration file.

``sweep`` serves the cell's prompt and answer lengths as Poisson arrivals
at each of the given rates and prints the tails, the throughput and the
backlog at the close: the highest rate whose backlog stays bounded is the
knee. Run from the serving loop, not from the window of a cell.

Neither is run by the benchmark itself. One JSON object per line.
"""
from __future__ import annotations

import argparse
import copy
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

CONTROL_BITS = 4


def _setup(workload: str):
    from bench import harness
    from bench.run import require_chips
    bm = harness.benchmark(ROOT)
    cell = harness.find_cell(bm, workload)
    devices = require_chips(int(cell.get("chips", 1)))
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return harness, cell, devices[0]


def _values(checks: dict) -> dict:
    return {k: c["value"] for k, c in checks.items()}


def _per_request(harness, run) -> list:
    """[prompt tokens, served tokens, prefix-hit pages, the program's mean
    gap, the control's mean gap] of each sampled request."""
    items = [(r.spec.prompt, r.served) for r in run.sample]
    gaps, ctl = harness.reference_gaps(run.cfg_file, run.seed, items,
                                       CONTROL_BITS)
    return [[len(r.spec.prompt), len(r.served), r.hit_pages,
             float(g.mean()), float(c.mean())]
            for r, g, c in zip(run.sample, gaps, ctl)]


def seeds(args) -> None:
    harness, cell, device = _setup(args.workload)
    for seed in [int(s) for s in args.seeds.split(",")]:
        run, checks = harness.run_cell(cell, seed, args.seconds,
                                       time.perf_counter(), device)
        control = harness.check_outputs(run.cfg_file, seed, run.window,
                                        run.sample,
                                        run.extra["shares_prefix"],
                                        control_bits=CONTROL_BITS)
        line = harness.result_line(run, {}, checks)
        print(json.dumps({
            "workload": cell["name"], "seed": seed,
            "correct": line["correct"],
            "control_correct": all(c.get("ok", True)
                                   for c in control.values()),
            "tokens_in_window": harness.window_tokens(run.window),
            "setup_s": run.setup_s,
            "memory_peak_bytes": run.device["memory_peak_bytes"],
            "program": _values(checks), "control": _values(control),
            "sample": _per_request(harness, run)}), flush=True)
        del run
        gc.collect()


def sweep(args) -> None:
    harness, cell, device = _setup(args.workload)
    from bench import readings
    from bench.traffic import Traffic
    cfg_file = harness.load_config(cell["config"])
    mix = harness.load_traffic(cell["traffic"])
    eng = harness.build_engine(cfg_file,
                               harness.make_params(cfg_file, args.seed))
    vocab = cfg_file["reference"]["vocab_size"]
    for rate in [float(r) for r in args.rates.split(",")]:
        m = copy.deepcopy(mix)
        m["arrivals"] = {"kind": "poisson", "rate_per_s": rate}
        batcher = harness.new_batcher(cfg_file, eng)
        harness.warm_compile(batcher, vocab, args.seed)
        win = harness.serve_window(
            batcher, Traffic(m, args.seed, args.seconds, vocab, batcher.n),
            args.seconds)
        depth = len(batcher.queue)
        del batcher
        gc.collect()
        run = harness.Run(cell, cfg_file, m, args.seed, win, 0.0, {})
        due = harness.due_in_window(win)
        print(json.dumps({
            "workload": cell["name"], "rate_per_s": rate,
            "due": len(due),
            "unstarted_at_close": sum(1 for r in due if r.first_t is None),
            "queued_at_close": depth,
            "ttft_p50_s": harness.percentile(harness.ttft_values(win), 50),
            "ttft_p95_s": harness.percentile(harness.ttft_values(win), 95),
            "tpot_p95_s": harness.percentile(harness.tpot_values(win), 95),
            "output_tokens_per_s": readings.output_tokens_per_s(run),
            "generator_late_p99_s": harness.percentile(win.late_s, 99)}),
            flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="mode", required=True)
    a = sub.add_parser("seeds")
    a.add_argument("--workload", required=True)
    a.add_argument("--seeds", required=True)
    a.add_argument("--seconds", type=float, required=True)
    b = sub.add_parser("sweep")
    b.add_argument("--workload", required=True)
    b.add_argument("--rates", required=True)
    b.add_argument("--seconds", type=float, required=True)
    b.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    (seeds if args.mode == "seeds" else sweep)(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
