"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload gpt2-large.chat --seed 7 --seconds 30 \\
        --trace 0

The cell (configuration x traffic mix) is looked up by name in
BENCHMARK.json; its files are found by name under bench/. With ``--trace
0`` the result's metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window's last seconds. The last line of stdout is one JSON object; the
numbers the output check compared are the last lines of stderr. A run
that finds no TPU, or fewer chips than the cell asks for, exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_chips(n: int):
    """The devices of the cell, or exit: only a TPU measures anything."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench: no TPU (JAX platform is {devices[0].platform!r});"
                 f" nothing was measured")
    if len(devices) < n:
        sys.exit(f"bench: the cell needs {n} TPU chips, found "
                 f"{len(devices)}")
    return devices[:n]


def main(argv=None) -> int:
    args = parse(argv)
    from bench import harness
    bm = harness.benchmark(ROOT)
    cell = harness.find_cell(bm, args.workload)
    devices = require_chips(int(cell.get("chips", 1)))
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    try:
        run, checks = harness.run_cell(cell, args.seed, args.seconds,
                                       T_PROCESS, devices[0],
                                       trace_dir=trace_dir)
        breakdown = None
        if args.trace:
            from bench import trace_reduce
            run.trace = trace_reduce.reduce(trace_dir, len(devices))
            run.device["busy_s"] = run.trace.busy_s
            run.device["window_s"] = run.trace.window_s
            breakdown = run.trace.breakdown()
        metrics = harness.read_metrics(
            run, harness.cell_metrics(bm, cell["name"], bool(args.trace)))
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    line = harness.result_line(run, metrics, checks, breakdown)
    for name, c in line["checks"].items():
        limit = f" limit {c['limit']}" if "limit" in c else ""
        print(f"check {name} {c['value']}{limit}", file=sys.stderr)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
