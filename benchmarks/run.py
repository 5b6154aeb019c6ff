"""Benchmark driver — one harness per paper table/figure + roofline.

Prints a ``name,us_per_call,derived`` CSV summary at the end, and exits
non-zero when any phase failed.
"""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))


def main() -> None:
    from benchmarks import (fig13_tablev, fig14_accuracy, fig15_gce,
                            kernels_bench, roofline, table_iv)

    all_rows, failed = [], []
    for name, mod in (("table_iv", table_iv), ("fig13_tablev", fig13_tablev),
                      ("fig15_gce", fig15_gce), ("kernels", kernels_bench),
                      ("fig14_accuracy", fig14_accuracy),
                      ("roofline", roofline)):
        print(f"\n=== {name} " + "=" * (60 - len(name)))
        try:
            rows = mod.run()
            all_rows.extend(rows)
            if name == "kernels":  # machine-readable perf trajectory artifact
                kernels_bench.write_artifact(rows)
        except Exception as e:  # noqa: BLE001
            print(f"  FAILED: {e}")
            all_rows.append((f"{name}/FAILED", 0.0, str(e)[:60]))
            failed.append(name)

    print("\n# CSV summary")
    print("name,us_per_call,derived")
    for name, us, derived in all_rows:
        print(f"{name},{us:.1f},{derived}")
    if failed:
        raise SystemExit(f"failed phases: {', '.join(failed)}")


if __name__ == "__main__":
    main()
