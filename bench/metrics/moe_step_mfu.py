"""Whole step of a routed model: model operations (the reference
module's step_ops, with this chip's routed rows from the expert_rows
counter) over step wall time x int8 peak, %."""
from bench import readings


def read(run):
    if "expert_rows" not in run.window.counters_after:
        return None
    return readings.step_mfu_pct(run)
