"""Whole step: model operations over step wall time x int8 peak, %."""
from bench import readings


def read(run):
    return readings.step_mfu_pct(run)
