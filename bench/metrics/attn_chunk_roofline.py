"""Kernels: chunk attention's share of its roofline, %."""
from bench import readings


def read(run):
    return readings.attn_roofline_pct(run, "chunk")
