"""Prefix cache: pages served from the cache in the window, %."""
from bench import readings


def read(run):
    return readings.prefix_hit_pct(run)
