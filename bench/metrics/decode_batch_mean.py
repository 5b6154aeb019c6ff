"""Batcher: decode tokens per decode step in the window."""
from bench import readings


def read(run):
    return readings.decode_batch_mean(run)
