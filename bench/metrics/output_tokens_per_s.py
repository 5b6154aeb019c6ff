"""Tokens sampled inside the window, per second of the window."""
from bench import readings


def read(run):
    return readings.output_tokens_per_s(run)
