"""Batcher chunk call: prompt tokens fed over the call's slot rows, %."""
from bench import scopes


def read(run):
    return scopes.read(run, scopes.chunk_fill_pct)
