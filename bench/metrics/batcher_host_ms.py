"""Batcher host path: ms of a step's own host work, the step less its
blocking reads (batcher.wait), mean over the window's steps."""
from bench import scopes


def read(run):
    return scopes.read(run, scopes.batcher_host_ms)
