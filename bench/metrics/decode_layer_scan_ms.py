"""Layer scan: device ms of one decode run whose innermost layer scope is
layer_scan (the scan's own slicing and stacking)."""
from bench import scopes


def read(run):
    return scopes.decode_scope_ms(run, "layer_scan")
