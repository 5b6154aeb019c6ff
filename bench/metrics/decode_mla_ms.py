"""Latent attention: device ms of one decode run in the mla scope,
kv_pool excluded (projections, the per-head absorptions, the kernel)."""
from bench import layer_scopes


def read(run):
    return layer_scopes.decode_scope_ms(run, "mla")
