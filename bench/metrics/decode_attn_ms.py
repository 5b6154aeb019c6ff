"""Attention: device ms of one decode run in the attn scope, kv_pool
excluded (projections, Q quantization, the kernel)."""
from bench import scopes


def read(run):
    return scopes.decode_scope_ms(run, "attn")
