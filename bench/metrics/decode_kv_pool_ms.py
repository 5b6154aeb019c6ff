"""KV pool: device ms of one decode run in the kv_pool scope (cache writes,
the pool's quantization)."""
from bench import scopes


def read(run):
    return scopes.decode_scope_ms(run, "kv_pool")
