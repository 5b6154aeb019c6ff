"""Expert layer: device ms of one decode run in the moe scope (router,
held experts' matmuls and activations, shared expert, combine)."""
from bench import layer_scopes


def read(run):
    return layer_scopes.decode_scope_ms(run, "moe")
