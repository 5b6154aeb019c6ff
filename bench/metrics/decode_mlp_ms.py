"""MLP: device ms of one decode run in the mlp scope (the FFN's ExecPlan
matmuls and activation)."""
from bench import scopes


def read(run):
    return scopes.decode_scope_ms(run, "mlp")
