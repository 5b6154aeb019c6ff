"""Kernels: the latent-attention decode kernel's share of its roofline,
%, from the configuration's reference module's attn_cost."""
from bench import readings


def read(run):
    return readings.attn_roofline_pct(run, "decode")
