"""The yardstick's arithmetic: chip peaks, and the operations and bytes
that attention and a whole model step need, counted from shapes.

Counts are what the algorithm needs, not what an implementation happens
to do: every live key and value read once at the width the attention
consumes them (``kv_bytes``: the int8 codes for the raceit kernels),
queries read and outputs written once, two operations per multiply-add.
A kernel that does extra work is slower against the same count; one that
skips needed work cannot lower it.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": per chip, 197 TFLOP/s bf16,
    # 393 TOP/s int8, 16 GB HBM at 819 GB/s
    "TPU v5 lite": {"int8_ops_per_s": 393e12, "bf16_flops_per_s": 197e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}
PEAKS_SOURCE = "Google Cloud documentation, 'TPU v5e'"


def peaks(device_kind: str) -> dict:
    """A chip's published peaks; an unknown kind is an error."""
    if device_kind not in PEAKS:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def attn_decode_cost(lens, n_heads: int, n_kv: int, head_dim: int,
                     kv_bytes: int, io_bytes: int = 4) -> tuple[int, int]:
    """(ops, bytes) of one layer's decode attention over rows whose live
    key counts are ``lens`` (the new token included); rows of 0 are idle.
    """
    ops = nbytes = 0
    for n in lens:
        if n <= 0:
            continue
        ops += 4 * n * n_heads * head_dim          # q.k and p.v
        nbytes += 2 * n * n_kv * head_dim * kv_bytes
        nbytes += 2 * n_heads * head_dim * io_bytes  # q in, out
    return ops, nbytes


def attn_chunk_cost(offsets, feeds, n_heads: int, n_kv: int, head_dim: int,
                    kv_bytes: int, io_bytes: int = 4) -> tuple[int, int]:
    """(ops, bytes) of one layer's chunked-prefill attention: row b feeds
    ``feeds[b]`` queries at absolute positions ``offsets[b]`` onwards,
    each attending every key up to itself (causal)."""
    ops = nbytes = 0
    for off, f in zip(offsets, feeds):
        if f <= 0:
            continue
        keys = off + f
        pairs = f * off + f * (f + 1) // 2     # sum over queries of keys seen
        ops += 4 * pairs * n_heads * head_dim
        nbytes += 2 * keys * n_kv * head_dim * kv_bytes
        nbytes += 2 * f * n_heads * head_dim * io_bytes
    return ops, nbytes


def layer_matmul_params(arch: dict) -> int:
    """Weights one token multiplies per layer (q, k, v, o and the MLP)."""
    d, h, hd, f = (arch["d_model"], arch["n_heads"], arch["head_dim"],
                   arch["d_ff"])
    n_kv = arch.get("n_kv_heads", h)
    mlp = (3 if arch["mlp"] == "swiglu" else 2) * d * f
    return d * (h + 2 * n_kv) * hd + h * hd * d + mlp


def step_ops(arch: dict, chunk_rows, decode_lens) -> int:
    """Model operations of one batcher step: the useful rows of its chunk
    call (``chunk_rows`` = [(offset, feed), ...]) and of its decode call
    (``decode_lens`` = live keys per decoding row). Each row computes
    logits at one position (the chunk call's last fed one)."""
    L, h, hd = arch["n_layers"], arch["n_heads"], arch["head_dim"]
    per_tok = 2 * layer_matmul_params(arch)
    head = 2 * arch["d_model"] * arch["vocab_size"]
    ops = 0
    for off, f in chunk_rows:
        if f <= 0:
            continue
        pairs = f * off + f * (f + 1) // 2
        ops += L * (f * per_tok + 4 * pairs * h * hd) + head
    for n in decode_lens:
        if n <= 0:
            continue
        ops += L * (per_tok + 4 * n * h * hd) + head
    return ops


def bound_seconds(ops: float, nbytes: float, pk: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_ops = ops / pk["int8_ops_per_s"]
    t_mem = nbytes / pk["hbm_bytes_per_s"]
    return (t_ops, "int8") if t_ops >= t_mem else (t_mem, "hbm")
