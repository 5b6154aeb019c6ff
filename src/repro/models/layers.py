"""Transformer building blocks: norms, positions, attention, FFN.

Everything is a pure function over explicit parameter pytrees (dicts).
Operator dispatch goes through a resolved `repro.exec.ExecPlan`: each layer
calls ``plan.matmul`` / ``plan.activation`` / ``plan.attention_prefill`` /
``plan.attention_decode`` instead of branching on an execution mode — the
plan was resolved once per (ModelConfig, ExecConfig) pair and names exactly
one backend per operator slot (``plan.explain()`` shows the table).

The analog-faithful math that the raceit backends bind lives here as
private helpers (`_raceit_staged_attention`, `_raceit_fused_attention`,
`_raceit_fused_decode`, `_raceit_gqa_decode`) next to the float
formulations they are validated against (`_chunked_attention`,
`_local_block_attention`); the backend registrations that expose them as
named plan entries are in `repro.exec.backends`.

Attention uses a KV-chunked online-softmax (flash-style) formulation under
``jax.lax.scan`` so scores are never fully materialized — required to fit
prefill_32k in HBM and mirrored by the Pallas kernel.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ExecConfig, ModelConfig
from repro.core.quant import quantize_tensor
from repro.dist.sharding import constraint
from repro.exec.plan import ExecPlan, as_plan

Params = dict
NEG_INF = -1e9


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedWeight:
    """Resident crossbar weight: int8 codes + per-column scale (static shape)."""

    codes: jax.Array   # (K, N) int8  (or stacked (R, K, N))
    scale: jax.Array   # (1, N) f32   (or (R, 1, N))
    shape: tuple       # static out-shape after the contraction dim

    def tree_flatten(self):
        return (self.codes, self.scale), (self.shape,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux[0])


def dequantize(w):
    """A weight as floats: resident codes times their scales, reshaped."""
    if not isinstance(w, QuantizedWeight):
        return w
    return (w.codes.astype(jnp.float32) * w.scale).reshape(
        *w.codes.shape[:-1], *w.shape)


def _probs_dtype(cfg: ModelConfig):
    """dtype of the p matrix fed to the PV matmul (perf knob; f32 compute
    keeps both paths bit-consistent)."""
    if cfg.attn_probs_dtype == "float32" or cfg.compute_dtype == "float32":
        return jnp.float32
    return jnp.bfloat16


# --------------------------------------------------------------------------
# initializers
# --------------------------------------------------------------------------

def _dense_init(key, shape, dtype, fan_in=None):
    fan_in = fan_in if fan_in is not None else shape[0]
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------

def init_norm(cfg: ModelConfig, dtype) -> Params:
    if cfg.norm == "rmsnorm":
        return {"scale": jnp.ones((cfg.d_model,), dtype)}
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((cfg.d_model,), dtype),
                "bias": jnp.zeros((cfg.d_model,), dtype)}
    return {}  # np_layernorm: non-parametric (olmo)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def apply_norm(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.norm == "rmsnorm":
        return rms_norm(x, p["scale"], cfg.norm_eps)
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, -1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
    if cfg.norm == "layernorm":
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# --------------------------------------------------------------------------
# rotary positions (standard + M-RoPE)
# --------------------------------------------------------------------------

def _rope_angles(positions: jax.Array, head_dim: int, theta: float) -> tuple:
    """positions (..., S) -> cos/sin (..., S, head_dim/2)."""
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, positions: jax.Array, cfg: ModelConfig) -> jax.Array:
    """x: (B, S, H, hd). positions: (B, S) or (3, B, S) for M-RoPE."""
    hd = x.shape[-1]
    if cfg.pos_emb == "mrope":
        # M-RoPE (qwen2-vl): the half-dim frequency bands are partitioned into
        # (t, h, w) sections; each section takes its positions from the
        # corresponding channel. Text-only inputs use identical channels.
        if positions.ndim == 2:
            positions = jnp.broadcast_to(positions[None], (3,) + positions.shape)
        cos, sin = _rope_angles(positions, hd, cfg.rope_theta)  # (3, B, S, hd/2)
        secs = np.array(cfg.mrope_sections, np.int64)
        if secs.sum() != hd // 2:  # reduced smoke configs: rescale sections
            secs = np.maximum(1, secs * (hd // 2) // secs.sum())
            secs[-1] = hd // 2 - secs[:-1].sum()
        sections = np.cumsum(secs)[:-1]
        cos = jnp.concatenate(
            [c for c in (jnp.split(cos, sections, axis=-1)[i][i] for i in range(3))], -1)
        sin = jnp.concatenate(
            [s for s in (jnp.split(sin, sections, axis=-1)[i][i] for i in range(3))], -1)
    else:
        cos, sin = _rope_angles(positions, hd, cfg.rope_theta)  # (B, S, hd/2)
    cos = cos[:, :, None, :]  # (B, S, 1, hd/2)
    sin = sin[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# linear projections (dispatched through the plan's matmul slot)
# --------------------------------------------------------------------------

def _linear(x: jax.Array, w: jax.Array, plan: ExecPlan,
            bias: Optional[jax.Array] = None) -> jax.Array:
    """x (..., K) @ w (K, ...) on the plan's matmul backend.

    `w` may be a pre-quantized resident weight (`QuantizedWeight`) — the
    crossbar-native serving form: weights stored as conductance codes,
    halving HBM weight traffic. The resident path always quantizes
    activations with the plan's ``act_bits``.
    """
    return plan.matmul(x, w, bias=bias)


def _per_head_linear(x: jax.Array, w, plan: ExecPlan) -> jax.Array:
    """x (B, S, H, K) @ w (H, K, N), head by head: each head's matrix is
    its own crossbar (`_linear` per head, activations quantized per head).
    ``w`` may be a head-stacked `QuantizedWeight` (codes (H, K, N))."""
    return jax.vmap(lambda xh, wh: _linear(xh, wh, plan),
                    in_axes=(2, 0), out_axes=2)(x, w)


def _out_proj(w):
    """An output projection (heads, head_dim, D) as the (heads*head_dim, D)
    matrix `_linear` contracts; resident codes are already flat."""
    return w if isinstance(w, QuantizedWeight) else w.reshape(-1, w.shape[-1])


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, dtype) -> Params:
    hd = cfg.resolved_head_dim
    heff = cfg.head_pad_to or cfg.n_heads
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(ks[0], (cfg.d_model, heff, hd), dtype),
        "wk": _dense_init(ks[1], (cfg.d_model, cfg.n_kv_heads, hd), dtype),
        "wv": _dense_init(ks[2], (cfg.d_model, cfg.n_kv_heads, hd), dtype),
        "wo": _dense_init(ks[3], (heff, hd, cfg.d_model), dtype,
                          fan_in=cfg.n_heads * hd),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((heff, hd), dtype)
        p["bk"] = jnp.zeros((cfg.n_kv_heads, hd), dtype)
        p["bv"] = jnp.zeros((cfg.n_kv_heads, hd), dtype)
    return p


def _split_gqa(q, n_kv):
    """(B, S, H, hd) -> (B, S, KV, H//KV, hd)."""
    b, s, h, hd = q.shape
    return q.reshape(b, s, n_kv, h // n_kv, hd)


def _chunked_attention(q, k, v, mask_fn, chunk: int, scale: float,
                       probs_dtype, pad_lens=None):
    """Online-softmax attention, scanning over KV chunks, flat-head layout.

    q: (B, Sq, H, hd); k/v: (B, Sk, KV, hd). KV heads are repeated to H inside
    each chunk step so scores shard cleanly over "heads" for any GQA ratio.
    mask_fn(q_idx, k_idx) -> bool; ``pad_lens`` (B,) int32 additionally
    masks each row's first ``pad_lens[b]`` keys (left-padded batch buckets).

    Masked-row semantics: a query row with *no* valid key outputs zeros.
    (With the finite ``NEG_INF`` sentinel, a fully-masked row's running max
    ``m`` never moves off its init, so ``p = exp(s - m_new) = exp(0) = 1``
    on every masked position and the row would silently emit the uniform
    average of V. ``m`` still at the sentinel after the scan is exactly the
    "no valid key" signature — those rows are zeroed. Rows with >= 1 valid
    key are unaffected: their masked positions get ``exp(NEG_INF - m) = 0``
    and any garbage accumulated before the first valid chunk is killed by
    the ``corr = exp(NEG_INF - m_new) = 0`` rescale.)
    """
    b, sq, h, hd = q.shape
    kv = k.shape[2]
    rep = h // kv
    sk_real = k.shape[1]
    pad = (-sk_real) % chunk  # e.g. whisper's 1500 encoder frames
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    sk = k.shape[1]
    nchunks = sk // chunk
    q32 = constraint(q.astype(jnp.float32).transpose(0, 2, 1, 3) * scale,
                     "batch", "heads", None, None)  # (B,H,Sq,hd)
    qpos = jnp.arange(sq)

    def body(carry, xs):
        m, l, acc = carry
        kc, vc, c0 = xs
        kr = jnp.repeat(kc.astype(jnp.float32), rep, axis=2)  # (B,C,H,hd)
        s = jnp.einsum("bhqd,bchd->bhqc", q32, kr)
        s = constraint(s, "batch", "heads", None, None)
        kpos = c0 + jnp.arange(chunk)
        msk = mask_fn(qpos[:, None], kpos[None, :]) & (kpos < sk_real)[None, :]
        if pad_lens is not None:  # per-row: left-pad keys do not exist
            msk = msk[None] & (kpos[None, :] >= pad_lens[:, None])[:, None, :]
            s = jnp.where(msk[:, None], s, NEG_INF)
        else:
            s = jnp.where(msk[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        # storing p in bf16 halves the dominant HBM tensor of the chunk loop;
        # the accumulator stays f32 (online-softmax stability)
        pv = p.astype(probs_dtype)
        vr = jnp.repeat(vc.astype(pv.dtype), rep, axis=2)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqc,bchd->bhqd", pv, vr, preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    init = (
        jnp.full((b, h, sq), NEG_INF, jnp.float32),
        jnp.zeros((b, h, sq), jnp.float32),
        constraint(jnp.zeros((b, h, sq, hd), jnp.float32),
                   "batch", "heads", None, None),
    )
    ks = k.reshape(b, nchunks, chunk, kv, hd).swapaxes(0, 1)
    vs = v.reshape(b, nchunks, chunk, kv, hd).swapaxes(0, 1)
    c0s = jnp.arange(nchunks) * chunk
    (m, l, acc), _ = jax.lax.scan(body, init, (ks, vs, c0s))
    out = jnp.where(m[..., None] > NEG_INF * 0.5,
                    acc / jnp.maximum(l, 1e-30)[..., None], 0.0)
    return out.transpose(0, 2, 1, 3)  # (B, Sq, H, hd)


def _local_block_attention(q, k, v, window: int, scale: float, probs_dtype):
    """Sliding-window attention in q-blocks: each W-token block attends only
    its own and the previous KV block (2W keys instead of S), cutting local
    layers' score FLOPs/bytes by S/(2W) vs the masked-full path.
    q: (B,S,H,hd); k/v: (B,S,KV,hd); requires S % window == 0.
    """
    B, S, H, hd = q.shape
    kv = k.shape[2]
    rep = H // kv
    W = window
    nb = S // W
    qb = (q.astype(jnp.float32) * scale).reshape(B, nb, W, H, hd)
    kb = k.reshape(B, nb, W, kv, hd)
    vb = v.reshape(B, nb, W, kv, hd)
    kprev = jnp.pad(kb, ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))[:, :nb]
    vprev = jnp.pad(vb, ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))[:, :nb]
    kcat = jnp.repeat(jnp.concatenate([kprev, kb], axis=2), rep, axis=3)
    vcat = jnp.repeat(jnp.concatenate([vprev, vb], axis=2), rep, axis=3)
    s = jnp.einsum("bnwhd,bnchd->bnhwc", qb, kcat.astype(jnp.float32))
    s = constraint(s, "batch", None, "heads", None, None)
    # mask: causal + window + block-0 has no previous block
    qpos = jnp.arange(W)[:, None]
    kpos = (jnp.arange(2 * W) - W)[None, :]
    base = (kpos <= qpos) & (kpos > qpos - W)  # (W, 2W)
    blk0 = base & (kpos >= 0)
    mask = jnp.where((jnp.arange(nb) == 0)[:, None, None], blk0[None], base[None])
    s = jnp.where(mask[None, :, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(probs_dtype)
    o = jnp.einsum("bnhwc,bnchd->bnwhd", p, vcat.astype(p.dtype),
                   preferred_element_type=jnp.float32)
    return o.reshape(B, S, H, hd)


def _decode_quantize(q, k, v, kv_len, scale):
    """Shared fused-decode prolog: int8 codes + scales, native KV layout.

    q (B, 1, H, hd) is quantized with 1/sqrt(d) pre-folded; the k/v cache
    buffers (B, Smax, KV, hd) are quantized ONCE, unrepeated, with scales
    reduced over the valid prefix only. This (with `_decode_descale`) is
    the single point of truth both fused decode backends share — their
    bit-identical contract lives here, the backends differ only in how
    codes are grouped for their kernel entry.
    """
    from repro.kernels.ops import masked_prefix_quantize
    qq = quantize_tensor(q.astype(jnp.float32) * scale, bits=8)
    kq = masked_prefix_quantize(k.astype(jnp.float32), kv_len, axis=1)
    vq = masked_prefix_quantize(v.astype(jnp.float32), kv_len, axis=1)
    return qq, kq, vq


def _decode_descale(out32, cmax, v_scale, shape):
    """Shared fused-decode epilog: the oracle's PROB requant + V scales."""
    from repro.kernels.ops import prob_requant_scale
    return (out32.astype(jnp.float32)
            * (prob_requant_scale(cmax) * v_scale)).reshape(shape)


def _raceit_fused_decode(q, k, v, kv_len, scale, plan: ExecPlan,
                         pad_valid=None):
    """Decode-step (Sq=1) attention on the fused streaming kernel.

    q: (B, 1, H, hd) flat heads; k/v: (B, Smax, KV, hd) — the fixed-shape
    cache buffers, of which only the first ``kv_len`` rows are valid. The
    kernel masks the invalid tail out of the softmax, the global PROB max,
    and matmul-2 (fully-invalid key blocks are skipped outright via
    scalar-prefetched grid bounds), and the k/v quantizer scales are
    reduced over the valid prefix only, so the result is bit-exact vs the
    staged oracle on the cache slice. ``pad_valid`` (B, Smax) bool marks
    per-row attendable slots inside the prefix (left-padded batch buckets);
    masked slots sit at the LOGIT minimum, exactly like the oracle's
    additive mask. Returns (B, 1, H, hd).

    GQA heads are repeated to H *after* quantization, as int8 codes: the
    repeated tensor has the same max-abs as the original, so the scales are
    bit-identical to quantizing the repeated floats, at a quarter of the
    bytes and 1/rep of the quantizer scan. The ExecPlan prefers the
    `_raceit_gqa_decode` backend below for GQA configs, which skips the
    repeat entirely — this flat path stays registered as ``raceit_fused``
    (the MHA default and the GQA parity partner).
    """
    from repro.kernels.ops import (acam_attention_codes,
                                   acam_attention_decode_codes,
                                   expand_row_lens)
    b, sq, h, hd = q.shape
    smax, kv = k.shape[1], k.shape[2]
    rep = h // kv
    qq, (k_codes, k_scale), (v_codes, v_scale) = _decode_quantize(
        q, k, v, kv_len, scale)
    fold = lambda c: jnp.repeat(c, rep, axis=2).transpose(0, 2, 1, 3
                                                          ).reshape(b * h,
                                                                    smax, hd)
    mask = None
    if pad_valid is not None:  # (B, Smax) or (B, Sq, Smax) -> (B*H, Sq, Smax)
        pv = pad_valid[:, None, :] if pad_valid.ndim == 2 else pad_valid
        mask = jnp.broadcast_to(pv[:, None], (b, h, sq, smax)
                                ).reshape(b * h, sq, smax)
    kvl = expand_row_lens(kv_len, h)
    qc = qq.codes.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    if sq == 1:
        out32, cmax = acam_attention_decode_codes(
            qc, fold(k_codes), fold(v_codes), qq.scale * k_scale,
            kvl, mask=mask, mode=plan.exec_cfg.softmax_mode)
    else:
        # Sq > 1 is the chunked-prefill step (a prompt chunk's queries vs
        # the same cache contract, causality carried by ``mask``); it takes
        # the general entry — the decode entry is its Sq=1 specialization
        out32, cmax = acam_attention_codes(
            qc, fold(k_codes), fold(v_codes), qq.scale * k_scale,
            mask, kv_len=kvl, mode=plan.exec_cfg.softmax_mode)
    return _decode_descale(out32, cmax, v_scale, (b, h, sq, hd)
                           ).transpose(0, 2, 1, 3)


def _raceit_gqa_decode(q, k, v, kv_len, scale, plan: ExecPlan,
                       pad_valid=None):
    """GQA-native decode-step attention: the KV cache is never repeated.

    Same contract as `_raceit_fused_decode` — bit-identical outputs, in
    fact (same quantizer scales and codes, same per-row sums in the same
    key-block order, same order-free integer cmax) — but k/v stay in their
    native (B, Smax, KV, hd) cache layout end to end: quantized once, and
    handed to `acam_attention_decode_gqa_codes` as (B*KV, Smax, hd) groups
    whose ``rep = H/KV`` sharing queries ride the tile's row dimension.
    The decode hot loop's ``jnp.repeat`` of cache codes disappears, and
    with it rep x of the KV-cache read traffic (see the ``decode_gqa_*``
    rows in BENCH_kernels.json).
    """
    from repro.kernels.ops import (acam_attention_decode_gqa_codes,
                                   expand_row_lens)
    b, sq, h, hd = q.shape
    smax, kv = k.shape[1], k.shape[2]
    rep = h // kv
    if sq > 1:
        # chunked-prefill steps ride the flat entry: the GQA grid's row dim
        # carries the rep sharing queries, which a chunk needs for its Sq
        # positions — bit-identical either way, this is a dataflow choice
        return _raceit_fused_decode(q, k, v, kv_len, scale, plan,
                                    pad_valid=pad_valid)
    qq, (k_codes, k_scale), (v_codes, v_scale) = _decode_quantize(
        q, k, v, kv_len, scale)
    to_groups = lambda c: c.transpose(0, 2, 1, 3).reshape(b * kv, smax, hd)
    mask = None
    if pad_valid is not None:  # (B, Smax) -> (B*KV, rep, Smax)
        mask = jnp.broadcast_to(pad_valid[:, None, None, :],
                                (b, kv, rep, smax)).reshape(b * kv, rep, smax)
    kvl = expand_row_lens(kv_len, kv)
    out32, cmax = acam_attention_decode_gqa_codes(
        qq.codes.reshape(b, h, hd).reshape(b, kv, rep, hd
                                           ).reshape(b * kv, rep, hd),
        to_groups(k_codes), to_groups(v_codes), qq.scale * k_scale,
        kvl, mask=mask,
        mode=plan.exec_cfg.softmax_mode)
    # (b*kv, rep, hd) rows land in head order
    return _decode_descale(out32, cmax, v_scale, (b, sq, h, hd))


def _raceit_paged_decode(q, k_pool, v_pool, kv_len, scale, plan: ExecPlan,
                         pad_valid=None, block_table=None, gqa=False):
    """Decode / chunk attention over a block-paged KV pool.

    q: (B, Sq, H, hd) layer layout — Sq=1 for the decode hot loop, Sq=C
    for chunked-prefill steps; k/v: the (n_pages, page_size, KV, hd) page
    pool shared by all slots, with ``block_table`` (B, max_pages) naming
    each slot's pages (0 = the trash page). Delegates to the jitted paged
    wrappers (`repro.kernels.ops.raceit_attention_decode_paged` /
    `_gqa_paged`), which quantize the pool per page with scales reduced
    over the union of live page entries — bit-identical to
    `_raceit_fused_decode` / `_raceit_gqa_decode` on the gathered
    contiguous layout of the same table. ``pad_valid`` (B, Smax) or
    (B, Sq, Smax) bool is the chunk path's intra-chunk causal mask.
    """
    from repro.kernels.ops import (raceit_attention_decode_gqa_paged,
                                   raceit_attention_decode_paged)
    b, sq, h, hd = q.shape
    qh = (q.astype(jnp.float32) * scale).transpose(0, 2, 1, 3)  # (B,H,Sq,hd)
    mask = pad_valid
    if mask is not None and mask.ndim == 2:  # (B, Smax) -> (B, Sq, Smax)
        mask = mask[:, None, :]
    fn = (raceit_attention_decode_gqa_paged if gqa
          else raceit_attention_decode_paged)
    out = fn(qh, k_pool.astype(jnp.float32), v_pool.astype(jnp.float32),
             kv_len, block_table, mask=mask,
             softmax_mode=plan.exec_cfg.softmax_mode, fold_scale=True)
    return out.transpose(0, 2, 1, 3)  # (B, Sq, H, hd)


def paged_write_targets_chunk(block_table, lens, chunk_offs, sq: int,
                              page_size: int):
    """Physical (pages, slots), each (B, sq), for a chunked-prefill write.

    Row b streams its chunk into logical columns [chunk_offs[b], lens[b]).
    The trash-page fence: any column that is not live — past the row's
    feed, a whole row with lens == chunk_offs, or *beyond the block
    table's capacity* — routes to physical page 0, which no live row ever
    reads (the read side caps kv_len at capacity and the allocator never
    issues page 0). Without the capacity clause an overflowing write
    would be clamped into the slot's last live page, silently corrupting
    a resident token; `repro.analysis` (KC107) checks this contract
    exhaustively.
    """
    ps = int(page_size)
    bt = jnp.asarray(block_table, jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    offs = jnp.asarray(chunk_offs, jnp.int32)
    rows = jnp.arange(bt.shape[0])
    capacity = bt.shape[1] * ps
    cols = offs[:, None] + jnp.arange(sq, dtype=jnp.int32)[None, :]
    live = (cols < lens[:, None]) & (cols < capacity)
    pages = jnp.where(live, bt[rows[:, None],
                               jnp.minimum(cols // ps, bt.shape[1] - 1)], 0)
    slots = jnp.where(live, cols % ps, 0)
    return pages, slots


def paged_write_targets_decode(block_table, lens, page_size: int):
    """Physical (pages, slots), each (B,), for a decode-step write.

    The new token is logical column lens[b] - 1. Empty slots (lens == 0)
    and slots filled past the block table's capacity write to the trash
    page 0 — same fence contract as the chunk path (KC107).
    """
    ps = int(page_size)
    bt = jnp.asarray(block_table, jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    rows = jnp.arange(bt.shape[0])
    capacity = bt.shape[1] * ps
    pos = jnp.minimum(jnp.maximum(lens - 1, 0), capacity - 1)
    live = (lens > 0) & (lens <= capacity)
    pages = jnp.where(live, bt[rows, pos // ps], 0)
    return pages, pos % ps


def _attn_quantize(q, k, v, scale):
    """Shared Fig.-12 prolog: repeat KV heads to H, quantize to int8 codes."""
    rep = q.shape[2] // k.shape[2]
    kf = jnp.repeat(k, rep, axis=2)
    vf = jnp.repeat(v, rep, axis=2)
    qq = quantize_tensor(q.astype(jnp.float32) * scale, bits=8)
    kq = quantize_tensor(kf.astype(jnp.float32), bits=8)
    vq = quantize_tensor(vf.astype(jnp.float32), bits=8)
    return qq, kq, vq


def _raceit_staged_attention(q, k, v, mask, scale, plan: ExecPlan):
    """Analog-faithful attention, stage by stage (the bit-accurate oracle
    formulation): quantized matmul-1, div-add mask, ACAM softmax, PROB
    re-quantization, matmul-2. The data-dependent matmuls go through the
    plan's ``dd_matmul`` slot, so ``matmul_fidelity="acam"`` routes them
    through the compiled 4-bit nibble tables (bit-identical to the integer
    matmul, per tests/test_core_acam.py).

    q: (B, Sq, H, hd) flat heads; k/v: (B, Sk, KV, hd); mask (B, Sq, Sk).
    """
    from repro.core.ops import LOGIT_FMT
    from repro.core.softmax import acam_softmax
    qq, kq, vq = _attn_quantize(q, k, v, scale)
    s32 = plan.dd_matmul(qq.codes.transpose(0, 2, 1, 3),      # (B,H,Sq,hd)
                         kq.codes.transpose(0, 2, 3, 1))      # (B,H,hd,Sk)
    logits = s32.astype(jnp.float32) * (qq.scale * kq.scale)
    logits = jnp.where(mask[:, None], logits, LOGIT_FMT.min_value)
    probs = acam_softmax(logits, axis=-1, mode=plan.exec_cfg.softmax_mode)
    pq = quantize_tensor(probs, bits=8)
    o32 = plan.dd_matmul(pq.codes,                            # (B,H,Sq,Sk)
                         vq.codes.transpose(0, 2, 1, 3))      # (B,H,Sk,hd)
    out = o32.astype(jnp.float32) * (pq.scale * vq.scale)
    return out.transpose(0, 2, 1, 3)  # (B, Sq, H, hd)


def _raceit_fused_attention(q, k, v, mask, scale, plan: ExecPlan,
                            causal_offset=None):
    """Analog-faithful attention on the streaming Pallas kernel: the whole
    Fig.-12 pipeline per VMEM tile, no (Sq, Sk) intermediates.

    ``causal_offset`` replaces the mask array with the kernel's in-kernel
    causal mask, so not even a mask of score shape is ever built; otherwise
    ``mask`` is (B, Sq, Sk) and broadcast over heads.
    """
    from repro.kernels.ops import acam_attention_codes, prob_requant_scale
    qq, kq, vq = _attn_quantize(q, k, v, scale)
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    if causal_offset is None:
        mb = jnp.broadcast_to(mask[:, None],
                              (b, h, sq, sk)).reshape(b * h, sq, sk)
    else:
        mb = None
    out32, cmax = acam_attention_codes(
        qq.codes.transpose(0, 2, 1, 3).reshape(b * h, sq, hd),
        kq.codes.transpose(0, 2, 1, 3).reshape(b * h, sk, hd),
        vq.codes.transpose(0, 2, 1, 3).reshape(b * h, sk, hd),
        qq.scale * kq.scale, mb,
        q_offset=causal_offset if causal_offset is not None else 0,
        causal=causal_offset is not None,
        mode=plan.exec_cfg.softmax_mode)
    out = out32.astype(jnp.float32) * (prob_requant_scale(cmax) * vq.scale)
    return out.reshape(b, h, sq, hd).transpose(0, 2, 1, 3)


def attention(
    p: Params,
    x: jax.Array,
    *,
    cfg: ModelConfig,
    plan: ExecPlan | ExecConfig,
    positions: jax.Array,
    local: bool = False,
    cache: Optional[Params] = None,
    cross_kv: Optional[tuple] = None,
    chunk: int = 1024,
    pad_lens: Optional[jax.Array] = None,
    pad_prompt_len: Optional[jax.Array] = None,
    slot_lens: Optional[jax.Array] = None,
    block_table: Optional[jax.Array] = None,
    page_size: Optional[int] = None,
    chunk_offs: Optional[jax.Array] = None,
) -> tuple[jax.Array, Optional[Params]]:
    """Self- (or cross-) attention with optional KV cache.

    cache = {"k": (B, Smax, KV, hd), "v": ..., "idx": int32 scalar — or a
    (B,) vector of per-slot write indices for slot-pool caches}.
    prefill: x covers [0, S); decode: x is a single new token (Sq=1).

    ``block_table`` (B, max_pages) int32 + ``page_size`` (static int) switch
    the cache to its block-paged form: ``cache["k"]``/``"v"`` are a page
    *pool* (n_pages, page_size, KV, hd) shared by every slot, and row b's
    logical column c lives at pool position (block_table[b, c // page_size],
    c % page_size). Physical page 0 is the trash page — a block-table row
    full of zeros makes its slot's writes land harmlessly there, which is
    how the serving layer fences non-participating rows out of a batched
    call. Paged caches take ``slot_lens`` as their only length authority
    (``cache["idx"]`` mirrors it post-call) and come in two step shapes:

    * the Sq=1 decode step — the new k/v land at logical column
      ``slot_lens[b] - 1`` through the table;
    * the chunked-prefill step (``chunk_offs`` (B,) given) — row b streams
      prompt tokens into logical columns [chunk_offs[b], slot_lens[b]), so
      a long prompt enters its slot across several pinned-width calls
      (one compiled executable) interleaved with other slots' decode
      steps. Queries past a row's chunk (and all queries of rows with
      slot_lens == chunk_offs) are garbage rows: their writes route to the
      trash page and their outputs are the caller's to discard. Causality
      inside the chunk is a per-query mask (query j attends logical
      columns <= chunk_offs[b] + j), built here and carried through the
      backend as a (B, Sq, Smax) ``pad_valid``.

    Paged dispatch honors the resolved backend's `BackendSpec.paged` flag:
    paged-capable backends get the pool + table directly (the Pallas
    kernels follow the indirection per key block and skip dead pages);
    anything else — the digital/staged baselines, a pinned contiguous
    backend — is served by gathering the table's pages back to contiguous
    (B, max_pages*page_size, KV, hd) rows first, a degrade, never an
    error. Local/ring layers and left-padded buckets (``pad_lens``) are
    out of the paged contract and raise.

    ``slot_lens`` (B,) int32 is the per-row decode length authority for
    slot-level continuous batching (`repro.serve.continuous`): row b's
    query attends exactly the first ``slot_lens[b]`` cache columns
    (including the token written this step), so every slot decodes at its
    own fill level and a 0 entry marks an empty slot whose row is dead
    (no valid key; the raceit kernels define its output as zeros and its
    stale cache never touches a quantizer scale). When ``slot_lens`` is
    None the length comes from the cache's own ``idx``, scalar or
    per-slot vector alike. Per-slot caches also write each row's new k/v
    at its *own* column (a batched scatter instead of one shared
    `dynamic_update_slice` offset).

    ``pad_lens`` (B,) int32 marks each row's left-pad prefix (mixed-length
    batch buckets, see `repro.serve.batching`): those key slots do not
    exist for self-attention — prefill masks them per row, and the decode
    step masks the corresponding cache slots (including the ring-overwrite
    rule for local layers: a pad slot stays masked only until a later
    token's ring write reclaims it). Cross-attention ignores ``pad_lens``
    (its keys come from the encoder, not from ``x``); position offsets are
    the *caller's* job — `repro.models.model` computes per-row positions
    from the same pad lengths before RoPE ever sees them.

    ``pad_prompt_len`` (scalar) is the bucket's padded prompt length,
    needed only by the decode step: the slot-index == column-index mapping
    the pad mask relies on breaks when the *prefill* overflowed a ring
    buffer (the ``sq >= L`` branch below keeps the last L columns, putting
    column ``plen - L + s`` at slot ``s``), so for layers with
    ``pad_prompt_len > L`` the mask is dropped — every slot already holds
    one of the last L tokens, mostly real ones, and the remaining pads are
    the documented local-layer softening, not a mis-masked real token.

    Dispatch goes through the resolved plan: prefill (and full/cross
    attention) through ``plan.attention_prefill``, the Sq=1 cache step
    through ``plan.attention_decode`` — the backend (digital chunked,
    staged Fig.-12, or the streaming Pallas kernel) was chosen once at
    `repro.exec.resolve_plan` time, with unsupported combos degraded and
    the reasons recorded on the plan. ``plan`` also accepts a raw
    ExecConfig and resolves it against ``cfg`` (cached).

    The mask *kind* is computed here from the call-site ``cfg`` (encoder
    sub-stacks pass a replaced config), then the backend builds whatever
    mask representation it needs — a mask_fn for the chunked float path, a
    (B, Sq, Sk) array for the staged pipeline, or no mask at all for the
    fused kernel's in-kernel causal path.
    """
    plan = as_plan(cfg, plan)
    b, sq, _ = x.shape
    hd = cfg.resolved_head_dim
    q = _linear(x, p["wq"], plan, p.get("bq"))
    q = constraint(q, "batch", None, "heads", None)
    if cross_kv is None:
        k = _linear(x, p["wk"], plan, p.get("bk"))
        v = _linear(x, p["wv"], plan, p.get("bv"))
        if cfg.pos_emb in ("rope", "mrope"):
            q = apply_rope(q, positions, cfg)
            k = apply_rope(k, positions, cfg)
    else:
        k, v = cross_kv  # encoder keys/values, precomputed

    paged = block_table is not None
    if chunk_offs is not None and not paged:
        raise ValueError("chunk_offs is the chunked-prefill surface of "
                         "block-paged caches; pass block_table/page_size")
    if paged:
        if page_size is None:
            raise ValueError("paged caches need a static page_size")
        if local:
            raise NotImplementedError(
                "block-paged KV does not cover local/ring layers (a ring "
                "overwrite would need page recycling inside a slot)")
        if cache is None or cross_kv is not None:
            raise ValueError("block_table requires a self-attention KV cache")
        if slot_lens is None:
            raise ValueError("paged caches take their per-slot lengths from "
                             "slot_lens")
        if pad_lens is not None:
            raise ValueError("paged slots are never left-padded; pad_lens "
                             "does not apply")

    scale = 1.0 / math.sqrt(hd)
    if paged:
        o, new_cache = _paged_attend(
            q, {"k": k, "v": v}, cache, plan=plan, scale=scale,
            slot_lens=slot_lens, block_table=block_table,
            page_size=page_size, chunk_offs=chunk_offs)
        return _attention_out(p, o, x, cfg, plan), new_cache

    new_cache = None
    # the cache writes (and, inside the paged decode wrappers, the
    # pool's quantization) report under kv_pool
    with jax.named_scope("kv_pool"):
        if cache is not None and cross_kv is None:
            idx = cache["idx"]
            per_slot = getattr(idx, "ndim", 0) == 1  # slot-pool cache
            L = cache["k"].shape[1]
            if sq >= L:
                # prefill past the buffer (ring caches of local layers):
                # keep the last L rotated-in-place entries; RoPE is
                # pre-applied so storage order is irrelevant under the
                # all-valid mask.
                ck = k[:, -L:].astype(cache["k"].dtype)
                cv = v[:, -L:].astype(cache["v"].dtype)
            elif per_slot:
                # per-slot write indices: each row's new token lands at its
                # own column (slots fill independently under continuous
                # batching)
                if sq != 1:
                    raise ValueError("per-slot caches only take Sq=1 decode "
                                     "steps; prefill into a slot goes through "
                                     "a solo prefill + row scatter "
                                     "(repro.serve.continuous)")
                pos = idx % L if local else idx
                rows = jnp.arange(b)
                ck = cache["k"].at[rows, pos].set(
                    k[:, 0].astype(cache["k"].dtype))
                cv = cache["v"].at[rows, pos].set(
                    v[:, 0].astype(cache["v"].dtype))
            else:
                pos = idx % L if local else idx  # ring write for local layers
                ck = jax.lax.dynamic_update_slice(
                    cache["k"], k.astype(cache["k"].dtype), (0, pos, 0, 0))
                cv = jax.lax.dynamic_update_slice(
                    cache["v"], v.astype(cache["v"].dtype), (0, pos, 0, 0))
            new_cache = {"k": ck, "v": cv, "idx": idx + sq}
            if sq == 1:  # decode attends through the cache
                k, v = ck, cv

    if sq == 1 and cache is not None:
        # decode: single query against the cache, masked by validity/window.
        # (ring buffers: every written slot is inside the window by design,
        # so validity is always a prefix of length min(idx, buffer_len))
        L = k.shape[1]
        # slot_lens is the per-row length authority when given (continuous
        # batching: slots at independent fill levels, 0 = empty slot);
        # otherwise the cache's own post-write index — () or (B,) — rules
        lens = (jnp.asarray(slot_lens, jnp.int32) if slot_lens is not None
                else new_cache["idx"])
        kv_len = jnp.minimum(lens, L)
        pad_valid = None
        if pad_lens is not None:
            # slot s of row b is attendable unless it still holds a pad
            # token: pads occupy slots [0, pad_lens[b]) until the ring
            # write for token s + L reclaims them (lens > L + s); non-ring
            # caches have L = max_len >= lens, so the clause is inert there
            slots = jnp.arange(L)
            pad_valid = ((slots[None, :] >= pad_lens[:, None])
                         | (jnp.reshape(lens, (-1, 1)) > L + slots[None, :]))
            if pad_prompt_len is not None:
                # prompt overflowed this ring buffer: prefill kept the last
                # L columns (column plen-L+s at slot s), so slot-space pad
                # masking would hit real tokens — drop it for this layer
                pad_valid = pad_valid | (
                    jnp.reshape(jnp.asarray(pad_prompt_len), (-1, 1)) > L)
        o = plan.attention_decode(q, k, v, kv_len=kv_len, scale=scale,
                                  pad_valid=pad_valid)
    else:
        q_off = cache["idx"] if cache is not None else 0
        if cross_kv is not None:
            kind = "cross"       # full cross attention
        elif not cfg.causal:
            kind = "bidir"       # bidirectional (encoder-only)
        elif local:
            kind = "local"       # causal sliding window
        else:
            kind = "causal"
        o = plan.attention_prefill(q, k, v, scale=scale, q_offset=q_off,
                                   kind=kind, window=cfg.window, chunk=chunk,
                                   probs_dtype=_probs_dtype(cfg),
                                   pad_lens=(pad_lens if cross_kv is None
                                             else None))

    return _attention_out(p, o, x, cfg, plan), new_cache


def _attention_out(p: Params, o, x, cfg: ModelConfig, plan: ExecPlan):
    """The heads' outputs (B, Sq, H, hd) through the output projection."""
    b, sq = x.shape[:2]
    hd = cfg.resolved_head_dim
    wq = p["wq"]
    heff = wq.shape[0] if isinstance(wq, QuantizedWeight) else wq.shape[1]
    o = o.reshape(b, sq, heff, hd).astype(x.dtype)
    if heff > cfg.n_heads:  # hard-mask padded heads: function == unpadded model
        o = o * (jnp.arange(heff) < cfg.n_heads)[None, None, :, None].astype(o.dtype)
    wo = p["wo"]
    if isinstance(wo, QuantizedWeight):  # codes already (H*hd, D)
        return _linear(o.reshape(b, sq, heff * hd), wo, plan)
    return jnp.einsum("bshd,hdm->bsm", o, wo.astype(x.dtype))


def _paged_attend(q, rows: dict, cache: Params, *, plan: ExecPlan, scale,
                  slot_lens, block_table, page_size, chunk_offs):
    """Write this step's cache rows into the page pool(s), then attend the
    pool: the decode step (Sq=1) or a chunked-prefill call (``chunk_offs``
    given), lengths from ``slot_lens`` (see `attention`).

    ``rows`` maps each pool of ``cache`` to its new rows (B, Sq, KV, hd):
    ``{"k": k, "v": v}``, or ``{"kv": row}`` for a latent cache whose one
    pool serves as keys and values both. Returns (o, new_cache).
    """
    b, sq = q.shape[:2]
    ps = int(page_size)
    lens = jnp.asarray(slot_lens, jnp.int32)
    bt = jnp.asarray(block_table, jnp.int32)
    # the cache writes (and, inside the paged decode wrappers, the
    # pool's quantization) report under kv_pool
    with jax.named_scope("kv_pool"):
        if chunk_offs is not None:
            # chunked prefill: fenced physical targets from the shared
            # routing helper (trash page 0 for dead or overflow columns)
            pages, slot = paged_write_targets_chunk(bt, lens, chunk_offs,
                                                    sq, ps)
            new = {n: cache[n].at[pages, slot].set(r.astype(cache[n].dtype))
                   for n, r in rows.items()}
        else:
            if sq != 1:
                raise ValueError(
                    "paged caches take Sq=1 decode steps or chunked "
                    "prefill (chunk_offs); whole-prompt prefill goes "
                    "through Model.prefill_chunk")
            pages, slot = paged_write_targets_decode(bt, lens, ps)
            new = {n: cache[n].at[pages, slot].set(
                r[:, 0].astype(cache[n].dtype)) for n, r in rows.items()}
    new_cache = dict(new, idx=lens)
    k = new["k"] if "k" in new else new["kv"]
    v = new["v"] if "v" in new else k

    # decode or chunk step against the page pool, lengths from
    # slot_lens; kv_len is the logical fill, capped at table capacity
    mp = bt.shape[1]
    lk = mp * ps
    kv_len = jnp.minimum(lens, lk)
    pad_valid = None
    if chunk_offs is not None:
        # intra-chunk causality: query j of row b sits at absolute
        # position chunk_offs[b] + j and attends logical columns <= it
        qpos = (jnp.asarray(chunk_offs, jnp.int32)[:, None]
                + jnp.arange(sq, dtype=jnp.int32)[None, :])
        pad_valid = (jnp.arange(lk, dtype=jnp.int32)[None, None, :]
                     <= qpos[..., None])
    if plan.op("attention_decode").spec.paged:
        o = plan.attention_decode(q, k, v, kv_len=kv_len, scale=scale,
                                  pad_valid=pad_valid, block_table=bt,
                                  page_size=ps)
    else:
        # non-paged backend under a paged cache (digital/staged
        # baselines, explicit contiguous pins): gather the table's
        # pages back to contiguous rows — a degrade, never an error.
        # Columns past each row's kv_len are zeroed to reproduce a
        # contiguous cache's never-written tail exactly: a row's
        # out-of-range columns gather the shared trash page, whose
        # content is other rows' fenced garbage — left in place it
        # would pollute whole-tensor quantizer scales in the staged
        # raceit paths and, when a faulted row parked NaNs there,
        # contaminate healthy rows through prob-0 * NaN
        kvh, hdim = k.shape[2], k.shape[3]
        live = (jnp.arange(lk, dtype=jnp.int32)[None, :]
                < kv_len[:, None])[:, :, None, None]
        gather = lambda pool: jnp.where(
            live, pool[bt].reshape(b, lk, kvh, hdim), 0)
        kg = gather(k)
        o = plan.attention_decode(q, kg, kg if v is k else gather(v),
                                  kv_len=kv_len, scale=scale,
                                  pad_valid=pad_valid)
    return o, new_cache


# --------------------------------------------------------------------------
# latent attention (MLA, deepseek-v2/v3)
# --------------------------------------------------------------------------

MLA_LATENT_NORM_EPS = 1e-6  # deepseek-v3's kv_a_layernorm keeps the default


def init_mla(key, cfg: ModelConfig, dtype) -> Params:
    """Latent attention without a query low-rank path: ``wq`` (d -> H x
    (nope + rope)), ``wkv_a`` (d -> latent + rope key), an RMSNorm on the
    latent, the per-head up-projections ``w_uk`` (nope -> latent, read as
    keys) and ``w_uv`` (latent -> v), and ``wo`` (H x v -> d)."""
    d, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 5)
    return {
        "wq": _dense_init(ks[0], (d, H, dn + dr), dtype),
        "wkv_a": _dense_init(ks[1], (d, r + dr), dtype),
        "kv_norm": {"scale": jnp.ones((r,), dtype)},
        "w_uk": _dense_init(ks[2], (H, dn, r), dtype, fan_in=r),
        "w_uv": _dense_init(ks[3], (H, r, dv), dtype, fan_in=r),
        "wo": _dense_init(ks[4], (H, dv, d), dtype, fan_in=H * dv),
    }


def mla_attention(p: Params, x: jax.Array, *, cfg: ModelConfig,
                  plan: ExecPlan | ExecConfig, positions: jax.Array,
                  cache: Optional[Params] = None,
                  slot_lens: Optional[jax.Array] = None,
                  block_table: Optional[jax.Array] = None,
                  page_size: Optional[int] = None,
                  chunk_offs: Optional[jax.Array] = None,
                  ) -> tuple[jax.Array, Optional[Params]]:
    """Latent attention in its absorbed form.

    A token's cache row is its normalized latent ``c_kv`` (``kv_lora_rank``)
    followed by its RoPE'd key ``k_pe`` (``qk_rope_head_dim``), one row
    shared by every head: head h's key is ``[c_kv @ w_uk[h].T, k_pe]`` and
    its value ``c_kv @ w_uv[h]``. Folding ``w_uk[h]`` into the query
    (``q_lat = q_nope @ w_uk[h]``) scores every head against the same
    cache row, so attention is MQA over one (latent + rope)-wide KV head:
    ``score = [q_lat, q_pe] . [c_kv, k_pe]``, the published
    ``q_nope . k_nope + q_pe . k_pe``. The weighted sum of rows is taken
    as values too; its latent part through ``w_uv[h]`` is head h's
    output (the rope lanes of the sum are dropped, which is exactly
    ``w_uv`` zero-padded over them). Softmax scale (nope + rope) ** -0.5.

    Every projection, the per-head absorptions included, runs on the
    plan's matmul slot. ``cache`` is the paged latent pool
    ``{"kv": (n_pages, page_size, 1, latent + rope), "idx"}`` (decode and
    chunk calls, as `attention` takes them); without a cache the whole
    sequence attends causally (`Model.forward`).
    """
    plan = as_plan(cfg, plan)
    b, sq, _ = x.shape
    r, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    q = _linear(x, p["wq"], plan)                        # (B, S, H, dn+dr)
    kv = _linear(x, p["wkv_a"], plan)                    # (B, S, r+dr)
    c_kv = rms_norm(kv[..., :r], p["kv_norm"]["scale"], MLA_LATENT_NORM_EPS)
    k_pe = apply_rope(kv[..., None, r:], positions, cfg)  # (B, S, 1, dr)
    q_pe = apply_rope(q[..., dn:], positions, cfg)
    q_lat = _per_head_linear(q[..., :dn], p["w_uk"], plan)  # (B, S, H, r)
    q_abs = jnp.concatenate([q_lat, q_pe.astype(q_lat.dtype)], -1)
    row = jnp.concatenate([c_kv[:, :, None, :], k_pe.astype(c_kv.dtype)], -1)
    scale = 1.0 / math.sqrt(dn + cfg.qk_rope_head_dim)
    new_cache = None
    if cache is not None:
        if block_table is None or slot_lens is None or page_size is None:
            raise NotImplementedError(
                "the latent cache is block-paged only: pass block_table, "
                "page_size and slot_lens")
        o, new_cache = _paged_attend(
            q_abs, {"kv": row}, cache, plan=plan, scale=scale,
            slot_lens=slot_lens, block_table=block_table,
            page_size=page_size, chunk_offs=chunk_offs)
    else:
        o = plan.attention_prefill(q_abs, row, row, scale=scale, q_offset=0,
                                   kind="causal", window=cfg.window,
                                   chunk=1024, probs_dtype=_probs_dtype(cfg),
                                   pad_lens=None)
    o = _per_head_linear(o[..., :r].astype(x.dtype), p["w_uv"], plan)
    out = _linear(o.reshape(b, sq, -1), _out_proj(p["wo"]), plan)
    return out, new_cache


# --------------------------------------------------------------------------
# dense FFN
# --------------------------------------------------------------------------

def init_ffn(key, cfg: ModelConfig, dtype) -> Params:
    ks = jax.random.split(key, 3)
    p = {"w1": _dense_init(ks[0], (cfg.d_model, cfg.d_ff), dtype),
         "w2": _dense_init(ks[1], (cfg.d_ff, cfg.d_model), dtype, fan_in=cfg.d_ff)}
    if cfg.glu:
        p["w3"] = _dense_init(ks[2], (cfg.d_model, cfg.d_ff), dtype)
    return p


def ffn(p: Params, x: jax.Array, cfg: ModelConfig,
        plan: ExecPlan | ExecConfig) -> jax.Array:
    plan = as_plan(cfg, plan)
    h = _linear(x, p["w1"], plan)
    h = plan.activation(h, cfg.activation)
    if cfg.glu:
        h = h * _linear(x, p["w3"], plan)
    h = constraint(h, "batch", None, "mlp")
    return _linear(h, p["w2"], plan)


# --------------------------------------------------------------------------
# embeddings / unembedding
# --------------------------------------------------------------------------

def init_embeddings(key, cfg: ModelConfig, dtype) -> Params:
    ks = jax.random.split(key, 3)
    p = {"tok_emb": (jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model),
                                       jnp.float32) * 0.02).astype(dtype)}
    if cfg.pos_emb == "learned":
        max_pos = max(cfg.max_seq_len if cfg.family != "encoder" else 8192, 8192)
        max_pos = min(max_pos, 65_536)
        p["pos_emb"] = (jax.random.normal(ks[1], (max_pos, cfg.d_model),
                                          jnp.float32) * 0.02).astype(dtype)
    if not cfg.tie_embeddings:
        p["unembed"] = _dense_init(ks[2], (cfg.d_model, cfg.vocab_size), dtype)
    return p


def embed(p: Params, tokens: jax.Array, positions: jax.Array, cfg: ModelConfig) -> jax.Array:
    x = jnp.take(p["tok_emb"], tokens, axis=0)
    if cfg.pos_emb == "learned":
        x = x + jnp.take(p["pos_emb"], positions, axis=0)
    elif cfg.pos_emb == "sinusoidal":
        hd = cfg.d_model
        freqs = 1.0 / (10_000 ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
        ang = positions[..., None].astype(jnp.float32) * freqs
        pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)
        x = x + pe.astype(x.dtype)
    return constraint(x, "batch", None, None)


def unembed(p: Params, x: jax.Array, cfg: ModelConfig,
            plan: ExecPlan | ExecConfig) -> jax.Array:
    """Logits through the plan's ``lm_head`` slot.

    Resident int8 unembeddings (`QuantizedWeight`, the raceit_q8 serving
    form) take the quantized path *with the plan's act_bits* — previously
    this spot rebuilt a bare ``ExecConfig(mode="raceit")`` and silently
    dropped the caller's bit-width knobs.
    """
    plan = as_plan(cfg, plan)
    w = p["tok_emb"].T if cfg.tie_embeddings else p["unembed"]
    logits = plan.lm_head(x, w)
    return constraint(logits, "batch", None, "vocab")
