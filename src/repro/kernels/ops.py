"""jit'd public wrappers over the Pallas kernels.

These are the entry points the serving stack uses on TPU; ``interpret=None``
resolves via `runtime.default_interpret` — kernel bodies execute as traced
jax ops on CPU containers (bit-exact validation against ref.py) and compile
to Mosaic on real TPU backends with no code change.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import ops as acam_ops
from repro.core.crossbar import CrossbarConfig
from repro.core.quant import quantize_tensor

from .acam_attention import (  # noqa: F401
    FUSED_SOFTMAX_MODES, acam_attention_codes, acam_attention_decode_codes,
    acam_attention_decode_gqa_codes)
from .acam_lut import acam_lut, acam_lut_2d  # noqa: F401
from .acam_mvm import acam_mvm  # noqa: F401
from .acam_softmax import acam_softmax_codes, acam_softmax_kernel  # noqa: F401
from .runtime import default_interpret  # noqa: F401


def acam_activation(x: jax.Array, name: str = "gelu",
                    interpret: Optional[bool] = None) -> jax.Array:
    """Float tensor through a named Compute-ACAM activation (kernelized)."""
    op = acam_ops.get_op(name)
    codes = op.in_fmt.encode(x)
    out = acam_lut(codes, jnp.asarray(op._lut), bias=1 << (op.in_fmt.bits - 1),
                   interpret=interpret)
    return op.out_fmt.decode(out)


def raceit_linear(x: jax.Array, w: jax.Array,
                  cfg: CrossbarConfig = CrossbarConfig(),
                  interpret: Optional[bool] = None) -> jax.Array:
    """Float linear layer on the kernelized crossbar DPE lane."""
    xq = quantize_tensor(x.astype(jnp.float32), bits=cfg.input_bits)
    wq = quantize_tensor(w.astype(jnp.float32), bits=cfg.weight_bits, axis=1)
    lead = x.shape[:-1]
    y = acam_mvm(xq.codes.reshape(-1, x.shape[-1]), wq.codes, cfg,
                 interpret=interpret)
    return (y.astype(jnp.float32) * (xq.scale * wq.scale)).reshape(*lead, -1)


def prob_requant_scale(cmax: jax.Array) -> jax.Array:
    """The oracle's PROB re-quantization scale (see acam_attention.requant_scale)."""
    from .acam_attention import requant_scale
    return requant_scale(cmax).astype(jnp.float32)


@partial(jax.jit, static_argnames=("softmax_mode", "fold_scale", "causal",
                                   "block_q", "block_k", "interpret"))
def raceit_attention_fused(
    q: jax.Array,  # (B, H, Sq, D) float
    k: jax.Array,  # (B, H, Sk, D) float
    v: jax.Array,  # (B, H, Sk, D) float
    mask: Optional[jax.Array] = None,  # broadcastable to (B, H, Sq, Sk), bool
    softmax_mode: str = "pot",
    q_offset: jax.Array | int = 0,
    fold_scale: bool = False,  # True: 1/sqrt(d) already folded into q
    causal: bool = False,      # in-kernel causal mask (no mask array at all)
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused Fig.-12 attention, float in/out — drop-in for `raceit_attention`.

    Streams over key blocks in one Pallas kernel; the (Sq, Sk) logit and
    probability matrices never exist (pass an in-kernel ``causal`` mask, or
    no mask, to avoid materializing a mask array too). ``softmax_mode``
    accepts "pot", "pot_fine", and "uniform" — every mode the staged path
    takes. Matches the staged `repro.core.attention.raceit_attention` oracle
    to <=1 PROB_FMT ulp (bit-exact on every shape in
    tests/test_attention_fused.py). For the Sq=1 KV-cache serving step use
    `raceit_attention_decode_fused`.
    """
    from .acam_attention import DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    qq = quantize_tensor(q, bits=8)
    kq = quantize_tensor(k, bits=8)
    vq = quantize_tensor(v, bits=8)
    if mask is not None:
        mask = jnp.broadcast_to(mask, (B, H, Sq, Sk)).reshape(B * H, Sq, Sk)
    out32, cmax = acam_attention_codes(
        qq.codes.reshape(B * H, Sq, D), kq.codes.reshape(B * H, Sk, D),
        vq.codes.reshape(B * H, Sk, D), qq.scale * kq.scale, mask,
        q_offset=q_offset, mode=softmax_mode,
        scale_by_sqrt_d=None if fold_scale else D, causal=causal,
        block_q=block_q or DEFAULT_BLOCK_Q, block_k=block_k or DEFAULT_BLOCK_K,
        interpret=interpret)
    p_scale = prob_requant_scale(cmax)
    return (out32.astype(jnp.float32) * (p_scale * vq.scale)
            ).reshape(B, H, Sq, D)


def masked_prefix_quantize(x: jax.Array, kv_len: jax.Array, axis: int = 2):
    """`quantize_tensor(x_sliced_to_kv_len, bits=8)` without slicing.

    Replicates quantize_tensor's exact f32 op sequence on the valid prefix:
    |x| >= 0, so the max over {valid entries} U {zeros} equals the max over
    the slice, and round(x/scale) is elementwise — codes on valid entries are
    bit-identical to quantizing the dynamic slice, while invalid entries are
    zeroed (the kernel masks them out anyway; zeroing keeps the buffer
    contents irrelevant). Returns (codes int8, scale f32) with static shapes.

    ``kv_len`` may be a scalar (one prefix for the whole tensor) or a
    *(B,)* vector of per-row prefixes along the leading batch dim
    (per-request serving): the scale then reduces over the *union* of the
    rows' valid prefixes — one tensor-wide scale, exactly the quantizer
    granularity batched raceit serving already has — and each row's stale
    tail is zeroed/excluded at its own fill level.
    """
    idx = jnp.reshape(jnp.arange(x.shape[axis]),
                      tuple(x.shape[axis] if d == axis else 1
                            for d in range(x.ndim)))
    kvl = jnp.asarray(kv_len, jnp.int32)
    if kvl.ndim == 1:  # per-row prefixes along the leading batch dim
        kvl = kvl.reshape((-1,) + (1,) * (x.ndim - 1))
    valid = idx < kvl
    amax = jnp.max(jnp.where(valid, jnp.abs(x), 0.0))
    scale = (jnp.maximum(amax, 1e-12) / 127).astype(jnp.float32)
    codes = jnp.clip(jnp.round(x / scale), -128, 127).astype(jnp.int8)
    return jnp.where(valid, codes, 0), scale


def page_valid_lengths(block_table: jax.Array, kv_len: jax.Array,
                       n_pages: int, page_size: int) -> jax.Array:
    """Per-physical-page valid entry counts for a paged KV pool.

    Slot ``b``'s logical page ``j`` holds ``clip(kv_len[b] - j*page_size,
    0, page_size)`` live entries; scatter-maxing those through the block
    table yields, for every physical page, how many of its rows hold live
    cache data. Unmapped pages (never named by any table row with a live
    extent) come out 0, and physical page 0 — the conventional trash page
    dead/unmapped table entries resolve to — is forced to 0 so garbage
    routed there can never look valid.
    """
    bt = jnp.asarray(block_table, jnp.int32)
    kvl = jnp.asarray(kv_len, jnp.int32)
    live = jnp.clip(kvl[:, None] - jnp.arange(bt.shape[1], dtype=jnp.int32)
                    * page_size, 0, page_size)
    pv = jnp.zeros((n_pages,), jnp.int32).at[bt].max(live)
    return pv.at[0].set(0)


def masked_page_quantize(x: jax.Array, page_valid: jax.Array):
    """`masked_prefix_quantize` for a page pool: (n_pages, page_size, ...).

    Same f32 op sequence (max of |x| over valid entries padded with zeros,
    ``max(amax, 1e-12)/127``, elementwise round/clip) with validity given
    per page row by ``page_valid`` (`page_valid_lengths`). Because the pool
    holds exactly the live prefixes' values — scattered into pages — and
    f32 max is order-free, the scale is *bit-identical* to what
    `masked_prefix_quantize` computes on the contiguous layout of the same
    logical contents, and so are the codes on every valid entry. Invalid
    entries (stale pages, tails past each slot's fill, the trash page) are
    zeroed and can never perturb the quantizer.
    """
    idx = jnp.reshape(jnp.arange(x.shape[1]), (1, -1) + (1,) * (x.ndim - 2))
    valid = idx < jnp.reshape(page_valid, (-1,) + (1,) * (x.ndim - 1))
    amax = jnp.max(jnp.where(valid, jnp.abs(x), 0.0))
    scale = (jnp.maximum(amax, 1e-12) / 127).astype(jnp.float32)
    codes = jnp.clip(jnp.round(x / scale), -128, 127).astype(jnp.int8)
    return jnp.where(valid, codes, 0), scale


def expand_row_lens(kv_len: jax.Array, rep: int) -> jax.Array:
    """Per-request lengths (B,) -> per-group lengths (B*rep,), b-major.

    The single point of truth for how per-request ``kv_len`` vectors map
    onto kernel grid groups: every one of a request's ``rep`` groups (its
    query heads on the flat decode entry, its KV heads on the GQA entry)
    shares the request's fill level. Scalars pass through untouched.
    """
    kvl = jnp.asarray(kv_len, jnp.int32)
    return jnp.repeat(kvl, rep) if kvl.ndim == 1 else kvl


def _decode_quantize_operands(q, k, v, kv_len):
    """Shared decode-wrapper prolog: q whole-tensor int8, k/v valid-prefix
    int8 (the single point of truth for both the flat and GQA wrappers —
    their bit-identical contract starts with identical codes and scales).
    The cache's quantization reports under the ``kv_pool`` scope."""
    qq = quantize_tensor(q, bits=8)
    with jax.named_scope("kv_pool"):
        return (qq, masked_prefix_quantize(k, kv_len),
                masked_prefix_quantize(v, kv_len))


@partial(jax.jit, static_argnames=("softmax_mode", "fold_scale",
                                   "block_k", "block_g", "interpret"))
def raceit_attention_decode_fused(
    q: jax.Array,   # (B, H, 1, D) float — the new token's query
    k: jax.Array,   # (B, H, Smax, D) float — KV cache buffer (fixed shape)
    v: jax.Array,   # (B, H, Smax, D) float
    kv_len: jax.Array,              # () int32 (>= 1) or (B,) per-request
    softmax_mode: str = "pot",
    fold_scale: bool = False,       # True: 1/sqrt(d) already folded into q
    block_k: int | None = None,
    block_g: int | None = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused decode-step attention over a KV cache, float in/out.

    Bit-exact (to the same <=1 PROB ulp contract as the prefill path) vs the
    staged oracle evaluated on the cache *slice*::

        raceit_attention(q, k[:, :, :kv_len], v[:, :, :kv_len])

    k/v are quantized with `masked_prefix_quantize`, so the tensor scale is
    computed over the valid prefix only — entries past ``kv_len`` (stale or
    zero-initialized cache rows) cannot perturb the quantizer. Partially
    valid key blocks are masked out of the softmax and matmul-2; *fully*
    invalid blocks are skipped outright via scalar-prefetched grid bounds
    (kv_len rides as a `pltpu.PrefetchScalarGridSpec` operand, so their
    k/v tiles are never fetched and their compute is gated off — see
    `acam_attention_codes`).

    This wrapper is what the ExecPlan's ``attention_decode`` slot resolves
    to as the ``raceit_fused`` backend (via `models.layers`); it remains
    directly callable for kernel-level tests and benchmarks.

    A *(B,)* vector ``kv_len`` gives every batch row its own valid prefix
    (per-request serving decode): all H head groups of a row share its
    length, k/v quantizer scales reduce over the union of the rows' valid
    prefixes (one tensor-wide scale, the batched-raceit granularity), and
    zero-length rows output zeros. The ``raceit_fused_rows`` backend is
    this path.
    """
    from .acam_attention import DEFAULT_BLOCK_G, DEFAULT_BLOCK_K
    B, H, Sq, D = q.shape
    Smax = k.shape[2]
    qq, (k_codes, k_scale), (v_codes, v_scale) = \
        _decode_quantize_operands(q, k, v, kv_len)
    kvl = expand_row_lens(kv_len, H)
    out32, cmax = acam_attention_decode_codes(
        qq.codes.reshape(B * H, Sq, D), k_codes.reshape(B * H, Smax, D),
        v_codes.reshape(B * H, Smax, D), qq.scale * k_scale,
        kvl, mode=softmax_mode,
        scale_by_sqrt_d=None if fold_scale else D,
        block_k=block_k or DEFAULT_BLOCK_K, block_g=block_g or DEFAULT_BLOCK_G,
        interpret=interpret)
    p_scale = prob_requant_scale(cmax)
    return (out32.astype(jnp.float32) * (p_scale * v_scale)
            ).reshape(B, H, Sq, D)


def _paged_quantize_operands(q, k_pool, v_pool, block_table, kv_len):
    """Paged decode-wrapper prolog: q whole-tensor int8, pooled k/v per-page
    int8 with scales over the union of live page entries — bit-identical to
    `_decode_quantize_operands` on the contiguous gather of the same table
    (the paged wrappers' parity contract starts here). The pool's
    quantization reports under the ``kv_pool`` scope."""
    qq = quantize_tensor(q, bits=8)
    with jax.named_scope("kv_pool"):
        pv = page_valid_lengths(block_table, kv_len,
                                k_pool.shape[0], k_pool.shape[1])
        return (qq, masked_page_quantize(k_pool, pv),
                masked_page_quantize(v_pool, pv))


@partial(jax.jit, static_argnames=("softmax_mode", "fold_scale",
                                   "block_k", "block_g", "interpret"))
def raceit_attention_decode_paged(
    q: jax.Array,       # (B, H, Sq, D) float — Sq=1 decode or Sq=C chunk
    k_pool: jax.Array,  # (n_pages, page_size, KV, D) float — the page pool
    v_pool: jax.Array,  # (n_pages, page_size, KV, D) float
    kv_len: jax.Array,              # (B,) int32 per-slot fill levels
    block_table: jax.Array,         # (B, max_pages) int32; 0 = trash page
    mask: Optional[jax.Array] = None,  # (B, Sq, max_pages*page_size) bool
    softmax_mode: str = "pot",
    fold_scale: bool = False,
    block_k: int | None = None,
    block_g: int | None = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused attention over a block-paged KV pool, float in/out.

    The paged twin of `raceit_attention_decode_fused`: instead of one
    contiguous ``(B, H, Smax, D)`` cache buffer, k/v live in a shared page
    *pool* — ``n_pages`` pages of ``page_size`` cache rows each, stored
    once per KV head — and ``block_table[b, j]`` names the physical page
    backing slot ``b``'s logical page ``j``. The kernel reads tiles
    through the table (a third scalar-prefetch operand consumed only by
    the k/v index maps), so the logical key extent is
    ``max_pages * page_size`` and memory scales with pages *allocated*,
    not ``Smax x slots``. Bit-identical to the contiguous wrapper on the
    gathered layout of the same table: per-page quantizer scales reduce
    over the same union of live prefixes (`masked_page_quantize`), and
    page indirection moves only the DMA source of each key tile.

    ``Sq > 1`` is the *chunked-prefill* call: the ``Sq`` queries of a
    prompt chunk attend the slot's pages through the same executable, with
    ``mask`` carrying the intra-chunk causal rule (query row ``j`` sees
    columns ``< chunk_off + j + 1``); rows masked to nothing output zeros.
    KV heads are repeated to H in int8 codes (the flat grid layout); the
    decode hot loop should prefer `raceit_attention_decode_gqa_paged` when
    ``n_kv_heads < n_heads``.
    """
    from .acam_attention import DEFAULT_BLOCK_G, DEFAULT_BLOCK_K
    B, H, Sq, D = q.shape
    n_pages, ps, KV, hd = k_pool.shape
    rep = H // KV
    qq, (k_codes, k_scale), (v_codes, v_scale) = \
        _paged_quantize_operands(q, k_pool, v_pool, block_table, kv_len)
    # flat grid layout: groups are query heads, so each physical page's
    # stripe row page*H + h holds KV head h//rep (codes repeated, pool not)
    with jax.named_scope("kv_pool"):
        k_rows, v_rows = (jnp.repeat(c, rep, axis=2).transpose(0, 2, 1, 3)
                          .reshape(n_pages * H, ps, hd)
                          for c in (k_codes, v_codes))
    if mask is not None:
        Sk = block_table.shape[1] * ps
        mask = jnp.broadcast_to(mask[:, None], (B, H, Sq, Sk)) \
            .reshape(B * H, Sq, Sk)
    out32, cmax = acam_attention_codes(
        qq.codes.reshape(B * H, Sq, D), k_rows, v_rows,
        qq.scale * k_scale, mask, kv_len=expand_row_lens(kv_len, H),
        mode=softmax_mode, scale_by_sqrt_d=None if fold_scale else D,
        block_k=block_k or DEFAULT_BLOCK_K, block_g=block_g or DEFAULT_BLOCK_G,
        interpret=interpret, block_table=block_table, page_size=ps,
        groups_per_slot=H)
    p_scale = prob_requant_scale(cmax)
    return (out32.astype(jnp.float32) * (p_scale * v_scale)
            ).reshape(B, H, Sq, D)


@partial(jax.jit, static_argnames=("softmax_mode", "fold_scale",
                                   "block_k", "block_g", "interpret"))
def raceit_attention_decode_gqa_paged(
    q: jax.Array,       # (B, H, Sq, D) float — Sq=1 decode or Sq=C chunk
    k_pool: jax.Array,  # (n_pages, page_size, KV, D) float — the page pool
    v_pool: jax.Array,  # (n_pages, page_size, KV, D) float
    kv_len: jax.Array,              # (B,) int32 per-slot fill levels
    block_table: jax.Array,         # (B, max_pages) int32; 0 = trash page
    mask: Optional[jax.Array] = None,  # (B, Sq, max_pages*page_size) bool
    softmax_mode: str = "pot",
    fold_scale: bool = False,
    block_k: int | None = None,
    block_g: int | None = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """GQA-native fused decode over a block-paged KV pool, float in/out.

    `raceit_attention_decode_gqa` with page-table indirection: the pool
    keeps KV heads native (never repeated, as floats or codes — each
    physical page's stripe row ``page*KV + kvh`` is KV head ``kvh``), the
    grid's group dimension iterates B*KV KV-head groups with the ``rep``
    sharing queries riding the tile's row dim, and the block table routes
    each logical key tile to its physical page. Bit-identical to
    `raceit_attention_decode_paged` on the same pool (repeat commutes with
    everything after quantization) and hence to the contiguous wrappers on
    the gathered layout. A chunk call (Sq > 1) puts each group's rep x Sq
    (head, position) queries on the tile's rows, with ``mask`` carrying
    each position's causal rule to its heads' rows: rows are independent
    in the kernel, so this too is bit-identical to the flat entry, and the
    pool is never repeated to H.
    """
    from .acam_attention import DEFAULT_BLOCK_G, DEFAULT_BLOCK_K
    B, H, Sq, D = q.shape
    n_pages, ps, KV, hd = k_pool.shape
    if H % KV:
        raise ValueError(f"n_heads={H} not a multiple of n_kv_heads={KV}")
    rep = H // KV
    qq, (k_codes, k_scale), (v_codes, v_scale) = \
        _paged_quantize_operands(q, k_pool, v_pool, block_table, kv_len)
    with jax.named_scope("kv_pool"):
        k_rows, v_rows = (c.transpose(0, 2, 1, 3).reshape(n_pages * KV, ps, hd)
                          for c in (k_codes, v_codes))
    if mask is not None:
        Sk = block_table.shape[1] * ps
        mask = jnp.broadcast_to(mask[:, None, None], (B, KV, rep, Sq, Sk)) \
            .reshape(B * KV, rep * Sq, Sk)
    out32, cmax = acam_attention_decode_gqa_codes(
        qq.codes.reshape(B * KV, rep * Sq, D),
        k_rows, v_rows, qq.scale * k_scale,
        expand_row_lens(kv_len, KV), mask=mask,
        mode=softmax_mode, scale_by_sqrt_d=None if fold_scale else D,
        block_k=block_k or DEFAULT_BLOCK_K, block_g=block_g or DEFAULT_BLOCK_G,
        interpret=interpret, block_table=block_table, page_size=ps,
        groups_per_slot=KV)
    p_scale = prob_requant_scale(cmax)
    return (out32.astype(jnp.float32) * (p_scale * v_scale)
            ).reshape(B, H, Sq, D)


# ---------------------------------------------------------------------------
# tensor-parallel quantizer twins (used inside repro.dist.shard_map bodies
# by the exec/sharded.py backends)
# ---------------------------------------------------------------------------
# Each is the same f32 op sequence as its single-device twin above with one
# change: the local |x| max is `jax.lax.pmax`-ed over the mesh axis before
# the shared scale formula. f32 max is order-free, so the globalized amax —
# and therefore the scale and every code — is bit-identical to what the
# unsharded twin computes on the gathered tensor.

def tp_quantize_tensor(x: jax.Array, axis_name: str):
    """`quantize_tensor(x, bits=8)` inside a shard_map body, scale global."""
    from repro.core.quant import QuantizedTensor
    amax = jax.lax.pmax(jnp.max(jnp.abs(x)), axis_name)
    scale = jnp.maximum(amax, 1e-12) / 127
    codes = jnp.clip(jnp.round(x / scale), -128, 127).astype(jnp.int8)
    return QuantizedTensor(codes, scale.astype(jnp.float32), 8)


def tp_masked_prefix_quantize(x: jax.Array, kv_len: jax.Array,
                              axis_name: str, axis: int = 2):
    """`masked_prefix_quantize` with the amax pmax-ed over the mesh axis."""
    idx = jnp.reshape(jnp.arange(x.shape[axis]),
                      tuple(x.shape[axis] if d == axis else 1
                            for d in range(x.ndim)))
    kvl = jnp.asarray(kv_len, jnp.int32)
    if kvl.ndim == 1:
        kvl = kvl.reshape((-1,) + (1,) * (x.ndim - 1))
    valid = idx < kvl
    amax = jax.lax.pmax(jnp.max(jnp.where(valid, jnp.abs(x), 0.0)), axis_name)
    scale = (jnp.maximum(amax, 1e-12) / 127).astype(jnp.float32)
    codes = jnp.clip(jnp.round(x / scale), -128, 127).astype(jnp.int8)
    return jnp.where(valid, codes, 0), scale


def tp_masked_page_quantize(x: jax.Array, page_valid: jax.Array,
                            axis_name: str):
    """`masked_page_quantize` with the amax pmax-ed over the mesh axis."""
    idx = jnp.reshape(jnp.arange(x.shape[1]), (1, -1) + (1,) * (x.ndim - 2))
    valid = idx < jnp.reshape(page_valid, (-1,) + (1,) * (x.ndim - 1))
    amax = jax.lax.pmax(jnp.max(jnp.where(valid, jnp.abs(x), 0.0)), axis_name)
    scale = (jnp.maximum(amax, 1e-12) / 127).astype(jnp.float32)
    codes = jnp.clip(jnp.round(x / scale), -128, 127).astype(jnp.int8)
    return jnp.where(valid, codes, 0), scale


def tp_exact_call(call, axis_name: str):
    """The probe -> pmax -> exact protocol for a tensor-parallel kernel call.

    ``call(cmax_floor)`` must run one of the ``acam_attention*_codes``
    entries on this shard's groups and return its (out32, cmax). The probe
    call (floor 0 — the exact-identity seed) yields the shard's local max
    PROB code; `jax.lax.pmax` over the mesh axis turns it into the global
    one (integer max is order-free); and the second call re-runs the shard
    with the global floor, so every shard re-quantizes PROB with the same
    table the unsharded kernel would have used — the returned cmax *is*
    the global cmax on every shard, and the sharded output is bit-identical
    to the single-device call on the gathered operands.
    """
    _, local_cmax = call(jnp.zeros((), jnp.int32))
    return call(jax.lax.pmax(local_cmax, axis_name))


@partial(jax.jit, static_argnames=("softmax_mode", "fold_scale",
                                   "block_k", "block_g", "interpret"))
def raceit_attention_decode_gqa(
    q: jax.Array,   # (B, H, 1, D) float — the new token's queries, all heads
    k: jax.Array,   # (B, KV, Smax, D) float — native-layout KV cache buffer
    v: jax.Array,   # (B, KV, Smax, D) float
    kv_len: jax.Array,              # () int32 (>= 1) or (B,) per-request
    softmax_mode: str = "pot",
    fold_scale: bool = False,       # True: 1/sqrt(d) already folded into q
    block_k: int | None = None,
    block_g: int | None = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """GQA-native fused decode attention, float in/out.

    Takes the KV cache in its *native* grouped layout — KV heads are never
    repeated to H, neither as floats nor as int8 codes — and hands the
    kernel (`acam_attention_decode_gqa_codes`) one group per KV head with
    the ``rep = H/KV`` sharing queries riding the tile's row dimension, so
    each KV tile is fetched once per group. Bit-identical to
    `raceit_attention_decode_fused` on ``jnp.repeat(k, rep, axis=1)`` (and
    hence bit-exact vs the staged oracle on the cache slice, to the same
    <=1 PROB ulp contract): the repeated tensor has the same max-abs as the
    native one, so quantizer scales, codes, per-row PoT sums, and the
    global cmax are all unchanged — only the dataflow is.

    At rep=1 (MHA) the two entries coincide; the ExecPlan only resolves
    ``raceit_gqa_native`` when ``n_kv_heads < n_heads``.

    A *(B,)* vector ``kv_len`` gives every batch row its own valid prefix
    (per-request serving decode, the ``raceit_gqa_rows`` backend): all KV
    groups of a row share its length, scales reduce over the union of
    valid prefixes, zero-length rows output zeros.
    """
    from .acam_attention import DEFAULT_BLOCK_G, DEFAULT_BLOCK_K
    B, H, Sq, D = q.shape
    KV, Smax = k.shape[1], k.shape[2]
    if Sq != 1:
        raise ValueError(f"decode path expects Sq=1, got {Sq}")
    if H % KV:
        raise ValueError(f"n_heads={H} not a multiple of n_kv_heads={KV}")
    rep = H // KV
    qq, (k_codes, k_scale), (v_codes, v_scale) = \
        _decode_quantize_operands(q, k, v, kv_len)
    kvl = expand_row_lens(kv_len, KV)
    out32, cmax = acam_attention_decode_gqa_codes(
        qq.codes.reshape(B, KV, rep, D).reshape(B * KV, rep, D),
        k_codes.reshape(B * KV, Smax, D), v_codes.reshape(B * KV, Smax, D),
        qq.scale * k_scale, kvl,
        mode=softmax_mode, scale_by_sqrt_d=None if fold_scale else D,
        block_k=block_k or DEFAULT_BLOCK_K, block_g=block_g or DEFAULT_BLOCK_G,
        interpret=interpret)
    p_scale = prob_requant_scale(cmax)
    return (out32.astype(jnp.float32) * (p_scale * v_scale)
            ).reshape(B, H, Sq, D)
