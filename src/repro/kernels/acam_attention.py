"""Pallas TPU kernel: the fused streaming RACE-IT attention pipeline (Fig. 12).

The staged reference (`repro.core.attention.raceit_attention`) runs the five
Fig.-12 stages as separate XLA ops, materializing the full (Sq, Sk) logit and
probability matrices in HBM and re-quantizing between every stage. This
kernel executes the whole pipeline per (head-block x row-block x key-block)
tile in VMEM, flash-attention style, so the (Sq, Sk) intermediates never
exist:

  matmul-1   int8 q . K^T, batched over the head block, on the MXU
  div-add    scale by s_q s_k / sqrt(d), additive mask -> LOGIT codes
  softmax    the Fig. 8 exp/log LUT dataflow, evaluated *online*: the PoT
             row-sum streams over key blocks, and LOG(S) is applied lazily
  matmul-2   PROB codes . V accumulated in an int32 VMEM scratch

The ACAM softmax has no running-max rescale (d_i = x_i - LOG(S) needs only
the final row sum), but the oracle's PROB re-quantization uses the *global*
probability max. The kernel therefore makes two passes over the key stream
(grid dim 0 is the pass):

  pass A  per row: accumulate S = sum EXP(x) and the row logit max; at the
          last key block fold them into LOG(S) and the row's max PROB code,
          reducing a global cmax in SMEM (the tensor-wide quantizer scale).
  pass B  recompute the tile's logit codes, finish d = x - LOG(S)<<1 ->
          PROB codes, re-quantize with the global cmax exactly like
          `quantize_tensor`, and accumulate codes . V on the MXU.

Pass A/B recompute matmul-1 twice — the same flops-for-memory trade as
flash attention's backward — except when the whole problem fits one tile,
where the kernel collapses to a single grid step with the logit codes live
in registers. Heads ride inside the block (bg of them per tile) because grid
steps, not flops, dominate interpret-mode latency; on a real TPU the same
knob bounds VMEM instead. Every arithmetic step replicates the oracle's
f32 op sequence, so outputs are bit-identical to the staged path up to
float summation order of the PoT row sum (asserted to <= 1 PROB ulp in
tests, and observed exact on every shape exercised there).

Two entry points share the kernel bodies:

* `acam_attention_codes`   — prefill/forward: (G, Sq, D) queries, optional
  mask / in-kernel causal offset;
* `acam_attention_decode_codes` — serving decode: Sq=1 queries against a
  fixed-shape KV cache whose valid prefix length ``kv_len`` is a *traced*
  scalar — or, for per-request serving, a *per-group vector* (one length
  per grid group) — ridden in as a scalar-prefetch operand: key blocks
  fully past the fill level are skipped outright (clamped index maps +
  gated compute), and only the partially valid boundary block is masked —
  instead of slicing the buffer (dynamic shapes) or sweeping it whole.
  With a vector ``kv_len`` the skip bound is per *group tile* (the max
  length of the ``bg`` groups riding the tile, prefetched as a second
  scalar operand), so a short request in a mixed batch stops streaming at
  its own fill level instead of the batch max;
* `acam_attention_decode_gqa_codes` — GQA-native serving decode: k/v stay
  in their native (B*KV, Smax, hd) cache layout and the ``rep = H/KV``
  query heads that share a KV head ride the *row* dimension of one tile,
  so the grid's group dimension iterates B*KV groups (not B*H) and each
  KV tile is fetched once per head group instead of once per query head —
  the ``jnp.repeat`` of int8 cache codes disappears from the decode hot
  loop along with rep x of its cache-read traffic. Same scalar-prefetched
  ``kv_len`` machinery (clamped index maps + `guard_live` gating).

Every entry additionally accepts a **block-paged** k/v layout: instead of
one contiguous (Smax,) stripe per group, keys live in a pool of fixed-size
pages — k/v arrive as ``(n_pages * groups_per_slot, page_size, D)`` and a
per-slot ``block_table`` maps each slot's logical page index to a physical
pool page. The table rides the same `PrefetchScalarGridSpec` as the kv_len
operands (a third scalar-prefetch arg consumed *only* by the k/v index
maps), so the kernel bodies are untouched: logical key coordinates —
`key_valid`, the per-row frontier clamp, `guard_live` skipping — all work
exactly as in the contiguous layout, and a shuffled block table is
bit-identical to the contiguous stripe because only the DMA source of each
tile moves, never its logical contents or the block visit order.

All entries accept every softmax configuration of the staged path: "pot",
"pot_fine", and the Fig.-14 "uniform" exp-quantization ablation — the LOG
stage always consumes a PoT-encoded row sum, so only the exp gather table
differs per mode (see `softmax_tables`).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import ops as acam_ops
from repro.core.ops import LOGIT_FMT, PROB_FMT
from repro.core.quant import PoTFormat

from .acam_lut import TABLE_SHAPE, lane_lookup, lane_table
from .runtime import resolve_interpret

__all__ = ["acam_attention_codes", "acam_attention_decode_codes",
           "acam_attention_decode_gqa_codes", "softmax_tables",
           "FUSED_SOFTMAX_MODES", "DEFAULT_BLOCK_Q", "DEFAULT_BLOCK_K",
           "DEFAULT_BLOCK_G"]

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 512
DEFAULT_BLOCK_G = 8
_LANES = 128
_VMEM_TILE_ELEMS = 1 << 18  # elements of one compiled (bg, bq, bk) tile

# every softmax configuration the staged acam_softmax accepts; the fused
# kernels cover all of them (core.attention.fused_attention_supported is the
# single dispatchability predicate built on this)
FUSED_SOFTMAX_MODES = ("pot", "pot_fine", "uniform")

_EXP_OPS = {"pot": "exp_pot", "pot_fine": "exp_pot_fine",
            "uniform": "exp_uniform"}
_LOG_OPS = {"pot": "log", "pot_fine": "log_fine", "uniform": "log"}


def softmax_tables(mode: str):
    """(exp_val, log_lut, prob_lut, e_min, octave_step, frac_shift) for a mode.

    ``exp_val`` is the exp LUT pre-composed with its output-format decode into
    one f32 gather table (256 entries), built with the *same jnp ops* as the
    format's ``decode`` so table entries are bit-identical to the staged
    ``acam_softmax``'s step-1 values. ``e_min``/``octave_step`` describe the
    log op's PoT *input* format (the row-sum re-quantization grid) — for
    "pot"/"pot_fine" that coincides with the exp output format; for "uniform"
    the exp output is a uniform `ScaledFormat` but the LOG stage still takes a
    PoT-encoded sum, exactly as in `core.softmax.acam_softmax`.
    """
    if mode not in FUSED_SOFTMAX_MODES:
        raise ValueError(
            f"fused attention softmax_mode must be one of {FUSED_SOFTMAX_MODES},"
            f" got {mode!r}")
    exp_op = acam_ops.get_op(_EXP_OPS[mode])
    log_op = acam_ops.get_op(_LOG_OPS[mode])
    prob_op = acam_ops.get_op("exp_prob")
    ec = jnp.asarray(exp_op._lut, jnp.int32)
    if isinstance(exp_op.out_fmt, PoTFormat):
        step, e0 = exp_op.out_fmt.octave_step, exp_op.out_fmt.e_min
        exp_val = jnp.where(
            ec == 0, 0.0,
            jnp.exp2(jnp.minimum((ec - 1).astype(jnp.float32) * step + e0,
                                 126.0)))
    else:  # uniform ScaledFormat: decode is a plain scale multiply
        exp_val = ec.astype(jnp.float32) * exp_op.out_fmt.scale
    pot_in = log_op.in_fmt
    frac_shift = LOGIT_FMT.frac_bits - log_op.out_fmt.frac_bits
    return (exp_val, log_op._lut, prob_op._lut,
            float(pot_in.e_min), float(pot_in.octave_step), frac_shift)


def _pot_encode_sum(S, e_min: float, octave_step: float):
    """PoT-encode the row sum exactly as `PoTFormat.encode` (f32 op order)."""
    safe = jnp.maximum(S, 2.0 ** (e_min - 1))
    e = jnp.clip(jnp.round((jnp.log2(safe) - e_min) / octave_step), 0, 254)
    codes = (e + 1).astype(jnp.int32)
    return jnp.where(S < 2.0 ** (e_min - octave_step / 2), 0, codes)


def requant_scale(cmax):
    """`quantize_tensor(probs, bits=8).scale` from the max PROB code.

    Probs live on the exact 2^-8 grid, so their tensor max is cmax * 2^-8
    with no rounding; this f32 op sequence is the bit-exactness contract
    with the oracle — it exists only here (kernels and wrappers share it).
    """
    amax = cmax.astype(jnp.float32) * PROB_FMT.scale
    return jnp.maximum(amax, 1e-12) / 127


def _requant_code_table(cmax, prob_lut_vals):
    """PROB-code -> re-quantized int8 code, composed per code (256 entries).

    Elementwise application of a value-wise function commutes with the
    table, so gathering this is bit-identical to quantizing the
    materialized probabilities with `quantize_tensor`. Works on the table in
    any layout (the kernels pass the (2, 128) lane table).
    """
    p_tab = prob_lut_vals.astype(jnp.float32) * PROB_FMT.scale
    return jnp.clip(jnp.round(p_tab / requant_scale(cmax)),
                    -128, 127).astype(jnp.int32)


def _group_lens(kvlen_ref, g0, bg: int):
    """Valid lengths of groups ``g0 .. g0+bg`` as a (bg, 1, 1) vector.

    SMEM serves scalar loads only, so the vector is assembled from ``bg``
    of them.
    """
    gi = jax.lax.broadcasted_iota(jnp.int32, (bg, 1, 1), 0)
    lens = jnp.zeros((bg, 1, 1), jnp.int32)
    for b in range(bg):
        lens = jnp.where(gi == b, kvlen_ref[g0 + b], lens)
    return lens


def _attn_kernel(kvlen_ref, kvmax_ref, s1_ref, qoff_ref, ecmax_ref, q_ref,
                 k_ref, v_ref, *rest,
                 nq: int, nk: int, bg: int, bq: int, bk: int,
                 g_real: int, sq_real: int, sk_real: int,
                 sqrt_d: Optional[float],
                 e_min: float, octave_step: float, frac_shift: int,
                 causal: bool, has_mask: bool, dyn_len: bool,
                 per_row: bool, skip_blocks: bool):
    if has_mask:
        mask_ref, exp_val_ref, log_lut_ref, prob_lut_ref = rest[:4]
        rest = rest[4:]
    else:
        mask_ref = None
        exp_val_ref, log_lut_ref, prob_lut_ref = rest[:3]
        rest = rest[3:]
    o_ref, cmax_out_ref, sum_ref, xmax_ref, acc_ref, cmax_ref = rest

    ph = pl.program_id(0)
    g = pl.program_id(1)
    i = pl.program_id(2)
    k = pl.program_id(3)
    tile = g * nq + i  # this tile's slot in the row-sum scratch
    # keys past the real/valid length carry no weight at all (they do not
    # exist in the oracle's input): static block padding, or — decode path —
    # the dynamic KV-cache fill level streamed in as a prefetched scalar
    mask_keys = (sk_real % bk != 0) or dyn_len
    def guard_live(body):
        """Run ``body`` only for key blocks intersecting the valid prefix.

        Scalar-prefetch decode grids (``skip_blocks``: dynamic length AND
        more than one key block): fully-invalid blocks (k*bk >= kv_len)
        are skipped outright — their accumulation work is gated off here,
        and the k/v BlockSpec index maps clamp them to the last valid
        block so no fresh tile is ever fetched for them (grid bounds
        instead of masked sweeps over the whole cache buffer). The bound
        is ``kvmax_ref[g]`` — the max valid length across the ``bg``
        groups riding this tile: equal to the lone kv_len for a scalar
        fill, and the *tile's own* fill frontier for a per-group vector
        (a short request in a mixed batch stops streaming at its own
        level, not the batch max). kv_len is an SMEM scalar load, safe to
        branch on. Every other grid keeps the unconditional body: static
        (prefill) grids have nothing to skip, and an nk==1 dynamic grid's
        only block always intersects the prefix — gating there would
        predicate control flow on a VMEM-resident scalar for a condition
        that is always true.
        """
        if skip_blocks:
            pl.when((k * bk) < kvmax_ref[g])(body)
        else:
            body()

    def row_lens():
        """Per-group valid lengths of this tile's rows: (bg, 1, 1)."""
        return _group_lens(kvlen_ref, g * bg, bg)

    def key_valid():
        kpos = k * bk + jax.lax.broadcasted_iota(jnp.int32, (bg, bq, bk), 2)
        if per_row:  # each group row attends its own request's prefix
            return kpos < row_lens()
        return kpos < kvlen_ref[0]

    def tile_logit_codes():
        """matmul-1 + div-add: (bg, bq, bk) LOGIT codes."""
        r = jax.lax.dot_general(
            q_ref[...].astype(jnp.int8), k_ref[...].astype(jnp.int8),
            (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.int32)
        logits = r.astype(jnp.float32) * s1_ref[0, 0]
        if sqrt_d is not None:
            logits = logits / sqrt_d
        xc = jnp.clip(jnp.round(logits / LOGIT_FMT.scale),
                      LOGIT_FMT.code_min, LOGIT_FMT.code_max).astype(jnp.int32)
        if has_mask:
            msk = mask_ref[...] != 0
        elif causal:
            qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bg, bq, bk), 1)
            kpos = k * bk + jax.lax.broadcasted_iota(jnp.int32, (bg, bq, bk), 2)
            msk = kpos <= qpos + qoff_ref[0, 0]
        else:
            msk = None
        if msk is not None:  # masked keys sit at the LOGIT minimum (div-add)
            xc = jnp.where(msk, xc, LOGIT_FMT.code_min)
        return xc

    def load_row_sums():
        return sum_ref[tile][:, :, None]

    # ---------------- pass A: streaming PoT row sum + global PROB max ------
    @pl.when(ph == 0)
    def _pass_a():
        @pl.when((g == 0) & (i == 0) & (k == 0))
        def _init_global():
            # the running global PROB max starts at the external floor
            # (0 for single-device calls): tensor-parallel shards seed it
            # with the cross-shard pmax so every shard requantizes with
            # the same — true global — scale. max(floor, local) needs no
            # extra op: the floor is just the accumulator's initial value.
            cmax_ref[0, 0] = ecmax_ref[0, 0]

        @pl.when(k == 0)
        def _init_rows():
            sum_ref[tile] = jnp.zeros((bg, bq), jnp.float32)
            xmax_ref[...] = jnp.full((bg, bq, 1), LOGIT_FMT.code_min, jnp.int32)

        @guard_live
        def _accumulate():
            xc = tile_logit_codes()
            # exp_val_ref folds the exp LUT with its decode: one f32 gather
            e_vals = lane_lookup(exp_val_ref[...], xc + 128)
            xmax_tile = xc
            if mask_keys:
                valid = key_valid()
                e_vals = jnp.where(valid, e_vals, 0.0)
                xmax_tile = jnp.where(valid, xc, LOGIT_FMT.code_min)
            sum_ref[tile] += jnp.sum(e_vals, axis=-1)
            xmax_ref[...] = jnp.maximum(
                xmax_ref[...], jnp.max(xmax_tile, axis=-1, keepdims=True))

        @pl.when(k == nk - 1)
        def _row_finish():
            L = lane_lookup(log_lut_ref[...], _pot_encode_sum(
                load_row_sums(), e_min, octave_step))
            dmax = jnp.clip(xmax_ref[...] - (L << frac_shift),
                            LOGIT_FMT.code_min, LOGIT_FMT.code_max)
            c_row = lane_lookup(prob_lut_ref[...], dmax + 128)
            rpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bg, bq, 1), 1)
            gpos = g * bg + jax.lax.broadcasted_iota(jnp.int32, (bg, bq, 1), 0)
            c_row = jnp.where((rpos < sq_real) & (gpos < g_real), c_row, 0)
            if per_row:
                # a zero-length group has NO keys: its row sum is 0 and its
                # xmax sits at the LOGIT minimum, which LOG(0) could still
                # lift into a nonzero PROB code — such rows are defined as
                # all-zero output and must not pollute the global cmax
                c_row = jnp.where(row_lens() > 0, c_row, 0)
            cmax_ref[0, 0] = jnp.maximum(cmax_ref[0, 0], jnp.max(c_row))

    # ---------------- pass B: PROB codes . V with the exact oracle scale ---
    @pl.when(ph == 1)
    def _pass_b():
        @pl.when(k == 0)
        def _init_acc():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @guard_live
        def _accumulate():
            xc = tile_logit_codes()
            L = lane_lookup(log_lut_ref[...], _pot_encode_sum(
                load_row_sums(), e_min, octave_step))
            d = jnp.clip(xc - (L << frac_shift),
                         LOGIT_FMT.code_min, LOGIT_FMT.code_max)
            pc = lane_lookup(
                _requant_code_table(cmax_ref[0, 0], prob_lut_ref[...]),
                d + 128)
            if mask_keys:  # padded/invalid keys: PROB code 0 -> requant code 0
                pc = jnp.where(key_valid(), pc, 0)
            # requant codes are clipped to int8: an int8 MXU product
            acc_ref[...] += jax.lax.dot_general(
                pc.astype(jnp.int8), v_ref[...].astype(jnp.int8),
                (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.int32)

        @pl.when(k == nk - 1)
        def _write():
            o_ref[...] = acc_ref[...]
            cmax_out_ref[0, 0] = cmax_ref[0, 0]


def _attn_kernel_single(kvlen_ref, kvmax_ref, s1_ref, qoff_ref, ecmax_ref,
                        q_ref, k_ref, v_ref, *rest, bg: int, bq: int, bk: int,
                        g_real: int, sq_real: int, sk_real: int,
                        sqrt_d: Optional[float],
                        e_min: float, octave_step: float, frac_shift: int,
                        causal: bool, has_mask: bool, dyn_len: bool,
                        per_row: bool):
    """One-tile fast path: the whole pipeline in a single grid step.

    When (heads, Sq, Sk) fit one VMEM tile the two-pass structure degenerates
    — the logit codes stay live in registers between the row-sum and the
    PROB matmul, so there is no second key sweep and no scratch traffic.
    Numerics are identical to the streaming kernel.
    """
    if has_mask:
        mask_ref, exp_val_ref, log_lut_ref, prob_lut_ref, o_ref, cmax_out_ref \
            = rest
    else:
        mask_ref = None
        exp_val_ref, log_lut_ref, prob_lut_ref, o_ref, cmax_out_ref = rest
    mask_keys = (sk_real % bk != 0) or dyn_len

    r = jax.lax.dot_general(
        q_ref[...].astype(jnp.int8), k_ref[...].astype(jnp.int8),
        (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.int32)
    logits = r.astype(jnp.float32) * s1_ref[0, 0]
    if sqrt_d is not None:
        logits = logits / sqrt_d
    xc = jnp.clip(jnp.round(logits / LOGIT_FMT.scale),
                  LOGIT_FMT.code_min, LOGIT_FMT.code_max).astype(jnp.int32)
    if has_mask:
        xc = jnp.where(mask_ref[...] != 0, xc, LOGIT_FMT.code_min)
    elif causal:
        qpos = jax.lax.broadcasted_iota(jnp.int32, (bg, bq, bk), 1)
        kpos = jax.lax.broadcasted_iota(jnp.int32, (bg, bq, bk), 2)
        xc = jnp.where(kpos <= qpos + qoff_ref[0, 0], xc, LOGIT_FMT.code_min)

    e_vals = lane_lookup(exp_val_ref[...], xc + 128)
    xmax_tile = xc
    if mask_keys:
        kpos = jax.lax.broadcasted_iota(jnp.int32, (bg, bq, bk), 2)
        if per_row:
            lens = _group_lens(kvlen_ref, 0, bg)
            valid = kpos < lens
        else:
            valid = kpos < kvlen_ref[0]
        e_vals = jnp.where(valid, e_vals, 0.0)
        xmax_tile = jnp.where(valid, xc, LOGIT_FMT.code_min)
    S = jnp.sum(e_vals, axis=-1, keepdims=True)
    L = lane_lookup(log_lut_ref[...], _pot_encode_sum(S, e_min, octave_step))

    dmax = jnp.clip(jnp.max(xmax_tile, axis=-1, keepdims=True)
                    - (L << frac_shift),
                    LOGIT_FMT.code_min, LOGIT_FMT.code_max)
    c_row = lane_lookup(prob_lut_ref[...], dmax + 128)
    rpos = jax.lax.broadcasted_iota(jnp.int32, (bg, bq, 1), 1)
    gpos = jax.lax.broadcasted_iota(jnp.int32, (bg, bq, 1), 0)
    c_row = jnp.where((rpos < sq_real) & (gpos < g_real), c_row, 0)
    if per_row:  # zero-length groups: all-zero rows, no cmax pollution
        c_row = jnp.where(lens > 0, c_row, 0)
    cmax = jnp.maximum(jnp.max(c_row), ecmax_ref[0, 0])

    d = jnp.clip(xc - (L << frac_shift),
                 LOGIT_FMT.code_min, LOGIT_FMT.code_max)
    pc = lane_lookup(_requant_code_table(cmax, prob_lut_ref[...]), d + 128)
    if mask_keys:
        pc = jnp.where(valid, pc, 0)
    o_ref[...] = jax.lax.dot_general(
        pc.astype(jnp.int8), v_ref[...].astype(jnp.int8),
        (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.int32)
    cmax_out_ref[0, 0] = cmax


@functools.partial(
    jax.jit, static_argnames=("mode", "scale_by_sqrt_d", "causal",
                              "block_q", "block_k", "block_g", "interpret",
                              "page_size", "groups_per_slot"))
def acam_attention_codes(
    q_codes: jax.Array,   # (G, Sq, D) int8 — G folds batch x heads
    k_codes: jax.Array,   # (G, Sk, D) int8 — or the paged pool, see below
    v_codes: jax.Array,   # (G, Sk, D) int8
    logit_scale: jax.Array,          # () f32: s_q * s_k (div-add numerator)
    mask: Optional[jax.Array] = None,  # (G, Sq, Sk) bool; None => causal/full
    q_offset: jax.Array | int = 0,     # causal decode offset (cache index)
    kv_len: Optional[jax.Array] = None,  # () or (G,): valid key prefix(es)
    mode: str = "pot",
    scale_by_sqrt_d: Optional[int] = None,  # d to fold 1/sqrt(d); None = folded
    causal: bool = False,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    block_g: int = DEFAULT_BLOCK_G,
    interpret: Optional[bool] = None,
    block_table: Optional[jax.Array] = None,  # (n_slots, max_pages) int32
    page_size: Optional[int] = None,          # static: rows per pool page
    groups_per_slot: Optional[int] = None,    # static: grid groups per slot
    cmax_floor: Optional[jax.Array] = None,   # () int32: external PROB-max seed
) -> tuple[jax.Array, jax.Array]:
    """Fused Fig.-12 attention on int8 codes.

    Returns (out, cmax): out (G, Sq, D) int32 — the matmul-2 accumulator over
    re-quantized PROB codes — and cmax () int32, the tensor-wide max PROB
    code, from which the caller rebuilds the oracle's probability scale
    ``max(cmax/256, 1e-12)/127``. Never materializes an (Sq, Sk) array.

    ``kv_len`` (traced int32 scalar) marks only the first ``kv_len`` keys as
    existing — keys past it contribute nothing to the row sum, the global
    PROB max, or matmul-2, exactly as if k/v had been sliced to that length
    (the KV-cache decode path: a fixed-shape cache buffer, dynamic fill).
    A *(G,) vector* ``kv_len`` gives every grid group its own valid prefix
    (per-request serving decode): each group's keys past its own length do
    not exist for that group, zero-length groups output all-zero rows (and
    contribute nothing to the global PROB max), and block skipping clamps
    to the per-tile max length, so short requests in a mixed batch stop
    streaming at their own fill level. ``mode`` accepts every staged
    softmax config: "pot", "pot_fine", "uniform" (the Fig.-14 ablation's
    uniform exp quantization).

    **Paged k/v** (``block_table`` given): k/v are a page *pool* of shape
    ``(n_pages * groups_per_slot, page_size, D)`` — physical page ``p``
    stores the ``groups_per_slot`` group stripes of one logical page at
    rows ``[p*gps, (p+1)*gps)`` — and ``block_table[slot, j]`` names the
    physical page backing slot ``slot``'s logical page ``j``. The logical
    key extent is ``max_pages * page_size``; ``kv_len`` must be a (G,)
    per-group vector. The table rides as a third scalar-prefetch operand
    consumed only by the k/v index maps; physical page 0 is the
    conventional trash page dead/unmapped entries resolve to (its tiles
    are fetched but fully masked/skipped). Output is bit-identical to the
    contiguous layout holding the same logical contents — pages move the
    DMA source of each key tile, never its logical coordinates or the
    block visit order.

    ``cmax_floor`` (traced int32 scalar, default 0) seeds the global PROB
    max: the returned cmax and the requant scale use
    ``max(cmax_floor, local max)``. Since PROB codes are non-negative, 0 is
    the exact identity. Tensor-parallel shards use this to agree on the
    global scale: each shard runs a probe call, ``lax.pmax``es the local
    cmax over the mesh axis, and re-runs with the floor set to the global
    — every shard then requantizes with the same table and the sharded
    output is bit-identical to the unsharded call (integer max is
    order-free, so the floored local reduction equals the global one).
    """
    interpret = resolve_interpret(interpret)
    exp_val, log_lut, prob_lut, e_min, octave_step, frac_shift = \
        softmax_tables(mode)

    paged = block_table is not None
    G, Sq, D = q_codes.shape
    if paged:
        if page_size is None or groups_per_slot is None:
            raise ValueError("paged attention needs static page_size and "
                             "groups_per_slot alongside block_table")
        if kv_len is None or jnp.ndim(kv_len) != 1:
            raise ValueError("paged attention requires a per-group (G,) "
                             "kv_len vector")
        gps = groups_per_slot
        n_slots, max_pages = block_table.shape
        if G != n_slots * gps:
            raise ValueError(f"paged G={G} != n_slots*groups_per_slot = "
                             f"{n_slots}*{gps}")
        if k_codes.shape[1] != page_size or k_codes.shape[0] % gps:
            raise ValueError(f"paged k/v pool must be (n_pages*{gps}, "
                             f"{page_size}, D), got {k_codes.shape}")
        Sk = max_pages * page_size         # logical key extent
        # group tiles must never straddle a slot (all bg groups share one
        # block-table row), and key blocks must never straddle a page
        bg = max(d for d in range(1, min(block_g, gps) + 1) if gps % d == 0)
        bk = math.gcd(page_size, min(block_k, page_size))
    else:
        Sk = k_codes.shape[1]
        bg = min(block_g, G)
        bk = min(block_k, max(_LANES, Sk))
    bq = min(block_q, max(8, Sq))
    if not interpret:
        # a compiled tile keeps about a dozen (bg, bq, bk) 32-bit
        # temporaries live; cap each at 1 MiB to stay inside the 16 MiB
        # scoped-VMEM default. Only bq shrinks: rows are independent, so
        # the result is the same for every bq (bk sets the row-sum order)
        bq = min(bq, max(8, (_VMEM_TILE_ELEMS // (bg * bk)) // 8 * 8))
    pad_g, pad_q, pad_k = (-G) % bg, (-Sq) % bq, (-Sk) % bk
    # lane-align the head dim only when compiling for real hardware; in
    # interpret mode the padding would just double the MXU work
    pad_d = 0 if interpret else (-D) % _LANES
    pad3 = lambda a: jnp.pad(a, ((0, pad_g), (0, 0), (0, 0)))
    qp = pad3(jnp.pad(q_codes, ((0, 0), (0, pad_q), (0, pad_d))))
    if paged:  # pool rows are physical pages — only the head dim pads
        kp = jnp.pad(k_codes, ((0, 0), (0, 0), (0, pad_d)))
        vp = jnp.pad(v_codes, ((0, 0), (0, 0), (0, pad_d)))
    else:
        kp = pad3(jnp.pad(k_codes, ((0, 0), (0, pad_k), (0, pad_d))))
        vp = pad3(jnp.pad(v_codes, ((0, 0), (0, pad_k), (0, pad_d))))
    Gp, Sqp, Skp, Dp = G + pad_g, Sq + pad_q, Sk + pad_k, D + pad_d
    ng, nq, nk = Gp // bg, Sqp // bq, Skp // bk
    # whole problem fits a single VMEM tile (paged always streams: even one
    # key block needs the block-table indirection in its index map)
    one_tile = ng == nq == nk == 1 and not paged

    sqrt_d = float(np.sqrt(np.float32(scale_by_sqrt_d), dtype=np.float32)) \
        if scale_by_sqrt_d is not None else None
    logit_scale = jnp.asarray(logit_scale, jnp.float32)
    if sqrt_d is not None and (float(np.log2(sqrt_d)) % 1.0 == 0.0):
        # power-of-two scaling commutes with f32 rounding, so folding the
        # exact /sqrt(d) into the scalar is bit-identical to the oracle's
        # multiply-then-divide and saves a full-tile division per pass
        logit_scale = logit_scale / sqrt_d
        sqrt_d = None

    dyn_len = kv_len is not None
    per_row = dyn_len and jnp.ndim(kv_len) == 1
    if per_row:
        kvv = jnp.asarray(kv_len, jnp.int32)
        if kvv.shape[0] != G:
            raise ValueError(f"per-group kv_len must have one entry per "
                             f"group: got {kvv.shape} for G={G}")
        # padded groups carry length 0: no keys exist for them, their rows
        # are all-zero and they never contribute to the global PROB max
        kv_len_val = jnp.pad(jnp.minimum(kvv, Sk), (0, pad_g))
        # per group-tile fill frontier: the skip bound for each tile's key
        # stream (max over the bg groups riding the tile)
        kv_blockmax = jnp.max(kv_len_val.reshape(ng, bg), axis=1)
    else:
        kv_len_val = (jnp.minimum(jnp.asarray(kv_len, jnp.int32), Sk)
                      if dyn_len else jnp.asarray(Sk, jnp.int32)).reshape(1)
        kv_blockmax = jnp.broadcast_to(kv_len_val, (ng,))

    # When the decode grid streams multiple key blocks, kv_len rides as a
    # *scalar-prefetch* operand: it is available before each grid step, so
    # the k/v BlockSpec index maps can clamp fully-invalid key blocks to
    # the last valid block — the grid keeps a static shape, but blocks past
    # the fill level never DMA a fresh tile and their compute is gated off
    # in-kernel (`guard_live`). A second prefetched operand carries the
    # per-group-tile max lengths, so the clamp/skip bound is one scalar
    # load (``kvmax[g]``) for scalar and per-group fills alike. Static
    # grids (prefill, and single-tile decode, where there is no whole
    # block to skip) keep both as plain operands and pay none of the
    # prefetch machinery; the kernels see identical refs either way.
    use_prefetch = (dyn_len and nk > 1) or paged

    def _im(f):
        """Index map with the right arity: scalar-prefetch index maps
        receive the prefetched refs as trailing arguments (the paged grid
        prefetches a third operand, the block table)."""
        if use_prefetch:
            if paged:
                return lambda p, g, i, k, kvl, kvm, bt: f(p, g, i, k, kvl, kvm)
            return lambda p, g, i, k, kvl, kvm: f(p, g, i, k, kvl, kvm)
        return lambda p, g, i, k: f(p, g, i, k, None, None)

    # scalars live in SMEM, the only memory Mosaic loads scalars from
    spec_scalar = pl.BlockSpec(memory_space=pltpu.SMEM)
    spec_lut = pl.BlockSpec(TABLE_SHAPE,
                            _im(lambda p, g, i, k, kvl, kvm: (0, 0)))

    if paged:
        spb = page_size // bk  # key blocks per page

        def kv_index(p, g, i, k, kvl, kvm, bt):
            # same per-tile frontier clamp as the contiguous prefetch path,
            # then translate the logical key block through the slot's
            # block-table row: logical page kc//spb -> physical pool page,
            # whose bg-group stripe for this tile starts at row
            # page*gps + (g*bg) % gps (bg divides gps, so it is block-aligned)
            last_live = jnp.maximum((kvm[g] + bk - 1) // bk - 1, 0)
            kc = jnp.minimum(k, last_live)
            page = bt[(g * bg) // gps, kc // spb]
            return ((page * gps + (g * bg) % gps) // bg, kc % spb, 0)
    elif use_prefetch:
        def kv_index(p, g, i, k, kvl, kvm):
            last_live = jnp.maximum((kvm[g] + bk - 1) // bk - 1, 0)
            return (g, jnp.minimum(k, last_live), 0)
    else:
        kv_index = _im(lambda p, g, i, k, kvl, kvm: (g, k, 0))

    in_specs = [
        spec_scalar,                                                # logit scale
        spec_scalar,                                                # q offset
        spec_scalar,                                                # cmax floor
        pl.BlockSpec((bg, bq, Dp), _im(lambda p, g, i, k, kvl, kvm: (g, i, 0))),
        pl.BlockSpec((bg, bk, Dp), kv_index),                       # k
        pl.BlockSpec((bg, bk, Dp), kv_index),                       # v
    ]
    operands = [
        kv_len_val,    # leading: scalar-prefetch args / plain operands
        kv_blockmax,
    ]
    if paged:      # third prefetched scalar: the block table (index-map only)
        operands.append(jnp.asarray(block_table, jnp.int32))
    operands += [
        logit_scale.reshape(1, 1),
        jnp.asarray(q_offset, jnp.int32).reshape(1, 1),
        jnp.asarray(0 if cmax_floor is None else cmax_floor,
                    jnp.int32).reshape(1, 1),
        qp, kp, vp,
    ]
    if mask is not None:
        mp = pad3(jnp.pad(mask.astype(jnp.int8),
                          ((0, 0), (0, pad_q), (0, pad_k))))
        # key blocks become row blocks, (Gp, nk*Sqp, bk): a mask tile's
        # lane dim is then the whole array's, which Mosaic accepts for
        # any bk (a 64-row page gives 64-key blocks, not whole lane tiles)
        mp = mp.reshape(Gp, Sqp, nk, bk).transpose(0, 2, 1, 3) \
            .reshape(Gp, nk * Sqp, bk)
        in_specs.append(pl.BlockSpec(
            (bg, bq, bk),
            _im(lambda p, g, i, k, kvl, kvm: (g, k * nq + i, 0))))
        operands.append(mp)
    in_specs += [spec_lut, spec_lut, spec_lut]
    operands += [lane_table(exp_val),
                 lane_table(jnp.asarray(log_lut, jnp.int32)),
                 lane_table(jnp.asarray(prob_lut, jnp.int32))]

    if one_tile:  # single grid step, no scratch, no second key sweep
        kernel = functools.partial(
            _attn_kernel_single, bg=bg, bq=bq, bk=bk,
            g_real=G, sq_real=Sq, sk_real=Sk,
            sqrt_d=sqrt_d, e_min=e_min, octave_step=octave_step,
            frac_shift=frac_shift, causal=causal, has_mask=mask is not None,
            dyn_len=dyn_len, per_row=per_row)
        scratch = []
        grid = (1, 1, 1, 1)
    else:
        kernel = functools.partial(
            _attn_kernel, nq=nq, nk=nk, bg=bg, bq=bq, bk=bk,
            g_real=G, sq_real=Sq, sk_real=Sk,
            sqrt_d=sqrt_d, e_min=e_min, octave_step=octave_step,
            frac_shift=frac_shift, causal=causal, has_mask=mask is not None,
            dyn_len=dyn_len, per_row=per_row, skip_blocks=use_prefetch)
        scratch = [
            # streaming PoT row sums, rows on lanes: a (rows, 1) column
            # would pad every row to a 128-lane tile in VMEM
            pltpu.VMEM((ng * nq, bg, bq), jnp.float32),
            pltpu.VMEM((bg, bq, 1), jnp.int32),      # row logit max (pass A)
            pltpu.VMEM((bg, bq, Dp), jnp.int32),     # matmul-2 accumulator
            pltpu.SMEM((1, 1), jnp.int32),           # global PROB code max
        ]
        grid = (2, ng, nq, nk)

    out_shape = (jax.ShapeDtypeStruct((Gp, Sqp, Dp), jnp.int32),
                 jax.ShapeDtypeStruct((1, 1), jnp.int32))
    out_specs = (pl.BlockSpec((bg, bq, Dp),
                              _im(lambda p, g, i, k, kvl, kvm: (g, i, 0))),
                 spec_scalar)
    if use_prefetch:
        if paged:
            # the kernel bodies never read the block table (it exists for
            # the k/v index maps alone) — drop its ref before dispatch
            inner = kernel
            kernel = lambda kvl, kvm, bt, *rest: inner(kvl, kvm, *rest)
        call = pl.pallas_call(
            kernel, out_shape=out_shape,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3 if paged else 2, grid=grid,
                in_specs=in_specs, out_specs=out_specs,
                scratch_shapes=scratch),
            interpret=interpret)
    else:
        call = pl.pallas_call(
            kernel, out_shape=out_shape, grid=grid,
            in_specs=[spec_scalar, spec_scalar] + in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch, interpret=interpret)
    out, cmax = call(*operands)
    return out[:G, :Sq, :D], cmax[0, 0]


def acam_attention_decode_codes(
    q_codes: jax.Array,   # (G, 1, D) int8 — one new token per folded B x H
    k_codes: jax.Array,   # (G, Smax, D) int8 — fixed-shape KV cache buffer
    v_codes: jax.Array,   # (G, Smax, D) int8
    logit_scale: jax.Array,          # () f32: s_q * s_k
    kv_len: jax.Array,               # () int32 (>= 1) or (G,) per-group
    mask: Optional[jax.Array] = None,  # (G, 1, Smax) bool/int8, 0 => mask out
    mode: str = "pot",
    scale_by_sqrt_d: Optional[int] = None,
    block_k: int = DEFAULT_BLOCK_K,
    block_g: int = DEFAULT_BLOCK_G,
    interpret: Optional[bool] = None,
    block_table: Optional[jax.Array] = None,
    page_size: Optional[int] = None,
    groups_per_slot: Optional[int] = None,
    cmax_floor: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array]:
    """Decode-mode fused attention: Sq=1 queries against a KV cache.

    The same streaming pipeline as `acam_attention_codes`, specialized to the
    serving decode step: a single new query per (batch x head) group attends
    the first ``kv_len`` entries of a fixed-shape cache buffer. Keys past
    ``kv_len`` do not exist for the kernel — no exp weight, no PROB max
    contribution, no matmul-2 term — so (out, cmax) are exactly what
    `acam_attention_codes` returns on the sliced cache ``k[:, :kv_len]``,
    with no dynamic shapes anywhere: the grid keeps a static shape, but
    ``kv_len`` is scalar-prefetched, so fully-invalid key blocks are
    *skipped* (index maps clamp to the last valid block — no fresh tile
    fetch — and `guard_live` gates off their compute), while the partially
    valid boundary block is masked.

    No mask array or causal offset is needed for solo serving: decode
    causality is precisely "attend the valid prefix", which ``kv_len``
    already encodes. ``mask`` exists for *batched* serving with left-padded
    buckets: per-group key validity (pad slots masked to the LOGIT minimum,
    exactly like the staged oracle's additive mask) on top of the prefix
    rule.

    ``kv_len`` may also be a *(G,)* vector — one valid prefix per group
    (per-request serving: slot-level continuous batching hands every slot
    its own fill level). Each group then attends exactly its own prefix,
    zero-length groups are defined as all-zero output rows (a drained or
    never-filled slot), and the dead-block skip clamps per group tile, so
    a short request stops streaming where *its* cache ends, not at the
    batch max.

    With ``block_table``/``page_size``, k/v are the paged pool
    ``(n_pages * groups_per_slot, page_size, D)`` — ``groups_per_slot``
    defaults to G // n_slots (the flat layout folds every query head of a
    slot into its group stripe). See `acam_attention_codes` for the paged
    contract; decode is its hot consumer (slot-level continuous batching
    hands each slot a block-table row instead of a contiguous cache
    stripe).
    """
    if q_codes.shape[1] != 1:
        raise ValueError(f"decode path expects Sq=1, got {q_codes.shape[1]}")
    if block_table is not None and groups_per_slot is None:
        groups_per_slot = q_codes.shape[0] // block_table.shape[0]
    return acam_attention_codes(
        q_codes, k_codes, v_codes, logit_scale, mask, kv_len=kv_len,
        mode=mode, scale_by_sqrt_d=scale_by_sqrt_d,
        block_k=block_k, block_g=block_g, interpret=interpret,
        block_table=block_table, page_size=page_size,
        groups_per_slot=groups_per_slot, cmax_floor=cmax_floor)


def acam_attention_decode_gqa_codes(
    q_codes: jax.Array,   # (B*KV, rep, D) int8 — the rep queries of a group
    k_codes: jax.Array,   # (B*KV, Smax, D) int8 — native-layout cache buffer
    v_codes: jax.Array,   # (B*KV, Smax, D) int8
    logit_scale: jax.Array,          # () f32: s_q * s_k
    kv_len: jax.Array,               # () int32 (>= 1) or (B*KV,) per-group
    mask: Optional[jax.Array] = None,  # (B*KV, rep, Smax), 0 => mask out
    mode: str = "pot",
    scale_by_sqrt_d: Optional[int] = None,
    block_k: int = DEFAULT_BLOCK_K,
    block_g: int = DEFAULT_BLOCK_G,
    interpret: Optional[bool] = None,
    block_table: Optional[jax.Array] = None,
    page_size: Optional[int] = None,
    groups_per_slot: Optional[int] = None,
    cmax_floor: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array]:
    """GQA-native decode: k/v in their (B*KV, Smax, D) cache layout.

    The flat decode entry above folds batch x *query* heads into the group
    dimension, which forces GQA callers to `jnp.repeat` the KV cache codes
    to H groups first — rep x the cache bytes the grouped-query layout was
    designed to avoid. This entry keeps the cache native: the grid's group
    dimension iterates the B*KV *KV-head* groups, and the ``rep`` query
    heads that share each KV head ride the row (``bq``) dimension of the
    tile — the same slot the prefill grid uses for query positions. Decode
    queries all sit at the same position (causality == "attend the valid
    prefix", encoded by ``kv_len``), so rows are interchangeable and the
    kernel bodies, the scalar-prefetched ``kv_len`` skip machinery, and the
    global-cmax reduction apply unchanged.

    Per key block the tile now loads one k/v tile for ``bg`` *groups*
    instead of ``bg`` query heads: 1/rep of the grid steps and 1/rep of the
    KV bytes of the flat entry, with bit-identical (out, cmax) — same
    logits per (head, key), same per-row PoT sums in the same block order,
    same integer cmax reduction (order-free), same requant scale.

    A *(B*KV,)* vector ``kv_len`` gives every KV-head group its own valid
    prefix — all ``rep`` query rows riding a group's tile share that
    group's length, which is exactly the per-request semantics (a
    request's heads all see the same cache fill). See
    `acam_attention_decode_codes` for the per-row contract.

    With ``block_table``/``page_size``, k/v are the paged pool
    ``(n_pages * groups_per_slot, page_size, D)`` with
    ``groups_per_slot = KV`` (each pool page holds one logical page for
    every KV head of its slot); the pool's group-dim divisibility replaces
    the contiguous entry's shared-group-dim check.
    """
    if block_table is not None:
        if groups_per_slot is None:
            raise ValueError("GQA paged decode needs groups_per_slot (=KV)")
    elif k_codes.shape[0] != q_codes.shape[0]:
        raise ValueError(
            f"GQA decode expects q and k/v to share the group dim "
            f"(B*KV): got q {q_codes.shape} vs k {k_codes.shape}")
    return acam_attention_codes(
        q_codes, k_codes, v_codes, logit_scale, mask, kv_len=kv_len,
        mode=mode, scale_by_sqrt_d=scale_by_sqrt_d,
        block_k=block_k, block_g=block_g, interpret=interpret,
        block_table=block_table, page_size=page_size,
        groups_per_slot=groups_per_slot, cmax_floor=cmax_floor)
