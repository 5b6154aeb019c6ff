"""Built-in backend registrations for every RaceOp slot.

Each backend is a thin adapter: the math lives with its owner
(`repro.models.layers` for the attention formulations and quantized
matmuls, `repro.core` for the staged numerics, `repro.kernels` for the
fused Pallas paths); this module binds those implementations to named
registry entries with capability predicates, so `resolve_plan` can pick
between them and `plan.explain()` can name what is running and why.

Naming convention: ``digital`` is the bf16/f32 baseline; ``raceit_*``
backends are the paper's analog-faithful paths (``raceit_staged`` = the
stage-by-stage XLA pipeline, ``raceit_fused`` = the streaming Pallas
kernel, ``raceit_int`` = exact-ADC int8 crossbar matmul, ``raceit_lut`` =
Compute-ACAM LUT activations by XLA's gather, ``raceit_lut_kernel`` = the
same table by the Pallas lane-gather kernel, ``raceit_acam`` = the Fig. 8
softmax dataflow). The resident-`QuantizedWeight` form is handled inside the
matmul/lm_head backends (it is a property of the *weight*, not the
config), always with the plan's ``act_bits`` — never a reconstructed
default ExecConfig.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import ops as acam_ops
from repro.core.attention import dd_matmul_codes, fused_attention_supported
from repro.core.ops import LOGIT_FMT
from repro.core.quant import quantize_tensor
from repro.core.softmax import acam_softmax
from repro.kernels.ops import acam_activation
from repro.models import layers
from repro.models.layers import NEG_INF, QuantizedWeight

from .registry import register

# the staged/fused raceit attention formulations materialize (or stream)
# O(Sq*Sk) work per head; past this key length the model stack has always
# degraded to the chunked float path (a runtime shape rule, so it lives in
# the backend impls, not the config-level capability predicate)
RACEIT_ATTENTION_MAX_KEYS = 4096
_SEQ_NOTE = (f"falls back to the digital path beyond "
             f"Sk={RACEIT_ATTENTION_MAX_KEYS}")


def _fused_supported(model_cfg, exec_cfg):
    if exec_cfg.noise is not None:
        # the streaming Pallas kernels model ideal devices; device-noise
        # injection rides the staged raceit_noisy_* path, so a fused
        # request under an active NoiseConfig degrades with this reason
        # recorded on the plan (and the one-time warning)
        return ("device-noise injection active (ExecConfig.noise); fused "
                "kernels model ideal devices — noise rides the staged "
                "raceit_noisy_* path")
    return fused_attention_supported(fidelity=exec_cfg.matmul_fidelity,
                                     softmax_mode=exec_cfg.softmax_mode)


# ---------------------------------------------------------------------------
# matmul (weight matmuls: QKV / FFN / SSM projections — the crossbar DPE lane)
# ---------------------------------------------------------------------------

def _resident_matmul(plan, x, w: QuantizedWeight, bias):
    """Resident int8 crossbar weight: codes + per-column scale.

    Activation quantization uses the *plan's* ``act_bits`` — this is the
    path that previously rebuilt a bare ``ExecConfig(mode="raceit")`` in
    the lm head and silently dropped the caller's bit-width knobs.
    """
    k = w.codes.shape[0]
    xq = quantize_tensor(x.astype(jnp.float32), bits=plan.exec_cfg.act_bits)
    y32 = jax.lax.dot(xq.codes.reshape(-1, k).astype(jnp.int32),
                      w.codes.astype(jnp.int32),
                      preferred_element_type=jnp.int32)
    y = y32.astype(jnp.float32) * (xq.scale * w.scale)
    y = y.reshape(*x.shape[:-1], *w.shape).astype(x.dtype)
    if bias is not None:
        y = y + bias.reshape(w.shape).astype(y.dtype)
    return y


@register("matmul", "digital")
def _matmul_digital(plan, x, w, bias):
    if isinstance(w, QuantizedWeight):
        return _resident_matmul(plan, x, w, bias)
    k = w.shape[0]
    w2 = w.reshape(k, -1)
    # preferred f32 materializes f32 outputs (and f32 TP collectives); the
    # MXU accumulates in f32 internally either way, so the default keeps
    # the boundary in compute dtype and halves collective bytes.
    pref = (jnp.float32 if plan.model_cfg.matmul_out_dtype == "f32"
            else x.dtype)
    y = jax.lax.dot_general(
        x, w2.astype(x.dtype), (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=pref).astype(x.dtype)
    y = y.reshape(*x.shape[:-1], *w.shape[1:])
    if bias is not None:
        y = y + bias.reshape(w.shape[1:]).astype(y.dtype)
    return y


@register("matmul", "raceit_int")
def _matmul_raceit_int(plan, x, w, bias):
    """Exact-ADC int8 crossbar matmul (equivalence proven vs core.crossbar)."""
    if isinstance(w, QuantizedWeight):
        return _resident_matmul(plan, x, w, bias)
    ec = plan.exec_cfg
    k = w.shape[0]
    w2 = w.reshape(k, -1)
    xq = quantize_tensor(x.astype(jnp.float32), bits=ec.act_bits)
    wq = quantize_tensor(w2.astype(jnp.float32), bits=ec.weight_bits, axis=1)
    y32 = jax.lax.dot(xq.codes.reshape(-1, k).astype(jnp.int32),
                      wq.codes.astype(jnp.int32),
                      preferred_element_type=jnp.int32)
    y = y32.astype(jnp.float32) * (xq.scale * wq.scale)
    y = y.reshape(*x.shape[:-1], *w.shape[1:]).astype(x.dtype)
    if bias is not None:
        y = y + bias.reshape(w.shape[1:]).astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# activation (FFN nonlinearity)
# ---------------------------------------------------------------------------

@register("activation", "digital")
def _activation_digital(plan, x, name=None):
    name = name or plan.model_cfg.activation
    if name == "gelu":
        return jax.nn.gelu(x)
    return jax.nn.silu(x)


def _lut_name(plan, name):
    """The ACAM table an activation name runs (unlisted names map to gelu)."""
    name = name or plan.model_cfg.activation
    return name if name in ("gelu", "silu") else "gelu"


@register("activation", "raceit_lut")
def _activation_raceit_lut(plan, x, name=None):
    """Compute-ACAM LUT activation, the table applied by XLA's gather."""
    op = acam_ops.get_op(_lut_name(plan, name))
    return op(x.astype(jnp.float32)).astype(x.dtype)


def _lut_kernel_supported(model_cfg, exec_cfg):
    if exec_cfg.noise is not None:
        return ("device-noise injection active (ExecConfig.noise); the LUT "
                "kernel applies the ideal table — noise rides "
                "raceit_noisy_lut")
    n_devices = getattr(exec_cfg.mesh, "n_devices", 1)
    if n_devices > 1:
        return (f"mesh of {n_devices} devices; the SPMD partitioner does not "
                f"split a pallas_call, so the sharded hidden activation "
                f"would be gathered whole — XLA's table lookup partitions")
    return None


@register("activation", "raceit_lut_kernel", supported=_lut_kernel_supported,
          notes="the same table through the Pallas lane-gather kernel")
def _activation_raceit_lut_kernel(plan, x, name=None):
    """Compute-ACAM LUT activation, bit-identical to ``raceit_lut``: one
    Pallas kernel (`kernels.acam_lut.lane_lookup`) applies the table,
    where XLA's gather would fetch one element at a time."""
    y = acam_activation(x.astype(jnp.float32), _lut_name(plan, name))
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# softmax (standalone rows: the MoE router, the staged decode scores)
# ---------------------------------------------------------------------------

@register("softmax", "digital")
def _softmax_digital(plan, logits, axis):
    return jax.nn.softmax(logits, axis=axis)


@register("softmax", "raceit_acam")
def _softmax_raceit_acam(plan, logits, axis):
    return acam_softmax(logits, axis=axis, mode=plan.exec_cfg.softmax_mode)


# ---------------------------------------------------------------------------
# dd_matmul (data-dependent matmuls on int8 codes: q.K^T, probs.V)
# ---------------------------------------------------------------------------

@register("dd_matmul", "int")
def _dd_matmul_int(plan, a_codes, b_codes):
    return dd_matmul_codes(a_codes, b_codes, fidelity="int")


@register("dd_matmul", "acam",
          notes="4-bit nibble-table multiplies; bit-identical to 'int', slow")
def _dd_matmul_acam(plan, a_codes, b_codes):
    return dd_matmul_codes(a_codes, b_codes, fidelity="acam")


# ---------------------------------------------------------------------------
# attention_prefill (full / prefill attention)
# ---------------------------------------------------------------------------
# Interface: impl(plan, q, k, v, *, scale, q_offset, kind, window, chunk,
#                 probs_dtype, pad_lens)
#   q (B, Sq, H, hd) flat heads; k/v (B, Sk, KV, hd); kind in
#   ("cross", "bidir", "local", "causal"); pad_lens (B,) int32 marks each
#   row's left-pad key prefix (batched-serving buckets) — those keys are
#   masked on top of the structural mask.
#
# The rule for ModelConfig-derived knobs: anything a sub-stack may *replace*
# (mask kind, window, probs dtype, activation name) is computed by the call
# site from ITS cfg and passed in — encoder sub-stacks run with a replaced
# ModelConfig the plan was not resolved against. ``plan.model_cfg`` is only
# read for knobs that are constant across sub-stacks by construction
# (matmul_out_dtype) and as a fallback when the call site passes None.

def _mask_fn(kind: str, sk: int, q_offset, window: int):
    if kind == "cross":
        return lambda qi, ki: jnp.ones((), bool)  # full cross attention
    if kind == "bidir":
        return lambda qi, ki: ki < sk + 0 * qi    # bidirectional
    if kind == "local":
        return lambda qi, ki: ((ki <= qi + q_offset)
                               & (ki > qi + q_offset - window))
    return lambda qi, ki: ki <= qi + q_offset     # causal


def _mask_array(kind, b, sq, sk, q_offset, window, pad_lens=None):
    msk = _mask_fn(kind, sk, q_offset, window)(
        jnp.arange(sq)[:, None], jnp.arange(sk)[None, :])
    msk = jnp.broadcast_to(msk, (b, sq, sk))
    if pad_lens is not None:  # left-pad keys do not exist for their row
        msk = msk & (jnp.arange(sk)[None, None, :] >= pad_lens[:, None, None])
    return msk


@register("attention_prefill", "digital")
def _prefill_digital(plan, q, k, v, *, scale, q_offset, kind, window, chunk,
                     probs_dtype=None, pad_lens=None):
    if probs_dtype is None:
        probs_dtype = layers._probs_dtype(plan.model_cfg)
    sq, sk = q.shape[1], k.shape[1]
    if (kind == "local" and sq == sk and sq % window == 0 and sq > window
            and pad_lens is None):
        # sliding-window layers, train & single-shot prefill: q-blocked
        # 2W-key attention instead of the masked-full path (the blocked
        # form has no per-row mask slot, so padded buckets take the
        # chunked path below)
        return layers._local_block_attention(q, k, v, window, scale,
                                             probs_dtype)
    mask_fn = _mask_fn(kind, sk, q_offset, window)
    return layers._chunked_attention(q, k, v, mask_fn, min(chunk, sk), scale,
                                     probs_dtype, pad_lens=pad_lens)


@register("attention_prefill", "raceit_staged", notes=_SEQ_NOTE)
def _prefill_raceit_staged(plan, q, k, v, *, scale, q_offset, kind, window,
                           chunk, probs_dtype=None, pad_lens=None):
    sk = k.shape[1]
    if sk > RACEIT_ATTENTION_MAX_KEYS:
        return _prefill_digital(plan, q, k, v, scale=scale, q_offset=q_offset,
                                kind=kind, window=window, chunk=chunk,
                                probs_dtype=probs_dtype, pad_lens=pad_lens)
    mask = _mask_array(kind, q.shape[0], q.shape[1], sk, q_offset, window,
                       pad_lens)
    return layers._raceit_staged_attention(q, k, v, mask, scale, plan)


@register("attention_prefill", "raceit_fused", supported=_fused_supported,
          notes=_SEQ_NOTE)
def _prefill_raceit_fused(plan, q, k, v, *, scale, q_offset, kind, window,
                          chunk, probs_dtype=None, pad_lens=None):
    sk = k.shape[1]
    if sk > RACEIT_ATTENTION_MAX_KEYS:
        return _prefill_digital(plan, q, k, v, scale=scale, q_offset=q_offset,
                                kind=kind, window=window, chunk=chunk,
                                probs_dtype=probs_dtype, pad_lens=pad_lens)
    if kind == "causal" and pad_lens is None:
        # plain causal: the kernel masks from block indices, so not even a
        # mask of score shape is ever built (padded buckets need the
        # per-row mask array)
        return layers._raceit_fused_attention(q, k, v, None, scale, plan,
                                              causal_offset=q_offset)
    mask = _mask_array(kind, q.shape[0], q.shape[1], sk, q_offset, window,
                       pad_lens)
    return layers._raceit_fused_attention(q, k, v, mask, scale, plan)


# ---------------------------------------------------------------------------
# attention_decode (Sq=1 against the KV cache's valid prefix)
# ---------------------------------------------------------------------------
# Interface: impl(plan, q, k, v, *, kv_len, scale, pad_valid) -> (B, 1, H, hd)
#   q (B, 1, H, hd) flat heads; k/v (B, Smax, KV, hd) fixed-shape buffers;
#   kv_len is a () scalar (one shared fill level) or a (B,) vector of
#   per-request fill levels (slot-level continuous batching; 0 = empty
#   slot, a dead row); pad_valid (B, Smax) bool restricts each row's
#   attendable slots inside the valid prefix (left-padded batch buckets),
#   None = all attendable.

def _decode_scores(q, k, kv_heads, scale):
    """Float decode scores in grouped-query layout: (B, KV, G, 1, Smax)."""
    qg = layers._split_gqa(q, kv_heads)  # (B, 1, KV, G, hd)
    return jnp.einsum("bqkgd,bckd->bkgqc", qg.astype(jnp.float32) * scale,
                      k.astype(jnp.float32))


def _decode_combine(pr, v):
    o = jnp.einsum("bkgqc,bckd->bkgqd", pr, v.astype(jnp.float32))
    b, kv, g, sq, hd = o.shape
    return o.transpose(0, 3, 1, 2, 4).reshape(b, sq, kv * g, hd)


def _decode_valid(k, kv_len, pad_valid):
    """Key-validity mask for the float decode paths: (B, Smax), or
    (B, Sq, Smax) when ``pad_valid`` carries a per-query mask (the chunked
    prefill step's intra-chunk causality).

    ``kv_len`` may be a scalar or a (B,) per-row vector — the float paths
    are per-row-native either way (the mask is already per row).
    """
    valid = (jnp.arange(k.shape[1])[None, :]
             < jnp.reshape(jnp.asarray(kv_len), (-1, 1)))
    if pad_valid is not None:
        valid = (valid[:, None, :] & pad_valid if pad_valid.ndim == 3
                 else valid & pad_valid)
    return valid


def _decode_mask_scores(s, valid, sentinel):
    """Apply a `_decode_valid` mask to grouped scores (B, KV, G, Sq, Smax)."""
    vm = (valid[:, None, None, None] if valid.ndim == 2
          else valid[:, None, None])  # (B, Sq, Smax) -> (B, 1, 1, Sq, Smax)
    return jnp.where(vm, s, sentinel)


def _flatten_row_lens(k, kv_len, pad_valid):
    """Degrade a per-row kv_len vector to the shared-max-fill contract.

    The flat fused kernels take one scalar fill level; a per-row vector is
    served by decoding every row to the batch max and masking each row's
    tail via the pad mask — correct attention, but every row streams to
    the shared frontier (the pre-rows occupancy behavior) and, unlike the
    per-row kernels, stale cache entries inside [row_len, max_len) still
    sit inside the quantizer-scale reduction window (they are *masked*,
    not nonexistent). The ``*_rows`` backends exist to remove both; this
    path keeps scalar-kv_len callers and explicit flat-backend pins
    working when a per-row vector shows up.
    """
    if jnp.ndim(kv_len) == 0:
        return kv_len, pad_valid
    valid = jnp.arange(k.shape[1])[None, :] < kv_len[:, None]
    if pad_valid is not None and pad_valid.ndim == 3:  # per-query chunk mask
        return jnp.max(kv_len), valid[:, None, :] & pad_valid
    return jnp.max(kv_len), (valid if pad_valid is None
                             else valid & pad_valid)


@register("attention_decode", "digital")
def _decode_digital(plan, q, k, v, *, kv_len, scale, pad_valid=None):
    s = _decode_scores(q, k, k.shape[2], scale)
    valid = _decode_valid(k, kv_len, pad_valid)
    s = _decode_mask_scores(s, valid, NEG_INF)
    return _decode_combine(jax.nn.softmax(s, axis=-1), v)


@register("attention_decode", "raceit_staged",
          notes="float scores + ACAM softmax (the pre-PR2 serving decode)")
def _decode_raceit_staged(plan, q, k, v, *, kv_len, scale, pad_valid=None):
    s = _decode_scores(q, k, k.shape[2], scale)
    valid = _decode_valid(k, kv_len, pad_valid)
    s = _decode_mask_scores(s, valid, LOGIT_FMT.min_value)
    pr = acam_softmax(s, axis=-1, mode=plan.exec_cfg.softmax_mode)
    return _decode_combine(pr, v)


@register("attention_decode", "raceit_fused", supported=_fused_supported,
          notes="per-row kv_len vectors degrade to the shared max fill")
def _decode_raceit_fused(plan, q, k, v, *, kv_len, scale, pad_valid=None):
    # full quantized Fig.-12 numerics over the cache's valid prefix — same
    # contract as the fused prefill path
    kv_len, pad_valid = _flatten_row_lens(k, kv_len, pad_valid)
    return layers._raceit_fused_decode(q, k, v, kv_len, scale, plan,
                                       pad_valid=pad_valid)


def _gqa_native_supported(model_cfg, exec_cfg):
    why = _fused_supported(model_cfg, exec_cfg)
    if why is not None:
        return why
    if model_cfg.n_kv_heads >= model_cfg.n_heads:
        return (f"n_kv_heads={model_cfg.n_kv_heads} == "
                f"n_heads={model_cfg.n_heads} (no KV-head sharing to "
                f"exploit; the flat fused kernel is the same dataflow)")
    return None


@register("attention_decode", "raceit_gqa_native",
          supported=_gqa_native_supported,
          notes="native (B*KV) cache layout; the rep queries sharing a KV "
                "head ride one tile — no cache-code repeat in the hot loop")
def _decode_raceit_gqa(plan, q, k, v, *, kv_len, scale, pad_valid=None):
    # bit-identical to raceit_fused, at 1/rep of the KV-cache reads: the
    # cache codes are never repeated to H (see layers._raceit_gqa_decode)
    kv_len, pad_valid = _flatten_row_lens(k, kv_len, pad_valid)
    return layers._raceit_gqa_decode(q, k, v, kv_len, scale, plan,
                                     pad_valid=pad_valid)


@register("attention_decode", "raceit_fused_rows", supported=_fused_supported,
          notes="per-row kv_len: every batch row decodes at its own cache "
                "fill level (continuous batching); scalar kv_len callers "
                "are served unchanged")
def _decode_raceit_fused_rows(plan, q, k, v, *, kv_len, scale,
                              pad_valid=None):
    # the per-row serving decode: a (B,) kv_len vector reaches the kernel
    # as per-group valid prefixes — per-row masks, per-row dead-block
    # skipping, stale tails outside every quantizer-scale window, empty
    # rows (kv_len 0) defined as zeros. A scalar kv_len is the flat path.
    return layers._raceit_fused_decode(q, k, v, kv_len, scale, plan,
                                       pad_valid=pad_valid)


@register("attention_decode", "raceit_gqa_rows",
          supported=_gqa_native_supported,
          notes="per-row kv_len on the GQA-native cache layout — the "
                "serving default for grouped-query configs")
def _decode_raceit_gqa_rows(plan, q, k, v, *, kv_len, scale, pad_valid=None):
    # per-row lengths + the GQA-native dataflow: each KV-head group's tile
    # streams to its own request's fill frontier and is fetched once for
    # the rep sharing queries (see layers._raceit_gqa_decode)
    return layers._raceit_gqa_decode(q, k, v, kv_len, scale, plan,
                                     pad_valid=pad_valid)


@register("attention_decode", "raceit_fused_paged",
          supported=_fused_supported, paged=True,
          notes="block-paged KV pool (block_table/page_size); contiguous "
                "callers are served on the per-row flat kernel unchanged")
def _decode_raceit_fused_paged(plan, q, k, v, *, kv_len, scale,
                               pad_valid=None, block_table=None,
                               page_size=None):
    # the paged serving decode: k/v are the (n_pages, page_size, KV, hd)
    # page pool, block_table (B, max_pages) names each row's pages (0 = the
    # trash page), and the per-page quantizer reduces each page's scale over
    # the union of its live entries — bit-identical to raceit_fused_rows on
    # the gathered contiguous layout (tests/test_attention_paged.py)
    if block_table is None:
        return layers._raceit_fused_decode(q, k, v, kv_len, scale, plan,
                                           pad_valid=pad_valid)
    return layers._raceit_paged_decode(q, k, v, kv_len, scale, plan,
                                       pad_valid=pad_valid,
                                       block_table=block_table, gqa=False)


@register("attention_decode", "raceit_gqa_paged",
          supported=_gqa_native_supported, paged=True,
          notes="block-paged KV pool on the GQA-native layout — the paged "
                "serving default for grouped-query configs")
def _decode_raceit_gqa_paged(plan, q, k, v, *, kv_len, scale,
                             pad_valid=None, block_table=None,
                             page_size=None):
    if block_table is None:
        return layers._raceit_gqa_decode(q, k, v, kv_len, scale, plan,
                                         pad_valid=pad_valid)
    # chunked-prefill steps (Sq > 1) put rep x Sq queries on the GQA
    # grid's rows, so the pool is not repeated to H for them either
    return layers._raceit_paged_decode(q, k, v, kv_len, scale, plan,
                                       pad_valid=pad_valid,
                                       block_table=block_table, gqa=True)


# ---------------------------------------------------------------------------
# lm_head (the unembedding projection)
# ---------------------------------------------------------------------------

@register("lm_head", "digital",
          notes="resident int8 weights take the quantized path with the "
                "plan's act_bits")
def _lm_head_digital(plan, x, w):
    if isinstance(w, QuantizedWeight):  # resident int8 unembedding
        return _resident_matmul(plan, x, w, None).astype(jnp.float32)
    return jnp.einsum("bsd,dv->bsv", x.astype(jnp.float32),
                      w.astype(jnp.float32))


@register("lm_head", "raceit_q8",
          notes="fully-quantized lm head (beyond-paper; default stays "
                "full-precision)")
def _lm_head_raceit_q8(plan, x, w):
    if isinstance(w, QuantizedWeight):
        return _resident_matmul(plan, x, w, None).astype(jnp.float32)
    return _matmul_raceit_int(plan, x, w, None).astype(jnp.float32)


# the raceit_noisy_* family registers itself against the same slots; it
# lives in its own module but is part of the built-in registry surface,
# and its impls reuse the staged helpers above — importing it here (after
# every helper is defined) keeps `_ensure_backends_loaded` the single
# load point.
from . import noisy  # noqa: E402,F401

# likewise the tensor-parallel raceit_*_tp family (mesh-sharded attention):
# same slots, same registry surface, own module.
from . import sharded  # noqa: E402,F401
