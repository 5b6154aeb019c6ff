"""The ``raceit_*_tp`` backend family: tensor-parallel fused attention.

Multi-device serving resolves through the same `ExecPlan` machinery as
everything else: these backends register against the attention slots with
purely *structural* capability predicates (they read the declarative
`repro.dist.MeshSpec` on ``ExecConfig.mesh``, never device availability),
so plans resolve — and `plan_audit` exercises the catalog x mesh matrix —
on a one-device process, while actually *running* a resolved TP plan
materializes the concrete mesh via ``MeshSpec.build()``.

Sharding layout (the mesh ``"model"`` axis, ``ms`` shards):

  q heads     H  -> H/ms  contiguous chunks (q is kv-major: heads
                          ``[kvh*rep, (kvh+1)*rep)`` share KV head ``kvh``,
                          so an H-chunk boundary lands on a KV-group
                          boundary whenever ``KV % ms == 0`` — the
                          predicate's divisibility requirement)
  KV cache    KV -> KV/ms on the head axis of the contiguous buffer
                          (B, Smax, KV, hd) *and* of the paged pool
                          (n_pages, page_size, KV, hd); block tables,
                          kv_len vectors, and pad masks stay replicated
  output      H  -> H/ms  (the head axis again; the mixer's output
                          projection consumes it replicated)

Bitwise parity with the single-device chain is a two-collective protocol,
not an afterthought (tests/test_sharded_parity.py asserts it bit-for-bit):

1. quantizer scales are *globalized* — each shard computes its local
   ``max|x|`` and `jax.lax.pmax`-es it over the mesh axis before the
   shared scale formula (`repro.kernels.ops.tp_quantize_tensor` and
   friends); f32 max is order-free, so scales and codes match the
   unsharded quantizers bit-for-bit;
2. the kernel's grid-global PROB re-quantization max is globalized via
   the probe -> pmax -> exact flow (`repro.kernels.ops.tp_exact_call`):
   a probe call yields the shard-local cmax, pmax makes it global, and
   the exact call re-runs with ``cmax_floor`` seeded to the global so
   every shard re-quantizes with the same table the unsharded kernel
   would have used.

The probe call doubles the kernel work per shard; each shard holds
``1/ms`` of the heads, so the *total* work is ``2/ms`` of the
single-device call — a win for every real mesh (ms >= 2), and the
predicate refuses ms == 1 anyway (a 1-device mesh resolves to the same
single-device chain as ``mesh=None``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import shard_map
from repro.kernels import ops as kops

from .backends import (RACEIT_ATTENTION_MAX_KEYS, _fused_supported,
                       _mask_array, _prefill_digital)
from .registry import register

AXIS = "model"  # the mesh axis every TP backend shards over


def _tp_supported(model_cfg, exec_cfg):
    ms = getattr(exec_cfg.mesh, "model_size", 1)
    if ms <= 1:
        return ("no tensor-parallel mesh (ExecConfig.mesh has no 'model' "
                "axis of size > 1)")
    why = _fused_supported(model_cfg, exec_cfg)
    if why is not None:
        return why
    if model_cfg.n_kv_heads % ms:
        return (f"n_kv_heads={model_cfg.n_kv_heads} not divisible by the "
                f"mesh 'model' axis ({ms} shards) — KV-head chunks would "
                f"straddle shards")
    return None


def _gqa_tp_supported(model_cfg, exec_cfg):
    why = _tp_supported(model_cfg, exec_cfg)
    if why is not None:
        return why
    if model_cfg.n_kv_heads >= model_cfg.n_heads:
        return (f"n_kv_heads={model_cfg.n_kv_heads} == "
                f"n_heads={model_cfg.n_heads} (no KV-head sharing to "
                f"exploit; raceit_fused_tp is the same dataflow)")
    return None


def _shard(body, plan, operands, in_axes, out_axis):
    """Run ``body`` over the plan's mesh, operand i sharded on dim
    ``in_axes[i]`` of the "model" axis (None = fully replicated)."""
    mesh = plan.exec_cfg.mesh.build()
    specs = tuple(P() if ax is None else P(*([None] * ax + [AXIS]))
                  for ax in in_axes)
    out_spec = P(*([None] * out_axis + [AXIS]))
    return shard_map(body, mesh=mesh, in_specs=specs,
                     out_specs=out_spec)(*operands)


# ---------------------------------------------------------------------------
# attention_prefill
# ---------------------------------------------------------------------------

def _tp_fused_attention(q, k, v, mask, scale, plan, causal_offset=None):
    """`models.layers._raceit_fused_attention` sharded over heads.

    q (B, Sq, H, hd), k/v (B, Sk, KV, hd); ``mask`` (B, Sq, Sk) replicated
    (None with ``causal_offset`` takes the kernel's in-kernel causal mask,
    mirroring the single-device fast path).
    """
    mode = plan.exec_cfg.softmax_mode
    qs = q.astype(jnp.float32) * scale  # pre-fold outside the shard body

    def body(q, k, v, *rest):
        b, sq, h, hd = q.shape
        sk, kv = k.shape[1], k.shape[2]
        rep = h // kv
        qq = kops.tp_quantize_tensor(q, AXIS)
        kq = kops.tp_quantize_tensor(
            jnp.repeat(k.astype(jnp.float32), rep, axis=2), AXIS)
        vq = kops.tp_quantize_tensor(
            jnp.repeat(v.astype(jnp.float32), rep, axis=2), AXIS)
        mb = None
        if rest:
            mb = jnp.broadcast_to(rest[0][:, None],
                                  (b, h, sq, sk)).reshape(b * h, sq, sk)
        call = lambda floor: kops.acam_attention_codes(
            qq.codes.transpose(0, 2, 1, 3).reshape(b * h, sq, hd),
            kq.codes.transpose(0, 2, 1, 3).reshape(b * h, sk, hd),
            vq.codes.transpose(0, 2, 1, 3).reshape(b * h, sk, hd),
            qq.scale * kq.scale, mb,
            q_offset=causal_offset if causal_offset is not None else 0,
            causal=causal_offset is not None, mode=mode, cmax_floor=floor)
        out32, cmax = kops.tp_exact_call(call, AXIS)
        out = (out32.astype(jnp.float32)
               * (kops.prob_requant_scale(cmax) * vq.scale))
        return out.reshape(b, h, sq, hd).transpose(0, 2, 1, 3)

    operands = [qs, k, v] + ([] if mask is None else [mask])
    in_axes = [2, 2, 2] + ([] if mask is None else [None])
    return _shard(body, plan, operands, in_axes, out_axis=2)


@register("attention_prefill", "raceit_fused_tp", supported=_tp_supported,
          notes="tensor-parallel fused prefill: heads sharded over the mesh "
                "'model' axis, quantizer scales and the PROB requant max "
                "globalized (pmax) — bit-identical to raceit_fused; falls "
                f"back to the digital path beyond "
                f"Sk={RACEIT_ATTENTION_MAX_KEYS}")
def _prefill_raceit_fused_tp(plan, q, k, v, *, scale, q_offset, kind, window,
                             chunk, probs_dtype=None, pad_lens=None):
    sk = k.shape[1]
    if sk > RACEIT_ATTENTION_MAX_KEYS:
        return _prefill_digital(plan, q, k, v, scale=scale, q_offset=q_offset,
                                kind=kind, window=window, chunk=chunk,
                                probs_dtype=probs_dtype, pad_lens=pad_lens)
    if kind == "causal" and pad_lens is None:
        return _tp_fused_attention(q, k, v, None, scale, plan,
                                   causal_offset=q_offset)
    mask = _mask_array(kind, q.shape[0], q.shape[1], sk, q_offset, window,
                       pad_lens)
    return _tp_fused_attention(q, k, v, mask, scale, plan)


# ---------------------------------------------------------------------------
# attention_decode (contiguous and paged caches, flat and GQA-native grids)
# ---------------------------------------------------------------------------

def _tp_fused_decode(q, k, v, kv_len, scale, plan, pad_valid=None):
    """`models.layers._raceit_fused_decode` sharded over heads.

    q (B, Sq, H, hd), k/v (B, Smax, KV, hd); kv_len and pad_valid stay
    replicated — lengths are per *request*, and every shard serves every
    request (for a slice of its heads).
    """
    mode = plan.exec_cfg.softmax_mode
    qs = q.astype(jnp.float32) * scale
    kvl = jnp.asarray(kv_len, jnp.int32)

    def body(q, k, v, kvl, *rest):
        b, sq, h, hd = q.shape
        smax, kv = k.shape[1], k.shape[2]
        rep = h // kv
        qq = kops.tp_quantize_tensor(q, AXIS)
        with jax.named_scope("kv_pool"):
            k_codes, k_scale = kops.tp_masked_prefix_quantize(
                k.astype(jnp.float32), kvl, AXIS, axis=1)
            v_codes, v_scale = kops.tp_masked_prefix_quantize(
                v.astype(jnp.float32), kvl, AXIS, axis=1)
        fold = lambda c: jnp.repeat(c, rep, axis=2).transpose(
            0, 2, 1, 3).reshape(b * h, smax, hd)
        mask = None
        if rest:
            pv = rest[0][:, None, :] if rest[0].ndim == 2 else rest[0]
            mask = jnp.broadcast_to(pv[:, None],
                                    (b, h, sq, smax)).reshape(b * h, sq, smax)
        kvl_g = kops.expand_row_lens(kvl, h)
        qc = qq.codes.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
        if sq == 1:
            call = lambda floor: kops.acam_attention_decode_codes(
                qc, fold(k_codes), fold(v_codes), qq.scale * k_scale, kvl_g,
                mask=mask, mode=mode, cmax_floor=floor)
        else:  # the chunked-prefill step, same delegate as the flat backend
            call = lambda floor: kops.acam_attention_codes(
                qc, fold(k_codes), fold(v_codes), qq.scale * k_scale, mask,
                kv_len=kvl_g, mode=mode, cmax_floor=floor)
        out32, cmax = kops.tp_exact_call(call, AXIS)
        out = (out32.astype(jnp.float32)
               * (kops.prob_requant_scale(cmax) * v_scale))
        return out.reshape(b, h, sq, hd).transpose(0, 2, 1, 3)

    operands = [qs, k, v, kvl] + ([] if pad_valid is None else [pad_valid])
    in_axes = [2, 2, 2, None] + ([] if pad_valid is None else [None])
    return _shard(body, plan, operands, in_axes, out_axis=2)


def _tp_gqa_decode(q, k, v, kv_len, scale, plan, pad_valid=None):
    """`models.layers._raceit_gqa_decode` sharded over KV-head groups."""
    b, sq, h, hd = q.shape
    if sq > 1:  # chunk steps ride the flat grid, as on one device
        return _tp_fused_decode(q, k, v, kv_len, scale, plan,
                                pad_valid=pad_valid)
    mode = plan.exec_cfg.softmax_mode
    qs = q.astype(jnp.float32) * scale
    kvl = jnp.asarray(kv_len, jnp.int32)

    def body(q, k, v, kvl, *rest):
        b, sq, h, hd = q.shape
        smax, kv = k.shape[1], k.shape[2]
        rep = h // kv
        qq = kops.tp_quantize_tensor(q, AXIS)
        with jax.named_scope("kv_pool"):
            k_codes, k_scale = kops.tp_masked_prefix_quantize(
                k.astype(jnp.float32), kvl, AXIS, axis=1)
            v_codes, v_scale = kops.tp_masked_prefix_quantize(
                v.astype(jnp.float32), kvl, AXIS, axis=1)
        to_groups = lambda c: c.transpose(0, 2, 1, 3).reshape(b * kv, smax, hd)
        mask = None
        if rest:
            mask = jnp.broadcast_to(rest[0][:, None, None, :],
                                    (b, kv, rep, smax)).reshape(b * kv, rep,
                                                                smax)
        call = lambda floor: kops.acam_attention_decode_gqa_codes(
            qq.codes.reshape(b, h, hd).reshape(b, kv, rep, hd
                                               ).reshape(b * kv, rep, hd),
            to_groups(k_codes), to_groups(v_codes), qq.scale * k_scale,
            kops.expand_row_lens(kvl, kv), mask=mask, mode=mode,
            cmax_floor=floor)
        out32, cmax = kops.tp_exact_call(call, AXIS)
        return (out32.astype(jnp.float32)
                * (kops.prob_requant_scale(cmax) * v_scale)
                ).reshape(b, sq, h, hd)

    operands = [qs, k, v, kvl] + ([] if pad_valid is None else [pad_valid])
    in_axes = [2, 2, 2, None] + ([] if pad_valid is None else [None])
    return _shard(body, plan, operands, in_axes, out_axis=2)


def _tp_paged_decode(q, k_pool, v_pool, kv_len, scale, plan, pad_valid=None,
                     block_table=None, gqa=False):
    """`models.layers._raceit_paged_decode` sharded over the pool's KV axis.

    The page pool (n_pages, page_size, KV, hd) shards on its head axis;
    block tables and fill levels are replicated, so page routing — and the
    trash-page fence — is identical on every shard.
    """
    mode = plan.exec_cfg.softmax_mode
    b, sq, h, hd = q.shape
    qs = (q.astype(jnp.float32) * scale).transpose(0, 2, 1, 3)  # (B,H,Sq,hd)
    kvl = jnp.asarray(kv_len, jnp.int32)
    bt = jnp.asarray(block_table, jnp.int32)
    mask0 = pad_valid
    if mask0 is not None and mask0.ndim == 2:  # (B, Smax) -> (B, Sq, Smax)
        mask0 = mask0[:, None, :]

    def body(q, k_pool, v_pool, kvl, bt, *rest):
        b, h, sq, hd = q.shape
        n_pages, ps, kv, _ = k_pool.shape
        rep = h // kv
        qq = kops.tp_quantize_tensor(q, AXIS)
        with jax.named_scope("kv_pool"):
            pv = kops.page_valid_lengths(bt, kvl, n_pages, ps)
            k_codes, k_scale = kops.tp_masked_page_quantize(
                k_pool.astype(jnp.float32), pv, AXIS)
            v_codes, v_scale = kops.tp_masked_page_quantize(
                v_pool.astype(jnp.float32), pv, AXIS)
        sk = bt.shape[1] * ps
        if gqa:
            to_rows = lambda c: c.transpose(0, 2, 1, 3).reshape(
                n_pages * kv, ps, hd)
            mask = None
            if rest:
                mask = jnp.broadcast_to(rest[0][:, None],
                                        (b, kv, rep, sk)).reshape(b * kv,
                                                                  rep, sk)
            call = lambda floor: kops.acam_attention_decode_gqa_codes(
                qq.codes.reshape(b, kv, rep, hd).reshape(b * kv, rep, hd),
                to_rows(k_codes), to_rows(v_codes), qq.scale * k_scale,
                kops.expand_row_lens(kvl, kv), mask=mask, mode=mode,
                block_table=bt, page_size=ps, groups_per_slot=kv,
                cmax_floor=floor)
        else:
            to_rows = lambda c: jnp.repeat(c, rep, axis=2).transpose(
                0, 2, 1, 3).reshape(n_pages * h, ps, hd)
            mask = None
            if rest:
                mask = jnp.broadcast_to(rest[0][:, None],
                                        (b, h, sq, sk)).reshape(b * h, sq, sk)
            call = lambda floor: kops.acam_attention_codes(
                qq.codes.reshape(b * h, sq, hd), to_rows(k_codes),
                to_rows(v_codes), qq.scale * k_scale, mask,
                kv_len=kops.expand_row_lens(kvl, h), mode=mode,
                block_table=bt, page_size=ps, groups_per_slot=h,
                cmax_floor=floor)
        out32, cmax = kops.tp_exact_call(call, AXIS)
        return (out32.astype(jnp.float32)
                * (kops.prob_requant_scale(cmax) * v_scale)
                ).reshape(b, h, sq, hd)

    operands = [qs, k_pool, v_pool, kvl, bt] \
        + ([] if mask0 is None else [mask0])
    in_axes = [1, 2, 2, None, None] + ([] if mask0 is None else [None])
    out = _shard(body, plan, operands, in_axes, out_axis=1)
    return out.transpose(0, 2, 1, 3)  # (B, Sq, H, hd)


@register("attention_decode", "raceit_fused_tp", supported=_tp_supported,
          paged=True,
          notes="tensor-parallel fused decode: KV cache (contiguous or "
                "paged pool) sharded over heads on the mesh 'model' axis; "
                "probe->pmax->exact requant keeps it bit-identical to the "
                "single-device chain")
def _decode_raceit_fused_tp(plan, q, k, v, *, kv_len, scale, pad_valid=None,
                            block_table=None, page_size=None):
    if block_table is not None:
        return _tp_paged_decode(q, k, v, kv_len, scale, plan,
                                pad_valid=pad_valid, block_table=block_table,
                                gqa=False)
    return _tp_fused_decode(q, k, v, kv_len, scale, plan, pad_valid=pad_valid)


@register("attention_decode", "raceit_gqa_tp", supported=_gqa_tp_supported,
          paged=True,
          notes="tensor-parallel GQA-native decode: each shard's KV-head "
                "groups stream their own pool stripe — the multi-device "
                "serving default for grouped-query configs")
def _decode_raceit_gqa_tp(plan, q, k, v, *, kv_len, scale, pad_valid=None,
                          block_table=None, page_size=None):
    if block_table is not None:
        return _tp_paged_decode(q, k, v, kv_len, scale, plan,
                                pad_valid=pad_valid, block_table=block_table,
                                gqa=q.shape[1] == 1)
    return _tp_gqa_decode(q, k, v, kv_len, scale, plan, pad_valid=pad_valid)
