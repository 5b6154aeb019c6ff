"""Pallas TPU kernel: bit-sliced crossbar MVM with Compute-ACAM ADCs.

The DPE lane (paper §II-A/IV-A) adapted to the TPU memory hierarchy:

* HBM -> VMEM tiling via BlockSpec: (bm x bk) x-tiles and (bk x bn) w-tiles,
  grid (M/bm, N/bn, K/bk) with an int32 VMEM accumulator revisited over k.
* Inside a tile, the ISAAC-style offset-encoded operands are spatially sliced
  (cell_bits-wide weight planes) and temporally sliced (dac_bits input
  pulses); every plane product is an int MXU matmul, digitized by the ADC
  transfer and consolidated with shift-&-add — bit-identical to the analog
  pipeline with an ideal converter.
* ``exact`` mode folds all planes and offset corrections into one
  int8xint8->int32 MXU matmul on the signed codes (the mathematically-equal
  fast path; Mosaic refuses int32 matmul operands, so every product here
  feeds int8); tests assert the sliced and exact paths agree and match the
  pure-jnp oracle (ref.py).

bk defaults to the crossbar height (128 rows) so one k-step == one crossbar.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.crossbar import CrossbarConfig

from .runtime import resolve_interpret


def _mvm_kernel(x_ref, w_ref, o_ref, acc_ref, *, cfg: CrossbarConfig,
                nsteps: int, k_real: int, bk: int):
    """One (bm x bk) @ (bk x bn) tile-product with bit-slicing + ADC."""
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ox = 1 << (cfg.input_bits - 1)
    ow = 1 << (cfg.weight_bits - 1)
    dac_mask = (1 << cfg.dac_bits) - 1
    cell_mask = (1 << cfg.cell_bits) - 1
    p_max = cfg.rows * cell_mask * dac_mask
    levels = (1 << cfg.adc_bits) - 1
    sliced = cfg.adc_mode == "quantize" and p_max > levels

    if sliced:
        xu = x_ref[...].astype(jnp.int32) + ox  # offset encoding (ISAAC)
        wu = w_ref[...].astype(jnp.int32) + ow
        # zero out the offset on padded K rows so they contribute nothing
        kpos = k_step * bk + jax.lax.broadcasted_iota(jnp.int32, xu.shape, 1)
        xu = jnp.where(kpos < k_real, xu, 0)
        kposw = k_step * bk + jax.lax.broadcasted_iota(jnp.int32, wu.shape, 0)
        wu = jnp.where(kposw < k_real, wu, 0)
        step = p_max / levels
        acc = jnp.zeros(o_ref.shape, jnp.int32)
        for t in range(cfg.num_input_slices):      # temporal input slices
            # planes are < 2^dac_bits / 2^cell_bits: int8 MXU operands
            x_t = ((xu >> (t * cfg.dac_bits)) & dac_mask).astype(jnp.int8)
            for s in range(cfg.num_weight_slices):  # spatial weight slices
                w_s = ((wu >> (s * cfg.cell_bits)) & cell_mask
                       ).astype(jnp.int8)
                p = jax.lax.dot(x_t, w_s, preferred_element_type=jnp.int32)
                q = jnp.round(jnp.round(p / step) * step).astype(jnp.int32)
                acc += q << (t * cfg.dac_bits + s * cfg.cell_bits)
        # digital offset corrections (ones-column row-sum / precomputed
        # col-sum)
        rowsum = xu.sum(axis=1, keepdims=True)
        colsum = wu.sum(axis=0, keepdims=True)
        acc_ref[...] += acc - ow * rowsum - ox * colsum
    else:
        # exact ADC: the shift-&-add over planes and the offset corrections
        # telescope to one int8 x int8 -> int32 MXU matmul on the signed
        # codes (zero-padded K rows contribute nothing)
        acc_ref[...] += jax.lax.dot(
            x_ref[...].astype(jnp.int8), w_ref[...].astype(jnp.int8),
            preferred_element_type=jnp.int32)

    @pl.when(k_step == nsteps - 1)
    def _finish():
        o_ref[...] = acc_ref[...] + (k_real * ox * ow if sliced else 0)


@functools.partial(jax.jit, static_argnames=("cfg", "bm", "bn", "bk", "interpret"))
def acam_mvm(x: jax.Array, w: jax.Array, cfg: CrossbarConfig = CrossbarConfig(),
             bm: int = 256, bn: int = 256, bk: int | None = None,
             interpret: bool | None = None) -> jax.Array:
    """Bit-sliced crossbar matmul: x (M, K) int8 codes, w (K, N) int8 codes
    -> (M, N) int32, equal to x @ w under an ideal ADC."""
    interpret = resolve_interpret(interpret)
    M, K = x.shape
    K2, N = w.shape
    assert K == K2
    bk = bk or cfg.rows
    bm = min(bm, max(8, M))
    bn = min(bn, max(128, N))
    pm, pn, pk = (-M) % bm, (-N) % bn, (-K) % bk
    xp = jnp.pad(x, ((0, pm), (0, pk)))
    wp = jnp.pad(w, ((0, pk), (0, pn)))
    Mp, Kp = xp.shape
    Np = wp.shape[1]
    nsteps = Kp // bk

    out = pl.pallas_call(
        functools.partial(_mvm_kernel, cfg=cfg, nsteps=nsteps, k_real=K, bk=bk),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.int32),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        grid=(Mp // bm, Np // bn, nsteps),
        interpret=interpret,
    )(xp, wp)
    return out[:M, :N]
