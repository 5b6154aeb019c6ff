"""Pallas TPU kernel: the fused Compute-ACAM Softmax dataflow (paper Fig. 8).

One VMEM pass per row-block executes all five stages —
exp-LUT (PoT) -> adder-lane row sum -> log-LUT -> subtract -> exp-LUT —
so the intermediate exponent codes never touch HBM (the XLA baseline spills
them; see EXPERIMENTS.md §Perf). Tables are compiled by core.compiler and
passed in as int32 (2, 128) operands resident in VMEM and read with the
lane gather of `acam_lut.lane_lookup`.

Inputs are LOGIT_FMT (1-4-3) codes; output is PROB_FMT (0-0-8) codes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import ops as acam_ops
from repro.core.ops import LOGIT_FMT

from .acam_lut import TABLE_SHAPE, lane_lookup, lane_table
from .runtime import resolve_interpret

LANES = 128


def _softmax_kernel(x_ref, exp_lut_ref, log_lut_ref, prob_lut_ref, o_ref, *,
                    e_min: float, octave_step: float, frac_shift: int,
                    valid_cols: int):
    xc = x_ref[...].astype(jnp.int32)  # LOGIT codes, two's complement
    cols = jax.lax.broadcasted_iota(jnp.int32, xc.shape, 1)
    valid = cols < valid_cols

    # step 1: e = EXP(x) as PoT codes (bias to unsigned position first)
    e_codes = lane_lookup(exp_lut_ref[...], xc + 128)
    # adder lane works on decoded PoT values (code 0 == exactly 0)
    e_vals = jnp.where(e_codes == 0, 0.0,
                       jnp.exp2((e_codes - 1).astype(jnp.float32) * octave_step
                                + e_min))
    e_vals = jnp.where(valid, e_vals, 0.0)
    # step 2: S = sum (padded cols contribute zero)
    S = jnp.sum(e_vals, axis=-1, keepdims=True)
    # step 3: L = LOG(S); PoT-encode S to index the log table
    safe = jnp.maximum(S, 2.0 ** (e_min - 1))
    s_codes = jnp.clip(jnp.round((jnp.log2(safe) - e_min) / octave_step),
                       0, 254).astype(jnp.int32) + 1
    s_codes = jnp.where(S < 2.0 ** (e_min - octave_step / 2), 0, s_codes)
    L = lane_lookup(log_lut_ref[...], s_codes)  # LOG_OUT (1-5-2) codes
    # step 4: d = x - L in the logit grid (adder lane subtract)
    d = jnp.clip(xc - (L << frac_shift), -128, 127)
    # step 5: p = EXP(d) -> PROB codes
    o_ref[...] = lane_lookup(prob_lut_ref[...], d + 128).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("mode", "block_rows", "interpret"))
def acam_softmax_codes(x_codes: jax.Array, mode: str = "pot",
                       block_rows: int = 128,
                       interpret: bool | None = None) -> jax.Array:
    """x_codes: (R, L) int LOGIT_FMT codes -> (R, L) PROB_FMT codes (int32).

    Masked positions must already be LOGIT_FMT.code_min (the div-add stage
    writes the mask before softmax, paper Fig. 12).
    """
    interpret = resolve_interpret(interpret)
    exp_op = acam_ops.get_op("exp_pot" if mode == "pot" else "exp_pot_fine")
    log_op = acam_ops.get_op("log" if mode == "pot" else "log_fine")
    prob_op = acam_ops.get_op("exp_prob")
    pot = exp_op.out_fmt
    frac_shift = LOGIT_FMT.frac_bits - log_op.out_fmt.frac_bits

    R, L = x_codes.shape
    br = min(block_rows, max(8, R))
    pad_r = (-R) % br
    pad_c = (-L) % LANES
    xp = jnp.pad(x_codes, ((0, pad_r), (0, pad_c)),
                 constant_values=LOGIT_FMT.code_min)
    Rp, Lp = xp.shape

    out = pl.pallas_call(
        functools.partial(_softmax_kernel, e_min=float(pot.e_min),
                          octave_step=float(pot.octave_step),
                          frac_shift=frac_shift, valid_cols=L),
        out_shape=jax.ShapeDtypeStruct((Rp, Lp), jnp.int32),
        in_specs=[
            pl.BlockSpec((br, Lp), lambda i: (i, 0)),
            pl.BlockSpec(TABLE_SHAPE, lambda i: (0, 0)),
            pl.BlockSpec(TABLE_SHAPE, lambda i: (0, 0)),
            pl.BlockSpec(TABLE_SHAPE, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, Lp), lambda i: (i, 0)),
        grid=(Rp // br,),
        interpret=interpret,
    )(xp, *(lane_table(jnp.asarray(op._lut, jnp.int32))
            for op in (exp_op, log_op, prob_op)))
    return out[:R, :L]


def acam_softmax_kernel(x: jax.Array, mode: str = "pot",
                        interpret: bool | None = None) -> jax.Array:
    """Float logits -> float probs through the fused kernel (N-D wrapper)."""
    prob_op = acam_ops.get_op("exp_prob")
    shape = x.shape
    codes = LOGIT_FMT.encode(x).reshape(-1, shape[-1])
    p = acam_softmax_codes(codes, mode=mode, interpret=interpret)
    return prob_op.out_fmt.decode(p).reshape(shape)
