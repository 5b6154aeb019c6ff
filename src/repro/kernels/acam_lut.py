"""Pallas TPU kernel: Compute-ACAM 1-variable op as a vectorized 256-entry LUT.

Hardware adaptation: an ACAM array's OR-of-ranges per output bit is
provably equivalent to a 2^n-entry table, so the TPU-native form of an 8-bit
Compute-ACAM op is a 256-entry lookup over int8 codes. Mosaic lowers no
vector-indexed load (``lut_ref[x]``); what it lowers is a lane gather inside
one 128-lane vreg (`jnp.take_along_axis` on a 2-D tile). `lane_lookup` is
therefore the lookup every kernel of this package uses: the table rides as
two 128-lane rows, both are gathered with the code's low 7 bits, and bit 7
selects the row — a true gather, so values are bit-identical to indexing
the flat table, at a handful of VPU ops per element (a compare/select
chain over the table's steps would cost two ops per step: 37-185 steps for
the softmax tables).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .runtime import resolve_interpret

DEFAULT_BLOCK_ROWS = 256
BLOCK_COLS = 1024  # a power-of-two multiple of LANES
LANES = 128
TABLE_SHAPE = (2, LANES)  # a 256-entry table as two 128-lane rows


def lane_table(lut) -> jax.Array:
    """256-entry table -> the (2, 128) operand `lane_lookup` reads."""
    return jnp.asarray(lut).reshape(TABLE_SHAPE)


def lane_lookup(table: jax.Array, idx: jax.Array) -> jax.Array:
    """``table.reshape(-1)[idx]`` for a (2, 128) table, in a form Mosaic lowers.

    ``idx`` is int32 in [0, 256) of any shape with ndim >= 2; it is viewed
    as rows x lanes, its lanes padded up to whole 128-lane chunks, and each
    chunk gathers from the two table rows.
    """
    shape = idx.shape
    x = idx.reshape(-1, shape[-1])
    rows, width = x.shape
    pad = (-width) % LANES
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    lo = jnp.broadcast_to(table[0:1], (rows, LANES))
    hi = jnp.broadcast_to(table[1:2], (rows, LANES))
    chunks = []
    for c in range(0, width + pad, LANES):
        xc = x[:, c:c + LANES]
        low7 = xc & (LANES - 1)
        chunks.append(jnp.where(xc >= LANES,
                                jnp.take_along_axis(hi, low7, axis=1),
                                jnp.take_along_axis(lo, low7, axis=1)))
    out = chunks[0] if len(chunks) == 1 else jnp.concatenate(chunks, axis=1)
    return out[:, :width].reshape(shape)


def _lut_kernel(x_ref, lut_ref, o_ref, *, bias: int):
    x = x_ref[...].astype(jnp.int32) + bias  # codes -> unsigned positions
    o_ref[...] = lane_lookup(lut_ref[...], x).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bias", "block_rows", "interpret"))
def acam_lut_2d(x: jax.Array, lut: jax.Array, bias: int = 128,
                block_rows: int = DEFAULT_BLOCK_ROWS,
                interpret: bool | None = None) -> jax.Array:
    """Apply an ACAM LUT to a 2-D int tensor of shape (R, C).

    x: int8/int32 codes in [-2^(n-1), 2^(n-1)); lut: (256,) output codes.
    Rows are padded to the row block and columns to whole 128-lane chunks,
    and cropped after; a column block is the largest power-of-two multiple
    of 128 lanes, up to `BLOCK_COLS`, that divides the padded width, so
    a block stays a few MiB of VMEM however wide the rows are (an FFN
    hidden layer is 8192 wide).
    """
    interpret = resolve_interpret(interpret)
    R, C = x.shape
    br = min(block_rows, max(8, R))
    pad_r = (-R) % br
    pad_c = (-C) % LANES
    xp = jnp.pad(x, ((0, pad_r), (0, pad_c)))
    Rp, Cp = xp.shape
    bc = LANES * math.gcd(Cp // LANES, BLOCK_COLS // LANES)

    out = pl.pallas_call(
        functools.partial(_lut_kernel, bias=bias),
        out_shape=jax.ShapeDtypeStruct((Rp, Cp), jnp.int32),
        in_specs=[
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec(TABLE_SHAPE, lambda i, j: (0, 0)),  # table in VMEM
        ],
        out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        grid=(Rp // br, Cp // bc),
        interpret=interpret,
    )(xp, lane_table(lut.astype(jnp.int32)))
    return out[:R, :C]


def acam_lut(x: jax.Array, lut: jax.Array, bias: int = 128,
             interpret: bool | None = None) -> jax.Array:
    """N-D wrapper: flatten leading dims to rows."""
    shape = x.shape
    flat = x.reshape(-1, shape[-1]) if x.ndim >= 2 else x.reshape(1, -1)
    y = acam_lut_2d(flat, lut, bias=bias, interpret=interpret)
    return y.reshape(shape)
