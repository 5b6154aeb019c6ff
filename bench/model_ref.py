"""Seeded weights and the plain float32 reference of a dense decoder-only LM.

The reference is written from the published architectures (GPT-2: learned
positions, affine LayerNorm, tanh-GELU MLP, biased q/k/v; OLMo: rotary
positions, non-parametric LayerNorm, SwiGLU MLP, no biases) in plain
``jax.numpy`` at float32 with ``Precision.HIGHEST`` on every matmul. It
imports nothing of the program under test.

Weights are made here, from the seed, in the nested layout the program
serves (one stacked entry per weight, leading axis = layer), so the same
tree feeds the program (after its own int8 quantization) and this
reference. The architecture dictionary is the ``reference`` block of a
configuration file under ``bench/configs``.

The control of the output check is this same reference with every weight
matmul computed at ``bits`` (symmetric, per-column weight scales and one
activation scale per sequence): the next precision below the int8 the
serving mode states.

This module is the default reference module. A configuration whose
``reference`` block names ``"module": "<name>"`` brings its own, the file
``bench/refs/<name>.py``, and the harness and the metric readers reach
the architecture only through these five functions of it:

- ``served_params(key, arch, quantize)``: the served tree on the device,
  ``quantize`` (the program's own int8 conversion, or the identity)
  applied to the weights made from ``key``.
- ``reference_weights(key, arch)``: what ``gap_readings`` takes as ``w``,
  made from the same ``key``: the same weights in float32, or anything
  from which the reference makes them (the key itself, layer by layer).
- ``gap_readings(w, tokens, targets, valid, arch, control_bits)``: below.
  Here ``arch`` is the block frozen into nested tuples (a dict becomes
  its sorted items), so that it can be a static argument.
- ``attn_cost(arch, kind, rows, kv_bytes)``: ``(ops, bytes)`` of the
  attention of all layers in one ``"decode"`` call (``rows``: live keys
  per row) or ``"chunk"`` call (``rows``: ``(offset, feed)`` per row).
- ``step_ops(arch, chunk_rows, decode_lens, counters)``: the model
  operations of one call; ``counters`` holds the window's program
  counters (each one's change over the window), for a model whose work
  depends on what the program chose, as a routed one's does.

Elsewhere ``arch`` is the block as loaded from JSON.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from . import roofline

HIGHEST = jax.lax.Precision.HIGHEST
LN_EPS = 1e-5  # GPT-2 layer_norm_epsilon, OLMo layer_norm eps


def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def make_weights(key, a: dict) -> dict:
    """The whole f32 tree from one key; call under ``jax.jit``."""
    d, L, H, hd, f, V = (a["d_model"], a["n_layers"], a["n_heads"],
                         a["head_dim"], a["d_ff"], a["vocab_size"])
    ks = iter(jax.random.split(key, 24))
    affine = a["norm"] == "layernorm"

    def norm():
        if not affine:
            return {}
        return {"scale": 1.0 + _normal(next(ks), (L, d), 0.02),
                "bias": _normal(next(ks), (L, d), 0.02)}

    attn = {"wq": _normal(next(ks), (L, d, H, hd), d ** -0.5),
            "wk": _normal(next(ks), (L, d, H, hd), d ** -0.5),
            "wv": _normal(next(ks), (L, d, H, hd), d ** -0.5),
            "wo": _normal(next(ks), (L, H, hd, d), (H * hd) ** -0.5)}
    if a["qkv_bias"]:
        for name in ("bq", "bk", "bv"):
            attn[name] = _normal(next(ks), (L, H, hd), 0.02)
    ffn = {"w1": _normal(next(ks), (L, d, f), d ** -0.5),
           "w2": _normal(next(ks), (L, f, d), f ** -0.5)}
    if a["mlp"] == "swiglu":
        ffn["w3"] = _normal(next(ks), (L, d, f), d ** -0.5)
    layer = {"norm1": norm(), "attn": attn, "norm2": norm(), "ffn": ffn}
    embed = {"tok_emb": _normal(next(ks), (V, d), 0.02)}
    if a["positions"] == "learned":
        embed["pos_emb"] = _normal(next(ks), (a["max_positions"], d), 0.02)
    final = ({"scale": 1.0 + _normal(next(ks), (d,), 0.02),
              "bias": _normal(next(ks), (d,), 0.02)} if affine else {})
    return {"embed": embed, "final_norm": final,
            "blocks": {"scan": [layer], "tail": []}}


def served_params(key, arch: dict, quantize):
    """The served tree, made and quantized in one jitted call."""
    return jax.jit(lambda k: quantize(make_weights(k, arch)))(key)


def reference_weights(key, arch: dict) -> dict:
    """The whole f32 tree, made anew from the key."""
    return jax.jit(lambda k: make_weights(k, arch))(key)


def attn_cost(arch: dict, kind: str, rows, kv_bytes: int) -> tuple[int, int]:
    """``n_layers`` times one layer's attention cost (`bench/roofline.py`)."""
    h, hd = arch["n_heads"], arch["head_dim"]
    kv, L = arch.get("n_kv_heads", h), arch["n_layers"]
    if kind == "decode":
        ops, nbytes = roofline.attn_decode_cost(rows, h, kv, hd, kv_bytes)
    else:
        ops, nbytes = roofline.attn_chunk_cost(
            [o for o, _ in rows], [f for _, f in rows], h, kv, hd, kv_bytes)
    return L * ops, L * nbytes


def step_ops(arch: dict, chunk_rows, decode_lens, counters: dict) -> int:
    """A dense model's work does not depend on ``counters``."""
    return roofline.step_ops(arch, chunk_rows, decode_lens)


def _layernorm(x, p):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    y = (x - mu) / jnp.sqrt(var + LN_EPS)
    if p:
        y = y * p["scale"] + p["bias"]
    return y


def _fake_quant(x, bits, axis):
    """Symmetric round-to-nearest at ``bits``; scales reduce over ``axis``."""
    qmax = 2 ** (bits - 1) - 1
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-12) / qmax
    return jnp.clip(jnp.round(x / s), -qmax - 1, qmax) * s


def _mm(x, w, bits):
    """x (S, K) @ w (K, N); at ``bits``, both operands are quantized."""
    if bits is not None:
        x = _fake_quant(x, bits, axis=None)
        w = _fake_quant(w, bits, axis=0)
    return jnp.dot(x, w, precision=HIGHEST)


def _rope(x, pos, theta):
    """Rotary embedding, half-split (GPT-NeoX / OLMo) convention."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv            # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]  # (S, 1, hd/2)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(w: dict, tokens, a: dict, bits=None):
    """Logits (S, V) of one sequence ``tokens`` (S,), causal."""
    d, H, hd = a["d_model"], a["n_heads"], a["head_dim"]
    S = tokens.shape[0]
    pos = jnp.arange(S)
    x = w["embed"]["tok_emb"][tokens]
    if a["positions"] == "learned":
        x = x + w["embed"]["pos_emb"][pos]
    causal = pos[None, :] <= pos[:, None]

    def layer(x, p):
        h = _layernorm(x, p["norm1"])
        at = p["attn"]
        proj = lambda name: _mm(h, at[name].reshape(d, H * hd), bits) \
            .reshape(S, H, hd)
        q, k, v = proj("wq"), proj("wk"), proj("wv")
        if a["qkv_bias"]:
            q, k, v = q + at["bq"], k + at["bk"], v + at["bv"]
        if a["positions"] == "rope":
            q, k = _rope(q, pos, a["rope_theta"]), _rope(k, pos, a["rope_theta"])
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / math.sqrt(hd)
        s = jnp.where(causal[None], s, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                       precision=HIGHEST)
        x = x + _mm(o.reshape(S, H * hd), at["wo"].reshape(H * hd, d), bits)
        h = _layernorm(x, p["norm2"])
        fp = p["ffn"]
        if a["mlp"] == "swiglu":
            m = jax.nn.silu(_mm(h, fp["w1"], bits)) * _mm(h, fp["w3"], bits)
        else:
            m = jax.nn.gelu(_mm(h, fp["w1"], bits), approximate=True)
        return x + _mm(m, fp["w2"], bits), None

    x, _ = jax.lax.scan(layer, x, w["blocks"]["scan"][0])
    x = _layernorm(x, w["final_norm"])
    return jnp.dot(x, w["embed"]["tok_emb"].T, precision=HIGHEST)


@partial(jax.jit, static_argnames=("arch", "control_bits"))
def gap_readings(w, tokens, targets, valid, arch, control_bits=None):
    """Per position of one sequence: how far the served token's reference
    logit lies below the reference's best (``gap``), and, with
    ``control_bits``, how far the token that the lower-precision
    reference puts first lies below it (``control_gap``).

    ``tokens`` (S,) is the prompt followed by the served tokens, padded at
    the end; ``targets`` (S,) the served token at each position that
    predicts one; ``valid`` (S,) marks those positions. Padding comes
    after every valid position, so causality keeps it out of them.
    """
    a = dict(arch)
    logits = forward(w, tokens, a)
    best = jnp.max(logits, axis=-1)
    served = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    gap = jnp.where(valid, best - served, 0.0)
    if control_bits is None:
        return gap, None
    low = forward(w, tokens, a, bits=control_bits)
    pick = jnp.argmax(low, axis=-1)
    picked = jnp.take_along_axis(logits, pick[:, None], axis=-1)[:, 0]
    return gap, jnp.where(valid, best - picked, 0.0)
