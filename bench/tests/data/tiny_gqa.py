"""Reference module of the tiny GQA configuration (``tiny-gqa.json``): a
decoder with grouped-query attention, RMSNorm, rotary positions, SwiGLU
and an unembedding of its own, which the dense default module does not
describe. The tests copy it in as ``bench/refs/tiny_gqa.py``; it keeps
the contract stated in `bench/model_ref`, at float32 with
``Precision.HIGHEST``, and imports nothing of the program under test.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from bench import roofline

HIGHEST = jax.lax.Precision.HIGHEST


def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def _make(key, a: dict) -> dict:
    d, L, H, kv, hd, f, V = (a["d_model"], a["n_layers"], a["n_heads"],
                             a["n_kv_heads"], a["head_dim"], a["d_ff"],
                             a["vocab_size"])
    ks = iter(jax.random.split(key, 12))
    scale = lambda shape: 1.0 + _normal(next(ks), shape, 0.02)
    layer = {"norm1": {"scale": scale((L, d))},
             "attn": {"wq": _normal(next(ks), (L, d, H, hd), d ** -0.5),
                      "wk": _normal(next(ks), (L, d, kv, hd), d ** -0.5),
                      "wv": _normal(next(ks), (L, d, kv, hd), d ** -0.5),
                      "wo": _normal(next(ks), (L, H, hd, d),
                                    (H * hd) ** -0.5)},
             "norm2": {"scale": scale((L, d))},
             "ffn": {"w1": _normal(next(ks), (L, d, f), d ** -0.5),
                     "w3": _normal(next(ks), (L, d, f), d ** -0.5),
                     "w2": _normal(next(ks), (L, f, d), f ** -0.5)}}
    embed = {"tok_emb": _normal(next(ks), (V, d), 0.02),
             "unembed": _normal(next(ks), (d, V), d ** -0.5)}
    return {"embed": embed, "final_norm": {"scale": scale((d,))},
            "blocks": {"scan": [layer], "tail": []}}


def served_params(key, arch: dict, quantize):
    return jax.jit(lambda k: quantize(_make(k, arch)))(key)


def reference_weights(key, arch: dict) -> dict:
    return jax.jit(lambda k: _make(k, arch))(key)


def attn_cost(arch: dict, kind: str, rows, kv_bytes: int):
    a, L = (arch["n_heads"], arch["n_kv_heads"], arch["head_dim"],
            kv_bytes), arch["n_layers"]
    if kind == "decode":
        ops, nbytes = roofline.attn_decode_cost(rows, *a)
    else:
        ops, nbytes = roofline.attn_chunk_cost(
            [o for o, _ in rows], [f for _, f in rows], *a)
    return L * ops, L * nbytes


def step_ops(arch: dict, chunk_rows, decode_lens, counters: dict) -> int:
    """Each row: its fed tokens through every weight, its causal key
    pairs through attention, and logits at one position."""
    d, L, H, kv, hd, f, V = (arch["d_model"], arch["n_layers"],
                             arch["n_heads"], arch["n_kv_heads"],
                             arch["head_dim"], arch["d_ff"],
                             arch["vocab_size"])
    per_tok = 2 * (d * (H + 2 * kv) * hd + H * hd * d + 3 * d * f)
    rows = ([(o, n) for o, n in chunk_rows if n > 0]
            + [(n - 1, 1) for n in decode_lens if n > 0])
    return sum(L * (n * per_tok + 4 * (n * o + n * (n + 1) // 2) * H * hd)
               + 2 * d * V for o, n in rows)


def _rmsnorm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _fake_quant(x, bits, axis):
    qmax = 2 ** (bits - 1) - 1
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-12) / qmax
    return jnp.clip(jnp.round(x / s), -qmax - 1, qmax) * s


def _mm(x, w, bits):
    if bits is not None:
        x = _fake_quant(x, bits, axis=None)
        w = _fake_quant(w, bits, axis=0)
    return jnp.dot(x, w, precision=HIGHEST)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(w: dict, tokens, a: dict, bits=None):
    """Logits (S, V) of one sequence, causal; query head h reads key and
    value head h // (n_heads / n_kv_heads)."""
    d, H, kv, hd = a["d_model"], a["n_heads"], a["n_kv_heads"], a["head_dim"]
    eps = dict(a["norm"])["eps"]
    S = tokens.shape[0]
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]
    x = w["embed"]["tok_emb"][tokens]

    def layer(x, p):
        h = _rmsnorm(x, p["norm1"]["scale"], eps)
        at = p["attn"]
        proj = lambda name, n: _mm(h, at[name].reshape(d, n * hd),
                                   bits).reshape(S, n, hd)
        q = _rope(proj("wq", H), pos, a["rope_theta"])
        k = _rope(proj("wk", kv), pos, a["rope_theta"])
        v = proj("wv", kv)
        k, v = jnp.repeat(k, H // kv, axis=1), jnp.repeat(v, H // kv, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / math.sqrt(hd)
        s = jnp.where(causal[None], s, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                       precision=HIGHEST)
        x = x + _mm(o.reshape(S, H * hd), at["wo"].reshape(H * hd, d), bits)
        h = _rmsnorm(x, p["norm2"]["scale"], eps)
        fp = p["ffn"]
        m = jax.nn.silu(_mm(h, fp["w1"], bits)) * _mm(h, fp["w3"], bits)
        return x + _mm(m, fp["w2"], bits), None

    x, _ = jax.lax.scan(layer, x, w["blocks"]["scan"][0])
    x = _rmsnorm(x, w["final_norm"]["scale"], eps)
    return _mm(x, w["embed"]["unembed"], bits)


@partial(jax.jit, static_argnames=("arch", "control_bits"))
def gap_readings(w, tokens, targets, valid, arch, control_bits=None):
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
