"""Reference module of Moonlight-16B-A3B (``bench/configs/moonlight-16b-a3b
.json``, ``model_type`` deepseek_v3) as one chip's share of an
expert-parallel deployment: the published architecture in plain
``jax.numpy`` at float32 with ``Precision.HIGHEST``, importing nothing of
the program under test, keeping the contract stated in `bench/model_ref`.

The architecture, from the configuration's ``reference`` block:

- RMSNorm (``norm_eps``) before each sub-layer and at the end; an untied
  unembedding.
- Latent attention, without a query low-rank path, in its published
  (non-absorbed) form: ``q = h @ wq`` per head ``nope + rope`` wide; ``[c,
  k_pe] = h @ wkv_a``; ``c`` is RMS-normalized (``latent_norm_eps``);
  head h's key is ``[c @ w_uk[h].T, rope(k_pe)]`` (the RoPE key shared by
  all heads) and its value ``c @ w_uv[h]``; full causal softmax attention
  at scale ``(nope + rope) ** -0.5``; ``wo`` over the heads. RoPE rotates
  the two halves of the rope dims (``rope_theta``).
- ``first_k_dense`` leading layers with a SwiGLU of ``d_ff``, then expert
  layers: sigmoid router scores over all ``n_experts``; the ``top_k`` of
  scores plus the correction bias ``router_bias`` are chosen, gated by
  their unbiased scores normalized to sum 1, times
  ``routed_scaling_factor``; only the experts ``experts_held`` (this
  chip's share) add their SwiGLU of ``moe_d_ff``, gate-weighted, and a
  shared SwiGLU of ``n_shared_experts * moe_d_ff`` adds on every row.

Weights are random from the seed, one layer at a time: layer ``i`` from
``fold_in(key, i)``, the embedding, unembedding and final norm from
``fold_in(key, n_layers)``, so the served tree (made and quantized a layer
at a time) and this reference (which makes each layer inside its scan)
never hold the f32 model whole. ``gap_readings`` takes the key itself.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
LANES = 128  # the kernels' lane width: a cache row is read padded to it


def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def _swiglu_weights(ks, d, f, lead=()):
    return {"w1": _normal(next(ks), lead + (d, f), d ** -0.5),
            "w3": _normal(next(ks), lead + (d, f), d ** -0.5),
            "w2": _normal(next(ks), lead + (f, d), f ** -0.5)}


def make_layer(key, a: dict, i, dense: bool) -> dict:
    """Layer ``i``'s f32 weights, in the tree the program serves: a dense
    SwiGLU or the expert layer after its attention."""
    d, H, r = a["d_model"], a["n_heads"], a["kv_lora_rank"]
    dn, dr, dv = a["qk_nope_head_dim"], a["qk_rope_head_dim"], a["v_head_dim"]
    ks = iter(jax.random.split(jax.random.fold_in(key, i), 20))
    scale = lambda n: 1.0 + _normal(next(ks), (n,), 0.02)
    layer = {
        "norm1": {"scale": scale(d)},
        "mla": {"wq": _normal(next(ks), (d, H, dn + dr), d ** -0.5),
                "wkv_a": _normal(next(ks), (d, r + dr), d ** -0.5),
                "kv_norm": {"scale": scale(r)},
                "w_uk": _normal(next(ks), (H, dn, r), r ** -0.5),
                "w_uv": _normal(next(ks), (H, r, dv), r ** -0.5),
                "wo": _normal(next(ks), (H, dv, d), (H * dv) ** -0.5)},
        "norm2": {"scale": scale(d)},
    }
    if dense:
        layer["ffn"] = _swiglu_weights(ks, d, a["d_ff"])
        return layer
    E, f = a["n_experts"], a["moe_d_ff"]
    moe = {"router": _normal(next(ks), (d, E), d ** -0.5),
           "router_bias": _normal(next(ks), (E,), a["router_bias_std"])}
    moe.update(_swiglu_weights(ks, d, f, (len(a["experts_held"]),)))
    moe["shared"] = _swiglu_weights(ks, d, a["n_shared_experts"] * f)
    layer["moe"] = moe
    return layer


def make_outer(key, a: dict) -> dict:
    """Embedding, unembedding and final norm."""
    d, V = a["d_model"], a["vocab_size"]
    ks = jax.random.split(jax.random.fold_in(key, a["n_layers"]), 3)
    return {"embed": {"tok_emb": _normal(ks[0], (V, d), 0.02),
                      "unembed": _normal(ks[1], (d, V), d ** -0.5)},
            "final_norm": {"scale": 1.0 + _normal(ks[2], (d,), 0.02)}}


def served_params(key, arch: dict, quantize):
    """The served tree, a layer at a time: each expert layer is made,
    quantized and written into its slot of the preallocated stack."""
    lead = arch["first_k_dense"]
    n_scan = arch["n_layers"] - lead
    one = lambda k, i, dense=False: quantize(make_layer(k, arch, i, dense))
    shapes = jax.eval_shape(one, key, lead)
    # the configuration serves int8 experts: refuse a quantizer that makes
    # attention int8 but leaves the expert stacks float
    int8 = jnp.dtype(jnp.int8)
    dtypes = lambda part: {x.dtype for x in jax.tree.leaves(shapes[part])}
    if int8 in dtypes("mla") and int8 not in dtypes("moe"):
        raise ValueError("the program's quantizer leaves the expert stacks "
                         "in float; this configuration serves them int8")
    stack = jax.tree.map(
        lambda s: jnp.zeros((n_scan,) + s.shape, s.dtype), shapes)

    @partial(jax.jit, donate_argnums=(0,))
    def put(stack, k, i):
        return jax.tree.map(
            lambda s, x: jax.lax.dynamic_update_index_in_dim(s, x, i - lead,
                                                             0),
            stack, one(k, i))

    for i in range(lead, arch["n_layers"]):
        stack = put(stack, key, i)
    tree = jax.jit(lambda k: quantize(make_outer(k, arch)))(key)
    tree["blocks"] = {
        "lead": [jax.jit(partial(one, dense=True))(key, i)
                 for i in range(lead)],
        "scan": [stack], "tail": []}
    return tree


def reference_weights(key, arch: dict):
    """The key: `gap_readings` makes each layer from it in its scan."""
    return key


def _row_width(a: dict) -> tuple[int, int]:
    """(used, read) lanes of one latent cache row: latent + RoPE key, and
    that padded to the lane width as the kernel reads it."""
    w = a["kv_lora_rank"] + a["qk_rope_head_dim"]
    return w, -(-w // LANES) * LANES


def attn_cost(arch: dict, kind: str, rows, kv_bytes: int):
    """(ops, bytes) of all layers' absorbed latent attention in one call.
    Every head scores each live latent row (latent + RoPE key wide) and
    sums the rows' latents. The kernel takes the pool as its key and its
    value operand both, so each row is read twice, at its padded width
    (the padding lanes are bytes moved, not useful operations); queries
    in and the latent outputs out in f32."""
    a, L, H = arch, arch["n_layers"], arch["n_heads"]
    w, w_read = _row_width(a)
    per_pair = 2 * H * (w + a["kv_lora_rank"])   # scores and the weighted sum
    io = 4 * H * (w + a["kv_lora_rank"])
    ops = nbytes = 0
    if kind == "decode":
        for n in rows:
            if n > 0:
                ops += n * per_pair
                nbytes += 2 * n * w_read * kv_bytes + io
    else:
        for off, f in rows:
            if f > 0:
                ops += (f * off + f * (f + 1) // 2) * per_pair
                nbytes += 2 * (off + f) * w_read * kv_bytes + f * io
    return L * ops, L * nbytes


def step_ops(arch: dict, chunk_rows, decode_lens, counters: dict) -> int:
    """One call's model operations, useful rows only: every row through
    attention's projections and its causal key pairs, the dense or shared
    SwiGLU and the router of each layer, logits at one position, and its
    share of the window's routed expert work: ``counters["expert_rows"]``
    (row, choice) pairs computed by this chip's experts, over the rows the
    window's calls fed (``decode_tokens`` + ``chunk_fed_tokens``)."""
    a = arch
    d, H, r, L = a["d_model"], a["n_heads"], a["kv_lora_rank"], a["n_layers"]
    dn, dr, dv = a["qk_nope_head_dim"], a["qk_rope_head_dim"], a["v_head_dim"]
    lead = a["first_k_dense"]
    attn = d * H * (dn + dr) + d * (r + dr) + H * dn * r + H * r * dv \
        + H * dv * d
    shared = 3 * d * a["n_shared_experts"] * a["moe_d_ff"]
    per_tok = 2 * (L * attn + lead * 3 * d * a["d_ff"]
                   + (L - lead) * (shared + d * a["n_experts"]))
    fed = counters["decode_tokens"] + counters["chunk_fed_tokens"]
    expert = (2 * 3 * d * a["moe_d_ff"] * counters["expert_rows"] / fed
              if fed else 0.0)
    per_pair = 2 * H * (r + dr + r)
    head = 2 * d * a["vocab_size"]
    rows = ([(o, n) for o, n in chunk_rows if n > 0]
            + [(n - 1, 1) for n in decode_lens if n > 0])
    return int(sum(n * (per_tok + expert) + head
                   + L * (n * o + n * (n + 1) // 2) * per_pair
                   for o, n in rows))


def _rmsnorm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _fake_quant(x, bits, axis):
    qmax = 2 ** (bits - 1) - 1
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-12) / qmax
    return jnp.clip(jnp.round(x / s), -qmax - 1, qmax) * s


def _mm(x, w, bits):
    """x (S, K) @ w (K, N); at ``bits``, both operands are quantized (one
    activation scale, a scale per weight column)."""
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


def _swiglu(h, w, bits):
    return _mm(jax.nn.silu(_mm(h, w["w1"], bits)) * _mm(h, w["w3"], bits),
               w["w2"], bits)


def _attention(h, p, pos, a, bits):
    S = h.shape[0]
    d, H, r = a["d_model"], a["n_heads"], a["kv_lora_rank"]
    dn, dr, dv = a["qk_nope_head_dim"], a["qk_rope_head_dim"], a["v_head_dim"]
    q = _mm(h, p["wq"].reshape(d, -1), bits).reshape(S, H, dn + dr)
    kv = _mm(h, p["wkv_a"], bits)
    c = _rmsnorm(kv[:, :r], p["kv_norm"]["scale"], a["latent_norm_eps"])
    k_pe = _rope(kv[:, None, r:], pos, a["rope_theta"])
    per_head = jax.vmap(lambda w: _mm(c, w, bits), out_axes=1)
    k_nope = per_head(p["w_uk"].transpose(0, 2, 1))          # (S, H, dn)
    v = per_head(p["w_uv"])                                   # (S, H, dv)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos,
                                            a["rope_theta"])], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (S, H, dr))], -1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
        / math.sqrt(dn + dr)
    s = jnp.where(pos[None, None, :] <= pos[None, :, None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                   precision=HIGHEST)
    return _mm(o.reshape(S, H * dv), p["wo"].reshape(H * dv, d), bits)


def _experts(h, p, a, bits):
    """This chip's experts' part of the routed result, plus the shared
    expert."""
    logits = jnp.dot(h, p["router"], precision=HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, top = jax.lax.top_k(scores + p["router_bias"], a["top_k"])
    gate = jnp.take_along_axis(scores, top, axis=-1)
    gate = gate / (gate.sum(-1, keepdims=True) + 1e-20) \
        * a["routed_scaling_factor"]
    out = _swiglu(h, p["shared"], bits)
    for e, gid in enumerate(a["experts_held"]):
        weight = jnp.sum(jnp.where(top == gid, gate, 0.0), axis=-1)
        he = jnp.where((weight > 0.0)[:, None], h, 0.0)
        w = {k: p[k][e] for k in ("w1", "w2", "w3")}
        out = out + weight[:, None] * _swiglu(he, w, bits)
    return out


def _layer(x, p, pos, a, bits):
    eps = a["norm_eps"]
    x = x + _attention(_rmsnorm(x, p["norm1"]["scale"], eps), p["mla"], pos,
                       a, bits)
    h = _rmsnorm(x, p["norm2"]["scale"], eps)
    if "ffn" in p:
        return x + _swiglu(h, p["ffn"], bits)
    return x + _experts(h, p["moe"], a, bits)


def forward(key, tokens, a: dict, bits=None):
    """Logits (S, V) of one sequence, causal; weights made from ``key``
    a layer at a time."""
    S = tokens.shape[0]
    pos = jnp.arange(S)
    outer = make_outer(key, a)
    x = outer["embed"]["tok_emb"][tokens]
    for i in range(a["first_k_dense"]):
        x = _layer(x, make_layer(key, a, i, True), pos, a, bits)

    def body(x, i):
        return _layer(x, make_layer(key, a, i, False), pos, a, bits), None

    x, _ = jax.lax.scan(body, x, jnp.arange(a["first_k_dense"],
                                            a["n_layers"]))
    x = _rmsnorm(x, outer["final_norm"]["scale"], a["norm_eps"])
    return _mm(x, outer["embed"]["unembed"], bits)


@partial(jax.jit, static_argnames=("arch", "control_bits"))
def gap_readings(w, tokens, targets, valid, arch, control_bits=None):
    a = dict(arch)
    with jax.default_matmul_precision("highest"):
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
