"""BlockPattern machinery: heterogeneous layer stacks under jax.lax.scan.

A config's (mixer_pattern, ffn_pattern) defines a repeating *period* of P
layers (jamba: P=8 with one attention + 7 mamba and MoE every 2nd; gemma3:
P=6 with 5 local + 1 global). The stack is executed as

    scan over n_full = n_layers // P repetitions of the period
      (each period position has its params stacked along the scan dim)
    + an unrolled tail of n_layers % P layers

which keeps HLO size O(P) instead of O(n_layers) — essential when lowering
at 512 devices — while preserving the exact layer ordering.
Caches thread through the scan as per-position stacked pytrees.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ExecConfig, ModelConfig
from repro.dist.sharding import MeshContext
from repro.exec.plan import ExecPlan, as_plan
from repro.exec.plan import layer_plan as _mixer_plan

from repro.dist.sharding import constraint

from . import layers, moe as moe_mod, ssm

Params = dict


# --------------------------------------------------------------------------
# single layer
# --------------------------------------------------------------------------

def init_layer(key, cfg: ModelConfig, mixer: str, ffn_kind: str, dtype,
               cross: bool = False) -> Params:
    ks = jax.random.split(key, 4)
    p: Params = {"norm1": layers.init_norm(cfg, dtype)}
    if mixer in ("attn", "attn_local"):
        p["attn"] = layers.init_attention(ks[0], cfg, dtype)
    elif mixer == "mla":
        p["mla"] = layers.init_mla(ks[0], cfg, dtype)
    elif mixer == "mamba":
        p["mamba"] = ssm.init_mamba_with_out(ks[0], cfg, dtype)
    if cross:
        p["norm_x"] = layers.init_norm(cfg, dtype)
        p["cross"] = layers.init_attention(ks[2], cfg, dtype)
    if ffn_kind == "dense":
        p["norm2"] = layers.init_norm(cfg, dtype)
        p["ffn"] = layers.init_ffn(ks[1], cfg, dtype)
    elif ffn_kind == "moe":
        p["norm2"] = layers.init_norm(cfg, dtype)
        p["moe"] = moe_mod.init_moe(ks[1], cfg, dtype)
    return p


def apply_layer(p: Params, x: jax.Array, *, cfg: ModelConfig,
                plan: ExecPlan | ExecConfig, mixer: str, ffn_kind: str,
                positions: jax.Array, cache: Optional[Params],
                mesh_ctx: Optional[MeshContext],
                enc_kv: Optional[tuple] = None,
                pad_lens: Optional[jax.Array] = None,
                pad_prompt_len: Optional[jax.Array] = None,
                slot_lens: Optional[jax.Array] = None,
                block_table: Optional[jax.Array] = None,
                page_size: Optional[int] = None,
                chunk_offs: Optional[jax.Array] = None,
                live: Optional[jax.Array] = None,
                ) -> tuple[jax.Array, Any]:
    """One layer. ``live`` (B, S) marks the rows that carry a token (None:
    all), the rows an expert layer routes. With a cache, an expert layer
    that counts its routed rows returns them as the new cache's
    ``expert_rows`` (`apply_stack` sums them)."""
    plan = as_plan(cfg, plan)
    # per-mixer-kind plan overrides (ExecConfig.layer_overrides): e.g. pin
    # sliding-window "attn_local" layers to the staged path while global
    # "attn" layers stay fused — resolved through the same lru-cached
    # resolve_plan, so this is a dict lookup per trace, not per step
    plan = _mixer_plan(plan, mixer)
    h = layers.apply_norm(p["norm1"], x, cfg)
    if mixer in ("attn", "attn_local"):
        # layer scopes (attn, mlp; kv_pool inside attention; mla inside
        # attn and moe inside mlp, so a reader that knows only the outer
        # names still sees each layer) name the layer's device work in the
        # HLO metadata a profile reports
        with jax.named_scope("attn"):
            m, new_cache = layers.attention(
                p["attn"], h, cfg=cfg, plan=plan, positions=positions,
                local=(mixer == "attn_local"),
                cache=cache.get("attn") if cache else None,
                pad_lens=pad_lens, pad_prompt_len=pad_prompt_len,
                slot_lens=slot_lens, block_table=block_table,
                page_size=page_size, chunk_offs=chunk_offs)
        if cache is not None:
            new_cache = {"attn": new_cache}
    elif mixer == "mla":
        with jax.named_scope("attn"), jax.named_scope("mla"):
            m, new_cache = layers.mla_attention(
                p["mla"], h, cfg=cfg, plan=plan, positions=positions,
                cache=cache.get("mla") if cache else None,
                slot_lens=slot_lens, block_table=block_table,
                page_size=page_size, chunk_offs=chunk_offs)
        if cache is not None:
            new_cache = {"mla": new_cache}
    elif mixer == "mamba":
        m, new_cache = ssm.mamba(p["mamba"], h, cfg=cfg, plan=plan,
                                 cache=cache.get("mamba") if cache else None)
        if cache is not None:
            new_cache = {"mamba": new_cache}
    else:
        raise ValueError(mixer)
    x = x + m

    if "cross" in p and enc_kv is not None:
        hx = layers.apply_norm(p["norm_x"], x, cfg)
        with jax.named_scope("attn"):
            cx, _ = layers.attention(p["cross"], hx, cfg=cfg, plan=plan,
                                     positions=positions, cross_kv=enc_kv)
        x = x + cx

    if ffn_kind == "dense":
        h2 = layers.apply_norm(p["norm2"], x, cfg)
        with jax.named_scope("mlp"):
            x = x + layers.ffn(p["ffn"], h2, cfg, plan)
    elif ffn_kind == "moe":
        h2 = layers.apply_norm(p["norm2"], x, cfg)
        with jax.named_scope("mlp"), jax.named_scope("moe"):
            y, n_routed = moe_mod.moe(p["moe"], h2, cfg, plan, mesh_ctx,
                                      live=live)
        x = x + y
        if cache is not None and n_routed is not None:
            new_cache = dict(new_cache or {}, expert_rows=n_routed)
    # sequence-parallel residual stream: the carried activation (and thus the
    # remat stash) lives sharded over "model"; XLA inserts AG/RS at the
    # boundaries that need full sequence (Megatron-SP pattern).
    x = constraint(x, "batch", "sp_seq", None)
    return x, (new_cache if cache is not None else None)


# --------------------------------------------------------------------------
# cache construction
# --------------------------------------------------------------------------

def init_layer_cache(cfg: ModelConfig, mixer: str, batch: int, max_len: int,
                     dtype, page_size: Optional[int] = None,
                     n_pages: Optional[int] = None) -> Optional[Params]:
    """One layer's decode cache; ``page_size``/``n_pages`` switch attention
    layers to the block-paged pool form.

    Paged caches store a page *pool* shared by every slot — k/v are
    (n_pages, page_size, KV, hd) and a slot's logical columns are resolved
    through the block table the caller threads alongside (one table for the
    whole stack: every layer's pool uses the same page assignments, so the
    table is serving state, not cache state). ``idx`` becomes a (batch,)
    per-slot fill vector mirroring the serving layer's slot_lens. Physical
    page 0 is the trash page and is never handed to a slot.
    """
    if page_size is not None:
        if mixer == "mla":
            # the latent pool: one row of latent + RoPE key per token,
            # read by every head as key and value (layers.mla_attention)
            if n_pages is None:
                raise ValueError("paged caches need n_pages")
            return {"mla": {
                "kv": jnp.zeros((n_pages, page_size, 1, cfg.mla_head_dim),
                                dtype),
                "idx": jnp.zeros((batch,), jnp.int32),
            }}
        if mixer == "attn":
            hd = cfg.resolved_head_dim
            if n_pages is None:
                raise ValueError("paged caches need n_pages")
            return {"attn": {
                "k": jnp.zeros((n_pages, page_size, cfg.n_kv_heads, hd),
                               dtype),
                "v": jnp.zeros((n_pages, page_size, cfg.n_kv_heads, hd),
                               dtype),
                "idx": jnp.zeros((batch,), jnp.int32),
            }}
        raise NotImplementedError(
            f"block-paged caches cover global attention layers only; "
            f"mixer {mixer!r} keeps its own state layout (serve contiguous "
            f"for this config)")
    if mixer == "mla":
        raise NotImplementedError(
            "latent attention caches are block-paged only (pass page_size "
            "and n_pages; serve with the paged ContinuousBatcher)")
    if mixer in ("attn", "attn_local"):
        hd = cfg.resolved_head_dim
        # local layers keep a ring buffer of window size (DESIGN.md §4)
        length = min(max_len, cfg.window) if mixer == "attn_local" else max_len
        return {"attn": {
            "k": jnp.zeros((batch, length, cfg.n_kv_heads, hd), dtype),
            "v": jnp.zeros((batch, length, cfg.n_kv_heads, hd), dtype),
            "idx": jnp.zeros((), jnp.int32),
        }}
    if mixer == "mamba":
        W = cfg.conv_width
        return {"mamba": {
            "state": jnp.zeros((batch, cfg.ssm_heads, cfg.ssm_headdim,
                                cfg.ssm_state), jnp.float32),
            "conv_x": jnp.zeros((batch, W - 1, cfg.d_inner), dtype),
            "conv_B": jnp.zeros((batch, W - 1, cfg.ssm_groups * cfg.ssm_state), dtype),
            "conv_C": jnp.zeros((batch, W - 1, cfg.ssm_groups * cfg.ssm_state), dtype),
        }}
    return None


# --------------------------------------------------------------------------
# the stack
# --------------------------------------------------------------------------

def layer_plan(cfg: ModelConfig, n_layers: Optional[int] = None):
    """(period P, scanned repetitions n_full, every layer's (mixer, ffn)).

    The stack runs the ``cfg.first_k_dense`` leading layers unrolled, then
    scans n_full repetitions of the period, then the unrolled tail."""
    n = n_layers if n_layers is not None else cfg.n_layers
    P = cfg.block_period
    n_full = (n - cfg.first_k_dense) // P
    specs = [cfg.layer_spec(i) for i in range(n)]
    return P, n_full, specs


def _counts_expert_rows(specs) -> bool:
    return any(ffn == "moe" for _, ffn in specs)


def live_rows(batch: int, sq: int, slot_lens=None, chunk_offs=None):
    """(B, Sq) bool: the rows of a serving call that carry a token — a
    chunk call's fed positions, a decode step's occupied slots — or None
    where every row does."""
    if slot_lens is None:
        return None
    lens = jnp.asarray(slot_lens, jnp.int32)[:, None]
    if chunk_offs is None:
        return jnp.broadcast_to(lens > 0, (batch, sq))
    cols = (jnp.asarray(chunk_offs, jnp.int32)[:, None]
            + jnp.arange(sq, dtype=jnp.int32)[None, :])
    return cols < lens


def init_stack(key, cfg: ModelConfig, dtype, n_layers: Optional[int] = None,
               cross: bool = False) -> Params:
    P, n_full, specs = layer_plan(cfg, n_layers)
    lead = cfg.first_k_dense
    keys = jax.random.split(key, len(specs))
    scan_params = []
    for j in range(P):
        if n_full == 0:
            break
        layer_keys = [keys[lead + r * P + j] for r in range(n_full)]
        mixer, ffn_kind = specs[lead + j]
        init_j = partial(init_layer, cfg=cfg, mixer=mixer, ffn_kind=ffn_kind,
                         dtype=dtype, cross=cross)
        scan_params.append(jax.vmap(init_j)(jnp.stack(layer_keys)))
    unrolled = lambda idx: [init_layer(keys[i], cfg, *specs[i], dtype,
                                       cross=cross) for i in idx]
    out = {"scan": scan_params,
           "tail": unrolled(range(lead + n_full * P, len(specs)))}
    if lead:
        out["lead"] = unrolled(range(lead))
    return out


def init_stack_cache(cfg: ModelConfig, batch: int, max_len: int, dtype,
                     n_layers: Optional[int] = None,
                     page_size: Optional[int] = None,
                     n_pages: Optional[int] = None) -> Params:
    P, n_full, specs = layer_plan(cfg, n_layers)
    lead = cfg.first_k_dense

    def stack_tree(tree, n):
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (n,) + a.shape).copy(), tree)

    scan_caches = []
    for j in range(P):
        if n_full == 0:
            break
        mixer, _ = specs[lead + j]
        c = init_layer_cache(cfg, mixer, batch, max_len, dtype,
                             page_size=page_size, n_pages=n_pages)
        scan_caches.append(stack_tree(c, n_full) if c is not None else {})
    unrolled = lambda idx: [init_layer_cache(
        cfg, specs[i][0], batch, max_len, dtype, page_size=page_size,
        n_pages=n_pages) or {} for i in idx]
    out = {"scan": scan_caches,
           "tail": unrolled(range(lead + n_full * P, len(specs)))}
    if lead:
        out["lead"] = unrolled(range(lead))
    if _counts_expert_rows(specs):
        # the last call's (row, choice) pairs routed to held experts
        out["expert_rows"] = jnp.zeros((), jnp.int32)
    return out


def _remat_wrap(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)


def apply_stack(params: Params, x: jax.Array, *, cfg: ModelConfig,
                plan: ExecPlan | ExecConfig, positions: jax.Array,
                caches: Optional[Params], mesh_ctx: Optional[MeshContext],
                enc_kv_stack: Optional[list] = None,
                n_layers: Optional[int] = None,
                use_remat: bool = False,
                pad_lens: Optional[jax.Array] = None,
                pad_prompt_len: Optional[jax.Array] = None,
                slot_lens: Optional[jax.Array] = None,
                block_table: Optional[jax.Array] = None,
                page_size: Optional[int] = None,
                chunk_offs: Optional[jax.Array] = None,
                ) -> tuple[jax.Array, Optional[Params]]:
    """Run the stack. caches is the pytree from init_stack_cache (or None).

    ``pad_lens`` (B,) marks per-row left-pad prefixes (batched serving);
    attention layers mask those key slots, SSM mixers currently scan
    through them (see `repro.serve.batching` for the exactness contract).
    ``slot_lens`` (B,) is the per-slot decode length authority for
    slot-pool caches (`repro.serve.continuous`): attention layers decode
    each row at its own fill level; SSM mixers ignore it (their state is
    overwritten whenever a slot is re-admitted).

    ``block_table`` (B, max_pages) + static ``page_size`` mark the caches
    as block-paged pools (see `init_layer_cache`); ONE table serves every
    layer — each layer's pool uses the same page assignments, so the table
    threads here as an argument, like slot_lens, not inside the cache
    pytree. ``chunk_offs`` (B,) turns the step into a chunked-prefill call
    (see `repro.models.layers.attention`).
    """
    plan = as_plan(cfg, plan)
    P, n_full, specs = layer_plan(cfg, n_layers)
    lead = cfg.first_k_dense
    has_cache = caches is not None
    counting = has_cache and _counts_expert_rows(specs)
    layer = partial(apply_layer, cfg=cfg, plan=plan, positions=positions,
                    mesh_ctx=mesh_ctx, enc_kv=None, pad_lens=pad_lens,
                    pad_prompt_len=pad_prompt_len, slot_lens=slot_lens,
                    block_table=block_table, page_size=page_size,
                    chunk_offs=chunk_offs,
                    live=live_rows(x.shape[0], x.shape[1], slot_lens,
                                   chunk_offs))

    def unrolled(x, n, group, idx):
        new = []
        for t, i in enumerate(idx):
            mixer, ffn_kind = specs[i]
            cache_t = caches[group][t] if has_cache else None
            x, nc = layer(params[group][t], x, mixer=mixer,
                          ffn_kind=ffn_kind,
                          cache=(cache_t if cache_t else None))
            nc = dict(nc or {})
            n = n + nc.pop("expert_rows", 0)
            new.append(nc)
        return x, n, new

    n_routed = jnp.zeros((), jnp.int32)
    x, n_routed, new_lead = unrolled(x, n_routed, "lead", range(lead))

    if n_full > 0:
        def body(carry, xs):
            x, n = carry if counting else (carry, 0)
            p_list, c_list = xs
            new_cs = []
            for j in range(P):
                mixer, ffn_kind = specs[lead + j]
                cache_j = c_list[j] if has_cache else None
                x, nc = layer(p_list[j], x, mixer=mixer, ffn_kind=ffn_kind,
                              cache=(cache_j if cache_j else None))
                nc = dict(nc or {})
                n = n + nc.pop("expert_rows", 0)
                new_cs.append(nc)
            return ((x, n) if counting else x), tuple(new_cs)

        body_fn = _remat_wrap(body, cfg) if use_remat else body
        scan_caches = tuple(caches["scan"]) if has_cache else tuple(
            {} for _ in range(P))
        # what the scan adds itself (slicing each layer's weights and
        # cache in, stacking the updated cache out) reports as layer_scan
        with jax.named_scope("layer_scan"):
            carry, new_scan = jax.lax.scan(
                body_fn, (x, n_routed) if counting else x,
                (tuple(params["scan"]), scan_caches),
                unroll=cfg.scan_unroll)
        x, n_routed = carry if counting else (carry, n_routed)
    else:
        new_scan = ()

    x, n_routed, new_tail = unrolled(x, n_routed, "tail",
                                     range(lead + n_full * P, len(specs)))

    if not has_cache:
        return x, None
    new_caches = {"scan": list(new_scan), "tail": new_tail}
    if lead:
        new_caches["lead"] = new_lead
    if counting:
        new_caches["expert_rows"] = n_routed
    return x, new_caches
