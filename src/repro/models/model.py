"""Top-level model assembly: decoder LMs, encoder-only (BERT), enc-dec (Whisper).

Public API:

    model = Model(cfg, exec_cfg, mesh_ctx)
    params = model.init(rng)
    logits = model.forward(params, batch)                  # train / scoring
    cache = model.init_cache(batch_size, max_len)
    logits, cache = model.prefill(params, tokens, cache)
    logits, cache = model.decode_step(params, token, cache)

`batch` for forward is a dict: {"tokens": (B,S) int32, optional "positions",
optional "enc_feats": (B, enc_len, d_model) for stub-frontend models}.
All functions are pure and jit/pjit-friendly.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ExecConfig, ModelConfig
from repro.dist.sharding import MeshContext, constraint
from repro.exec.plan import ExecPlan, as_plan

from . import blocks, layers

Params = dict

# weight leaves that live on crossbars as int8 conductance codes when serving
_QUANTIZABLE = {"wq", "wk", "wv", "wo", "w1", "w2", "w3", "unembed",
                "w_z", "w_x", "w_B", "w_C", "w_dt", "out_proj",
                "wkv_a", "w_uk", "w_uv"}
# leaves whose leading axis stacks one matrix per head or per held expert,
# each its own crossbar: latent attention's per-head absorptions, and the
# expert stacks directly under a "moe" layer
_PER_HEAD = {"w_uk", "w_uv"}
_PER_EXPERT = {"w1", "w2", "w3"}


def quantize_model_params(params: Params) -> Params:
    """Convert weight matrices to resident int8 codes + per-column scales
    (the paper's deployment form: weights ARE the crossbar conductances).

    Stacked scan leaves (R, K, ...) quantize per layer: codes (R, K, N),
    scale (R, 1, N); the scan slices them to exactly what _linear consumes.
    Per-head and per-expert stacks (G, K, N) quantize per matrix the same
    way, scale (G, 1, N), under the scan's layer axis where there is one.
    """
    def q2d(leaf, lead: int, name: str):
        # ``lead`` leading axes (scan layer, head or expert) index separate
        # matrices; the next is the contraction, the rest the columns
        arr = jnp.asarray(leaf, jnp.float32)
        if name == "wo":  # contraction spans (heads, head_dim)
            arr = arr.reshape(*arr.shape[:lead], -1, arr.shape[-1])
        flat = arr.reshape(*arr.shape[:lead + 1], -1)
        amax = jnp.max(jnp.abs(flat), axis=lead, keepdims=True)
        shape = tuple(arr.shape[lead + 1:])
        scale = jnp.maximum(amax, 1e-12) / 127.0
        codes = jnp.clip(jnp.round(flat / scale), -128, 127).astype(jnp.int8)
        return layers.QuantizedWeight(codes, scale.astype(jnp.float32), shape)

    def walk(tree, stacked=False, in_moe=False):
        if isinstance(tree, dict):
            out = {}
            for name, leaf in tree.items():
                lead = int(stacked) + int(name in _PER_HEAD
                                          or (in_moe and name in _PER_EXPERT))
                if (name in _QUANTIZABLE and hasattr(leaf, "ndim")
                        and leaf.ndim >= lead + 2):
                    out[name] = q2d(leaf, lead, name)
                elif name == "scan":
                    out[name] = [walk(x, stacked=True) for x in leaf]
                else:
                    out[name] = walk(leaf, stacked, in_moe=name == "moe")
            return out
        if isinstance(tree, list):
            return [walk(x, stacked) for x in tree]
        if isinstance(tree, tuple):
            return tuple(walk(x, stacked) for x in tree)
        return tree

    return walk(params)


def _dtype(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]


def map_cache_idx(cache, fn):
    """Apply ``fn`` to every ``idx`` leaf of a cache pytree.

    ``idx`` leaves are the per-layer write indices (`blocks.init_layer_cache`
    puts one in every attention cache dict); scan-stacked layers carry a
    leading repetition dim on theirs. Used to turn a fresh cache into a
    slot pool (scalar idx -> (n_slots,) vectors) and by the continuous
    batcher's row scatter.
    """
    def walk(t):
        if isinstance(t, dict):
            return {k: (fn(v) if k == "idx" else walk(v))
                    for k, v in t.items()}
        if isinstance(t, list):
            return [walk(x) for x in t]
        if isinstance(t, tuple):
            return tuple(walk(x) for x in t)
        return t
    return walk(cache)


class Model:
    def __init__(self, cfg: ModelConfig,
                 exec_cfg: "ExecConfig | ExecPlan" = ExecConfig(),
                 mesh_ctx: Optional[MeshContext] = None):
        self.cfg = cfg
        # resolve the operator dispatch table once: every layer call below
        # goes through self.plan's slot methods, never through mode branches
        self.plan = as_plan(cfg, exec_cfg)
        self.exec_cfg = self.plan.exec_cfg
        self.mesh_ctx = mesh_ctx

    # ------------------------------------------------------------------ init
    def init(self, rng) -> Params:
        cfg = self.cfg
        dtype = _dtype(cfg.param_dtype)
        ks = jax.random.split(rng, 6)
        p: Params = {"embed": layers.init_embeddings(ks[0], cfg, dtype),
                     "final_norm": layers.init_norm(cfg, dtype)}
        if cfg.is_encoder_decoder:
            enc_cfg = cfg.replace(causal=False, mixer_pattern=("attn",),
                                  ffn_pattern=("dense",))
            p["encoder"] = blocks.init_stack(ks[1], enc_cfg, dtype,
                                             n_layers=cfg.n_encoder_layers)
            p["enc_norm"] = layers.init_norm(cfg, dtype)
            p["decoder"] = blocks.init_stack(ks[2], cfg, dtype, cross=True)
        else:
            p["blocks"] = blocks.init_stack(ks[1], cfg, dtype)
        return p

    # ------------------------------------------------------------- internals
    def _positions(self, tokens: jax.Array, offset=0) -> jax.Array:
        b, s = tokens.shape[:2]
        return jnp.broadcast_to(jnp.arange(s) + offset, (b, s))

    def _encode(self, params: Params, enc_feats: jax.Array) -> jax.Array:
        """Whisper encoder over stub-frontend frame embeddings."""
        enc_cfg = self.cfg.replace(causal=False, mixer_pattern=("attn",),
                                   ffn_pattern=("dense",))
        pos = self._positions(enc_feats[..., 0])
        x = enc_feats.astype(_dtype(self.cfg.compute_dtype))
        x, _ = blocks.apply_stack(params["encoder"], x, cfg=enc_cfg,
                                  plan=self.plan, positions=pos,
                                  caches=None, mesh_ctx=self.mesh_ctx,
                                  n_layers=self.cfg.n_encoder_layers)
        return layers.apply_norm(params["enc_norm"], x, self.cfg)

    def _enc_kv(self, params: Params, enc_out: jax.Array) -> list:
        """Per-decoder-layer cross K/V from the encoder output."""
        kvs = []
        for t in range(self.cfg.n_layers):
            lp = self._decoder_layer_params(params, t)["cross"]
            k = layers._linear(enc_out, lp["wk"], self.plan, lp.get("bk"))
            v = layers._linear(enc_out, lp["wv"], self.plan, lp.get("bv"))
            kvs.append((k, v))
        return kvs

    def _decoder_layer_params(self, params: Params, t: int) -> Params:
        P, n_full, _ = blocks.layer_plan(self.cfg)
        if t < n_full * P:
            r, j = divmod(t, P)
            return jax.tree.map(lambda a: a[r], params["decoder"]["scan"][j])
        return params["decoder"]["tail"][t - n_full * P]

    def _trunk(self, params: Params, tokens, positions, caches, enc_feats,
               use_remat: bool, pad_lens=None, pad_prompt_len=None,
               slot_lens=None, block_table=None, page_size=None,
               chunk_offs=None):
        cfg = self.cfg
        with jax.named_scope("embed"):
            x = layers.embed(params["embed"], tokens,
                             positions if positions.ndim == 2
                             else positions[0], cfg)
            x = x.astype(_dtype(cfg.compute_dtype))

        if cfg.is_encoder_decoder:
            if caches is not None and "enc_kv" in caches:
                enc_kv = caches["enc_kv"]  # cached cross K/V (prefill/decode)
            else:
                enc_out = self._encode(params, enc_feats)
                enc_kv = self._enc_kv(params, enc_out)
            # decoder: unrolled (whisper is 4L), cross-attn per layer
            new_tail = []
            dec_caches = caches["dec"] if caches is not None else None
            for t in range(cfg.n_layers):
                lp = self._decoder_layer_params(params, t)
                mixer, ffn_kind = cfg.layer_spec(t)
                cache_t = dec_caches[t] if dec_caches is not None else None
                x, nc = blocks.apply_layer(
                    lp, x, cfg=cfg, plan=self.plan, mixer=mixer,
                    ffn_kind=ffn_kind, positions=positions,
                    cache=cache_t if cache_t else None, mesh_ctx=self.mesh_ctx,
                    enc_kv=enc_kv[t], pad_lens=pad_lens,
                    pad_prompt_len=pad_prompt_len, slot_lens=slot_lens)
                new_tail.append(nc if nc is not None else {})
            new_caches = ({"dec": new_tail, "enc_kv": enc_kv}
                          if caches is not None else None)
        else:
            x, new_caches = blocks.apply_stack(
                params["blocks"], x, cfg=cfg, plan=self.plan,
                positions=positions, caches=caches, mesh_ctx=self.mesh_ctx,
                use_remat=use_remat, pad_lens=pad_lens,
                pad_prompt_len=pad_prompt_len, slot_lens=slot_lens,
                block_table=block_table, page_size=page_size,
                chunk_offs=chunk_offs)

        x = layers.apply_norm(params["final_norm"], x, cfg)
        return x, new_caches

    def _unembed(self, params: Params, x: jax.Array) -> jax.Array:
        with jax.named_scope("unembed"):
            return layers.unembed(params["embed"], x, self.cfg, self.plan)

    # ---------------------------------------------------------------- public
    def forward(self, params: Params, batch: dict, use_remat: bool = True):
        tokens = batch["tokens"]
        positions = batch.get("positions")
        if positions is None:
            positions = self._positions(tokens)
        x, _ = self._trunk(params, tokens, positions, None,
                           batch.get("enc_feats"), use_remat)
        return self._unembed(params, x)

    def init_slot_cache(self, n_slots: int, max_len: int, dtype=None,
                        page_size: Optional[int] = None,
                        n_pages: Optional[int] = None) -> Params:
        """A fixed-shape *slot-pool* cache for continuous batching.

        Identical buffers to `init_cache`, but every per-layer ``idx``
        leaf is a (n_slots,) vector — one independent write index per
        slot — so `decode_step` writes each row's new k/v at its own
        column and slots fill/retire independently
        (`repro.serve.continuous.ContinuousBatcher` owns the lifecycle).

        ``page_size``/``n_pages`` switch every attention layer's buffers
        to the block-paged pool form (`blocks.init_layer_cache`): k/v
        become an (n_pages, page_size, KV, hd) page pool shared by all
        slots, addressed through the (n_slots, max_pages) block table the
        serving layer owns and threads into `decode_step` /
        `prefill_chunk`. ``max_len`` then only documents intent — capacity
        is ``(n_pages - 1) * page_size`` pooled across slots (page 0 is
        the trash page), which is the point: memory follows actual fill,
        not n_slots x max_len. Raises for stacks with non-attention or
        local mixers (their state has no paged form).
        """
        if self.cfg.is_encoder_decoder:
            raise NotImplementedError(
                "slot-pool caches cover decoder-only stacks; encoder-"
                "decoder serving stays on bucketed batching")
        if page_size is not None:
            # paged caches are born per-slot: idx is already (n_slots,)
            return blocks.init_stack_cache(
                self.cfg, n_slots, max_len,
                dtype or _dtype(self.cfg.compute_dtype),
                page_size=page_size, n_pages=n_pages)
        cache = self.init_cache(n_slots, max_len, dtype)
        vec = lambda a: jnp.broadcast_to(a[..., None],
                                         a.shape + (n_slots,)).copy()
        return map_cache_idx(cache, vec)

    def init_cache(self, batch: int, max_len: int, dtype=None) -> Params:
        cfg = self.cfg
        dtype = dtype or _dtype(cfg.compute_dtype)
        if cfg.is_encoder_decoder:
            hd = cfg.resolved_head_dim
            dec = [blocks.init_layer_cache(cfg, cfg.layer_spec(t)[0], batch,
                                           max_len, dtype) or {}
                   for t in range(cfg.n_layers)]
            enc_kv = [(jnp.zeros((batch, cfg.encoder_len, cfg.n_kv_heads, hd), dtype),
                       jnp.zeros((batch, cfg.encoder_len, cfg.n_kv_heads, hd), dtype))
                      for _ in range(cfg.n_layers)]
            return {"dec": dec, "enc_kv": enc_kv}
        return blocks.init_stack_cache(cfg, batch, max_len, dtype)

    def prefill(self, params: Params, tokens: jax.Array, cache: Params,
                enc_feats=None, positions=None, pad_lens=None):
        """Process the prompt; returns last-position logits + filled cache.

        ``pad_lens`` (B,) int32: per-row left-pad prefix lengths (batched
        serving buckets). Real tokens then sit at positions shifted down by
        their row's pad count (pad rows are clipped to position 0 — their
        outputs are masked out of every real row's attention anyway), and
        attention masks the pad columns per row, so a request's prefill is
        independent of its bucket-mates. The last column is a real token
        for every row by construction (left-padding), so the returned
        last-position logits are per-request first-token logits.
        """
        if positions is None:
            positions = self._positions(tokens)
            if pad_lens is not None:
                positions = jnp.maximum(
                    positions - pad_lens[:, None].astype(jnp.int32), 0)
        if self.cfg.is_encoder_decoder and enc_feats is not None:
            enc_out = self._encode(params, enc_feats)
            cache = dict(cache, enc_kv=[
                (k.astype(c[0].dtype), v.astype(c[1].dtype))
                for (k, v), c in zip(self._enc_kv(params, enc_out), cache["enc_kv"])])
        x, new_cache = self._trunk(params, tokens, positions, cache, None,
                                   False, pad_lens=pad_lens)
        logits = self._unembed(params, x[:, -1:])
        return logits, new_cache

    def decode_step(self, params: Params, token: jax.Array, cache: Params,
                    pad_lens=None, pad_prompt_len=None, slot_lens=None,
                    block_table=None, page_size=None):
        """token: (B, 1). Returns (logits (B,1,V), cache).

        Each attention layer's decode step runs whatever backend the plan
        resolved for the ``attention_decode`` slot — the serving default
        (`ExecConfig.serving()`) is ``raceit_gqa_rows`` when the config
        shares KV heads (``n_kv_heads < n_heads``), else
        ``raceit_fused_rows``; both stream each row's valid cache prefix
        in one kernel pass (`layers._raceit_gqa_decode` /
        `layers._raceit_fused_decode`), and ``plan.explain()`` names the
        backend and any degrade reason. ``pad_lens`` (B,) keeps
        left-padded rows at their true positions and masks their pad cache
        slots; ``pad_prompt_len`` (the padded prompt length — scalar for a
        bucket, (B,) for slot pools) lets layers whose ring buffer the
        prompt overflowed drop the slot-space pad mask (the last-L prefill
        broke the slot == column mapping it relies on).

        ``slot_lens`` (B,) int32 drives slot-level continuous batching
        (`repro.serve.continuous`): entry b is the number of valid cache
        columns for row b *including the token decoded this step* (0 = an
        empty slot whose row is dead), so each slot decodes at its own
        fill level against a per-slot-``idx`` cache
        (`Model.init_slot_cache`) and the pool's shapes — hence the
        compiled executable — never change as requests come and go.

        ``block_table`` (B, max_pages) int32 + static ``page_size`` address
        a block-paged slot cache (`init_slot_cache(page_size=..., ...)`):
        row b's logical cache column c lives at pool page
        ``block_table[b, c // page_size]`` — one table for the whole stack.
        Requires ``slot_lens``; the paged decode backends
        (``raceit_*_paged``) follow the indirection in-kernel, anything
        else is served by gathering pages to contiguous rows.
        """
        if slot_lens is not None:
            # per-slot positions: the new token's index among the row's
            # real tokens (pads excluded below); empty slots clamp to 0
            idx = jnp.maximum(jnp.asarray(slot_lens, jnp.int32)[:, None] - 1,
                              0)
            positions = jnp.broadcast_to(idx, token.shape)
        else:
            idx = self._cache_index(cache)
            positions = jnp.broadcast_to(idx, token.shape).astype(jnp.int32)
        if pad_lens is not None:
            positions = jnp.maximum(
                positions - pad_lens[:, None].astype(jnp.int32), 0)
        x, new_cache = self._trunk(params, token, positions, cache, None,
                                   False, pad_lens=pad_lens,
                                   pad_prompt_len=pad_prompt_len,
                                   slot_lens=slot_lens,
                                   block_table=block_table,
                                   page_size=page_size)
        logits = self._unembed(params, x)
        return logits, new_cache

    def prefill_chunk(self, params: Params, tokens: jax.Array, cache: Params,
                      chunk_offs, chunk_lens, block_table, page_size):
        """Stream one prompt chunk per slot into a block-paged cache.

        tokens: (B, C) — row b carries ``chunk_lens[b]`` prompt tokens
        destined for logical cache columns [chunk_offs[b], chunk_offs[b] +
        chunk_lens[b]); columns past the feed are garbage padding whose
        cache writes route to the trash page. C is the *pinned* chunk
        width: every admission streams through the same (B, C) call, so
        chunked prefill adds exactly one compiled executable regardless of
        prompt length (Sarathi-style prefill/decode interleave without
        shape churn). A row with ``chunk_lens[b] == 0`` does not
        participate (its block-table row should be all trash, its output
        row is garbage).

        Returns (logits (B, 1, V), cache): row b's logits are taken at its
        last fed position — meaningful only for rows whose chunk completes
        their prompt (they are that request's first-token logits, the
        chunked analog of `prefill`'s last-column logits).
        """
        offs = jnp.asarray(chunk_offs, jnp.int32)
        feed = jnp.asarray(chunk_lens, jnp.int32)
        positions = offs[:, None] + jnp.broadcast_to(
            jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape)
        x, new_cache = self._trunk(params, tokens, positions, cache, None,
                                   False, slot_lens=offs + feed,
                                   block_table=block_table,
                                   page_size=page_size, chunk_offs=offs)
        last = jnp.maximum(feed - 1, 0)[:, None, None]
        x_last = jnp.take_along_axis(x, jnp.broadcast_to(
            last, (x.shape[0], 1, x.shape[2])), axis=1)
        logits = self._unembed(params, x_last)
        return logits, new_cache

    def _cache_index(self, cache: Params):
        leaves = jax.tree_util.tree_leaves(
            jax.tree.map(lambda d: d.get("idx", None) if isinstance(d, dict) else None,
                         cache, is_leaf=lambda d: isinstance(d, dict) and "idx" in d))
        for leaf in leaves:
            if leaf is not None:
                return jnp.max(leaf) if getattr(leaf, "ndim", 0) else leaf
        return jnp.zeros((), jnp.int32)

    # --------------------------------------------------------------- loss
    def loss_fn(self, params: Params, batch: dict, use_remat: bool = True):
        """Next-token cross entropy (mean over non-masked tokens)."""
        logits = self.forward(params, batch, use_remat=use_remat)
        tokens = batch["tokens"]
        if self.cfg.causal:
            targets = tokens[:, 1:]
            logits = logits[:, :-1]
        else:  # encoder-only: masked-token style (predict identity here)
            targets = tokens
        mask = batch.get("loss_mask")
        if mask is None:
            mask = jnp.ones_like(targets, jnp.float32)
        else:
            mask = mask[:, -targets.shape[1]:].astype(jnp.float32)
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        nll = (logz - gold) * mask
        return nll.sum() / jnp.maximum(mask.sum(), 1.0)
