"""Serving engine: jit'd prefill/decode with KV caches + batched generation.

`GenerationEngine` serves one batch bucket end-to-end (prefill then greedy /
temperature sampling decode); `serve/batching.py` schedules request queues
onto buckets and `serve/continuous.py` runs slot-level continuous batching
over the same jitted entry points (per-slot caches via
`Model.init_slot_cache`, per-slot lengths via ``slot_lens`` on
`Model.decode_step`). Operator dispatch goes through the engine's resolved
`repro.exec.ExecPlan` (``engine.plan``, also ``engine.explain_plan()``) —
the engine itself contains no execution-mode branches.

With the serving default (``ExecConfig.serving()``), the plan resolves the
``attention_prefill`` slot to ``raceit_fused`` and ``attention_decode`` to
``raceit_gqa_rows`` for grouped-query configs (``n_kv_heads < n_heads``;
MHA configs take ``raceit_fused_rows``): both the jitted prefill and the jitted
per-token ``_decode`` step run the fused streaming Pallas kernel (one VMEM
pass over the Fig.-12 pipeline, no (Sq, Sk) intermediates in HBM), and the
GQA decode keeps the KV cache in its native (B, Smax, KV, hd) layout — the
rep queries sharing a KV head ride one kernel tile, so cache codes are
never repeated to H. The decode step attends the KV cache's valid prefix
via a traced ``kv_len`` — a scalar for buckets, a *per-request vector* for
slot pools (each row decodes at its own fill level) — over fixed buffer
shapes, so the decode executable compiles once and is reused for every
token; fully invalid key blocks are skipped via scalar-prefetched grid
bounds, per group tile when lengths are per-row. Every
``softmax_mode`` ("pot", "pot_fine", "uniform") is covered; configs the
kernels can't serve (``matmul_fidelity="acam"``) resolve to
``raceit_staged`` with the reason recorded on the plan (and a one-time
RuntimeWarning) — `repro.exec.resolve_plan` has the exact rules.

Mixed-length buckets (`serve.batching`) arrive left-padded with per-row
``pad_lens``; prefill and decode mask the pad slots and shift positions so
each row's tokens match serving it solo.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ExecConfig, ModelConfig
from repro.models import Model

__all__ = ["GenerationEngine"]


@dataclasses.dataclass
class GenerationEngine:
    cfg: ModelConfig
    params: dict
    exec_cfg: ExecConfig = ExecConfig()
    max_len: int = 512
    temperature: float = 0.0  # 0 => greedy
    mesh_ctx: object = None

    def __post_init__(self):
        # mesh-sharded serving: an ExecConfig.mesh (repro.dist.MeshSpec)
        # materializes here — once, at engine construction — into the
        # concrete Mesh and a MeshContext for the model stack, and the
        # parameter tree is device_put onto it under `param_specs` (TP
        # Megatron splits; ModelConfig.fsdp additionally hands the data
        # axes to the weight shards, so command-r-35B/mixtral-8x22B-class
        # trees load without ever fitting one device).
        spec = self.exec_cfg.mesh
        if spec is not None and getattr(spec, "n_devices", 1) > 1:
            from repro.dist.sharding import (ShardingPolicy,
                                             named_sharding_tree, param_specs)
            mesh = spec.build()
            if self.mesh_ctx is None:
                self.mesh_ctx = spec.context()
            policy = ShardingPolicy(mesh)
            if self.cfg.fsdp:
                amap = dict(policy.axis_map)
                dp = tuple(a for a in ("pod", "data")
                           if a in mesh.axis_names)
                for name in ("heads", "mlp", "vocab"):
                    amap[name] = tuple(amap.get(name, ())) + dp
                policy.axis_map = amap
            pspecs = param_specs(self.params, self.cfg, policy)
            self.params = jax.device_put(
                self.params, named_sharding_tree(pspecs, mesh))
        self.model = Model(self.cfg, self.exec_cfg, self.mesh_ctx)
        self.plan = self.model.plan  # resolved operator dispatch table
        # one jitted prefill serves both paths: encoder-decoder models pass
        # enc_feats as an extra traced arg (re-jitting per generate() call
        # recompiled the whole prefill graph every request).
        self._prefill = jax.jit(self.model.prefill)
        # one decode executable serves contiguous and block-paged slot
        # pools alike: page_size is static (it shapes the index math), the
        # block table is traced (tables change every step, the executable
        # must not)
        self._decode = jax.jit(self.model.decode_step, donate_argnums=(2,),
                               static_argnames=("page_size",))
        # the chunked-prefill step of paged continuous batching: one
        # pinned (n_slots, chunk) executable streams every admission's
        # prompt into its slot's pages (repro.serve.continuous)
        self._prefill_chunk = jax.jit(self.model.prefill_chunk,
                                      donate_argnums=(2,),
                                      static_argnames=("page_size",))

    def generate(self, prompts: jax.Array, n_new: int,
                 rng: Optional[jax.Array] = None,
                 enc_feats: Optional[jax.Array] = None,
                 pad_lens: Optional[np.ndarray] = None) -> np.ndarray:
        """prompts: (B, P) int32 -> (B, n_new) generated ids.

        ``pad_lens`` (B,) int32: per-row *left-pad* prefix lengths for
        mixed-length buckets (`repro.serve.batching` passes this). Pad
        columns are masked out of every attention step and real tokens keep
        their solo positions, so a row's generation matches serving the
        unpadded prompt alone.

        Every sampling step uses a fresh key split off the request ``rng``
        — including the first token (sampling it with the root key and then
        splitting that same key for later tokens would reuse the root as
        both a sampling key and a split source, the classic JAX key-reuse
        hazard).
        """
        B, P = prompts.shape
        assert P + n_new <= self.max_len
        if pad_lens is not None:
            pad_lens = jnp.asarray(pad_lens, jnp.int32)
        cache = self.model.init_cache(B, self.max_len)
        if self.cfg.is_encoder_decoder:
            logits, cache = self._prefill(self.params, prompts, cache,
                                          enc_feats=enc_feats,
                                          pad_lens=pad_lens)
        else:
            logits, cache = self._prefill(self.params, prompts, cache,
                                          pad_lens=pad_lens)
        out = []
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        rng, sub = jax.random.split(rng)
        tok = self._sample(logits[:, -1], sub)
        out.append(tok)
        pad_plen = jnp.int32(P) if pad_lens is not None else None
        for i in range(n_new - 1):
            rng, sub = jax.random.split(rng)
            logits, cache = self._decode(self.params, tok[:, None], cache,
                                         pad_lens, pad_plen)
            tok = self._sample(logits[:, -1], sub)
            out.append(tok)
        return np.stack([np.asarray(t) for t in out], axis=1)

    def explain_plan(self) -> str:
        """The resolved slot -> backend table this engine serves with."""
        return self.plan.explain()

    @staticmethod
    def nonfinite_rows(logits: jax.Array) -> jax.Array:
        """(B,) bool mask: rows whose logits contain NaN/Inf, left on the
        device (the caller reads it back with its other results).

        The fail-safe serving check — a device-faulted row (e.g. the
        ``fault_rate`` knob of `repro.hw.noise.NoiseConfig` on the noisy
        attention backends) surfaces as non-finite logits; schedulers call
        this on the step's last-position logits and retire the affected
        rows with a structured `repro.serve.batching.RequestError` instead
        of sampling garbage (argmax over NaN logits returns token 0 with
        no error signal at all).
        """
        finite = jnp.isfinite(logits).all(
            axis=tuple(range(1, jnp.ndim(logits))))
        return ~finite

    def _sample(self, logits: jax.Array, rng) -> jax.Array:
        if self.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            rng, logits / self.temperature, axis=-1).astype(jnp.int32)
