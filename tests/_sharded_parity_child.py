"""Multi-device child for tests/test_sharded_parity.py (not collected).

Runs under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` in a
subprocess (the parent pytest process pins JAX to 1 CPU device, and the
flag only takes effect before jax initializes). Two modes:

  python tests/_sharded_parity_child.py ops
      op-level bitwise parity: the raceit_*_tp attention backends vs the
      single-device serving chain, MHA + GQA x mesh model={1,2,4,8}, over
      contiguous decode (per-row kv_len), block-paged decode, causal
      prefill, and padded-bucket prefill. All calls are jitted — the
      single-device paged references are @jax.jit wrappers, and eager
      f32 epilogs round differently by ~1 ulp, so bitwise comparison is
      only meaningful jit-vs-jit (serving always runs jitted anyway).

  python tests/_sharded_parity_child.py soak
      end-to-end: GenerationEngine token parity (mesh model=4 vs no mesh,
      FSDP'd params via device_put) and a paged continuous-batching soak
      on a 4-device mesh — generated mixed-length traces through
      ContinuousBatcher must produce tokens identical to the no-mesh
      batcher, with the pool invariants held after every step.

Prints PARITY_OK / SOAK_OK on success; any assertion kills the process
and the parent test surfaces stderr.
"""
import os
import sys
from pathlib import Path

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import ExecConfig
from repro.dist import MeshSpec
from repro.exec.plan import resolve_plan

MESH_SIZES = (1, 2, 4, 8)


def _cfg(n_heads, n_kv_heads, d_model):
    return get_config("gpt2-large").replace(
        name=f"tp-parity-h{n_heads}kv{n_kv_heads}", n_layers=2,
        d_model=d_model, n_heads=n_heads, n_kv_heads=n_kv_heads,
        d_ff=2 * d_model, vocab_size=256, pos_emb="rope", norm="rmsnorm",
        glu=False, qkv_bias=False, param_dtype="float32",
        compute_dtype="float32", remat="none", tie_embeddings=True)


# n_kv_heads=8 in both so every mesh size in {1,2,4,8} divides the KV heads
MHA = _cfg(8, 8, 128)    # hd=16, flat fused decode family
GQA = _cfg(16, 8, 256)   # hd=16, rep=2, gqa-native decode family


def _mesh_exec(ms):
    mesh = None if ms == 0 else MeshSpec.parse(f"model={ms}")
    return ExecConfig.serving(mesh=mesh)


def _rand(key, shape):
    return jax.random.normal(key, shape, jnp.float32)


def _assert_bitwise(ref, out, what):
    ref, out = np.asarray(ref), np.asarray(out)
    if not np.array_equal(ref, out):
        diff = np.abs(ref - out)
        raise AssertionError(
            f"{what}: sharded output differs from single-device "
            f"(max abs diff {diff.max():.3e} at {diff.argmax()})")


def _assert_ulp(ref, out, what):
    # the prefill epilog is f32 math XLA fuses differently inside
    # shard_map (a*b*c re-association) — identical quantized codes, but
    # the float product can land 1-2 ulp apart. Decode is held bitwise
    # (the serving-parity claim); prefill to <= 4 ulp.
    ref, out = np.asarray(ref), np.asarray(out)
    r, o = ref.view(np.int32), out.view(np.int32)
    ulp = np.abs(r - o).max()
    assert ulp <= 4, (
        f"{what}: sharded prefill drifted past ulp noise "
        f"({ulp} ulp, max abs diff {np.abs(ref - out).max():.3e})")


def _op_parity(cfg):
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    scale = 1.0 / float(np.sqrt(hd))
    key = jax.random.PRNGKey(7)
    kq, kk, kv_, kp, kkp, kvp = jax.random.split(key, 6)

    B, Smax = 2, 24
    q1 = _rand(kq, (B, 1, H, hd))
    k = _rand(kk, (B, Smax, KV, hd))
    v = _rand(kv_, (B, Smax, KV, hd))
    kv_len = jnp.asarray([17, 9], jnp.int32)

    ps, n_pages, blocks = 8, 12, 3
    kpool = _rand(kkp, (n_pages, ps, KV, hd))
    vpool = _rand(kvp, (n_pages, ps, KV, hd))
    bt = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    pkv_len = jnp.asarray([ps * blocks - 3, ps + 1], jnp.int32)

    Sq = 12
    qp = _rand(kp, (B, Sq, H, hd))
    kpre = k[:, :Sq]
    vpre = v[:, :Sq]
    pad_lens = jnp.asarray([0, 3], jnp.int32)

    def run(plan):
        dec = jax.jit(lambda: plan.attention_decode(
            q1, k, v, kv_len=kv_len, scale=scale))
        paged = jax.jit(lambda: plan.attention_decode(
            q1, kpool, vpool, kv_len=pkv_len, scale=scale,
            block_table=bt, page_size=ps))
        causal = jax.jit(lambda: plan.attention_prefill(
            qp, kpre, vpre, scale=scale, q_offset=0, kind="causal",
            window=None, chunk=None))
        padded = jax.jit(lambda: plan.attention_prefill(
            qp, kpre, vpre, scale=scale, q_offset=0, kind="causal",
            window=None, chunk=None, pad_lens=pad_lens))
        return {"decode": dec(), "paged_decode": paged(),
                "prefill_causal": causal(), "prefill_padded": padded()}

    ref_plan = resolve_plan(cfg, _mesh_exec(0))
    assert "tp" not in ref_plan.backend("attention_decode")
    ref = run(ref_plan)

    gqa = KV < H
    for ms in MESH_SIZES:
        plan = resolve_plan(cfg, _mesh_exec(ms))
        dec_backend = plan.backend("attention_decode")
        if ms > 1:
            want = "raceit_gqa_tp" if gqa else "raceit_fused_tp"
            assert dec_backend == want, (ms, dec_backend)
            assert plan.backend("attention_prefill") == "raceit_fused_tp"
        else:
            assert dec_backend == ref_plan.backend("attention_decode")
        out = run(plan)
        for name in ref:
            check = (_assert_bitwise if name.endswith("decode")
                     else _assert_ulp)
            check(ref[name], out[name], f"{cfg.name} model={ms} {name}")
        print(f"  {cfg.name}: model={ms} bitwise ok "
              f"({dec_backend})", flush=True)


def _token_parity(cfg):
    from repro.models import Model
    from repro.serve import GenerationEngine

    params = Model(cfg).init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(11)
    prompts = jnp.asarray(rng.integers(0, 256, (2, 12)), jnp.int32)

    ref_eng = GenerationEngine(cfg, params, exec_cfg=_mesh_exec(0),
                               max_len=32)
    ref = ref_eng.generate(prompts, n_new=6)
    eng = GenerationEngine(cfg, params, exec_cfg=_mesh_exec(4), max_len=32)
    assert eng.plan.backend("attention_decode").endswith("_tp")
    out = eng.generate(prompts, n_new=6)
    assert np.array_equal(ref, out), (
        f"{cfg.name}: greedy tokens diverged on model=4\n{ref}\n{out}")
    print(f"  {cfg.name}: engine tokens identical on model=4", flush=True)


def _paged_soak(cfg, n_traces=3):
    from repro.models import Model
    from repro.serve import ContinuousBatcher, GenerationEngine, Request

    PS, N_SLOTS, N_PAGES = 8, 3, 13
    params = Model(cfg).init(jax.random.PRNGKey(0))
    engines = {ms: GenerationEngine(cfg, params, exec_cfg=_mesh_exec(ms),
                                    max_len=64) for ms in (0, 4)}

    def trace(eng, seed):
        cb = ContinuousBatcher(eng, n_slots=N_SLOTS, page_size=PS,
                               n_pages=N_PAGES)
        assert cb.paged
        rng = np.random.default_rng(seed)
        for rid in range(int(rng.integers(3, 6))):
            L = int(rng.integers(1, 3 * PS))
            prompt = np.random.default_rng(1000 + L).integers(
                0, 256, size=L, dtype=np.int64).tolist()
            cb.submit(Request(rid, prompt, n_new=int(rng.integers(1, 5))))
        steps = 0
        while cb.queue or any(s is not None for s in cb.slots):
            cb.step()
            steps += 1
            assert steps < 500, "soak trace failed to drain"
            cb.allocator.assert_invariants()
        return cb, {rid: [int(t) for t in r.result]
                    for rid, r in cb.done.items()}

    for seed in range(n_traces):
        cb_ref, ref = trace(engines[0], seed)
        cb_tp, out = trace(engines[4], seed)
        assert ref == out, (
            f"soak trace {seed}: paged tokens diverged on model=4 mesh\n"
            f"ref={ref}\ntp={out}")
        s = cb_tp.summary()
        assert s["mesh"] == "model=4" and s["decode_backend"].endswith("_tp")
        assert "mesh" not in cb_ref.summary()
        print(f"  soak trace {seed}: {len(ref)} requests identical "
              f"(backend {s['decode_backend']})", flush=True)


def main():
    mode = sys.argv[1] if len(sys.argv) > 1 else "ops"
    assert len(jax.devices()) == 8, jax.devices()
    if mode == "ops":
        for cfg in (MHA, GQA):
            _op_parity(cfg)
        print("PARITY_OK")
    elif mode == "soak":
        for cfg in (MHA, GQA):
            _token_parity(cfg)
        _paged_soak(GQA)
        print("SOAK_OK")
    else:
        raise SystemExit(f"unknown mode {mode!r}")


if __name__ == "__main__":
    main()
