"""Chip smoke test: serve gpt2-large on a TPU through the normal launcher.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the tensor-parallel phase, four chips

One process drives every phase. With no arguments (one chip):

1. gpt2-large at full width (random weights from seed 0) is served through
   `repro.launch.serve` with paged continuous batching, once with
   ``--mode digital`` and once with ``--mode raceit_q8``. Every request must
   finish without a `RequestError`, and the raceit plan must run attention
   on the fused Pallas kernels (``attention_decode -> raceit_fused_paged``).
2. The compiled fused kernels at gpt2-large attention width (20 heads,
   head_dim 64, 64-row pages) are compared with the staged oracle
   (`repro.core.attention.raceit_attention`) on the same inputs: one paged
   decode step, one paged prefill chunk, and one causal prefill. Each must
   be bitwise equal.

With ``--chips 4`` only the path that exists across chips runs: gpt2-large
served at ``--mesh model=4`` (tensor-parallel attention), and the
tensor-parallel paged decode compared bitwise with the one-device kernel on
the same operands (the paged prefill chunk's difference is reported).

Wall times printed here are smoke timings (compilation included), not
benchmark numbers. The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
any failure exits non-zero before it is printed, as does a run that finds
no TPU.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

N_REQUESTS = 4
N_NEW = 8


def _log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def _require(ok, what) -> None:
    """A smoke check; unlike `assert`, it also holds under ``python -O``."""
    if not ok:
        raise AssertionError(what)


def _require_tpu(n_chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX platform is "
                 f"{devices[0].platform!r}); nothing was run")
    if len(devices) < n_chips:
        sys.exit(f"chip_smoke: --chips {n_chips} needs {n_chips} TPU "
                 f"devices, found {len(devices)}")
    return devices


def _serve(mode: str, extra: tuple = ()):
    """One launcher run; returns (engine, scheduler, done, seconds)."""
    from repro.launch import serve as launcher
    argv = ["--arch", "gpt2-large", "--continuous", "--mode", mode,
            "--requests", str(N_REQUESTS), "--n-new", str(N_NEW), *extra]
    _log(f"serving: python -m repro.launch.serve {' '.join(argv)}")
    t0 = time.perf_counter()
    eng, sched, done = launcher.serve(launcher.build_parser().parse_args(argv))
    seconds = time.perf_counter() - t0
    _require(sched.paged, "continuous serving fell back to contiguous slots")
    _require(sorted(done) == list(range(N_REQUESTS)), sorted(done))
    for rid, req in sorted(done.items()):
        _require(req.error is None, f"req{rid}: {req.error}")
        toks = [int(t) for t in req.result]
        _require(len(toks) == N_NEW
                 and all(0 <= t < eng.cfg.vocab_size for t in toks),
                 f"req{rid}: tokens {toks}")
    _log(f"smoke timing: {mode} served {N_REQUESTS * N_NEW} tokens in "
         f"{seconds:.1f} s wall (compilation included), "
         f"{sched.chunk_calls} chunk calls, {sched.decode_steps} decode "
         f"steps")
    return eng, sched, done, seconds


def _assert_bitwise(got, want, what: str) -> None:
    import numpy as np
    got, want = np.asarray(got), np.asarray(want)
    _require(got.shape == want.shape, f"{what}: {got.shape} vs {want.shape}")
    _require(np.isfinite(got).all(), f"{what}: non-finite kernel output")
    if not np.array_equal(got, want):
        ulp = np.abs(got.view(np.int32).astype(np.int64)
                     - want.view(np.int32).astype(np.int64))
        raise AssertionError(
            f"{what}: {int((ulp > 0).sum())} of {got.size} values differ, "
            f"max {int(ulp.max())} ulp, max abs diff "
            f"{float(np.abs(got - want).max()):.3e}")
    _log(f"{what}: bitwise equal ({got.size} values)")


def _paged_case(seed: int, B: int, H: int, KV: int, hd: int, ps: int,
                max_pages: int, lens):
    """Random contiguous k/v (B, S, KV, hd) and the same live entries
    scattered into a shuffled page pool (page 0 is the trash page)."""
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(seed)
    S = ps * max_pages
    k = np.zeros((B, S, KV, hd), np.float32)
    v = np.zeros((B, S, KV, hd), np.float32)
    for b, n in enumerate(lens):
        k[b, :n] = rng.normal(0, 1.5, (n, KV, hd))
        v[b, :n] = rng.normal(0, 1.5, (n, KV, hd))
    n_pages = 1 + B * max_pages
    pool_k = rng.normal(0, 1.5, (n_pages, ps, KV, hd)).astype(np.float32)
    pool_v = rng.normal(0, 1.5, (n_pages, ps, KV, hd)).astype(np.float32)
    order = rng.permutation(np.arange(1, n_pages))
    bt = np.zeros((B, max_pages), np.int32)
    for b in range(B):
        for j in range(max_pages):
            bt[b, j] = order[b * max_pages + j]
            pool_k[bt[b, j]] = k[b, j * ps:(j + 1) * ps]
            pool_v[bt[b, j]] = v[b, j * ps:(j + 1) * ps]
    return (jnp.asarray(k), jnp.asarray(v), jnp.asarray(pool_k),
            jnp.asarray(pool_v), jnp.asarray(bt),
            jnp.asarray(lens, jnp.int32))


def _kernels_vs_oracle(cfg) -> None:
    """The compiled fused kernels against the staged oracle, bitwise."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.attention import raceit_attention
    from repro.kernels import default_interpret
    from repro.kernels.ops import (raceit_attention_decode_paged,
                                   raceit_attention_fused)
    _require(not default_interpret(), "Pallas kernels would run interpreted")
    H, hd, ps = cfg.n_heads, cfg.resolved_head_dim, 64
    L, chunk, off = 100, 64, 36
    k, v, pk, pv, bt, lens = _paged_case(1, 1, H, H, hd, ps, 2, (L,))
    rng = np.random.default_rng(2)
    q1 = jnp.asarray(rng.normal(0, 1.5, (1, H, 1, hd)), jnp.float32)
    heads = lambda a, n: a[:, :n].transpose(0, 2, 1, 3)  # (B, H, n, hd)

    # one paged decode step: the new token attends the first L keys
    got = raceit_attention_decode_paged(q1, pk, pv, lens, bt)
    want = raceit_attention(q1, heads(k, L), heads(v, L))
    _assert_bitwise(got, want, f"paged decode step (H={H}, hd={hd}, "
                               f"kv_len={L}, page {ps})")

    # one paged prefill chunk: query j attends keys < off + j + 1
    qc = jnp.asarray(rng.normal(0, 1.5, (1, H, chunk, hd)), jnp.float32)
    n = off + chunk
    mask = (jnp.arange(2 * ps)[None, :]
            < off + jnp.arange(chunk)[:, None] + 1)[None]
    got = raceit_attention_decode_paged(
        qc, pk, pv, jnp.asarray([n], jnp.int32), bt, mask=mask)
    want = raceit_attention(qc, heads(k, n), heads(v, n),
                            mask=mask[:, None, :, :n])
    _assert_bitwise(got, want, f"paged prefill chunk (Sq={chunk}, "
                               f"offset {off})")

    # causal fused prefill (the attention_prefill slot's kernel)
    S = 128
    qp = jnp.asarray(rng.normal(0, 1.5, (1, H, S, hd)), jnp.float32)
    got = raceit_attention_fused(qp, heads(k, S), heads(v, S), causal=True)
    causal = jnp.tril(jnp.ones((S, S), bool))
    want = raceit_attention(qp, heads(k, S), heads(v, S), mask=causal)
    _assert_bitwise(got, want, f"causal fused prefill (S={S})")


def _tp_vs_one_device(cfg) -> None:
    """TP paged decode on a model=4 mesh vs the one-device kernel on the
    same operands, bitwise; the paged chunk's difference is reported."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import ExecConfig
    from repro.dist import MeshSpec
    from repro.exec.plan import resolve_plan

    H, hd, ps, B = cfg.n_heads, cfg.resolved_head_dim, 64, 4
    _, _, pk, pv, bt, lens = _paged_case(3, B, H, H, hd, ps, 2,
                                         (128, 100, 64, 1))
    rng = np.random.default_rng(4)
    scale = 1.0 / float(np.sqrt(hd))
    q1 = jnp.asarray(rng.normal(0, 1.5, (B, 1, H, hd)), jnp.float32)
    qc = jnp.asarray(rng.normal(0, 1.5, (B, 64, H, hd)), jnp.float32)
    offs = jnp.asarray([64, 36, 0, 0], jnp.int32)
    chunk_mask = (jnp.arange(2 * ps)[None, None, :]
                  < offs[:, None, None] + jnp.arange(64)[None, :, None] + 1)
    one = resolve_plan(cfg, ExecConfig.serving(mode="raceit"))
    tp = resolve_plan(cfg, ExecConfig.serving(
        mode="raceit", mesh=MeshSpec.parse("model=4")))
    _require(one.backend("attention_decode") == "raceit_fused_paged",
             one.explain())
    _require(tp.backend("attention_decode") == "raceit_fused_tp",
             tp.explain())

    # operands are arguments, as in serving: closed-over constants would
    # let XLA fold the quantizers on the host, with a different f32 divide
    def run(plan):
        dec = jax.jit(lambda q, k, v, n, t: plan.attention_decode(
            q, k, v, kv_len=n, scale=scale, block_table=t, page_size=ps))
        chk = jax.jit(lambda q, k, v, n, t, m: plan.attention_decode(
            q, k, v, kv_len=n, scale=scale, pad_valid=m, block_table=t,
            page_size=ps))
        return (dec(q1, pk, pv, lens, bt),
                chk(qc, pk, pv, jnp.minimum(offs + 64, lens.max()), bt,
                    chunk_mask))

    (d1, c1), (d4, c4) = run(one), run(tp)
    _assert_bitwise(d4, d1, "TP paged decode model=4 vs one device")
    # Reported, not asserted: TP parity is claimed bitwise for decode only
    # (prefill to <= 4 ulp on simulated devices); the TPU's f32 divide is
    # not correctly rounded, so a quantizer code can differ between the
    # two programs' fusions.
    c1, c4 = np.asarray(c1), np.asarray(c4)
    _require(np.isfinite(c4).all(), "TP paged chunk: non-finite output")
    _log(f"TP paged chunk model=4 vs one device (not asserted): "
         f"{int((c1 != c4).sum())} of {c1.size} values differ, max abs "
         f"diff {float(np.abs(c1 - c4).max()):.3e}")


def _plan_lines(eng) -> None:
    for line in eng.explain_plan().splitlines():
        _log(f"plan: {line}")


def one_chip() -> None:
    from repro.configs import get_config
    cfg = get_config("gpt2-large")
    for mode in ("digital", "raceit_q8"):
        eng, sched, done, _ = _serve(mode)
        if mode == "raceit_q8":
            _plan_lines(eng)
            _require(eng.plan.backend("attention_decode")
                     == "raceit_fused_paged"
                     and eng.plan.backend("attention_prefill")
                     == "raceit_fused", eng.explain_plan())
        del eng, sched, done
        gc.collect()
    _kernels_vs_oracle(cfg)


def four_chips() -> None:
    from repro.configs import get_config
    cfg = get_config("gpt2-large")
    eng, sched, done, _ = _serve("raceit_q8", ("--mesh", "model=4"))
    _plan_lines(eng)
    _require(eng.plan.backend("attention_decode") == "raceit_fused_tp",
             eng.explain_plan())
    del eng, sched, done
    gc.collect()
    _tp_vs_one_device(cfg)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve + kernels vs oracle on one chip; "
                         "4: only the tensor-parallel phase")
    args = ap.parse_args()

    devices = _require_tpu(args.chips)
    dev = devices[0]
    _log(f"device: {dev.platform} / {dev.device_kind}, "
         f"{len(devices)} visible, using {args.chips}")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    _log(f"compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)()
    _log(f"smoke timing: all phases {time.perf_counter() - t0:.1f} s wall")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}))


if __name__ == "__main__":
    main()
