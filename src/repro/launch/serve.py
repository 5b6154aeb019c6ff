"""Serving launcher: load (or init) a model and serve batched requests,
optionally through the RACE-IT analog-faithful path with resident int8
crossbar weights.

  PYTHONPATH=src python -m repro.launch.serve --arch gpt2-large --mode raceit_q8 \
      --set n_layers=2 d_model=128 vocab_size=512 --requests 4

Operator dispatch is a resolved ExecPlan (printed at startup): A/B runs
name backends per op slot instead of flipping booleans, e.g.

  --exec-plan attention_decode=raceit_staged lm_head=raceit_q8
"""
from __future__ import annotations

import argparse
import json


def parse_tenant_weights(pairs: list[str]) -> dict:
    """["tenant=weight", ...] -> AdmissionRouter weights dict."""
    weights = {}
    for pair in pairs:
        tenant, _, w = pair.partition("=")
        try:
            weights[tenant] = float(w)
        except ValueError:
            w = ""
        if not tenant or not w:
            raise SystemExit(f"--tenant-weights entries are tenant=weight, "
                             f"got {pair!r}")
    return weights


def parse_exec_plan(pairs: list[str]) -> tuple:
    """["slot=backend", ...] -> ExecConfig.op_overrides tuple."""
    overrides = []
    for pair in pairs:
        slot, _, backend = pair.partition("=")
        if not slot or not backend:
            raise SystemExit(f"--exec-plan entries are slot=backend, got "
                             f"{pair!r}")
        overrides.append((slot, backend))
    return tuple(overrides)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--mode", default="digital",
                    choices=["digital", "raceit", "raceit_q8"])
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--n-new", type=int, default=8)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--continuous", action="store_true",
                    help="serve with the slot-level continuous batcher "
                         "(per-request kv_len decode, retire-and-admit "
                         "mid-stream, one compiled decode step) instead of "
                         "the bucketed scheduler")
    ap.add_argument("--slots", type=int, default=4,
                    help="slot-pool size (--continuous) / bucket size")
    ap.add_argument("--page-size", type=int, default=64,
                    help="KV page granularity for block-paged continuous "
                         "serving (the --continuous default on decoder-only "
                         "all-attention models): the slot pool becomes a "
                         "page pool and prompts stream into their slot in "
                         "chunks instead of one pinned-width prefill")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="tokens streamed per slot per step while a prompt "
                         "is mid-prefill (paged mode; default: --page-size)")
    ap.add_argument("--prefill-len", type=int, default=None,
                    help="pin the contiguous admission-prefill width "
                         "(opts OUT of paged serving; prompts are then "
                         "capped at this width)")
    ap.add_argument("--router", default="fifo",
                    choices=["fifo", "priority", "wfq"],
                    help="admission policy across tenants (--continuous): "
                         "global arrival order, strict priority by tenant "
                         "weight, or weighted-fair deficit round-robin on "
                         "a token budget")
    ap.add_argument("--tenant-weights", nargs="*", default=[],
                    metavar="TENANT=WEIGHT",
                    help="tenant weights for --router priority/wfq, e.g. "
                         "'paid=3 free=1'; the synthetic request trace "
                         "round-robins over the named tenants (default: "
                         "one 'default' tenant at weight 1)")
    ap.add_argument("--tenant-cap", type=int, default=None,
                    help="per-tenant queue-depth cap: submits past it are "
                         "rejected with a structured admit-stage error")
    ap.add_argument("--prefix-cache", dest="prefix_cache",
                    action="store_true", default=None,
                    help="share identical prompt-prefix KV pages across "
                         "requests (content-addressed, refcounted; the "
                         "paged default)")
    ap.add_argument("--no-prefix-cache", dest="prefix_cache",
                    action="store_false",
                    help="disable prompt-prefix KV page sharing")
    ap.add_argument("--staged-attention", action="store_true",
                    help="opt out of the fused-attention serving default "
                         "(sugar for --exec-plan attention_prefill="
                         "raceit_staged attention_decode=raceit_staged)")
    ap.add_argument("--exec-plan", nargs="*", default=[], metavar="SLOT=BACKEND",
                    help="pin op slots to named backends (see "
                         "repro.exec.registry.OP_SLOTS); unsupported combos "
                         "degrade and the startup plan table says why")
    ap.add_argument("--noise", default=None, metavar="PRESET|SIGMA",
                    help="serve on device-varied analog arrays: a "
                         "repro.hw.noise preset (clean/nominal/worst_case) "
                         "or a float scale of the nominal profile; routes "
                         "the raceit slots to the raceit_noisy_* backends "
                         "(fused kernels degrade, reason in the plan table)")
    ap.add_argument("--noise-seed", type=int, default=0,
                    help="device-variation seed (--noise); one seed = one "
                         "simulated chip, reproducibly")
    ap.add_argument("--mesh", default=None, metavar="AXES",
                    help="serve on a device mesh: '4' / 'model=4' / "
                         "'data=2,model=4' (repro.dist.MeshSpec syntax). "
                         "A 'model' axis shards attention heads and the "
                         "paged KV pool over the raceit_*_tp backends; "
                         "params load under FSDP specs when the config "
                         "sets fsdp=True. Simulate N devices on one host "
                         "with XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N")
    ap.add_argument("--set", nargs="*", default=[])
    return ap


def serve(args: argparse.Namespace) -> tuple:
    """Build the engine the arguments describe, serve the synthetic trace,
    and print the report; returns (engine, scheduler, finished requests)."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.configs.base import ExecConfig
    from repro.ckpt import CheckpointManager
    from repro.models import Model
    from repro.models.model import quantize_model_params
    from repro.serve import (BatchScheduler, ContinuousBatcher,
                             GenerationEngine, Request)

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        try:
            v = json.loads(v)
        except json.JSONDecodeError:
            pass
        overrides[k] = v
    cfg = get_config(args.arch).replace(
        param_dtype="float32", compute_dtype="float32", **overrides)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    if args.ckpt:
        (params, _), _ = CheckpointManager(args.ckpt).restore((params, None))
    # serving defaults to the fused streaming attention kernel on both the
    # prefill and decode paths (ExecConfig.serving); --exec-plan pins
    # individual op slots to named backends on top of that
    noise = None
    if args.noise is not None:
        from repro.hw.noise import NoiseConfig
        noise = NoiseConfig.parse(args.noise, seed=args.noise_seed)
        print(f"[serve] device noise: {noise}")
    mesh = None
    if args.mesh is not None:
        from repro.dist import MeshSpec
        mesh = MeshSpec.parse(args.mesh)
        print(f"[serve] device mesh: {mesh.describe()} "
              f"({mesh.n_devices} devices)")
    exec_cfg = ExecConfig.serving(
        mode="raceit" if args.mode.startswith("raceit") else "digital",
        fused_attention=not args.staged_attention,
        op_overrides=parse_exec_plan(args.exec_plan),
        noise=noise, mesh=mesh)
    if args.mode == "raceit_q8":
        params = quantize_model_params(params)
        print("[serve] weights quantized to resident int8 crossbar codes")

    eng = GenerationEngine(cfg, params, exec_cfg=exec_cfg, max_len=128)
    print("[serve] resolved execution plan:")
    print("\n".join("  " + l for l in eng.explain_plan().splitlines()))
    weights = parse_tenant_weights(args.tenant_weights)
    if args.continuous:
        sched = ContinuousBatcher(eng, n_slots=args.slots,
                                  prefill_len=args.prefill_len,
                                  page_size=args.page_size,
                                  prefill_chunk=args.prefill_chunk,
                                  router=args.router,
                                  tenant_weights=weights or None,
                                  tenant_cap=args.tenant_cap,
                                  prefix_cache=args.prefix_cache)
        if sched.paged:
            print(f"[serve] block-paged KV: page_size={sched.page_size}, "
                  f"prefill_chunk={sched.prefill_chunk}, "
                  f"{sched.n_pages} pages "
                  f"({sched.n_pages - 1} allocatable + trash); "
                  f"prefix cache "
                  f"{'on' if sched.prefix is not None else 'off'}")
        print(f"[serve] router: {sched.queue.policy}"
              + (f", weights {weights}" if weights else "")
              + (f", depth cap {args.tenant_cap}" if args.tenant_cap else ""))
    else:
        if (args.router != "fifo" or weights or args.tenant_cap is not None
                or args.prefix_cache is not None):
            raise SystemExit("--router/--tenant-weights/--tenant-cap/"
                             "--prefix-cache belong to the continuous "
                             "batcher; add --continuous")
        sched = BatchScheduler(eng, bucket_size=args.slots)
    # the synthetic trace round-robins requests over the named tenants so
    # the routing policies have traffic classes to arbitrate
    tenants = sorted(weights) or ["default"]
    rng = np.random.default_rng(0)
    for rid in range(args.requests):
        sched.submit(Request(rid, rng.integers(0, cfg.vocab_size,
                                               rng.integers(4, 9)).astype(np.int32),
                             n_new=args.n_new,
                             tenant=tenants[rid % len(tenants)]))
    done = sched.run_all()
    for rid in sorted(done):
        r = done[rid]
        if r.error is not None:  # fail-safe retirement (structured error)
            print(f"[serve] req{rid}: FAILED at {r.error.stage} "
                  f"step {r.error.step}: {r.error.reason}")
        else:
            print(f"[serve] req{rid}: {r.result.tolist()}")
    if args.continuous:
        occ = (sched.decode_tokens / sched.decode_steps
               if sched.decode_steps else float("nan"))
        extra = ""
        if sched.paged and sched.chunk_calls:
            fill = 100.0 * sched.chunk_fed_tokens / sched.chunk_slot_tokens
            extra = (f", {sched.chunk_calls} chunk calls ({fill:.1f}% of "
                     f"their slot rows fed)")
        print(f"[serve] continuous: {sched.prefills} prefills{extra}, "
              f"{sched.decode_steps} decode steps, "
              f"{occ:.2f} tokens/step occupancy")
        s = sched.summary()
        print(f"[serve] latency (steps): "
              f"ttft p50={s['ttft_p50']} p99={s['ttft_p99']} "
              f"(n={s['ttft_n']}); "
              f"per-token p50={s['tpl_p50']} p99={s['tpl_p99']} "
              f"(n={s['tpl_n']})")
        print(f"[serve] tenants: tokens {s['tenant_tokens']}, "
              f"fairness (Jain) {s['fairness_jain']:.3f}, "
              f"rejected {s['rejected']}, errored {s['errored']}")
        if sched.paged:
            print(f"[serve] pages: {s['pages_in_use']} private + "
                  f"{s['pages_shared']} shared in use, "
                  f"{s['pages_leaked']} leaked, {s['pages_free']} free "
                  f"(peak {s['pages_peak_in_use']} of "
                  f"{s['pages_allocatable']})")
            if sched.prefix is not None:
                print(f"[serve] prefix cache: "
                      f"{s['prefix_hit_pages']} hit / "
                      f"{s['prefix_miss_pages']} miss pages "
                      f"({s['prefix_hit_rate_pct']:.1f}% hit rate), "
                      f"{s['prefix_pages_saved']} pages saved, "
                      f"{s['prefix_promotions']} promotions, "
                      f"{s['prefix_evictions']} evictions, "
                      f"{s['prefix_entries']} entries resident")
    return eng, sched, done


def main(argv=None):
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    serve(build_parser().parse_args(argv))


if __name__ == "__main__":
    main()
