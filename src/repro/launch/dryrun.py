"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves the distribution config is coherent on the
production mesh (16x16 single-pod / 2x16x16 multi-pod) and records:
memory_analysis (fits HBM?), XLA cost_analysis, and our trip-count-aware HLO
cost (flops / HBM bytes / collective bytes by type) for §Roofline.

Usage:
  python -m repro.launch.dryrun --arch llama4-scout-17b-a16e --shape train_4k \
      --mesh single --out results/dryrun.json
  python -m repro.launch.dryrun --all             # every valid cell, both meshes

The CLI compiles on 512 simulated host devices (``DRYRUN_XLA_FLAGS``
overrides the XLA flags); importing the module changes nothing.
"""

import argparse
import json
import os
import time
import traceback
from pathlib import Path


def valid_cells(arch_names=None, shape_names=None):
    """The assigned 40-cell grid, minus skips documented in DESIGN.md §5."""
    from repro.configs import SHAPES, get_config
    from repro.configs.catalog import ASSIGNED

    cells = []
    for arch in arch_names or ASSIGNED:
        cfg = get_config(arch)
        for shp in shape_names or list(SHAPES):
            shape = SHAPES[shp]
            if shape.kind == "decode" and cfg.family == "encoder":
                cells.append((arch, shp, "skip:encoder-only, no decode step"))
                continue
            if shp == "long_500k" and not cfg.supports_long_context:
                cells.append((arch, shp, "skip:full-attention at 500k (DESIGN §5)"))
                continue
            cells.append((arch, shp, None))
    return cells


def run_cell(arch: str, shape_name: str, mesh_kind: str, mode: str = "digital",
             overrides: dict | None = None) -> dict:
    import jax
    from repro.configs import SHAPES, get_config
    from repro.configs.base import ExecConfig
    from repro.dist.sharding import MeshContext, use_policy
    from repro.launch import hlo_analysis, inputs
    from repro.launch.mesh import (PRODUCTION_DEVICE_KIND, chip_peaks,
                                   make_production_mesh)
    from repro.models import Model
    from repro.train import optim, trainer

    t0 = time.time()
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = mesh.size

    policy = inputs.make_policy(mesh, cfg, shape)
    mesh_ctx = MeshContext(mesh)
    exec_cfg = ExecConfig(mode="raceit" if mode.startswith("raceit") else mode)
    model = Model(cfg, exec_cfg, mesh_ctx)

    with use_policy(policy, mesh_ctx):
        spec = inputs.input_specs(cfg, shape, policy, model,
                                  quantize=(mode == "raceit_q8"))
        if shape.kind == "train":
            step = trainer.make_train_step(model, optim.AdamWConfig(
                schedule=optim.warmup_cosine(100, 10_000)))
            args = (spec["params"], spec["opt_state"], spec["batch"])
            jitted = jax.jit(step, donate_argnums=(0, 1))
        elif shape.kind == "prefill":
            if cfg.family == "encoder":
                step = lambda params, batch: model.forward(params, batch,
                                                           use_remat=False)
                args = (spec["params"], spec["batch"])
            else:
                def step(params, batch, cache):
                    return model.prefill(params, batch["tokens"], cache,
                                         enc_feats=batch.get("enc_feats"))
                args = (spec["params"], spec["batch"], spec["cache"])
            jitted = jax.jit(step)
        else:  # decode (serve_step: one new token against the KV/SSM cache)
            def step(params, token, cache):
                return model.decode_step(params, token, cache)
            args = (spec["params"], spec["token"], spec["cache"])
            jitted = jax.jit(step, donate_argnums=(2,))

        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = hlo_analysis.analyze_hlo(compiled.as_text())

    mf = inputs.model_flops(cfg, spec["params"], shape)
    bytes_per_device = (ma.argument_size_in_bytes + ma.temp_size_in_bytes +
                        ma.output_size_in_bytes - ma.alias_size_in_bytes)
    peaks = chip_peaks(PRODUCTION_DEVICE_KIND)
    compute_s = hlo.flops / peaks.bf16_flops
    memory_s = hlo.memory_bytes / peaks.hbm_bytes_per_s
    collective_s = hlo.collective_bytes / peaks.ici_link_bytes_per_s
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dominant = max(terms, key=terms.get)

    return {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind, "mode": mode,
        "status": "ok", "n_chips": n_chips,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "per_device_bytes": bytes_per_device,
            "fits_16GB": bool(bytes_per_device < peaks.hbm_bytes),
        },
        "xla_cost": {k: ca.get(k) for k in ("flops", "bytes accessed")},
        "hlo": hlo.to_dict(),
        "model_flops_global": mf,
        "model_flops_per_chip": mf / n_chips,
        "useful_flops_ratio": (mf / n_chips) / hlo.flops if hlo.flops else None,
        "roofline": {**terms, "dominant": dominant,
                     "bound_s": max(terms.values()),
                     "roofline_fraction": (mf / n_chips / peaks.bf16_flops)
                                          / max(max(terms.values()), 1e-30)},
    }


def main():
    os.environ["XLA_FLAGS"] = (os.environ.get("DRYRUN_XLA_FLAGS")
                               or "--xla_force_host_platform_device_count=512")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--mode", default="digital",
                    choices=["digital", "raceit", "raceit_q8"])
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (repeatable)")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        try:
            v = json.loads(v)
        except json.JSONDecodeError:
            pass
        overrides[k] = v

    out_path = Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    results = {}
    if out_path.exists():
        results = json.loads(out_path.read_text())

    if args.all:
        cells = [(a, s, skip, m)
                 for (a, s, skip) in valid_cells()
                 for m in ("single", "multi")]
    else:
        cells = [(args.arch, args.shape, None, args.mesh)]

    for arch, shp, skip, mesh_kind in cells:
        key = f"{arch}|{shp}|{mesh_kind}|{args.mode}"
        if key in results and results[key].get("status") in ("ok", "skipped"):
            continue
        if skip:
            results[key] = {"arch": arch, "shape": shp, "mesh": mesh_kind,
                            "status": "skipped", "reason": skip}
        else:
            print(f"=== {key}", flush=True)
            try:
                results[key] = run_cell(arch, shp, mesh_kind, args.mode,
                                        overrides or None)
                r = results[key]
                print(f"    ok: compile={r['compile_s']}s "
                      f"mem/dev={r['memory']['per_device_bytes']/1e9:.2f}GB "
                      f"dominant={r['roofline']['dominant']} "
                      f"frac={r['roofline']['roofline_fraction']:.3f}", flush=True)
            except Exception as e:  # noqa: BLE001 — record and continue
                results[key] = {"arch": arch, "shape": shp, "mesh": mesh_kind,
                                "status": "error", "error": str(e),
                                "traceback": traceback.format_exc()[-4000:]}
                print(f"    ERROR: {e}", flush=True)
        out_path.write_text(json.dumps(results, indent=1))


if __name__ == "__main__":
    main()
