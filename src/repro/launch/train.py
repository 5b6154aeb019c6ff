"""Distributed training launcher (pjit on the production mesh).

On real hardware this runs under `jax.distributed.initialize()`; here it
drives the same code path on however many devices exist. The dry-run
(`dryrun.py`) is the compile-only proof for the 256/512-chip meshes.

  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --steps 20 \
      --set n_layers=2 d_model=128 vocab_size=512 --data 1 --model 1
"""
from __future__ import annotations

import argparse
import json


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="results/launch_train")
    ap.add_argument("--set", nargs="*", default=[])
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.configs import get_config
    from repro.data import SyntheticLM
    from repro.dist.sharding import (MeshContext, ShardingPolicy,
                                     named_sharding_tree, param_specs,
                                     use_policy)
    from repro.launch.mesh import make_host_mesh
    from repro.models import Model
    from repro.train import (TrainLoopConfig, optim, run_training, trainer)

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

    mesh = make_host_mesh(data=args.data, model=args.model)
    policy = ShardingPolicy(mesh)
    mctx = MeshContext(mesh)
    model = Model(cfg, mesh_ctx=mctx)

    with use_policy(policy, mctx):
        params = model.init(jax.random.PRNGKey(0))
        pspecs = param_specs(params, cfg, policy)
        params = jax.device_put(params, named_sharding_tree(pspecs, mesh))
        opt_state = optim.adamw_init(params)
        step = jax.jit(trainer.make_train_step(
            model, optim.AdamWConfig(lr=3e-4,
                                     schedule=optim.warmup_cosine(20, args.steps))))
        data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=args.seq,
                           global_batch=args.batch, seed=0)
        params, opt_state, out = run_training(
            step, params, opt_state, data,
            TrainLoopConfig(steps=args.steps, ckpt_dir=args.ckpt_dir,
                            ckpt_every=max(10, args.steps // 4)),
            make_batch=lambda b: {k: jnp.asarray(v) for k, v in b.items()})
    h = out["history"]
    if h:
        print(f"[train] {args.arch}: step {out['final_step']} "
              f"loss {h[0]['loss']:.3f} -> {h[-1]['loss']:.3f}")


if __name__ == "__main__":
    main()
