"""Substrate: checkpointing, fault-tolerant loop, data pipeline, serving,
gradient compression, hw simulator sanity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import CheckpointManager
from repro.configs import get_config
from repro.data import SyntheticLM
from repro.dist.compress import ef_compress_update
from repro.models import Model
from repro.serve import BatchScheduler, GenerationEngine, Request
from repro.train import TrainLoopConfig, optim, run_training, trainer

from conftest import tiny_config


# ------------------------------------------------------------- checkpointing
def test_checkpoint_roundtrip(tmp_path, key):
    mgr = CheckpointManager(tmp_path, keep=2)
    tree = {"a": jnp.arange(5.0), "b": {"c": jnp.ones((2, 3), jnp.bfloat16)}}
    mgr.save(10, tree, extra={"step": 10})
    restored, extra = mgr.restore(tree)
    assert extra["step"] == 10
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_checkpoint_retention_and_async(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    tree = {"w": jnp.zeros(3)}
    for s in (1, 2, 3, 4):
        mgr.save(s, tree, block=False)
    mgr.wait()
    assert mgr.steps() == [3, 4]


def test_loop_resume_exact(tmp_path, key):
    """Kill the loop mid-run; resuming reproduces the uninterrupted run."""
    cfg = tiny_config(get_config("olmo-1b"))
    model = Model(cfg)
    params0 = model.init(key)
    opt0 = optim.adamw_init(params0)
    step = jax.jit(trainer.make_train_step(model, optim.AdamWConfig(lr=1e-3)))

    def train_to(steps, ckpt_dir, params, opt):
        data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=16,
                           global_batch=2, seed=7)
        lc = TrainLoopConfig(steps=steps, ckpt_dir=str(ckpt_dir),
                             ckpt_every=5, log_every=100)
        return run_training(
            step, params, opt, data, lc,
            make_batch=lambda b: {k: jnp.asarray(v) for k, v in b.items()},
            log=lambda *a: None)

    # uninterrupted 10 steps
    p_full, _, out_full = train_to(10, tmp_path / "full", params0, opt0)
    # interrupted: 5 steps, then resume to 10 in a fresh call
    p_half, o_half, _ = train_to(5, tmp_path / "resume", params0, opt0)
    p_res, _, out_res = train_to(10, tmp_path / "resume", params0, opt0)
    assert out_res["final_step"] == 10
    for a, b in zip(jax.tree.leaves(p_full), jax.tree.leaves(p_res)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)


def test_elastic_restore_resharding(tmp_path):
    """Restore under a different sharding layout (elastic restart)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mgr = CheckpointManager(tmp_path)
    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    mgr.save(1, tree)
    from repro.dist.sharding import make_mesh
    mesh = make_mesh((1,), ("model",))
    shard = {"w": NamedSharding(mesh, P("model", None))}
    restored, _ = mgr.restore(tree, shardings=shard)
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(tree["w"]))
    assert restored["w"].sharding.is_equivalent_to(shard["w"], 2)


# -------------------------------------------------------------------- data
def test_data_deterministic_and_skippable():
    d1 = SyntheticLM(seq_len=8, global_batch=4, seed=1)
    d2 = SyntheticLM(seq_len=8, global_batch=4, seed=1)
    a = d1.next_batch()
    b = d1.next_batch()
    d2.skip(1)
    b2 = d2.next_batch()
    np.testing.assert_array_equal(b["tokens"], b2["tokens"])
    assert not np.array_equal(a["tokens"], b["tokens"])


def test_data_sharding_partitions_batch():
    shards = [SyntheticLM(seq_len=8, global_batch=4, seed=1, shard_index=i,
                          shard_count=2) for i in range(2)]
    b0, b1 = (s.next_batch()["tokens"] for s in shards)
    assert b0.shape == (2, 8)
    assert not np.array_equal(b0, b1)


# ----------------------------------------------------------------- serving
def test_generation_engine_and_batching(key):
    cfg = tiny_config(get_config("gpt2-large"))
    model = Model(cfg)
    params = model.init(key)
    eng = GenerationEngine(cfg, params, max_len=64)
    sched = BatchScheduler(eng, bucket_size=2)
    rng = np.random.default_rng(0)
    for rid in range(3):
        sched.submit(Request(rid, rng.integers(0, 255, 5).astype(np.int32),
                             n_new=4))
    done = sched.run_all()
    assert sorted(done) == [0, 1, 2]
    for r in done.values():
        assert r.result.shape == (4,)
        assert (r.result >= 0).all() and (r.result < cfg.vocab_size).all()


def test_generation_matches_decode_path(key):
    """Greedy generate == manual argmax rollout through forward()."""
    cfg = tiny_config(get_config("olmo-1b"))
    model = Model(cfg)
    params = model.init(key)
    eng = GenerationEngine(cfg, params, max_len=32)
    prompt = jax.random.randint(key, (1, 6), 0, cfg.vocab_size)
    gen = eng.generate(prompt, n_new=5)
    toks = np.asarray(prompt)
    for t in range(5):
        logits = model.forward(params, {"tokens": jnp.asarray(toks)},
                               use_remat=False)
        nxt = int(jnp.argmax(logits[0, -1]))
        assert nxt == int(gen[0, t]), (t, nxt, gen)
        toks = np.concatenate([toks, [[nxt]]], axis=1)


# ------------------------------------------------------------- compression
def test_error_feedback_compression_converges():
    g = jnp.asarray(np.random.default_rng(0).normal(0, 1, (64,)), jnp.float32)
    residual = None
    acc = jnp.zeros_like(g)
    for _ in range(50):
        _, restored, residual = ef_compress_update({"g": g}, residual, "int8")
        acc = acc + restored["g"]
    # time-averaged compressed gradient converges to the true gradient
    np.testing.assert_allclose(np.asarray(acc / 50), np.asarray(g),
                               atol=2e-2)


# ------------------------------------------------------------- hw simulator
def test_simulator_reproduces_paper_ordering():
    from repro.hw.simulator import Workload, simulate
    w = Workload.from_config(get_config("bert-base"))
    r = {a: simulate(w, a) for a in ("raceit", "puma", "retransformer")}
    assert (r["raceit"]["tokens_per_s"] > r["retransformer"]["tokens_per_s"]
            > r["puma"]["tokens_per_s"])
    sp = r["raceit"]["tokens_per_s"] / r["puma"]["tokens_per_s"]
    assert 4.5 < sp < 7.5  # paper: 5.9x
    en = (r["puma"]["energy_per_token_uj"]
          / r["raceit"]["energy_per_token_uj"])
    assert 3.0 < en < 5.0  # paper: 3.9x
    assert abs(r["raceit"]["tops"] - 110.11) / 110.11 < 0.05  # Table V


def test_k_sweep_plateau_contains_paper_choice():
    from repro.hw.gce import k_sweep, optimal_k_range
    rows = k_sweep(get_config("bert-base"), seq_len=384)
    lo, hi = optimal_k_range(rows, 0.15)
    assert lo <= 28.3 <= hi  # the paper's design point is inside our plateau
