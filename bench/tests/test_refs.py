"""A configuration brings its own reference module (``bench/refs/<name>.py``),
and the harness and the metric readers reach the architecture only
through it; the dense default, `bench/model_ref`, makes and counts what it
did before the modules existed. Tiny sizes on the CPU."""
import dataclasses
import json
import shutil
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench import harness, model_ref, readings, roofline

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
SEED = 2 ** 33 + 5
GQA = {"name": "tiny-gqa.tiny-chat", "config": "tiny-gqa",
       "traffic": "tiny-chat", "chips": 1}
OLMO = json.loads((ROOT / "bench" / "configs" / "olmo-1b.json").read_text())


@pytest.fixture(scope="module")
def gqa_bench(tmp_path_factory):
    """A copy of bench/ plus the new files of one more architecture: its
    configuration and its reference module, and no other edit."""
    bd = tmp_path_factory.mktemp("files") / "bench"
    shutil.copytree(ROOT / "bench", bd,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(DATA / "tiny-gqa.json", bd / "configs" / "tiny-gqa.json")
    (bd / "refs").mkdir(exist_ok=True)
    shutil.copy(DATA / "tiny_gqa.py", bd / "refs" / "tiny_gqa.py")
    shutil.copy(DATA / "tiny-chat.json", bd / "traffic" / "tiny-chat.json")
    return bd


@pytest.fixture(scope="module")
def gqa_run(gqa_bench):
    return harness.run_cell(GQA, SEED, 4.0, time.perf_counter(),
                            jax.devices()[0], bench_dir=gqa_bench)


def test_new_architecture_runs_from_new_files(gqa_bench, gqa_run):
    run, checks = gqa_run
    assert Path(run.ref.__file__) == gqa_bench / "refs" / "tiny_gqa.py"
    assert run.ref is harness.reference_module(run.cfg_file, gqa_bench)
    assert harness.result_line(run, {}, checks)["correct"] is True, checks
    assert checks["sampled_tokens"]["value"] >= 40
    control = harness.check_outputs(run.cfg_file, SEED, run.window,
                                    run.sample, control_bits=4,
                                    bench_dir=gqa_bench)
    assert harness.result_line(run, {}, control)["correct"] is False


def _traced(run, ref):
    """``run`` as a traced run would leave it: two decode calls and one
    chunk call in the traced part, each with its kernel time."""
    calls = [("chunk", 0.1, [(0, 40), (64, 17)]),
             ("decode", 0.2, [41, 82, 7]),
             ("decode", 0.3, [42, 83])]
    win = dataclasses.replace(run.window, trace=(0.0, 1.0), calls=calls,
                              steps=[(0.05, 0.15), (0.15, 0.25),
                                     (0.25, 0.35)])
    trace = SimpleNamespace(kernel_s={"decode": [2e-5, 3e-5],
                                      "chunk": [4e-5]})
    return dataclasses.replace(run, window=win, trace=trace, ref=ref,
                               device={"kind": "TPU v5 lite"},
                               extra={"kv_bytes": 1})


def test_readers_count_through_the_module(gqa_bench, gqa_run, monkeypatch):
    run, _ = gqa_run
    ref, seen = run.ref, []
    for name in ("attn_cost", "step_ops"):
        fn = getattr(ref, name)

        def counted(*args, _fn=fn, _name=name):
            seen.append((_name, args))
            return _fn(*args)
        monkeypatch.setattr(ref, name, counted)
    traced = _traced(run, ref)
    metrics = [{"name": "attn_decode_roofline", "unit": "%"},
               {"name": "step_mfu", "unit": "%"}]
    got = harness.read_metrics(traced, metrics, gqa_bench)
    names = [name for name, _ in seen]
    assert names.count("attn_cost") == 2 and names.count("step_ops") == 3
    # a module reads any whole-number counter of the program, each one's
    # change over the window, also those the harness itself never names
    counters = seen[-1][1][3]
    win = run.window
    assert counters["chunk_calls"] == (win.counters_after["chunk_calls"]
                                       - win.counters_before["chunk_calls"])
    assert counters["decode_steps"] > 0
    a, pk = run.cfg_file["reference"], roofline.peaks("TPU v5 lite")
    need = sum(roofline.bound_seconds(
        *ref.attn_cost(a, "decode", rows, 1), pk)[0]
        for rows in ([41, 82, 7], [42, 83]))
    assert got["attn_decode_roofline"]["value"] == 100.0 * need / 5e-5
    # two kv heads for four query heads: the same operations as a
    # full-head count, and fewer bytes (half the keys and values)
    ops, nbytes = ref.attn_cost(a, "decode", [10], 1)
    full = model_ref.attn_cost(dict(a, n_kv_heads=4), "decode", [10], 1)
    assert ops == full[0] and nbytes < full[1]
    assert got["step_mfu"]["value"] > 0.0


def _dense_arch():
    return {"n_layers": 2, "d_model": 64, "n_heads": 4, "head_dim": 16,
            "d_ff": 128, "vocab_size": 256, "norm": "np_layernorm",
            "qkv_bias": False, "mlp": "swiglu", "positions": "rope",
            "max_positions": 128, "rope_theta": 10000.0}


@pytest.mark.parametrize("mode", ["raceit_q8", "digital"])
def test_dense_default_served_tree_is_unchanged(mode):
    """The default module's served tree and reference weights are the
    trees the one-jit construction made, bit for bit."""
    from repro.models.model import quantize_model_params
    arch = _dense_arch()
    cfg_file = {"reference": arch, "serving": {"mode": mode}}
    key = jax.random.PRNGKey(harness.run_key_int(SEED))
    make = lambda k: model_ref.make_weights(k, arch)
    before = jax.jit((lambda k: quantize_model_params(make(k)))
                     if mode == "raceit_q8" else make)(key)
    after = harness.make_params(cfg_file, SEED)
    assert (jax.tree.structure(after) == jax.tree.structure(before))
    for x, y in zip(jax.tree.leaves(after), jax.tree.leaves(before)):
        assert x.dtype == y.dtype and np.array_equal(x, y)
    w = model_ref.reference_weights(key, arch)
    for x, y in zip(jax.tree.leaves(w), jax.tree.leaves(jax.jit(make)(key))):
        assert np.array_equal(x, y)


def test_dense_default_readings_are_unchanged():
    """On a fixed synthetic run of the full olmo-1b configuration, the
    readers give what their formulas gave before the modules existed."""
    a = OLMO["reference"]
    win = harness.Window(40.0, 0.0, {}, [(0.05, 0.15), (0.15, 0.25),
                                         (0.25, 0.35)],
                         {"decode_steps": 3}, {"decode_steps": 9}, [])
    run = harness.Run({}, OLMO, {}, SEED, win, 20.0, {})
    traced = _traced(run, harness.reference_module(OLMO))
    assert traced.ref is model_ref
    pk = roofline.peaks("TPU v5 lite")
    # the attention roofline as it was counted in the reader itself
    h, kv, hd = a["n_heads"], a.get("n_kv_heads", a["n_heads"]), a["head_dim"]
    for kind, spent, calls in (("decode", 5e-5, traced.window.calls[1:]),
                               ("chunk", 4e-5, traced.window.calls[:1])):
        need = 0.0
        for _, _, rows in calls:
            if kind == "decode":
                ops, nbytes = roofline.attn_decode_cost(rows, h, kv, hd, 1)
            else:
                ops, nbytes = roofline.attn_chunk_cost(
                    [o for o, _ in rows], [f for _, f in rows], h, kv, hd, 1)
            need += roofline.bound_seconds(a["n_layers"] * ops,
                                           a["n_layers"] * nbytes, pk)[0]
        assert readings.attn_roofline_pct(traced, kind) == \
            100.0 * need / spent
    ops = 0
    for kind, t, rows in traced.window.calls:
        ops += (roofline.step_ops(a, rows, []) if kind == "chunk"
                else roofline.step_ops(a, [], rows))
    assert readings.step_mfu_pct(traced) == \
        100.0 * ops / (0.3 * pk["int8_ops_per_s"])


def test_reference_block_freezes_to_a_static_key():
    flat = {"n_layers": 2, "norm": "layernorm", "rope_theta": 0.0}
    # a flat block gives the key it always gave
    assert harness.freeze(flat) == tuple(sorted(flat.items()))
    nested = dict(flat, norm={"kind": "rmsnorm", "eps": 1e-6},
                  pattern=["attn", ["moe", "dense"]])
    key = harness.freeze(nested)
    hash(key)
    assert dict(key)["norm"] == (("eps", 1e-6), ("kind", "rmsnorm"))
    assert dict(key)["pattern"] == ("attn", ("moe", "dense"))
    assert harness.freeze(dict(reversed(list(nested.items())))) == key


def test_override_lists_become_layer_patterns():
    cfg_file = json.loads((DATA / "tiny-gqa.json").read_text())
    cfg_file["program"]["overrides"]["ffn_pattern"] = ["dense", "dense"]
    cfg = harness.program_config(cfg_file)
    assert cfg.ffn_pattern == ("dense", "dense")
    assert cfg.mixer_pattern == ("attn",) and cfg.n_kv_heads == 2
    hash(cfg)
