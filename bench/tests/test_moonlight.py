"""Moonlight-16B-A3B's reference module (``bench/refs/moonlight.py``)
against the program, at test widths on the CPU (``tiny-moonlight``: one
leading dense layer and two expert layers, 4 of 16 experts held, a shared
expert, latent attention): served through the paged continuous batcher,
chunked prefill and then decode against the latent pool."""
import json
import shutil
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, readings
from repro.serve import Request

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
SEED = 2 ** 34 + 11
CELL = {"name": "tiny-moonlight.tiny-chat", "config": "tiny-moonlight",
        "traffic": "tiny-chat", "chips": 1}
# float32 agreement of digital serving with the reference: both compute in
# f32, in different orders (the absorbed against the published attention,
# per-head and per-expert sums, the paged kernel's key blocks); the logits
# (about 5 at most here) agree to about 1e-5, and a single bf16 product
# anywhere in the path would move them by about 1e-2
DIGITAL_LOGIT_ATOL = 1e-4


@pytest.fixture(scope="module")
def moon_bench(tmp_path_factory):
    """A copy of bench/ plus the tiny configuration; its reference module
    is the cell's own ``bench/refs/moonlight.py``."""
    bd = tmp_path_factory.mktemp("files") / "bench"
    shutil.copytree(ROOT / "bench", bd,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(DATA / "tiny-moonlight.json",
                bd / "configs" / "tiny-moonlight.json")
    shutil.copy(DATA / "tiny-chat.json", bd / "traffic" / "tiny-chat.json")
    return bd


def _cfg_file(mode: str) -> dict:
    cfg_file = json.loads((DATA / "tiny-moonlight.json").read_text())
    cfg_file["serving"]["mode"] = mode
    return cfg_file


def _served_logits(cfg_file, seed, prompts, n_new):
    """Serve ``prompts`` through the paged batcher; returns the results
    and every (rid, position, logits) the chunk and decode calls made for
    a live row (position: the sequence index the logits predict from)."""
    eng = harness.build_engine(cfg_file, harness.make_params(cfg_file, seed))
    b = harness.new_batcher(cfg_file, eng)
    seen, decode, chunk = [], eng._decode, eng._prefill_chunk
    rids = lambda: [s.req.rid if s is not None else None for s in b.slots]

    def keep(r, last, logits):
        lg = np.asarray(logits[:, -1])
        seen.extend((rid, int(last[i]), lg[i]) for i, rid in enumerate(r)
                    if rid is not None and last[i] >= 0)

    def decode_step(params, tok, cache, pad, plen, lens, bt, page_size):
        r = rids()
        out = decode(params, tok, cache, pad, plen, lens, bt,
                     page_size=page_size)
        keep(r, np.asarray(lens) - 1, out[0])
        return out

    def prefill_chunk(params, toks, cache, offs, feeds, bt, ps):
        r = rids()
        out = chunk(params, toks, cache, offs, feeds, bt, ps)
        f = np.asarray(feeds)
        keep(r, np.where(f > 0, np.asarray(offs) + f - 1, -1), out[0])
        return out

    eng._decode, eng._prefill_chunk = decode_step, prefill_chunk
    for rid, p in enumerate(prompts):
        b.submit(Request(rid, p, n_new))
    done = b.run_all()
    assert b.summary()["chunk_calls"] > len(prompts)   # prompts span chunks
    return {rid: done[rid].result for rid in range(len(prompts))}, seen


def test_digital_serving_gives_the_reference_logits():
    """Chunked prefill and paged decode against the latent pool, with the
    dropless expert layer, give the published (non-absorbed) model's
    logits at every served position, to float32 rounding."""
    cfg_file = _cfg_file("digital")
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 1024, n).astype(np.int32)
               for n in (9, 40, 23, 57, 17, 33)]
    served, seen = _served_logits(cfg_file, SEED, prompts, 10)
    ref = harness.reference_module(cfg_file)
    key = jax.random.PRNGKey(harness.run_key_int(SEED))
    want = {rid: np.asarray(ref.forward(key, jnp.asarray(np.concatenate(
        [p, served[rid]])), cfg_file["reference"]))
        for rid, p in enumerate(prompts)}
    assert len(seen) >= sum(len(p) // 16 + 10 for p in prompts)
    worst = max(float(np.abs(lg - want[rid][pos]).max())
                for rid, pos, lg in seen)
    assert worst < DIGITAL_LOGIT_ATOL, worst
    # and so every served token is the reference's best
    for rid, p in enumerate(prompts):
        pos = np.arange(len(p) - 1, len(p) - 1 + len(served[rid]))
        np.testing.assert_array_equal(served[rid],
                                      want[rid][pos].argmax(-1))


@pytest.fixture(scope="module")
def moon_run(moon_bench):
    return harness.run_cell(CELL, SEED, 4.0, time.perf_counter(),
                            jax.devices()[0], bench_dir=moon_bench)


def test_raceit_q8_passes_the_check_and_the_int4_control_fails(moon_bench,
                                                               moon_run):
    run, checks = moon_run
    assert Path(run.ref.__file__) == moon_bench / "refs" / "moonlight.py"
    assert harness.result_line(run, {}, checks)["correct"] is True, checks
    assert checks["sampled_tokens"]["value"] >= 40
    control = harness.check_outputs(run.cfg_file, SEED, run.window,
                                    run.sample, control_bits=4,
                                    bench_dir=moon_bench)
    assert harness.result_line(run, {}, control)["correct"] is False, control


def test_routed_counters_reach_the_readers(moon_bench, moon_run):
    run, _ = moon_run
    win = run.window
    rows = readings.counter_delta(run, "decode_expert_rows")
    steps = readings.counter_delta(run, "decode_steps")
    assert rows > 0 and readings.counter_delta(run, "expert_rows") >= rows
    got = harness.read_metrics(
        run, [{"name": "expert_rows_per_step", "unit": "rows"}], moon_bench)
    assert got["expert_rows_per_step"]["value"] == rows / steps
    # a program that keeps no such counter reads nothing, and raises not
    bare = dict(win.counters_after)
    for k in ("expert_rows", "decode_expert_rows"):
        bare.pop(k)
    run_bare = harness.Run(run.cell, run.cfg_file, run.mix, run.seed,
                           harness.Window(win.seconds, win.start, {}, [],
                                          {}, bare, []), 1.0, run.device)
    assert harness.read_metrics(
        run_bare, [{"name": "expert_rows_per_step", "unit": "rows"},
                   {"name": "moe_step_mfu", "unit": "%"}], moon_bench) == {}


def test_step_ops_count_the_routed_rows():
    """A call's operations grow with the window's routed rows per fed row,
    by one expert's SwiGLU per (row, choice) pair."""
    a = _cfg_file("raceit_q8")["reference"]
    ref = harness.reference_module({"reference": a})
    base = {"decode_tokens": 90, "chunk_fed_tokens": 10, "expert_rows": 0}
    more = dict(base, expert_rows=400)          # 4 pairs per fed row
    calls = ([(0, 16), (16, 5)], [30, 7])
    diff = (ref.step_ops(a, *calls, more) - ref.step_ops(a, *calls, base))
    expert = 2 * 3 * a["d_model"] * a["moe_d_ff"]
    assert diff == pytest.approx(4 * expert * (16 + 5 + 2), rel=1e-12)
    # attention's cost: the latent row is read at its lane-padded width,
    # once as the kernel's key operand and once as its value operand
    ops, nbytes = ref.attn_cost(a, "decode", [10], 1)
    w = a["kv_lora_rank"] + a["qk_rope_head_dim"]
    w_read = -(-w // 128) * 128
    assert ops == a["n_layers"] * 10 * 2 * a["n_heads"] * (
        w + a["kv_lora_rank"])
    assert nbytes == a["n_layers"] * (
        2 * 10 * w_read + 4 * a["n_heads"] * (w + a["kv_lora_rank"]))


def test_a_program_with_float_experts_is_refused_at_once():
    """A quantizer that makes attention int8 and leaves the expert stacks
    float (a program that cannot serve this configuration's int8 experts)
    is refused before any weight is made."""
    from repro.models.model import quantize_model_params
    a = _cfg_file("raceit_q8")["reference"]
    ref = harness.reference_module({"reference": a})

    def float_experts(tree):
        out = quantize_model_params(tree)
        if "moe" in tree:
            out["moe"] = tree["moe"]
        return out

    with pytest.raises(ValueError, match="expert stacks"):
        ref.served_params(jax.random.PRNGKey(0), a, float_experts)


def test_nested_mla_and_moe_scopes_read_apart_and_as_their_layer():
    """The program nests ``mla`` in ``attn`` and ``moe`` in ``mlp``:
    `bench.layer_scopes` reads each apart (an op in ``mla/kv_pool`` stays
    ``kv_pool``), and `bench.scopes`, which knows only the outer names,
    still counts them as attention and MLP."""
    import types
    from bench import layer_scopes, scopes
    from bench.scopes import Op, Span
    ms = 1_000_000
    body = "jit(decode_step)/layer_scan/while/body/closed_call"
    ops = [Op("%fusion.1 = s8[8] fusion()", 10 * ms, 13 * ms,
              f"{body}/attn/mla/dot_general:"),
           Op("%scatter.2 = f32[8] scatter()", 13 * ms, 14 * ms,
              f"{body}/attn/mla/kv_pool/scatter:"),
           Op("%fusion.3 = s8[8] fusion()", 14 * ms, 16 * ms,
              f"{body}/mlp/moe/dot_general:"),
           Op("%fusion.4 = s8[8] fusion()", 16 * ms, 17 * ms,
              "jit(decode_step)/mlp/dot_general:")]   # the leading dense layer
    data = {"devices": {"/device:TPU:0": {
        "ops": ops, "modules": [Op("jit_decode_step(1)", 10 * ms, 17 * ms)]}},
        "host": [Span("bench.window", 0, 100 * ms)]}
    mine = layer_scopes.scope_runs(data)["decode"][0]
    assert mine == pytest.approx({"mla": 0.003, "kv_pool": 0.001,
                                  "moe": 0.002, "mlp": 0.001})
    outer = scopes.scope_runs(data)["decode"][0]
    assert outer == pytest.approx({"attn": 0.003, "kv_pool": 0.001,
                                   "mlp": 0.003})
    run = types.SimpleNamespace(trace=object(),
                                extra={"scoped_trace": data})
    assert layer_scopes.decode_scope_ms(run, "mla") == pytest.approx(3.0)
    assert layer_scopes.decode_scope_ms(run, "moe") == pytest.approx(2.0)
    dense = dict(data, devices={"/device:TPU:0": {
        "ops": ops[3:], "modules": data["devices"]["/device:TPU:0"][
            "modules"]}})
    run = types.SimpleNamespace(trace=object(),
                                extra={"scoped_trace": dense})
    assert layer_scopes.decode_scope_ms(run, "moe") is None
