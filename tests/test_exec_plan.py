"""RaceOp registry + ExecPlan resolution: the single dispatch API.

Covers the plan-resolution contract:

* every (mode x softmax_mode x fidelity x fused) combo resolves
  deterministically and **never raises** — unsupported combos degrade with
  a structured reason on the plan;
* per-op overrides (``ExecConfig.op_overrides`` / ``with_ops``) are
  honored, including degrade-on-unknown-backend;
* plan-dispatched layer outputs are bit-identical to calling the
  underlying staged/fused implementations directly (the pre-plan code
  paths, which now live as the registered backends);
* the lm head routes through the plan (act_bits honored for resident
  weights — the old code rebuilt a bare ``ExecConfig(mode="raceit")``).
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ExecConfig, ModelConfig
from repro.exec import (OP_SLOTS, ExecPlan, as_plan, list_backends,
                        reset_plan_cache, resolve_plan)
from repro.models import layers

MODES = ("digital", "raceit")
SOFTMAX_MODES = ("pot", "pot_fine", "uniform")
FIDELITIES = ("int", "acam")


def _cfg(**kw):
    base = dict(name="t", n_layers=1, d_model=32, n_heads=4, n_kv_heads=2,
                d_ff=64, vocab_size=64, param_dtype="float32",
                compute_dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


# ---------------------------------------------------------------------------
# resolution matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fused", [False, True])
@pytest.mark.parametrize("fidelity", FIDELITIES)
@pytest.mark.parametrize("softmax_mode", SOFTMAX_MODES)
@pytest.mark.parametrize("mode", MODES)
def test_every_combo_resolves_deterministically(mode, softmax_mode, fidelity,
                                                fused):
    ec = ExecConfig(mode=mode, softmax_mode=softmax_mode,
                    matmul_fidelity=fidelity, fused_attention=fused)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # fused degrades may warn once
        plan = resolve_plan(_cfg(), ec)
        again = resolve_plan(_cfg(), ec)
    assert isinstance(plan, ExecPlan)
    assert plan is again  # cached => trivially deterministic
    assert [op.slot for op in plan.ops] == list(OP_SLOTS)
    chosen = {op.slot: op.backend for op in plan.ops}
    if mode == "digital":
        assert chosen["attention_prefill"] == "digital"
        assert chosen["matmul"] == "digital"
        assert chosen["dd_matmul"] == "int"
    else:
        assert chosen["matmul"] == "raceit_int"
        assert chosen["activation"] == "raceit_lut_kernel"
        assert chosen["softmax"] == "raceit_acam"
        assert chosen["dd_matmul"] == fidelity
        want_attn = ("raceit_fused" if fused and fidelity == "int"
                     else "raceit_staged")
        assert chosen["attention_prefill"] == want_attn
        # _cfg() is a GQA config (n_kv_heads=2 < n_heads=4): a supported
        # fused decode resolves to the block-paged GQA-native kernel
        # (paged backends also serve contiguous caches — block_table=None
        # falls through to the per-row path)
        want_dec = ("raceit_gqa_paged" if fused and fidelity == "int"
                    else want_attn)
        assert chosen["attention_decode"] == want_dec
    # explain() renders every slot and never raises
    text = plan.explain()
    for slot in OP_SLOTS:
        assert slot in text


def test_unsupported_fused_degrades_with_structured_reason():
    reset_plan_cache()
    ec = ExecConfig(mode="raceit", fused_attention=True,
                    matmul_fidelity="acam")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        plan = resolve_plan(_cfg(), ec)
        resolve_plan(_cfg(), ec)  # cached: no second warning
    op = plan.op("attention_decode")
    assert op.backend == "raceit_staged"
    # decode's preference head is the paged GQA-native kernel; the whole
    # fused family is rejected by the same fidelity reason
    assert op.requested == "raceit_gqa_paged"
    assert "acam" in op.reason
    for name in ("raceit_gqa_paged", "raceit_gqa_rows", "raceit_gqa_native",
                 "raceit_fused_paged", "raceit_fused_rows", "raceit_fused"):
        assert any(d.slot == "attention_decode" and d.requested == name
                   and d.chosen == "raceit_staged" for d in plan.degrades)
    msgs = [x for x in w if issubclass(x.category, RuntimeWarning)
            and "fused_attention" in str(x.message)]
    assert len(msgs) == 1, [str(x.message) for x in w]
    assert "acam" in plan.explain()


def test_unknown_mode_degrades_to_digital():
    plan = resolve_plan(_cfg(), ExecConfig(mode="analog_dreams"))
    assert all(op.backend in ("digital", "int") for op in plan.ops)
    assert any("unknown mode" in d.reason for d in plan.degrades)


# ---------------------------------------------------------------------------
# per-op overrides
# ---------------------------------------------------------------------------

def test_op_overrides_pin_backends():
    ec = ExecConfig(mode="raceit", fused_attention=True).with_ops(
        attention_decode="raceit_staged", lm_head="raceit_q8")
    plan = resolve_plan(_cfg(), ec)
    assert plan.backend("attention_decode") == "raceit_staged"
    assert plan.backend("attention_prefill") == "raceit_fused"  # untouched
    assert plan.backend("lm_head") == "raceit_q8"


def test_with_ops_later_pins_win():
    ec = ExecConfig(mode="raceit").with_ops(lm_head="raceit_q8")
    ec = ec.with_ops(lm_head="digital")
    assert resolve_plan(_cfg(), ec).backend("lm_head") == "digital"


def test_unknown_backend_override_degrades_not_raises():
    ec = ExecConfig(mode="raceit").with_ops(attention_decode="warp_drive")
    plan = resolve_plan(_cfg(), ec)
    op = plan.op("attention_decode")
    assert op.backend == "raceit_staged"  # fell through to the default chain
    assert op.requested == "warp_drive"
    assert "no backend" in op.reason


def test_unknown_slot_override_recorded_not_raised():
    ec = ExecConfig(mode="raceit",
                    op_overrides=(("flux_capacitor", "digital"),))
    plan = resolve_plan(_cfg(), ec)
    assert any(d.slot == "flux_capacitor" and "unknown op slot" in d.reason
               for d in plan.degrades)
    # a typo'd --exec-plan slot must be *visible* in the startup table, not
    # silently ignored (the CLI help promises "the plan table says why")
    assert "flux_capacitor" in plan.explain()
    assert "unknown op slot" in plan.explain()


def test_registry_lists_expected_backends():
    resolve_plan(_cfg(), ExecConfig())  # force backend registration import
    names = {slot: set(b) for slot, b in list_backends().items()}
    assert {"digital", "raceit_int"} <= names["matmul"]
    assert {"digital", "raceit_staged", "raceit_fused"} <= names[
        "attention_prefill"]
    assert {"digital", "raceit_staged", "raceit_fused",
            "raceit_fused_rows", "raceit_gqa_rows",
            "raceit_fused_paged", "raceit_gqa_paged"} <= names[
        "attention_decode"]
    assert {"int", "acam"} <= names["dd_matmul"]
    assert {"digital", "raceit_q8"} <= names["lm_head"]


# ---------------------------------------------------------------------------
# dispatch parity: plan methods == the underlying implementations
# ---------------------------------------------------------------------------

def _attn_inputs(rng, cfg, B=2, S=24):
    p = layers.init_attention(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jnp.asarray(rng.normal(0, 1, (B, S, cfg.d_model)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    return p, x, pos


@pytest.mark.parametrize("mode", MODES)
def test_layer_attention_accepts_config_or_plan(rng, mode):
    """layers.attention(plan=ExecConfig) == layers.attention(plan=ExecPlan)."""
    cfg = _cfg()
    p, x, pos = _attn_inputs(rng, cfg)
    ec = ExecConfig(mode=mode)
    a, _ = layers.attention(p, x, cfg=cfg, plan=ec, positions=pos)
    b, _ = layers.attention(p, x, cfg=cfg, plan=as_plan(cfg, ec),
                            positions=pos)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_staged_prefill_parity_with_direct_oracle_call(rng):
    """Plan-dispatched staged attention == _raceit_staged_attention direct."""
    cfg = _cfg()
    p, x, pos = _attn_inputs(rng, cfg)
    plan = resolve_plan(cfg, ExecConfig(mode="raceit"))
    got, _ = layers.attention(p, x, cfg=cfg, plan=plan, positions=pos)

    # rebuild the projections exactly as the layer does, then call the
    # staged implementation directly with the causal mask
    q = plan.matmul(x, p["wq"])
    k = plan.matmul(x, p["wk"])
    v = plan.matmul(x, p["wv"])
    q, k = layers.apply_rope(q, pos, cfg), layers.apply_rope(k, pos, cfg)
    S = x.shape[1]
    mask = jnp.broadcast_to(
        jnp.arange(S)[None, :] <= jnp.arange(S)[:, None], (2, S, S))
    import math
    o = layers._raceit_staged_attention(q, k, v, mask,
                                        1.0 / math.sqrt(cfg.resolved_head_dim),
                                        plan)
    want = jnp.einsum("bshd,hdm->bsm", o.astype(x.dtype),
                      p["wo"].astype(x.dtype))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_acam_fidelity_staged_layer_matches_int(rng):
    """dd_matmul slot: 'acam' nibble-table matmuls are bit-identical to
    'int' through the whole staged layer path (the paper's §IV-B claim at
    the model layer)."""
    cfg = _cfg()
    p, x, pos = _attn_inputs(rng, cfg, S=12)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        a, _ = layers.attention(p, x, cfg=cfg, positions=pos,
                                plan=ExecConfig(mode="raceit"))
        b, _ = layers.attention(p, x, cfg=cfg, positions=pos,
                                plan=ExecConfig(mode="raceit",
                                                matmul_fidelity="acam"))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# lm head through the plan (the old bare-ExecConfig bug)
# ---------------------------------------------------------------------------

def _resident_unembed(rng, cfg):
    w = jnp.asarray(rng.normal(0, 0.1, (cfg.d_model, cfg.vocab_size)),
                    jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=0, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    codes = jnp.clip(jnp.round(w / scale), -128, 127).astype(jnp.int8)
    return layers.QuantizedWeight(codes, scale.astype(jnp.float32),
                                  (cfg.vocab_size,))


@pytest.mark.parametrize("act_bits", [8, 5])
def test_lm_head_resident_weight_honors_plan_act_bits(rng, act_bits):
    """Resident int8 unembeddings quantize activations with the *plan's*
    act_bits — the old path rebuilt ExecConfig() and always used 8."""
    from repro.core.quant import quantize_tensor
    cfg = _cfg()
    qw = _resident_unembed(rng, cfg)
    x = jnp.asarray(rng.normal(0, 1, (2, 3, cfg.d_model)), jnp.float32)
    params = {"unembed": qw, "tok_emb": jnp.zeros((cfg.vocab_size,
                                                   cfg.d_model))}
    plan = resolve_plan(cfg, ExecConfig(mode="raceit", act_bits=act_bits))
    got = layers.unembed(params, x, cfg, plan)
    xq = quantize_tensor(x, bits=act_bits)
    want = (jnp.einsum("bsk,kv->bsv", xq.codes.astype(jnp.int32),
                       qw.codes.astype(jnp.int32)).astype(jnp.float32)
            * (xq.scale * qw.scale))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    if act_bits != 8:  # and it actually differs from the old always-8 path
        xq8 = quantize_tensor(x, bits=8)
        old = (jnp.einsum("bsk,kv->bsv", xq8.codes.astype(jnp.int32),
                          qw.codes.astype(jnp.int32)).astype(jnp.float32)
               * (xq8.scale * qw.scale))
        assert not np.array_equal(np.asarray(got), np.asarray(old))


def test_lm_head_raceit_q8_override_quantizes_float_weights(rng):
    cfg = _cfg()
    x = jnp.asarray(rng.normal(0, 1, (1, 4, cfg.d_model)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 0.1, (cfg.d_model, cfg.vocab_size)),
                    jnp.float32)
    params = {"unembed": w, "tok_emb": jnp.zeros((cfg.vocab_size,
                                                  cfg.d_model))}
    full = layers.unembed(params, x, cfg,
                          resolve_plan(cfg, ExecConfig(mode="raceit")))
    q8 = layers.unembed(params, x, cfg, resolve_plan(
        cfg, ExecConfig(mode="raceit").with_ops(lm_head="raceit_q8")))
    # default stays the full-precision einsum; the q8 override quantizes
    assert not np.array_equal(np.asarray(full), np.asarray(q8))
    np.testing.assert_allclose(np.asarray(full), np.asarray(q8),
                               atol=0.05 * float(jnp.abs(full).max()))


# ---------------------------------------------------------------------------
# end-to-end: model forward identical through config-sugar and explicit plan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", MODES)
def test_model_forward_same_via_config_and_plan(key, mode):
    from repro.models import Model
    cfg = _cfg(n_layers=2)
    ec = ExecConfig(mode=mode)
    m1 = Model(cfg, ec)
    m2 = Model(cfg, resolve_plan(cfg, ec))
    params = m1.init(key)
    batch = {"tokens": jnp.arange(32).reshape(2, 16) % cfg.vocab_size}
    a = m1.forward(params, batch, use_remat=False)
    b = m2.forward(params, batch, use_remat=False)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# activation: the Pallas LUT kernel against XLA's gather
# ---------------------------------------------------------------------------

GATHER = ExecConfig(mode="raceit").with_ops(activation="raceit_lut")


def _activation_inputs(rng, shape):
    """Every input code's value first (the 1-2-5 range [-4, 3.97]), then
    random floats inside the range, then beyond it, repeated to fill."""
    from repro.core.ops import GELU_FMT
    codes = GELU_FMT.decode(np.arange(GELU_FMT.code_min,
                                      GELU_FMT.code_max + 1))
    inside = rng.uniform(GELU_FMT.min_value, GELU_FMT.max_value, 256)
    beyond = rng.normal(0, 8, 256)
    pool = np.concatenate([codes, inside, beyond]).astype(np.float32)
    return jnp.asarray(np.resize(pool, shape))


@pytest.mark.parametrize("shape", [(1, 1), (7, 130), (3, 5, 64), (16, 8192)])
@pytest.mark.parametrize("name", ["silu", "gelu"])
def test_lut_kernel_activation_bit_identical_to_gather(rng, name, shape):
    cfg = _cfg()
    kernel = resolve_plan(cfg, ExecConfig(mode="raceit"))
    gather = resolve_plan(cfg, GATHER)
    assert kernel.backend("activation") == "raceit_lut_kernel"
    assert gather.backend("activation") == "raceit_lut"
    x = _activation_inputs(rng, shape)
    got = kernel.activation(x, name)
    want = gather.activation(x, name)
    assert got.shape == want.shape == shape and got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_lut_kernel_keeps_name_mapping_and_dtype(rng):
    """Unlisted activations map to gelu, and bf16 comes back as bf16."""
    cfg = _cfg(activation="relu")
    x = _activation_inputs(rng, (4, 256)).astype(jnp.bfloat16)
    got = resolve_plan(cfg, ExecConfig(mode="raceit")).activation(x)
    want = resolve_plan(cfg, GATHER).activation(x, "gelu")
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_lut_kernel_resolution_records_each_degrade():
    from repro.dist import MeshSpec
    from repro.hw.noise import NoiseConfig
    cfg = _cfg()

    clean = resolve_plan(cfg, ExecConfig(mode="raceit"))
    assert clean.op("activation").backend == "raceit_lut_kernel"
    assert clean.op("activation").reason is None
    assert "raceit_lut_kernel" in clean.explain()

    # the noisy chain is unchanged; a pinned kernel degrades into it
    noise = NoiseConfig.preset("nominal")
    noisy = resolve_plan(cfg, ExecConfig(mode="raceit", noise=noise))
    assert noisy.backend("activation") == "raceit_noisy_lut"
    pinned = resolve_plan(cfg, ExecConfig(mode="raceit", noise=noise)
                          .with_ops(activation="raceit_lut_kernel"))
    op = pinned.op("activation")
    assert op.backend == "raceit_noisy_lut"
    assert "noise" in op.reason and op.reason in pinned.explain()

    mesh = resolve_plan(cfg, ExecConfig(mode="raceit",
                                        mesh=MeshSpec.parse("model=2")))
    op = mesh.op("activation")
    assert (op.backend, op.requested) == ("raceit_lut", "raceit_lut_kernel")
    assert "mesh of 2 devices" in op.reason and op.reason in mesh.explain()
    assert any(d.slot == "activation" and d.requested == "raceit_lut_kernel"
               and d.chosen == "raceit_lut" for d in mesh.degrades)
    one = resolve_plan(cfg, ExecConfig(mode="raceit",
                                       mesh=MeshSpec.parse("model=1")))
    assert one.backend("activation") == "raceit_lut_kernel"

    digital = resolve_plan(cfg, ExecConfig(mode="digital"))
    assert digital.backend("activation") == "digital"
    assert not any(d.slot == "activation" for d in digital.degrades)


def _q8_ffn_params(rng, cfg):
    from repro.models.model import quantize_model_params
    shapes = {"w1": (cfg.d_model, cfg.d_ff), "w3": (cfg.d_model, cfg.d_ff),
              "w2": (cfg.d_ff, cfg.d_model)}
    return quantize_model_params(
        {k: jnp.asarray(rng.normal(0, 0.1, s), jnp.float32)
         for k, s in shapes.items()})


def test_ffn_same_with_lut_kernel_and_gather(rng):
    cfg = _cfg(glu=True, activation="silu", d_ff=256)
    p = _q8_ffn_params(rng, cfg)
    assert isinstance(p["w1"], layers.QuantizedWeight)
    x = jnp.asarray(rng.normal(0, 1, (2, 8, cfg.d_model)), jnp.float32)
    got = layers.ffn(p, x, cfg, ExecConfig(mode="raceit"))
    want = layers.ffn(p, x, cfg, GATHER)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_paged_serving_same_tokens_with_lut_kernel_and_gather(key):
    from conftest import tiny_config
    from repro.configs import get_config
    from repro.models import Model
    from repro.models.model import quantize_model_params
    from repro.serve import ContinuousBatcher, GenerationEngine, Request
    cfg = tiny_config(get_config("olmo-1b"))
    params = quantize_model_params(Model(cfg, ExecConfig()).init(key))
    rng = np.random.default_rng(5)
    reqs = [(i, rng.integers(0, cfg.vocab_size, n).astype(np.int32), m)
            for i, (n, m) in enumerate([(9, 5), (4, 3), (12, 4)])]
    served = {}
    for ec in (ExecConfig.serving(),
               ExecConfig.serving().with_ops(activation="raceit_lut")):
        eng = GenerationEngine(cfg, params, exec_cfg=ec, max_len=64)
        cb = ContinuousBatcher(eng, n_slots=2, paged=True, page_size=8)
        for rid, prompt, n_new in reqs:
            cb.submit(Request(rid, prompt, n_new=n_new))
        served[eng.plan.backend("activation")] = {
            rid: r.result for rid, r in cb.run_all().items()}
    assert sorted(served) == ["raceit_lut", "raceit_lut_kernel"]
    for rid, _, n_new in reqs:
        got = served["raceit_lut_kernel"][rid]
        assert got.shape == (n_new,)
        np.testing.assert_array_equal(got, served["raceit_lut"][rid])
