"""Mosaic compile checks: the serving kernels compile for a TPU v5e.

Each test lowers one fused attention entry point, or one model layer,
with ``interpret=False`` at real model widths and compiles it for a
*described* v5e chip (the TPU compiler is installed; no chip is attached
or touched), then asserts that the executable holds the Pallas kernel
(``tpu_custom_call``). This catches what interpret mode cannot: in-kernel
vector gathers, int32 MXU operands, scalars outside SMEM, block shapes off
the (8, 128) tiling, and VMEM overruns. Nothing here runs a kernel;
numerics are the interpret-mode suites' job.

The topology is described inside a fixture, never at import time, so only
the worker that runs this file loads the TPU compiler library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import ExecConfig
from repro.exec import resolve_plan
from repro.kernels import ops
from repro.models import layers

GPT2 = get_config("gpt2-large")            # 20 heads x 64, MHA
STARCODER2 = get_config("starcoder2-15b")  # 48 heads, 4 KV heads, x 128
OLMO = get_config("olmo-1b")               # d 2048, SwiGLU d_ff 8192
MOONLIGHT = get_config("moonlight-16b-a3b")  # one 576-wide latent KV head
PAGE, SLOTS, MAX_PAGES = 64, 4, 2           # the serving launcher defaults
N_PAGES = 1 + SLOTS * MAX_PAGES


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _pool(cfg):
    return ((N_PAGES, PAGE, cfg.n_kv_heads, cfg.resolved_head_dim),
            jnp.float32)


def test_paged_decode_compiles(one_chip):
    H, hd = GPT2.n_heads, GPT2.resolved_head_dim
    _compile(lambda q, k, v, n, bt: ops.raceit_attention_decode_paged(
                 q, k, v, n, bt, interpret=False), one_chip,
             ((SLOTS, H, 1, hd), jnp.float32), _pool(GPT2), _pool(GPT2),
             ((SLOTS,), jnp.int32), ((SLOTS, MAX_PAGES), jnp.int32))


def test_paged_chunk_prefill_compiles(one_chip):
    H, hd = GPT2.n_heads, GPT2.resolved_head_dim
    _compile(lambda q, k, v, n, bt, m: ops.raceit_attention_decode_paged(
                 q, k, v, n, bt, mask=m, interpret=False), one_chip,
             ((SLOTS, H, PAGE, hd), jnp.float32), _pool(GPT2), _pool(GPT2),
             ((SLOTS,), jnp.int32), ((SLOTS, MAX_PAGES), jnp.int32),
             ((SLOTS, PAGE, MAX_PAGES * PAGE), jnp.bool_))


def test_causal_fused_prefill_compiles(one_chip):
    shape = ((1, GPT2.n_heads, 1024, GPT2.resolved_head_dim), jnp.float32)
    _compile(lambda q, k, v: ops.raceit_attention_fused(
                 q, k, v, causal=True, interpret=False), one_chip,
             shape, shape, shape)


D_MODEL = GPT2.d_model


@pytest.mark.parametrize("name", ["acam_lut", "acam_softmax_codes",
                                  "acam_mvm"])
def test_offpath_kernel_compiles(one_chip, name):
    """The ACAM kernels outside the fused attention compile too: the LUT,
    which applies the serving path's activation (here on int8 codes), and
    the softmax and crossbar dataflow, which it does not call."""
    from repro.core.crossbar import CrossbarConfig
    fn, shapes = {
        "acam_lut": (lambda x, t: ops.acam_lut(x, t, interpret=False),
                     [((256, D_MODEL), jnp.int8), ((256,), jnp.int32)]),
        "acam_softmax_codes": (
            lambda x: ops.acam_softmax_codes(x, interpret=False),
            [((256, 512), jnp.int32)]),
        "acam_mvm": (lambda x, w: ops.acam_mvm(x, w, CrossbarConfig(),
                                               interpret=False),
                     [((256, D_MODEL), jnp.int8),
                      ((D_MODEL, D_MODEL), jnp.int8)]),
    }[name]
    _compile(fn, one_chip, *shapes)


def test_gqa_paged_decode_compiles(one_chip):
    H, hd = STARCODER2.n_heads, STARCODER2.resolved_head_dim
    _compile(lambda q, k, v, n, bt: ops.raceit_attention_decode_gqa_paged(
                 q, k, v, n, bt, interpret=False), one_chip,
             ((SLOTS, H, 1, hd), jnp.float32), _pool(STARCODER2),
             _pool(STARCODER2), ((SLOTS,), jnp.int32),
             ((SLOTS, MAX_PAGES), jnp.int32))


@pytest.mark.parametrize("sq", [1, PAGE])
def test_latent_pool_paged_attention_compiles(one_chip, sq):
    """Moonlight's latent cache on the GQA paged entry: one 576-wide KV
    head (padded to 640 lanes in the kernel) that all 16 query heads read
    as keys and values, for a decode step and a 64-row chunk call (the
    chunk's heads x positions on the tile's rows)."""
    H, w = MOONLIGHT.n_heads, MOONLIGHT.mla_head_dim
    mask = [((SLOTS, sq, MAX_PAGES * PAGE), jnp.bool_)] if sq > 1 else []
    _compile(lambda q, kv, n, bt, *m: ops.raceit_attention_decode_gqa_paged(
                 q, kv, kv, n, bt, mask=m[0] if m else None,
                 interpret=False), one_chip,
             ((SLOTS, H, sq, w), jnp.float32),
             ((N_PAGES, PAGE, 1, w), jnp.float32), ((SLOTS,), jnp.int32),
             ((SLOTS, MAX_PAGES), jnp.int32), *mask)


@pytest.fixture
def mosaic_kernels(monkeypatch):
    """Model code resolves its kernels' interpret flag from the backend,
    which is the CPU here: make it compile them for Mosaic. jit's traces
    are dropped on both sides, so none traced in interpret mode is reused
    here and none traced for Mosaic is left to a later test."""
    from repro.kernels import runtime
    jax.clear_caches()
    monkeypatch.setattr(runtime, "default_interpret", lambda: False)
    yield
    monkeypatch.undo()
    jax.clear_caches()


@pytest.mark.parametrize("rows", [1, 64])
def test_raceit_ffn_applies_activation_with_lut_kernel(one_chip,
                                                       mosaic_kernels, rows):
    """The raceit_q8 FFN at OLMo-1B widths, for 16 slots of one decode row
    and of one 64-row prefill chunk: the activation's table is the Pallas
    kernel, and no XLA gather is left in the program."""
    cfg = OLMO.replace(param_dtype="float32", compute_dtype="float32")
    plan = resolve_plan(cfg, ExecConfig.serving())
    assert plan.backend("activation") == "raceit_lut_kernel"

    def weight(k, n):
        return layers.QuantizedWeight(
            jax.ShapeDtypeStruct((k, n), jnp.int8, sharding=one_chip),
            jax.ShapeDtypeStruct((1, n), jnp.float32, sharding=one_chip),
            (n,))

    d, f = cfg.d_model, cfg.d_ff
    p = {"w1": weight(d, f), "w3": weight(d, f), "w2": weight(f, d)}
    x = jax.ShapeDtypeStruct((16, rows, d), jnp.float32, sharding=one_chip)
    text = jax.jit(lambda p, x: layers.ffn(p, x, cfg, plan)).lower(
        p, x).compile().as_text()
    assert "tpu_custom_call" in text
    assert "gather(" not in text
