"""Mosaic compile checks: the serving kernels compile for a TPU v5e.

Each test lowers one fused attention entry point with ``interpret=False``
at real model widths and compiles it for a *described* v5e chip (the TPU
compiler is installed; no chip is attached or touched), then asserts that
the executable holds the Pallas kernel (``tpu_custom_call``). This catches
what interpret mode cannot: in-kernel vector gathers, int32 MXU operands,
scalars outside SMEM, block shapes off the (8, 128) tiling, and VMEM
overruns. Nothing here runs a kernel; numerics are the interpret-mode
suites' job.

The topology is described inside a fixture, never at import time, so only
the worker that runs this file loads the TPU compiler library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops

GPT2 = get_config("gpt2-large")            # 20 heads x 64, MHA
STARCODER2 = get_config("starcoder2-15b")  # 48 heads, 4 KV heads, x 128
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
    """The ACAM kernels the serving path does not call (the staged
    activation, softmax and crossbar dataflow) compile too."""
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
