"""The one-device expert layer: dropless routing over the experts a device
holds, the shares of an expert-parallel deployment, the routed-row
counter, and the layer scopes of latent attention and expert layers.
Tiny Moonlight-shaped configurations on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ExecConfig
from repro.models import Model, moe
from repro.serve import ContinuousBatcher, GenerationEngine, Request

E, K = 16, 4


def _cfg(**kw):
    base = dict(n_layers=3, d_model=64, n_heads=4, d_ff=96, vocab_size=256,
                kv_lora_rank=32, qk_nope_head_dim=32, qk_rope_head_dim=16,
                v_head_dim=32, n_experts=E, top_k=K, moe_d_ff=32,
                n_shared_experts=1, param_dtype="float32",
                compute_dtype="float32", max_seq_len=128)
    base.update(kw)
    return get_config("moonlight-16b-a3b").replace(**base)


def _moe_params(key, cfg):
    p = moe.init_moe(key, cfg, jnp.float32)
    ks = jax.random.split(key, 2)
    p["router_bias"] = jax.random.normal(ks[0], (E,)) * 0.1
    return p


def test_expert_parallel_shares_add_up_to_the_whole_layer(key):
    """Eight ranks of two experts each: their partial results, with the
    shared expert counted once, add up to the layer holding all 16."""
    cfg = _cfg()
    p = _moe_params(key, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, cfg.d_model))
    plan = ExecConfig()
    whole, n_whole = moe.moe(p, x, cfg, plan, None)
    total, n_total = jnp.zeros_like(whole), 0
    for rank in range(8):
        held = (2 * rank, 2 * rank + 1)
        share = {k: v for k, v in p.items() if k != "shared" or rank == 0}
        for k in ("w1", "w2", "w3"):
            share[k] = p[k][2 * rank: 2 * rank + 2]
        y, n = moe.moe(share, x, cfg.replace(experts_held=held), plan, None)
        total, n_total = total + y, n_total + int(n)
    # float32: the shares add the same per-expert products in another
    # order (outputs of about 1, so a few ulps)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    # every (row, choice) pair is computed by exactly one rank
    assert n_total == int(n_whole) == 2 * 9 * K


def _skewed_params(cfg, key):
    """Weights whose router sends every row to experts 0..K-1: a selection
    bias far above the sigmoid scores' spread."""
    m = Model(cfg, ExecConfig())
    params = m.init(key)
    bias = jnp.where(jnp.arange(E) < K, 100.0, 0.0)
    stack = params["blocks"]["scan"][0]["moe"]
    stack["router_bias"] = jnp.broadcast_to(bias, stack["router_bias"].shape)
    return params


def _serve(cfg, params, prompts, n_slots, n_new=6):
    eng = GenerationEngine(cfg, params, exec_cfg=ExecConfig.serving(
        mode="digital"), max_len=128)
    cb = ContinuousBatcher(eng, n_slots=n_slots, page_size=16)
    for rid, p in enumerate(prompts):
        cb.submit(Request(rid, p, n_new))
    done = cb.run_all()
    return {rid: done[rid].result for rid in range(len(prompts))}, cb


def test_uneven_routing_drops_nothing_served_among_others(key):
    """Every row goes to the same four experts, far over the capacity the
    mesh dispatch would give them; a request served among five others
    gets the tokens it gets served alone."""
    cfg = _cfg(experts_held=(0, 1, 2, 3))
    params = _skewed_params(cfg, key)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, n).astype(np.int32)
               for n in (21, 9, 30, 14, 25, 11)]
    # a decode step of six rows asks K=4 choices each of 4 experts: each
    # expert gets 6 rows, over the capacity ceil(K*6*1.25/E) = 2
    assert 6 > -(-K * 6 * cfg.capacity_factor // E)
    together, _ = _serve(cfg, params, prompts, n_slots=6)
    for rid, p in enumerate(prompts):
        alone, _ = _serve(cfg, params, [p], n_slots=1)
        np.testing.assert_array_equal(together[rid], alone[0])


@pytest.mark.parametrize("held,pairs", [((0, 1, 2, 3), K), ((2, 9), 1),
                                        ((8, 9, 10), 0)])
def test_expert_rows_count_the_routed_pairs(key, held, pairs):
    """With every row routed to experts 0..3, each fed row gives each
    expert layer ``pairs`` pairs with the held experts, by hand."""
    cfg = _cfg(experts_held=held)
    params = _skewed_params(_cfg(experts_held=held), key)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (19, 7, 33)]
    _, cb = _serve(cfg, params, prompts, n_slots=2)
    s = cb.summary()
    n_moe = cfg.n_layers - cfg.first_k_dense
    assert s["decode_expert_rows"] == n_moe * pairs * s["decode_tokens"]
    assert s["expert_rows"] == n_moe * pairs * (s["decode_tokens"]
                                                + s["chunk_fed_tokens"])


def test_mla_and_moe_scopes_name_the_decode_steps_ops(key):
    cfg = _cfg()
    m = Model(cfg, ExecConfig.serving())
    params = m.init(key)
    cache = m.init_slot_cache(2, 64, page_size=16, n_pages=9)
    step = jax.jit(m.decode_step, static_argnames=("page_size",))
    text = step.lower(params, jnp.zeros((2, 1), jnp.int32), cache, None,
                      None, jnp.array([3, 0], jnp.int32),
                      jnp.array([[1, 0, 0, 0], [0, 0, 0, 0]], jnp.int32),
                      page_size=16).compile().as_text()
    # mla and moe sit inside the attn and mlp scopes, so a reader of the
    # outer names alone still sees each layer
    for scope in ("layer_scan", "attn/mla", "mlp/moe", "kv_pool", "unembed"):
        assert f"/{scope}/" in text, scope
    assert "/attn/mla/kv_pool/" in text   # the pool's writes stay kv_pool


def test_resident_weights_are_per_expert_and_per_head(key):
    """Serving quantization gives each held expert's matrices and each
    head's absorption matrices scales of their own, per column, under
    the scan's layer axis; the router stays float32."""
    from repro.models.layers import QuantizedWeight
    from repro.models.model import quantize_model_params
    cfg = _cfg(experts_held=(1, 5, 9))
    params = Model(cfg, ExecConfig()).init(key)
    q = quantize_model_params(params)
    layer, qlayer = params["blocks"]["scan"][0], q["blocks"]["scan"][0]
    w1 = qlayer["moe"]["w1"]
    assert isinstance(w1, QuantizedWeight)
    assert w1.codes.shape == (2, 3, cfg.d_model, cfg.moe_d_ff)
    assert w1.scale.shape == (2, 3, 1, cfg.moe_d_ff)
    want = np.abs(np.asarray(layer["moe"]["w1"])).max(axis=2,
                                                      keepdims=True) / 127
    np.testing.assert_allclose(np.asarray(w1.scale), want, rtol=1e-6)
    w_uk = qlayer["mla"]["w_uk"]
    assert w_uk.codes.shape == (2, cfg.n_heads, cfg.qk_nope_head_dim,
                                cfg.kv_lora_rank)
    assert w_uk.scale.shape == (2, cfg.n_heads, 1, cfg.kv_lora_rank)
    assert isinstance(qlayer["moe"]["shared"]["w2"], QuantizedWeight)
    assert qlayer["moe"]["shared"]["w2"].codes.ndim == 3   # (layer, K, N)
    assert qlayer["moe"]["router"].dtype == jnp.float32
    assert isinstance(q["blocks"]["lead"][0]["ffn"]["w1"], QuantizedWeight)
    assert isinstance(q["embed"]["unembed"], QuantizedWeight)
