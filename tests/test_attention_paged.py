"""Block-paged decode kernels: page-table indirection, bit-exactly.

The paged tentpole contract (`raceit_attention_decode_paged` /
`raceit_attention_decode_gqa_paged` over a ``(n_pages, page_size, KV, D)``
pool + ``(B, max_pages)`` block table): output is **bit-identical** to the
contiguous per-row wrappers (`raceit_attention_decode_fused` /
`raceit_attention_decode_gqa`) evaluated on the gathered layout of the same
table — pages move the DMA source of each key tile, never its logical
coordinates, the block visit order, or the quantizer windows
(`masked_page_quantize` reduces over the same union of live prefixes as
`masked_prefix_quantize`, and f32 max is order-free).

Matrix: softmax_mode x fill (full / partial / single-key / EMPTY row) x
rep x page permutation (shuffled block tables), plus stale-page and
trash-page garbage immunity, the chunked Sq>1 masked call, and the
one-executable-per-run compile contract (block tables are traced).
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels.ops import (masked_page_quantize, masked_prefix_quantize,
                               page_valid_lengths,
                               raceit_attention_decode_fused,
                               raceit_attention_decode_gqa,
                               raceit_attention_decode_gqa_paged,
                               raceit_attention_decode_paged)
from test_attention_perrow import _assert_parity, _perrow_staged_oracle

LENS = (96, 33, 1, 0)  # one full, one partial, one single-key, one EMPTY row


def _paged_case(rng, rep, lens=LENS, B=4, KV=2, D=16, ps=16, mp=6,
                perm_seed=0, garbage=True):
    """A contiguous native-layout case plus its paged twin.

    Returns (q, k, v, lens, k_pool, v_pool, block_table): k/v are the
    zero-tailed contiguous (B, KV, Smax, D) buffers, the pools scatter the
    same live entries into shuffled physical pages of a shared
    (n_pages, ps, KV, D) pool, and — when ``garbage`` — every pool entry
    NOT holding live cache data (unmapped pages, the trash page, live-page
    rows past the slot's fill) is filled with +-1e4 junk the paged path
    must treat as nonexistent.
    """
    H = KV * rep
    Smax = ps * mp
    assert all(ln <= Smax for ln in lens) and len(lens) == B
    mk = lambda s: jnp.asarray(rng.normal(0, 1.5, s), jnp.float32)
    q = mk((B, H, 1, D))
    k = jnp.zeros((B, KV, Smax, D), jnp.float32)
    v = jnp.zeros((B, KV, Smax, D), jnp.float32)
    for b, ln in enumerate(lens):
        k = k.at[b, :, :ln].set(mk((KV, ln, D)))
        v = v.at[b, :, :ln].set(mk((KV, ln, D)))
    n_pages = 1 + B * mp
    if garbage:
        junk = np.random.default_rng(perm_seed + 7)
        pool_k = np.asarray(junk.choice((-1e4, 1e4), (n_pages, ps, KV, D)),
                            np.float32)
        pool_v = -pool_k
    else:
        pool_k = np.zeros((n_pages, ps, KV, D), np.float32)
        pool_v = np.zeros((n_pages, ps, KV, D), np.float32)
    order = np.random.default_rng(perm_seed).permutation(
        np.arange(1, n_pages))  # physical page 0 stays the trash page
    bt = np.zeros((B, mp), np.int32)
    nxt = 0
    for b, ln in enumerate(lens):
        for j in range(-(-ln // ps)):
            pg = int(order[nxt]); nxt += 1
            bt[b, j] = pg
            lv = min(ps, ln - j * ps)  # only live rows — page tail stays junk
            pool_k[pg, :lv] = np.asarray(
                k[b, :, j * ps:j * ps + lv]).transpose(1, 0, 2)
            pool_v[pg, :lv] = np.asarray(
                v[b, :, j * ps:j * ps + lv]).transpose(1, 0, 2)
    return (q, k, v, jnp.asarray(lens, jnp.int32), jnp.asarray(pool_k),
            jnp.asarray(pool_v), jnp.asarray(bt))


# ---------------------------------------------------------------------------
# the matrix: paged == contiguous rows == per-row staged oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rep", (1, 4))
@pytest.mark.parametrize("mode", ["pot", "pot_fine", "uniform"])
def test_paged_matrix_bitexact_vs_contiguous_and_oracle(rng, mode, rep):
    """Every softmax mode x rep, mixed fills incl. an empty slot: GQA-paged
    == flat-paged == the contiguous rows wrappers (matched block order)
    bitwise, and all of them match the per-row staged oracle."""
    q, k, v, lens, pk, pv, bt = _paged_case(rng, rep)
    ps = pk.shape[1]
    got_gqa = raceit_attention_decode_gqa_paged(q, pk, pv, lens, bt,
                                                softmax_mode=mode, block_k=ps)
    got_flat = raceit_attention_decode_paged(q, pk, pv, lens, bt,
                                             softmax_mode=mode, block_k=ps)
    np.testing.assert_array_equal(np.asarray(got_gqa), np.asarray(got_flat))
    # contiguous per-row wrappers on the gathered layout, same key-block
    # size so the streamed PoT row sums add in the same order
    kf, vf = (jnp.repeat(a, rep, axis=1) for a in (k, v))
    want_rows = raceit_attention_decode_fused(q, kf, vf, lens,
                                              softmax_mode=mode, block_k=ps)
    np.testing.assert_array_equal(np.asarray(got_flat), np.asarray(want_rows))
    oracle = _perrow_staged_oracle(q, kf, vf, lens, mode)
    _assert_parity(got_gqa, oracle, vf)


@pytest.mark.parametrize("perm_seed", (1, 2, 3))
def test_paged_shuffled_tables_bit_identical(rng, perm_seed):
    """The same logical contents under different page permutations are the
    same computation: outputs are bitwise invariant to the physical
    placement the allocator happened to pick."""
    draws = [np.random.default_rng(42) for _ in range(2)]
    a = _paged_case(draws[0], rep=2, perm_seed=0)
    b = _paged_case(draws[1], rep=2, perm_seed=perm_seed)
    out_a = raceit_attention_decode_gqa_paged(a[0], a[4], a[5], a[3], a[6],
                                              block_k=16)
    out_b = raceit_attention_decode_gqa_paged(b[0], b[4], b[5], b[3], b[6],
                                              block_k=16)
    np.testing.assert_array_equal(np.asarray(out_a), np.asarray(out_b))


def test_paged_garbage_everywhere_ignored(rng):
    """Junk in unmapped pages, the trash page, and live-page tails past each
    slot's fill must touch nothing — not the outputs, not the shared
    quantizer scales (`masked_page_quantize` zeroes them, the kernel's
    per-row frontier masks them)."""
    draws = [np.random.default_rng(9) for _ in range(2)]
    clean = _paged_case(draws[0], rep=2, lens=(96, 33, 17, 5), garbage=False)
    dirty = _paged_case(draws[1], rep=2, lens=(96, 33, 17, 5), garbage=True)
    out_clean = raceit_attention_decode_gqa_paged(
        clean[0], clean[4], clean[5], clean[3], clean[6], block_k=16)
    out_dirty = raceit_attention_decode_gqa_paged(
        dirty[0], dirty[4], dirty[5], dirty[3], dirty[6], block_k=16)
    np.testing.assert_array_equal(np.asarray(out_clean), np.asarray(out_dirty))


def test_paged_quantizer_scale_matches_contiguous(rng):
    """`masked_page_quantize` reduces over the union of live page entries —
    the *same set* `masked_prefix_quantize` reduces over on the gathered
    layout — so scales (and hence every downstream code) are bitwise
    equal, junk and shuffling notwithstanding."""
    q, k, v, lens, pk, pv, bt = _paged_case(rng, rep=1, lens=(96, 33, 17, 5))
    n_pages, ps = pk.shape[0], pk.shape[1]
    pvl = page_valid_lengths(bt, lens, n_pages, ps)
    # the trash page is never valid, reserved-but-unfilled entries scatter 0
    assert int(pvl[0]) == 0
    codes_p, scale_p = masked_page_quantize(pk, pvl)
    codes_c, scale_c = masked_prefix_quantize(
        k.transpose(0, 2, 1, 3), lens, axis=1)  # (B, Smax, KV, D) layout
    assert np.float32(scale_p) == np.float32(scale_c)
    # gather the pool back to contiguous: codes agree entry-for-entry
    gathered = np.asarray(codes_p)[np.asarray(bt)].reshape(
        len(lens), -1, *pk.shape[2:])
    np.testing.assert_array_equal(gathered, np.asarray(codes_c))


def test_paged_chunk_call_matches_masked_contiguous(rng):
    """The chunked-prefill call (Sq=C queries + intra-chunk causal mask)
    through the flat paged entry is bit-identical to the contiguous flat
    kernel under the same mask — the shape the batcher's prefill chunks
    compile to."""
    B, KV, rep, D, ps, mp, C = 3, 2, 2, 16, 8, 6, 4
    H = KV * rep
    offs, clens = np.array([9, 0, 3]), np.array([4, 4, 1])
    lens = tuple(int(t) for t in offs + clens)
    draws = np.random.default_rng(11)
    _, k, v, lv, pk, pv, bt = _paged_case(
        draws, rep=rep, lens=lens, B=B, KV=KV, D=D, ps=ps, mp=mp)
    q = jnp.asarray(draws.normal(0, 1.5, (B, H, C, D)), jnp.float32)
    cols = np.arange(ps * mp)[None, None, :]
    mask = jnp.asarray(
        cols < (offs[:, None] + np.arange(C)[None, :] + 1)[..., None])
    got = raceit_attention_decode_paged(q, pk, pv, lv, bt, mask=mask,
                                        block_k=ps)
    # contiguous reference with identical quantization (the decode wrappers'
    # prefix-restricted scales) and the same mask, at code level
    from repro.core.quant import quantize_tensor
    from repro.kernels.acam_attention import acam_attention_codes
    from repro.kernels.ops import expand_row_lens, prob_requant_scale
    kf, vf = (jnp.repeat(a, rep, axis=1) for a in (k, v))
    qq = quantize_tensor(q, bits=8)
    kc, ks = masked_prefix_quantize(kf, lv, axis=2)
    vc, vs = masked_prefix_quantize(vf, lv, axis=2)
    Smax = kf.shape[2]
    out32, cmax = acam_attention_codes(
        qq.codes.reshape(B * H, C, D), kc.reshape(B * H, Smax, D),
        vc.reshape(B * H, Smax, D), qq.scale * ks,
        jnp.broadcast_to(mask[:, None], (B, H, C, Smax)).reshape(
            B * H, C, Smax),
        kv_len=expand_row_lens(lv, H), scale_by_sqrt_d=D, block_k=ps)
    want = (out32.astype(jnp.float32)
            * (prob_requant_scale(cmax) * vs)).reshape(B, H, C, D)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("KV,rep,D,one_pool", [(2, 2, 16, False),
                                                (1, 4, 36, True)])
def test_gqa_paged_chunk_call_matches_flat(KV, rep, D, one_pool):
    """A chunk call (Sq=C) on the GQA paged entry puts each group's rep x C
    (head, position) queries on its tile's rows: bit-identical to the flat
    paged entry, which repeats the pool's codes to H. The second case is
    the latent cache's shape: one KV head read by every query head, keys
    and values the same pool, a head width off the lane multiple."""
    B, ps, mp, C = 3, 8, 6, 4
    H = KV * rep
    offs, clens = np.array([9, 0, 3]), np.array([4, 4, 1])
    lens = tuple(int(t) for t in offs + clens)
    draws = np.random.default_rng(17)
    _, _, _, lv, pk, pv, bt = _paged_case(
        draws, rep=rep, lens=lens, B=B, KV=KV, D=D, ps=ps, mp=mp)
    pv = pk if one_pool else pv
    q = jnp.asarray(draws.normal(0, 1.5, (B, H, C, D)), jnp.float32)
    cols = np.arange(ps * mp)[None, None, :]
    mask = jnp.asarray(
        cols < (offs[:, None] + np.arange(C)[None, :] + 1)[..., None])
    got = raceit_attention_decode_gqa_paged(q, pk, pv, lv, bt, mask=mask,
                                            block_k=ps)
    want = raceit_attention_decode_paged(q, pk, pv, lv, bt, mask=mask,
                                         block_k=ps)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_paged_block_table_is_traced_one_compile(rng):
    """One executable serves every block-table assignment and fill pattern
    — the allocator may shuffle pages freely without re-jitting."""
    q, k, v, lens, pk, pv, bt = _paged_case(rng, rep=2)
    fn = lambda lv, t: raceit_attention_decode_gqa_paged(q, pk, pv, lv, t,
                                                         block_k=16)
    fn(lens, bt)
    traces = raceit_attention_decode_gqa_paged._cache_size()
    rolled = jnp.roll(bt, 1, axis=0)
    fn(jnp.asarray((5, 96, 0, 12), jnp.int32), rolled)
    assert raceit_attention_decode_gqa_paged._cache_size() == traces


def test_paged_page_size_not_multiple_of_block_k(rng):
    """page_size smaller than / coprime-free vs the requested block_k: the
    kernel clamps the key block to gcd(page_size, block_k) so blocks never
    straddle pages — result still bitwise vs contiguous at that block."""
    draws = np.random.default_rng(13)
    q, k, v, lens, pk, pv, bt = _paged_case(
        draws, rep=1, lens=(40, 12, 1, 0), ps=8, mp=6)
    got = raceit_attention_decode_paged(q, pk, pv, lens, bt, block_k=32)
    kf, vf = k, v  # rep=1
    want = raceit_attention_decode_fused(q, kf, vf, lens, block_k=8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
