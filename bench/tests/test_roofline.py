"""The yardstick's counts against hand counts at small shapes."""
import pytest

from bench import roofline

PK = roofline.peaks("TPU v5 lite")


def test_decode_attention_hand_count():
    # two live rows of 3 and 5 keys, one idle row; 2 heads of 4, f32 pool
    ops, nbytes = roofline.attn_decode_cost([3, 0, 5], 2, 2, 4, 4)
    # q.k and p.v: 2 multiply-adds per key per head-dim element
    assert ops == 2 * 2 * (3 + 5) * 2 * 4
    # k and v once per live key (2 heads x 4 x 4 B), q and out per row
    assert nbytes == 2 * (3 + 5) * 2 * 4 * 4 + 2 * (2 * 2 * 4 * 4)


def test_chunk_attention_hand_count():
    # one row feeding 3 queries at offset 2: they see 3, 4 and 5 keys
    ops, nbytes = roofline.attn_chunk_cost([2, 7], [3, 0], 1, 1, 2, 1)
    assert ops == 4 * (3 + 4 + 5) * 1 * 2
    assert nbytes == 2 * 5 * 1 * 2 * 1 + 2 * 3 * 1 * 2 * 4


def test_chunk_of_one_query_is_a_decode_step():
    assert (roofline.attn_chunk_cost([9], [1], 4, 4, 8, 4)[0]
            == roofline.attn_decode_cost([10], 4, 4, 8, 4)[0])


ARCH = {"n_layers": 2, "d_model": 8, "n_heads": 2, "head_dim": 4,
        "d_ff": 16, "vocab_size": 10, "mlp": "gelu"}


def test_model_step_hand_count():
    per_layer = 8 * 8 * 3 + 8 * 8 + 2 * 8 * 16   # q,k,v + o + up,down
    assert roofline.layer_matmul_params(ARCH) == per_layer
    swiglu = dict(ARCH, mlp="swiglu")
    assert roofline.layer_matmul_params(swiglu) == per_layer + 8 * 16
    head = 2 * 8 * 10
    # one decode row with 6 live keys
    dec = roofline.step_ops(ARCH, [], [6])
    assert dec == 2 * (2 * per_layer + 4 * 6 * 2 * 4) + head
    # one chunk row of 2 queries at offset 1: 2 + 3 key pairs, logits once
    chk = roofline.step_ops(ARCH, [(1, 2)], [])
    assert chk == 2 * (2 * 2 * per_layer + 4 * 5 * 2 * 4) + head
    assert roofline.step_ops(ARCH, [(0, 0)], [0]) == 0


@pytest.mark.parametrize("ops,nbytes,which", [(393e12, 1.0, "int8"),
                                              (1.0, 819e9, "hbm")])
def test_bound_takes_the_larger_time(ops, nbytes, which):
    t, by = roofline.bound_seconds(ops, nbytes, PK)
    assert by == which and t == pytest.approx(1.0)
