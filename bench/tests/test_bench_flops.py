"""The operation and byte counts against hand counts at smoke width."""
import pytest

from bench import flops

M = {"n_layers": 2, "d_model": 8, "n_heads": 4, "n_kv_heads": 2,
     "head_dim": 2, "d_ff": 16, "vocab_size": 10, "qkv_bias": True,
     "tie_embeddings": True, "dtype": "float32"}


def test_decode_by_hand():
    # per layer: q 8*4*2=64, k,v 2*8*2*2=64, o 64, ffn 3*8*16=384 -> 576
    # flops: 2*2*576 + attention 2 layers*4*4 heads*2 hd*(pos+1) + head 2*80
    pos = 5
    want_flops = 2 * 2 * 576 + 2 * 4 * 4 * 2 * 6 + 2 * 80
    # bytes: weights (2*(576 + norms 16 + biases 16) + final norm 8) + head
    # 80, + one embedding row 8, all *4; K/V 2 layers*2*2 kv*2 hd*4 B = 64
    # per position, 6 positions
    want_bytes = (2 * (576 + 16 + 16) + 8 + 80 + 8) * 4 + 6 * 64
    c = flops.decode(M, pos)
    assert c.flops == want_flops
    assert c.bytes == want_bytes


def test_prefill_by_hand():
    t = 3
    pairs = 6                                      # causal: 1 + 2 + 3
    want_flops = 2 * t * 2 * 576 + 2 * 4 * 4 * 2 * pairs + 2 * 80
    want_bytes = (2 * (576 + 16 + 16) + 8 + 80) * 4 + t * 8 * 4 + t * 64
    c = flops.prefill(M, t)
    assert c.flops == want_flops
    assert c.bytes == want_bytes


def test_decode_counts_kv_only_up_to_the_position():
    a, b = flops.decode(M, 0), flops.decode(M, 100)
    assert b.bytes - a.bytes == 100 * flops.kv_bytes_per_position(M)


def test_untied_and_bfloat16():
    m = dict(M, qkv_bias=False, dtype="bfloat16")
    c = flops.decode(m, 0)
    assert c.bytes == (2 * (576 + 16) + 8 + 80 + 8) * 2 + 32


def test_least_seconds_takes_the_binding_bound():
    c = flops.Cost(flops=10.0, bytes=100.0)
    assert flops.least_seconds(c, 10.0, 10.0) == pytest.approx(10.0)
    assert flops.least_seconds(c, 1.0, 1000.0) == pytest.approx(10.0)
    assert flops.least_seconds(c, 1.0, 100.0) == pytest.approx(10.0)
    assert (c + c).flops == 20.0
