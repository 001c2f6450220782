"""The roofline numerators (bench/work.py) and the peaks table, checked by
hand arithmetic at a small shape."""
import pytest

from bench import work

SMALL = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 2, "vocab_size": 10,
         "num_hidden_layers": 3, "torch_dtype": "bfloat16"}
# per layer: wq 8x8, wk 8x4, wv 8x4, wo 8x8, gate/up 8x16, down 16x8
LAYER = 64 + 32 + 32 + 64 + 128 + 128 + 128
MATMUL = 3 * LAYER + 8 * 10                 # the layers and the head
KV_PER_TOKEN = 3 * 2 * 2 * 2 * 2            # layers x (k, v) x kv heads x dim x bytes


def test_decode_step_by_hand():
    flops, nbytes = work.DenseDecoder(SMALL).decode_step([5, 7])
    attn = 2 * 2 * 3 * 4 * 2 * (5 + 7)      # QK and PV, every query head
    assert flops == 2 * 2 * MATMUL + attn
    assert nbytes == 2 * MATMUL + 2 * 8 * 2 + KV_PER_TOKEN * (5 + 7)


def test_prefill_by_hand():
    flops, nbytes = work.DenseDecoder(SMALL).prefill(4)
    attn = 2 * 2 * 3 * 4 * 2 * (1 + 2 + 3 + 4)   # causal: position i sees i
    assert flops == 2 * 4 * 3 * LAYER + 2 * 8 * 10 + attn
    assert nbytes == 2 * MATMUL + 4 * 8 * 2 + KV_PER_TOKEN * 4


def test_idle_lanes_and_masked_positions_are_not_counted():
    model = work.DenseDecoder(SMALL)
    assert model.decode_step([]) == (0.0, 0.0)
    # only the slots' real contexts count, one token of cache at a time,
    # whatever the cache length or the number of lanes decoded
    f1, b1 = model.decode_step([5, 7])
    f2, b2 = model.decode_step([5, 8])
    assert b2 - b1 == KV_PER_TOKEN
    assert f2 - f1 == 2 * 2 * 3 * 4 * 2
    # a prefill counts its true length, not the bucket it is padded to
    assert model.prefill(100) < model.prefill(128)


def test_peaks_by_device_kind():
    peak = work.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("flops,nbytes,bound", [(197e12, 1.0, 1.0),
                                                (1.0, 819e9 * 2, 2.0)])
def test_least_time_is_the_larger_bound(flops, nbytes, bound):
    assert work.least_time_s(flops, nbytes, work.peaks("TPU v5 lite")) \
        == pytest.approx(bound)
