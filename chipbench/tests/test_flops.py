"""FLOP and byte counts against hand counts at smoke size."""
import pytest

from chipbench import flops
from chipbench.arch import dense

# d 64, 4 heads of 16, d_ff 128, vocab 256, one layer
SMALL = {"num_layers": 1, "d_model": 64, "num_heads": 4, "num_kv_heads": 4,
         "d_ff": 128, "vocab_size": 256}


def test_matmul_params_by_hand():
    # q, k, v, o: 4 * 64 * 64; SwiGLU: 3 * 64 * 128; head: 64 * 256
    assert dense.matmul_params(SMALL) == 4 * 4096 + 3 * 8192 + 16384


def test_forward_train_and_decode_by_hand():
    p = 4 * 4096 + 3 * 8192 + 16384
    # 2 rows of 3 tokens: 2 * 3 * 2p, plus attention over 1 + 2 + 3 keys
    # at 4 * heads * head_dim per key
    attn = 4 * 4 * 16 * (1 + 2 + 3)
    assert dense.forward_flops(SMALL, 2, 3) == 2 * (2 * p * 3 + attn)
    assert flops.train_flops(dense, SMALL, 2, 3) == 3 * 2 * (2 * p * 3 + attn)
    assert dense.decode_flops(SMALL, [0, 4]) == \
        2 * (2 * p) + 4 * 4 * 16 * (1 + 5)


def test_fleet_drift_cost_and_roofline():
    c = flops.fleet_drift_cost(4096, 256, 64)
    assert c["bytes"] == 4 * 4096 * (256 + 128 + 1)
    assert c["flops"] == 4096 * (256 + 12 * 64)
    peak = flops.peaks("TPU v5 lite")
    assert peak["flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    assert flops.roofline_seconds(c, peak) == pytest.approx(
        c["bytes"] / 819e9)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")
