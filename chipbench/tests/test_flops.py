"""FLOP counts by hand, and the table of peaks."""
import pytest

from chipbench import flops, model

# smollm-360m: d 960, 15 heads of 64 over 5 KV heads, d_ff 2560, 32 layers,
# vocab 49152, RMSNorm scales
#   attention  960 * (15 + 2 * 5) * 64 + 15 * 64 * 960 = 2,457,600
#   MLP        3 * 960 * 2560                          = 7,372,800
#   norms      2 * 960                                 =     1,920
#   layer                                              = 9,832,320
#   N = 32 * 9,832,320 + 960 (final norm) + 960 * 49152 (head) = 361,821,120
SMOLLM_N = 361_821_120
SMOLLM_ATTN = 32 * 15 * 64  # L * H * Q = 30,720
# olmo-1b: d 2048, 16 heads of 128 (MHA), d_ff 8192, 16 layers, vocab 50304,
# no norm parameters
#   attention  2048 * 48 * 128 + 16 * 128 * 2048 = 16,777,216
#   MLP        3 * 2048 * 8192                   = 50,331,648
#   N = 16 * 67,108,864 + 2048 * 50304           = 1,176,764,416
OLMO_N = 1_176_764_416
OLMO_ATTN = 16 * 16 * 128  # 32,768


@pytest.fixture(scope="module")
def smollm():
    return model.load_config("smollm-360m")[0]


@pytest.fixture(scope="module")
def olmo():
    return model.load_config("olmo-1b")[0]


def test_matmul_params(smollm, olmo):
    assert flops.matmul_params(smollm) == SMOLLM_N
    assert flops.matmul_params(olmo) == OLMO_N


def test_train_per_token(smollm, olmo):
    # 6 N + 6 L H Q s at s = 1024
    assert flops.train_per_token(smollm, 1024) == 2_170_926_720 + 188_743_680
    assert flops.train_per_token(smollm, 1024) == pytest.approx(2.3597e9, rel=1e-4)
    assert flops.train_per_token(olmo, 1024) == 7_060_586_496 + 201_326_592


def test_decode_token(smollm, olmo):
    # 2 N + 4 L H Q c
    assert flops.decode_token(smollm, 600) == 723_642_240 + 4 * SMOLLM_ATTN * 600
    assert flops.decode_token(olmo, 2048) == 2_353_528_832 + 268_435_456


def test_prefill_is_the_sum_of_its_tokens(smollm):
    p = 512
    by_token = sum(flops.decode_token(smollm, c) for c in range(1, p + 1))
    assert flops.prefill(smollm, p) == pytest.approx(by_token, rel=1e-12)


def test_peaks_are_keyed_by_device_kind():
    v5e = flops.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "source" in v5e
    with pytest.raises(ValueError):
        flops.peaks("TPU v9 imaginary")
