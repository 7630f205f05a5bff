import pytest

from lib import flops
from lib.peaks import peaks_for


def test_v5e_peaks_and_a_missing_kind_is_an_error():
    peaks = peaks_for("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12 and peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peak"):
        peaks_for("TPU v9 imaginary")


def test_gpt2_small_counts():
    # 12 blocks of 12 d^2 plus the tied head: 84.9M + 38.6M.
    assert flops.gpt_matmul_params(12, 768, 50304) == 12 * 12 * 768 * 768 + 768 * 50304
    per_token = flops.gpt_train_flops_per_token(12, 768, 50304, 1024)
    assert per_token == pytest.approx(6 * 123_568_128 + 3 * 12 * 4 * 1024 * 768 / 2)


def test_roofline_share_names_its_bound():
    peaks = peaks_for("TPU v5 lite")
    cost = flops.paged_decode_attention_cost(1024, 20, 64)
    # one decode token reads far more bytes than it computes: memory bound.
    took = cost["bytes"] / peaks["hbm_bytes_per_s"] * 2
    assert flops.roofline_share(cost["flops"], cost["bytes"], took, peaks) == {
        "share": pytest.approx(0.5), "bound": "memory",
    }
