"""The operation count and the peaks table."""
import pytest

import flops
import peaks

CREDITCARD = (29, 15, 18, 21, 24, 27, 29)
N = 255_883


def test_creditcard_gram_term_is_the_hand_derived_one():
    terms = flops.fit_terms(CREDITCARD, N)
    # sum over the logistic layers of m_{l-1} (m_l + 1)^2:
    # 15*19^2 + 18*22^2 + 21*25^2 + 24*28^2 = 46,068
    assert terms["hidden_gram"] == 2 * N * 46_068 == pytest.approx(2.3576e10, rel=1e-4)


def test_creditcard_total_is_the_gram_term_plus_the_small_terms():
    terms = flops.fit_terms(CREDITCARD, N)
    small = {k: v for k, v in terms.items() if k != "hidden_gram"}
    # encoder Gram 2 n 29^2; stage 1 and layer outputs 2 n sum(m_{l-1} m_l) each,
    # sum = 15*18 + 18*21 + 21*24 + 24*27 = 1,800; the last layer (a = 28)
    assert small["encoder_gram"] == 2 * N * 29 * 29
    assert small["stage1"] == small["hidden_out"] == 2 * N * 1_800
    assert small["last_gram"] == 2 * N * 28 * 28
    assert sum(small.values()) < 0.25 * terms["hidden_gram"]
    assert flops.fit_flops(CREDITCARD, N) == pytest.approx(sum(terms.values()))


def test_fleet_count_scales_with_tenants():
    one = flops.fit_flops((21, 4, 8, 12, 16, 21), 1489)
    assert flops.fit_flops((21, 4, 8, 12, 16, 21), 1489, tenants=1024) == 1024 * one


def test_peaks_are_keyed_by_device_kind_and_unknown_kinds_raise():
    assert peaks.peaks("TPU v5 lite").flops == 197e12
    assert peaks.peaks("TPU v5e").hbm_bytes == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")

