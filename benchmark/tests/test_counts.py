"""The roofline counters and the sizing helpers against hand-worked
values for GPT-2 medium, and the table of peaks."""

import json
import os

import pytest

from benchmark import counts
from benchmark.spec import ROOT


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


MEDIUM = _config("gpt2-medium")
LARGE = _config("gpt2-large")


def test_param_count_by_hand():
    e, v, p, layers = 1024, 50257, 1024, 24
    block = 4 * e * e + 4 * e + 8 * e * e + 5 * e + 4 * e
    assert counts.gpt_param_count(MEDIUM) == \
        v * e + p * e + layers * block + 2 * e + e * v == 406_286_336
    assert counts.gpt_param_count(LARGE) == 838_359_040


def test_train_flops_per_token_by_hand():
    e, v, layers, seq = 1024, 50257, 24, 1024
    matmul = layers * 12 * e * e + e * v            # 353,453,056
    attention = layers * 2 * e * (seq + 1) / 2      # 25,190,400 MACs
    want = 6 * matmul + 6 * attention
    assert counts.train_flops_per_token(MEDIUM, seq) == want
    assert want == pytest.approx(2.2719e9, rel=1e-4)


def test_decode_bytes_per_step_by_hand():
    e, v, layers = 1024, 50257, 24
    weights = (layers * 12 * e * e + e * v) * 2     # bf16
    per_token = layers * 2 * e * 2                  # K and V, bf16: 98,304
    assert counts.kv_bytes_per_token(MEDIUM) == per_token
    assert counts.decode_bytes_per_step(MEDIUM, 0) == weights
    assert counts.decode_bytes_per_step(MEDIUM, 16 * 300) == \
        weights + 16 * 300 * per_token
    # a full slot of 1024 tokens: 100.7 MB medium, 188.7 MB large
    assert 1024 * per_token == 100_663_296
    assert 1024 * counts.kv_bytes_per_token(LARGE) == 188_743_680


def test_batch_that_fits_is_chip_smokes():
    v5e = int(16.9e9)
    assert counts.train_batch_that_fits(MEDIUM, 1024, v5e) == 4
    assert counts.train_bytes_estimate(MEDIUM, 1024, 4) == \
        pytest.approx(11.0e9, rel=0.02)  # chip_smoke.py: 11.0 GB estimated
    with pytest.raises(ValueError):
        counts.train_batch_that_fits(LARGE, 1024, int(8e9))


def test_peaks_v5e_and_unknown_kind():
    p = counts.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        counts.peaks_for("cpu")
