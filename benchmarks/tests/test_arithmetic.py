"""Rates over whole units and real elapsed time; percentiles that know
their sample count; FLOP and byte counts at hand-worked shapes."""

import pytest

from benchmarks.lib import peaks, stats


def test_whole_unit_rate_uses_real_elapsed_time():
    # lead-in ends at 10.0; three whole epochs of 1000 tokens end at
    # 12.1, 14.0, 16.3: the asked-for 6 s is overrun and that is counted
    rate, n, elapsed = stats.whole_unit_rate([10.0, 12.1, 14.0, 16.3], 1000)
    assert (n, elapsed) == (3, pytest.approx(6.3))
    assert rate == pytest.approx(3000 / 6.3)
    assert stats.whole_unit_rate([10.0], 1000) is None


def test_completion_rate_counts_whole_requests_between_completions():
    fin = [(0.5, 100), (1.0, 100), (1.0005, 50), (2.0, 100), (3.0, 200), (9.0, 100)]
    rate, n, elapsed = stats.completion_rate(fin, 1.0, 4.0)
    # clock from the first completion in the window (1.0) to the last
    # (3.0); the one 0.5 ms after the first is the same scheduler step
    assert (n, elapsed) == (2, pytest.approx(2.0))
    assert rate == pytest.approx(300 / 2.0)


def test_percentile_needs_ten_samples_beyond():
    assert stats.percentile(range(1, 101), 90) == 90
    assert stats.percentile(range(1, 100), 90) is None  # 99 - 90 = 9 beyond
    assert stats.percentile([3, 1, 2], 50) == 2
    assert stats.percentile([], 50) is None


def test_train_flops_per_token_by_hand():
    # one layer, hidden 4, seq 2: projections 4*2*16 = 128, attention
    # 2*2*2*4 = 32, two 4x4 dense 2*2*16 = 64 -> 224; head 2*4 = 8;
    # forward 232, with backward 3 x
    assert peaks.transformer_train_flops_per_token(1, 4, 2, 2) == 3 * 232
    flagship = peaks.transformer_train_flops_per_token(12, 1024, 16, 512)
    assert flagship == 3 * (12 * (8 * 1024**2 + 4 * 512 * 1024 + 4 * 1024**2) + 2048)


def test_decode_attention_bytes_and_floor_by_hand():
    # two sequences of 10 and 30 tokens, 2 heads of 4, float32: a row is
    # 32 bytes; keys and values 2 * 40 * 32 = 2560; q in and o out 2*2*32
    assert peaks.paged_decode_attention_bytes([10, 30], 2, 4, 4) == 2560 + 128
    assert peaks.paged_decode_attention_flops([10, 30], 2, 4) == 4 * 40 * 8
    v5e = peaks.peaks_for("TPU v5 lite")
    assert (v5e["bf16_tflops"], v5e["hbm_gbps"], v5e["hbm_gb"]) == (197.0, 819.0, 16.0)
    floor, bound = peaks.roofline_floor_s(1280, 2688, v5e)
    assert bound == "memory" and floor == pytest.approx(2688 / 819e9)
    with pytest.raises(Exception):
        peaks.peaks_for("TPU v9")
